"""GPT: decoder-only transformer LM (BASELINE config #4).

TPU-native design notes:
- fused QKV projection: one [H, 3H] matmul feeding the MXU, then a
  reshape — the layout the reference reaches via fused_attention_op.cu.
- attention runs through F.scaled_dot_product_attention → the Pallas
  flash kernel on TPU, the ring-attention path when the "sep" mesh axis
  is active (sequence parallelism — new vs the reference).
- tensor parallelism by construction: when fleet.init raised an "mp"
  mesh axis, projections become Column/RowParallelLinear (GSPMD
  shardings), embedding becomes VocabParallelEmbedding.
"""
from __future__ import annotations

import math

import numpy as np

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..nn.initializer import Normal, Constant
from .generation import head_columns

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTForCausalLMPipe"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=None, max_position_embeddings=1024,
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 initializer_range=0.02, layer_norm_epsilon=1e-5,
                 use_recompute=False, tensor_parallel=None,
                 sequence_parallel=False, fuse_attention_qkv=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.layer_norm_epsilon = layer_norm_epsilon
        self.use_recompute = use_recompute
        self.sequence_parallel = sequence_parallel
        self.fuse_attention_qkv = fuse_attention_qkv


def _mp_active():
    from ..distributed.mesh import get_mesh
    m = get_mesh()
    return m is not None and "mp" in m.dim_names and \
        m.get_dim_size("mp") > 1


def _sep_active():
    from ..distributed.mesh import get_mesh
    m = get_mesh()
    return m is not None and "sep" in m.dim_names and \
        m.get_dim_size("sep") > 1


def _make_linear(in_f, out_f, cfg, parallel=None, gather_output=False,
                 input_is_parallel=True):
    init = Normal(0.0, cfg.initializer_range)
    attr = nn.ParamAttr(initializer=init)
    if parallel == "column" and _mp_active():
        from ..distributed import fleet
        return fleet.ColumnParallelLinear(
            in_f, out_f, weight_attr=attr, has_bias=True,
            gather_output=gather_output)
    if parallel == "row" and _mp_active():
        from ..distributed import fleet
        return fleet.RowParallelLinear(
            in_f, out_f, weight_attr=attr, has_bias=True,
            input_is_parallel=input_is_parallel)
    return nn.Linear(in_f, out_f, weight_attr=attr)


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.hidden_size = config.hidden_size
        self.dropout = config.attention_probs_dropout_prob
        self.qkv_proj = _make_linear(config.hidden_size,
                                     3 * config.hidden_size, config,
                                     parallel="column")
        self.out_proj = _make_linear(config.hidden_size,
                                     config.hidden_size, config,
                                     parallel="row")

    def forward(self, x, cache=None):
        from ..ops import manipulation
        from ..ops._helpers import apply_op
        b, l, h = x.shape[0], x.shape[1], self.hidden_size
        qkv = self.qkv_proj(x)
        qkv = manipulation.reshape(qkv, [b, l, self.num_heads,
                                         3 * self.head_dim])
        q, k, v = manipulation.split(qkv, 3, axis=-1)
        from .generation import DecodeCache, update_and_attend
        # multi-tenant LoRA (serving/adapters.py): per-row low-rank
        # deltas add AFTER the fused-QKV split (the delta pools are
        # stored per projection, not in the fused interleaved layout)
        lora = (cache.lora if isinstance(cache, DecodeCache)
                else None)
        # megakernel mode (PADDLE_TPU_MEGAKERNEL + adapters): the
        # q/k/v deltas fuse INTO the attend op's prologue — no rope in
        # GPT, so delta-then-attend and attend-with-fused-delta are
        # the same floats. Only the o-delta stays outside (it needs
        # the attention OUTPUT), via the paged-gather op.
        lora_paged = (cache.lora_paged
                      if isinstance(cache, DecodeCache) else None)
        if lora is not None:
            aq, bq, ak, bk, av, bv, ao, bo, sc = lora
            hd = [b, l, self.num_heads, self.head_dim]
            q = q + manipulation.reshape(
                apply_op("lora_delta", x, aq, bq, sc), hd)
            k = k + manipulation.reshape(
                apply_op("lora_delta", x, ak, bk, sc), hd)
            v = v + manipulation.reshape(
                apply_op("lora_delta", x, av, bv, sc), hd)
        if isinstance(cache, DecodeCache):
            out, new_cache = update_and_attend(
                q, k, v, cache, training=False,
                lora_x=x if lora_paged is not None else None)
            out = manipulation.reshape(out, [b, l, h])
            o = self.out_proj(out)
            if lora is not None:
                o = o + apply_op("lora_delta", out, ao, bo, sc)
            elif lora_paged is not None:
                ao, bo = lora_paged[6], lora_paged[7]
                apage, ascale = lora_paged[8], lora_paged[9]
                o = o + apply_op("lora_delta_paged", out, ao, bo,
                                 apage, ascale)
            return o, new_cache
        if cache is not None:
            k = manipulation.concat([cache[0], k], axis=1)
            v = manipulation.concat([cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None
        if _sep_active() and cache is None:
            from ..distributed import ring_attention
            out = ring_attention(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=self.dropout, is_causal=True,
                training=self.training)
        out = manipulation.reshape(out, [b, l, h])
        out = self.out_proj(out)
        if new_cache is not None:
            return out, new_cache
        return out


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc1 = _make_linear(config.hidden_size,
                                config.intermediate_size, config,
                                parallel="column")
        self.fc2 = _make_linear(config.intermediate_size,
                                config.hidden_size, config, parallel="row")

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    """Pre-LN block (reference structure: fused_multi_transformer_op.cu
    implements exactly this layer for inference)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout1 = nn.Dropout(config.hidden_dropout_prob,
                                   mode="upscale_in_train")
        self.dropout2 = nn.Dropout(config.hidden_dropout_prob,
                                   mode="upscale_in_train")
        self.use_recompute = config.use_recompute

    def _body(self, x):
        x = x + self.dropout1(self.attn(self.ln1(x)))
        x = x + self.dropout2(self.mlp(self.ln2(x)))
        return x

    def forward(self, x, cache=None):
        if cache is not None:
            h, new_cache = self.attn(self.ln1(x), cache=cache)
            x = x + self.dropout1(h)
            x = x + self.dropout2(self.mlp(self.ln2(x)))
            return x, new_cache
        if self.use_recompute and self.training:
            from ..distributed.fleet.utils import recompute
            return recompute(self._body, x)
        return self._body(x)


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = Normal(0.0, config.initializer_range)
        if _mp_active():
            from ..distributed import fleet
            self.word_embeddings = fleet.VocabParallelEmbedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        else:
            self.word_embeddings = nn.Embedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.dropout = nn.Dropout(config.hidden_dropout_prob,
                                  mode="upscale_in_train")

    def forward(self, input_ids, position_ids=None, offset=0):
        from ..ops import creation
        l = input_ids.shape[1]
        if position_ids is None:
            if isinstance(offset, Tensor):
                ar = creation.arange(0, l, dtype="int64")
                off = offset.astype("int64")
                if len(off.shape) == 1:
                    # per-row offsets (continuous-batching decode): each
                    # slot sits at its own position -> ids [B, l]
                    from ..ops import manipulation
                    position_ids = manipulation.unsqueeze(ar, axis=0) + \
                        manipulation.unsqueeze(off, axis=1)
                else:
                    # traced scalar offset (static-cache decode)
                    position_ids = ar + off
            else:
                position_ids = creation.arange(offset, offset + l,
                                               dtype="int64")
        x = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        return self.dropout(x)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None):
        from .generation import DecodeCache
        if caches and isinstance(caches[0], DecodeCache):
            offset = caches[0].pos
        else:
            offset = caches[0][0].shape[1] if caches else 0
        x = self.embeddings(input_ids, position_ids, offset=offset)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, cache=caches[i])
                new_caches.append(c)
            else:
                x = layer(x)
        x = self.ln_f(x)
        if caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(nn.Layer):
    """LM head ties the embedding weight (logits = h @ E^T)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        self._qhead_algo = None
        self._qhead_group = None

    def forward(self, input_ids, position_ids=None, labels=None,
                caches=None, columns=None):
        from ..ops import linalg
        if caches is not None:
            h, new_caches = self.gpt(input_ids, position_ids,
                                     caches=caches)
        else:
            h = self.gpt(input_ids, position_ids)
        h = head_columns(h, columns)
        if self._qhead_algo is not None:
            # weight-only quantized LM head (nn.quant): the vocab-sized
            # matmul streams int8/int4 from HBM — the decode hot spot
            from ..nn.quant import weight_only_linear
            logits = weight_only_linear(
                h, self.qhead_weight, None, self.qhead_scale,
                weight_dtype=("int4" if "int4" in self._qhead_algo
                              else "int8"),
                in_features=self.config.hidden_size,
                group_size=self._qhead_group)
        else:
            w = self.gpt.embeddings.word_embeddings.weight
            logits = linalg.matmul(h, w, transpose_y=True)
        if labels is not None:
            loss = F.cross_entropy(logits, labels)
            return loss
        if caches is not None:
            return logits, new_caches
        return logits

    def attach_quantized_head(self, algo="weight_only_int8",
                              group_size=None):
        """Quantize the tied LM head (logits = h @ E^T) for decode: the
        transposed embedding is stored int8/int4 as buffers so the
        compiled generator streams the narrow weight (nn.quant)."""
        from ..nn.quant import weight_quantize
        w = self.gpt.embeddings.word_embeddings.weight  # [V, H]
        wt = np.ascontiguousarray(np.asarray(w.numpy()).T)  # [H, V]
        if algo == "llm.int8":
            algo = "weight_only_int8"  # same storage; see WeightOnlyLinear
        q, s = weight_quantize(wt, algo=algo, group_size=group_size)
        self.register_buffer("qhead_weight", q)
        self.register_buffer("qhead_scale", s)
        self._qhead_algo = algo
        self._qhead_group = group_size

    def init_caches(self, batch_size):
        """Empty KV caches for incremental decoding."""
        import jax.numpy as jnp
        from ..core import dtype as dtypes
        cfg = self.config
        hd = cfg.hidden_size // cfg.num_attention_heads
        caches = []
        for _ in range(cfg.num_hidden_layers):
            k = Tensor(jnp.zeros((batch_size, 0, cfg.num_attention_heads,
                                  hd),
                                 dtypes.get_default_dtype().np_dtype))
            caches.append((k, Tensor(k._value)))
        return caches

    def _decode_cache_spec(self):
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_attention_heads,
                cfg.hidden_size // cfg.num_attention_heads)

    def generate(self, input_ids, max_new_tokens=16, temperature=1.0,
                 top_k=None, top_p=None, eos_token_id=None,
                 pad_token_id=0, decode_strategy=None, num_beams=4,
                 length_penalty=0.0, num_return_sequences=1,
                 use_compiled=True, kv_cache_dtype=None):
        """Autoregressive decoding with KV cache.

        Default path: one compiled XLA program (static cache +
        lax.while_loop — see nlp/generation.py). use_compiled=False
        keeps the eager per-token loop (growing concat caches) for
        debugging."""
        if decode_strategy == "greedy_search":
            # reference spelling; normalize BEFORE the eager-path check
            # so both loops accept it (ADVICE r4)
            decode_strategy = "greedy"
        if not use_compiled and (decode_strategy not in (None, "greedy")
                                 or int(num_return_sequences) != 1
                                 or top_p is not None):
            raise NotImplementedError(
                "the eager debug loop supports greedy/top-k decoding "
                "only; beam_search/sampling/top_p/num_return_sequences "
                "need the compiled path (use_compiled=True)")
        if use_compiled:
            from .generation import CompiledGenerator
            key = (float(temperature), top_k, top_p, eos_token_id,
                   int(pad_token_id), decode_strategy, int(num_beams),
                   float(length_penalty), int(num_return_sequences),
                   kv_cache_dtype)
            gens = getattr(self, "_compiled_generators", None)
            if gens is None:
                gens = self._compiled_generators = {}
            gen = gens.get(key)
            if gen is None:
                gen = CompiledGenerator(
                    self, self._decode_cache_spec(),
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    eos_token_id=eos_token_id, pad_token_id=pad_token_id,
                    decode_strategy=decode_strategy, num_beams=num_beams,
                    length_penalty=length_penalty,
                    num_return_sequences=num_return_sequences,
                    kv_cache_dtype=kv_cache_dtype)
                gens[key] = gen
            return gen(input_ids, max_new_tokens)
        from ..ops import manipulation, creation
        import jax
        from ..core import random as random_mod
        self.eval()
        logits, caches = self.forward(input_ids,
                                      caches=self.init_caches(
                                          input_ids.shape[0]))
        out = input_ids
        import jax.numpy as jnp
        for _ in range(max_new_tokens):
            last = Tensor(logits._value[:, -1, :])
            if temperature != 1.0:
                last = Tensor(last._value / temperature)
            if top_k:
                vals, _ = jax.lax.top_k(last._value, top_k)
                thresh = vals[:, -1:]
                last = Tensor(jnp.where(last._value < thresh, -1e30,
                                        last._value))
                key = random_mod.next_key()
                nxt = jax.random.categorical(key, last._value, axis=-1)
            else:
                nxt = jnp.argmax(last._value, axis=-1)
            nxt_t = Tensor(nxt[:, None])
            out = manipulation.concat([out, nxt_t], axis=1)
            logits, caches = self.forward(nxt_t, caches=caches)
        return out


class GPTForCausalLMPipe(nn.Layer):
    """Pipeline-parallel GPT: embeddings and LM head run outside the
    pipelined section (GSPMD TP applies there); the homogeneous decoder
    blocks are stacked along a layer axis sharded over "pp" and run as
    the compiled GPipe schedule (see distributed/fleet/pp_layers.py).
    Mirrors the reference's GPTForCausalLMPipe in PaddleNLP built on
    fleet/meta_parallel/parallel_layers/pp_layers.py:209."""

    def __init__(self, config: GPTConfig, num_stages=None,
                 num_microbatches=None):
        super().__init__()
        from ..distributed.fleet.pp_layers import PipelineLayer
        from ..distributed.mesh import get_mesh
        self.config = config
        if num_stages is None:
            m = get_mesh()
            num_stages = (m.get_dim_size("pp")
                          if m is not None and "pp" in m.dim_names else 1)
        emb = GPTEmbeddings(config)
        blocks = [GPTDecoderLayer(config)
                  for _ in range(config.num_hidden_layers)]
        ln_f = nn.LayerNorm(config.hidden_size,
                            epsilon=config.layer_norm_epsilon)

        def head(x):
            # ln_f already applied (it is the preceding pipeline entry)
            from ..ops import linalg
            return linalg.matmul(x, emb.word_embeddings.weight,
                                 transpose_y=True)

        self.pipeline = PipelineLayer(
            [emb] + blocks + [ln_f, head],
            num_stages=num_stages,
            loss_fn=nn.CrossEntropyLoss(),
            num_microbatches=num_microbatches)

    def forward(self, input_ids, labels=None):
        logits = self.pipeline(input_ids)
        if labels is not None:
            return F.cross_entropy(logits, labels)
        return logits
