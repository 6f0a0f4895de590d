"""Ahead-of-time compiles for a described TPU v5e — no chip attached.

Interpret mode never checks what Mosaic checks: block shapes against the
(8, 128) tiling, VMEM, 32-bit matmul accumulators. Every `pallas_call` the
server and the trainer reach is compiled here at real widths (GPT-3 1.3B
serving: 16 heads x 128, vocab 50304, page_size 16, 2048 pages, 8 slots x
chunk 128 and decode rows; GPT-124M training: bs 16 x 1024, 12 x 64), and
must come out as a `tpu_custom_call`.

This is the ONE file that describes a topology, and it does so inside a
module-scoped fixture: only one process may load the TPU library, the
driver runs several xdist workers, and every worker imports every test
file. Never describe a topology at import, in a `skipif`, in
`parametrize` or in conftest.py (on-chip-measurement guide, section 2).
"""
import os

import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import layer_norm as pln
from paddle_tpu.ops.pallas import mla
from paddle_tpu.ops.pallas import moe
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import sparse as sp

B, H, D, PS, PAGES, MP, CHUNK, VOCAB = 8, 16, 128, 16, 2048, 128, 128, 50304
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def real_kernels(monkeypatch):
    """Not interpret mode, whatever an earlier test file asked for
    (test_pallas_layer_norm.py sets PADDLE_TPU_PALLAS_INTERPRET at
    import, and the kernel modules read it when THEY are imported)."""
    for mod in (fa, pln, pa, moe, mla, sp):
        monkeypatch.setattr(mod, "_INTERPRET", False)


@pytest.fixture
def on_tpu_branch(monkeypatch):
    """The public ops pick their kernel branch from `jax.devices()`,
    which is the CPU here: steer them as the chip would."""
    monkeypatch.setattr(pa, "_use_kernel", lambda: True)


def _compiles_to_kernel(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _q(lq):
    return ((B, lq, H, D), BF16)


def _pool(dt):
    return ((PAGES, PS, H, D), dt)


SCALES = ((PAGES, PS, H), F32)
TABLE = ((B, MP), I32)
ROW = ((B,), I32)


@pytest.mark.parametrize("lq", [CHUNK, 1])
def test_paged_walk_bf16(one_chip, lq):
    _compiles_to_kernel(
        lambda q, k, v, t, p, n: pa._ragged_attention_kernel(
            q, k, v, t, p, n, None),
        one_chip, _q(lq), _pool(BF16), _pool(BF16), TABLE, ROW, ROW)


@pytest.mark.parametrize("lq", [CHUNK, 1])
@pytest.mark.parametrize("grouped", [False, True])
def test_paged_walk_heads_of_64(one_chip, lq, grouped):
    """GPT-2 small's heads (12 x 64): narrower than the 128 lanes Mosaic
    cuts HBM by, so K and V reach the walk as the rows' gathered views
    (`pa._row_view`) and not by a DMA a page; groups, which such views
    leave nothing to save for, take the ungrouped walk."""
    text = _compiles_to_kernel(
        lambda q, k, v, t, p, n: pa._ragged_attention_kernel(
            q, k, v, t, p, n, None, group=(n, n, n) if grouped else None),
        one_chip, ((B, lq, 12, 64), BF16), ((PAGES, PS, 12, 64), BF16),
        ((PAGES, PS, 12, 64), BF16), ((B, 64), I32), ROW, ROW)
    assert "ptk:grouped_phase1" not in text


@pytest.mark.parametrize("lq", [CHUNK, 1])
@pytest.mark.parametrize("heads,window", [(72, 512), (48, None)],
                         ids=["window72", "full48"])
def test_paged_walk_laguna(one_chip, lq, heads, window):
    """Laguna-S-2.1's two layer kinds at its serving shape: 16 slots,
    8 KV heads under 72 (window 512, over the per-slot ring's table) or
    48 query heads, max_len 8192. Every walk's grid has the dynamic
    bounds (`pa.walk_grid_bounds`): the full layer's live (row, q-block
    of 32) items and key blocks (32 of 256 keys at most), the window
    layer's live items (q-blocks of 16) over a key-block axis that is
    statically its window's 4."""
    slots, mp, ring = 16, 512, 41
    text = _compiles_to_kernel(
        lambda q, k, v, t, p, n: pa._ragged_attention_kernel(
            q, k, v, t, p, n, None, window=window),
        one_chip, ((slots, lq, heads, D), BF16),
        *[((slots * (ring if window else mp) + 1, PS, 8, D), BF16)] * 2,
        ((slots, mp), I32), ((slots,), I32), ((slots,), I32))
    assert "ptk:ragged_walk" in text


@pytest.mark.parametrize("rows", [16 * CHUNK, 16], ids=["step", "decode"])
def test_moe_routed_experts(one_chip, rows, monkeypatch):
    """The routed experts of one Laguna-S-2.1 layer, this chip's 128 of
    256, over the unified step's 2048 token rows (and over 16): the
    expert kernel under its `ptk:` name."""
    h, f, held = 3072, 1024, 128
    monkeypatch.setattr(moe, "_use_kernel", lambda: True)   # as the chip would
    text = _compiles_to_kernel(
        lambda x, v, wr, wg, wu, wd: moe.routed_experts(
            x, v, wr, wg, wu, wd, top_k=10, scale=2.5, norm_topk=True,
            first=0),
        one_chip, ((rows, h), BF16), ((rows,), jnp.bool_),
        ((h, 256), BF16), ((held, h, f), BF16), ((held, h, f), BF16),
        ((held, f, h), BF16))
    assert "ptk:moe_experts" in text


# MiMo-V2-Flash's two walks at its serving shape: 32 slots x chunk 128
# (and the decoding rows' one query), 64 query heads, keys of 192 and
# values of 128 a head in pools of split widths ([pages, 16, n_kv x
# width]: no lane or sublane of padding); full layers 4 kv heads over
# 2,048 pages a slot, window layers 8 over the per-slot rings of 17
# pages, a sink a query head
MI = dict(slots=32, mp=2048, ring=17, ps=16, heads=64)


@pytest.mark.parametrize("lq", [CHUNK, 1])
@pytest.mark.parametrize("kind", ["sink_walk", "split_walk"])
def test_paged_walk_mimo(one_chip, on_tpu_branch, lq, kind):
    a = MI
    sink = kind == "sink_walk"
    hkv = 8 if sink else 4
    pages = a["slots"] * (a["ring"] if sink else a["mp"]) + 1
    shapes = [((a["slots"], lq, a["heads"], 192), BF16),
              ((pages, a["ps"], hkv * 192), BF16),
              ((pages, a["ps"], hkv * 128), BF16),
              ((a["slots"], a["mp"]), I32), ((a["slots"],), I32),
              ((a["slots"],), I32)]
    if sink:
        shapes.append(((a["heads"],), BF16))
    text = _compiles_to_kernel(
        lambda *ops: pa.ragged_paged_attention_split(
            *ops, heads=hkv, window=128 if sink else None),
        one_chip, *shapes)
    assert f"ptk:{kind}" in text and "ptk:ragged_walk" not in text


def test_moe_routed_experts_sigmoid(one_chip, monkeypatch):
    """MiMo-V2-Flash's router over its decoding rows: sigmoid scores of
    256 outputs, the top 8 chosen with a selection bias, this chip's 16
    experts of width 2048."""
    h, f, held, rows = 4096, 2048, 16, 32
    monkeypatch.setattr(moe, "_use_kernel", lambda: True)
    text = _compiles_to_kernel(
        lambda x, v, wr, wg, wu, wd, b: moe.routed_experts(
            x, v, wr, wg, wu, wd, top_k=8, scale=1.0, norm_topk=True,
            first=0, scoring="sigmoid", bias=b),
        one_chip, ((rows, h), BF16), ((rows,), jnp.bool_),
        ((h, 256), BF16), ((held, h, f), BF16), ((held, h, f), BF16),
        ((held, f, h), BF16), ((256,), F32))
    assert "ptk:moe_experts" in text


# DeepSeek-V2's latent rows at its serving shape: 16 slots x chunk 128
# (and the decoding rows' one query), 128 heads over rows of 512 + 64
# (640 in the cache: `DeepseekV2Config.cache_row`), rows of max_len
# 16384 in pages of 16 of a pool of 16,385 (read in place: the pool in
# HBM, a DMA a page)
DS = dict(slots=16, chunk=128, heads=128, row=640, latent=512, n=16384,
          pages=16385, ps=16)


@pytest.mark.parametrize("lq", [DS["chunk"], 1])
def test_latent_mla_walk(one_chip, lq):
    a = DS
    text = _compiles_to_kernel(
        lambda q, kv, pt, p, n: mla.mla_walk(
            q, kv, pt, p, n, d_v=a["latent"], scale=0.1147),
        one_chip, ((a["slots"], lq, a["heads"], a["row"]), BF16),
        ((a["pages"], a["ps"], a["row"]), BF16),
        ((a["slots"], a["n"] // a["ps"]), I32),
        ((a["slots"],), I32), ((a["slots"],), I32))
    assert "ptk:mla_walk" in text


# the Keye-VL-2.0 cell's shapes: 8 slots, chunks of 128, 32 query heads
# over 4 kv heads of 128, an indexer of 16 heads over rows of 64 values
# padded to 128, contexts of 32768 in pages of 16, topk 2048
KY = dict(slots=8, chunk=128, heads=32, kv=4, d=128, ih=16, row=128,
          n=32768, pages=16385, ps=16, topk=2048)


def _ky(*dims):
    return tuple(KY.get(d, d) for d in dims)


def test_sparse_index(one_chip):
    text = _compiles_to_kernel(
        sp.sparse_index, one_chip,
        (_ky("slots", "chunk", "ih", "row"), BF16),
        (_ky("slots", "chunk", "ih"), BF16), (_ky("pages", "ps", "row"), BF16),
        ((KY["slots"], KY["n"] // KY["ps"]), I32), (_ky("slots"), I32),
        (_ky("slots"), I32))
    assert "ptk:sparse_index" in text


def test_sparse_select(one_chip):
    text = _compiles_to_kernel(
        lambda k, p, n: sp.sparse_select(k, p, n, topk=KY["topk"]), one_chip,
        ((KY["slots"], KY["n"] // 512, KY["chunk"], 512), I32),
        (_ky("slots"), I32), (_ky("slots"), I32))
    assert "ptk:sparse_select" in text


def test_sparse_walk(one_chip):
    per_query = (_ky("slots", "chunk", 128), I32)
    text = _compiles_to_kernel(
        sp.sparse_walk, one_chip, (_ky("slots", "chunk", "heads", "d"), BF16),
        (_ky("pages", "ps", "kv", "d"), BF16),
        (_ky("pages", "ps", "kv", "d"), BF16),
        ((KY["slots"], KY["n"] // KY["ps"]), I32), (_ky("slots"), I32),
        (_ky("slots"), I32),
        ((KY["slots"], KY["n"] // 512, KY["chunk"], 512), I32), per_query,
        per_query)
    assert "ptk:sparse_walk" in text


@pytest.mark.parametrize("rows", [16 * 128, 16], ids=["step", "decode"])
def test_moe_routed_experts_group_limited(one_chip, rows, monkeypatch):
    """One DeepSeek-V2 expert layer, this chip's 20 of 160 experts at
    hidden 5120 / width 1536, softmax scores, top-6 within 3 of 8
    groups, not renormalised, x 16."""
    h, f, held = 5120, 1536, 20
    monkeypatch.setattr(moe, "_use_kernel", lambda: True)   # as the chip would
    text = _compiles_to_kernel(
        lambda x, v, wr, wg, wu, wd: moe.routed_experts(
            x, v, wr, wg, wu, wd, top_k=6, scale=16.0, norm_topk=False,
            first=0, n_group=8, topk_group=3),
        one_chip, ((rows, h), BF16), ((rows,), jnp.bool_),
        ((h, 160), BF16), ((held, h, f), BF16),
        ((held, h, f), BF16), ((held, f, h), BF16))
    assert "ptk:moe_experts" in text


@pytest.mark.parametrize("rows", [8 * 128, 8], ids=["step", "decode"])
def test_moe_routed_experts_of_width_768(one_chip, rows, monkeypatch):
    """One Keye-VL-2.0 expert layer, this chip's 16 of 128 experts at
    hidden 2048 / width 768, top-8 renormalised: the expert kernel's
    block is 384 columns, twice, not 512 once."""
    h, f, held = 2048, 768, 16
    monkeypatch.setattr(moe, "_use_kernel", lambda: True)   # as the chip would
    text = _compiles_to_kernel(
        lambda x, v, wr, wg, wu, wd: moe.routed_experts(
            x, v, wr, wg, wu, wd, top_k=8, scale=1.0, norm_topk=True,
            first=0),
        one_chip, ((rows, h), BF16), ((rows,), jnp.bool_),
        ((h, 128), BF16), ((held, h, f), BF16), ((held, h, f), BF16),
        ((held, f, h), BF16))
    assert "ptk:moe_experts" in text


@pytest.mark.parametrize("lq", [CHUNK, 1])
def test_paged_walk_int8(one_chip, lq):
    _compiles_to_kernel(
        lambda q, k, v, ks, vs, t, p, n: pa._ragged_attention_kernel(
            q, k, v, t, p, n, None, k_scale=ks, v_scale=vs),
        one_chip, _q(lq), _pool(I8), _pool(I8), SCALES, SCALES, TABLE,
        ROW, ROW)


@pytest.mark.parametrize("lq", [CHUNK, 1])
def test_paged_walk_fp8(one_chip, lq):
    _compiles_to_kernel(
        lambda q, k, v, t, p, n: pa._ragged_attention_kernel(
            q, k, v, t, p, n, None),
        one_chip, _q(lq), _pool(pa.FP8_DTYPE), _pool(pa.FP8_DTYPE),
        TABLE, ROW, ROW)


@pytest.mark.parametrize("lq", [CHUNK, 1])
def test_paged_walk_user_mask(one_chip, lq):
    _compiles_to_kernel(
        lambda q, k, v, t, p, n, m: pa._ragged_attention_kernel(
            q, k, v, t, p, n, m),
        one_chip, _q(lq), _pool(BF16), _pool(BF16), TABLE, ROW, ROW,
        ((B, H, lq, MP * PS), F32))


@pytest.mark.parametrize("lq", [CHUNK, 1])
def test_grouped_walk_bf16(one_chip, on_tpu_branch, lq):
    """The grouped walk at the GPT-3 serving shape (8 slots, 16 heads x
    128, 128 pages; a chunk and one token), through the public op: both
    phases are in the program, and each takes its grid's dynamic bounds
    as leading scalar operands (phase 1 the sharing groups' items and
    the key blocks of the longest shared span, phase 2 the live (row,
    q-block) items and the key blocks of the longest context), then
    its work items and the rows' operands, the page table flat."""
    import re
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (_q(lq), _pool(BF16), _pool(BF16), TABLE, ROW, ROW, ROW, ROW,
             ROW)]
    lowered = jax.jit(pa.ragged_paged_attention_grouped).lower(*args)
    for name in ("grouped_phase1", "ragged_walk"):
        (call,) = [ln for ln in lowered.as_text().splitlines()
                   if f"ptk:{name}" in ln]
        assert re.search(
            rf": \(tensor<i32>, tensor<i32>(, tensor<{B}xi32>){{4}}, "
            rf"tensor<{B * MP}xi32>, ", call)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_grouped_walk_int8(one_chip, on_tpu_branch):
    _compiles_to_kernel(
        pa.ragged_paged_attention_grouped_q8, one_chip, _q(CHUNK),
        _pool(I8), _pool(I8), SCALES, SCALES, TABLE, ROW, ROW, ROW, ROW,
        ROW)


def test_paged_decode_attention(one_chip, on_tpu_branch):
    # the l == 1 decode op: the ragged walk at q_len 1
    _compiles_to_kernel(pa.paged_decode_attention, one_chip, _q(1),
                        _pool(BF16), _pool(BF16), TABLE, ROW)


@pytest.mark.parametrize("l", [CHUNK, 1])
@pytest.mark.parametrize("dt", [BF16, pa.FP8_DTYPE], ids=["bf16", "fp8"])
def test_paged_scatter(one_chip, l, dt):
    _compiles_to_kernel(pa._paged_scatter_kernel, one_chip, _pool(dt),
                        ((B, l, H, D), BF16), ROW, TABLE)


@pytest.mark.parametrize("l", [CHUNK, 1])
def test_paged_scatter_q8(one_chip, l):
    _compiles_to_kernel(pa._paged_scatter_q8_kernel, one_chip, _pool(I8),
                        SCALES, ((B, l, H, D), BF16), ROW, TABLE)


@pytest.mark.parametrize("rows", [B, B * 5, 5],
                         ids=["slots", "verify_BxW", "ragged_rows"])
@pytest.mark.parametrize("dt", [F32, BF16], ids=["f32", "bf16"])
def test_argmax_epilogue(one_chip, on_tpu_branch, rows, dt):
    _compiles_to_kernel(pa.decode_greedy_argmax, one_chip,
                        ((rows, VOCAB), dt))


def test_spec_verify_accept(one_chip, on_tpu_branch):
    _compiles_to_kernel(pa.spec_verify_accept, one_chip,
                        ((B, 5, VOCAB), F32), ((B, 5), I32), ROW,
                        ((B,), jnp.bool_))


@pytest.mark.parametrize("rank,out", [(16, 2048), (64, 2048), (64, 6144)])
def test_lora_delta_paged(one_chip, on_tpu_branch, rank, out):
    _compiles_to_kernel(
        pa.lora_delta_paged, one_chip, ((B, CHUNK, 2048), BF16),
        ((33, 2048, rank), BF16), ((33, rank, out), BF16), ROW,
        ((B,), F32))


def _flash_loss(q, k, v):
    return fa.flash_attention_blhd(q, k, v, causal=True) \
        .astype(F32).sum()


@pytest.mark.parametrize("b,l,h,d", [(16, 1024, 12, 64),
                                     (2, 2048, 16, 128)],
                         ids=["gpt124m_12x64", "gpt1p3b_16x128"])
def test_flash_attention_fwd_bwd(one_chip, b, l, h, d):
    qkv = ((b, l, h, d), BF16)
    _compiles_to_kernel(
        lambda q, k, v: fa.flash_attention_blhd(q, k, v, causal=True),
        one_chip, qkv, qkv, qkv)
    text = _compiles_to_kernel(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                               one_chip, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


def test_flash_attention_mask_dropout(one_chip):
    b, l, h, d = 4, 1024, 12, 64
    qkv = ((b, l, h, d), BF16)

    def loss(q, k, v, kvec, seeds):
        return fa.flash_attention_blhd(
            q, k, v, None, kvec, seeds, dropout_p=0.1).astype(F32).sum()

    _compiles_to_kernel(jax.grad(loss, argnums=(0, 1, 2)), one_chip, qkv,
                        qkv, qkv, ((b, l), F32), ((2,), I32))


@pytest.mark.parametrize("rows,c", [(16 * 1024, 768), (8 * CHUNK, 2048)],
                         ids=["gpt124m_768", "gpt1p3b_2048"])
def test_fused_layer_norm_fwd_bwd(one_chip, rows, c):
    shapes = (((rows, c), BF16), ((c,), BF16), ((c,), BF16))
    _compiles_to_kernel(pln.layer_norm_fused, one_chip, *shapes)
    _compiles_to_kernel(
        jax.grad(lambda x, w, b: pln.layer_norm_fused(x, w, b)
                 .astype(F32).sum(), argnums=(0, 1, 2)),
        one_chip, *shapes)


# -- four chips: the tensor-parallel serving replica's mesh ----------------
# GSPMD cannot partition a Mosaic kernel, so under a mesh every kernel
# wrapper runs per device (ops/pallas `kernel_mesh` / `per_device`). A
# virtual CPU mesh never shows this: off-TPU the jnp references run.

@pytest.mark.parametrize("dp,mp", [(1, 4), (2, 2)])
def test_kernels_under_serving_mesh(topo, on_tpu_branch, monkeypatch,
                                    dp, mp):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.nn.functional import norm as fnorm
    from paddle_tpu.ops.pallas import kernel_mesh
    monkeypatch.setattr(fnorm, "_use_pallas_ln", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(dp, mp), ("dp", "mp"))

    def shaped(shape_dtype, spec):
        return jax.ShapeDtypeStruct(*shape_dtype,
                                    sharding=NamedSharding(mesh, spec))

    heads, whole = P(None, None, "mp", None), P()
    row = shaped(ROW, whole)
    walk_args = [shaped(_q(CHUNK), heads), shaped(_pool(BF16), heads),
                 shaped(_pool(BF16), heads), shaped(TABLE, whole)] \
        + [row] * 5
    with kernel_mesh(mesh, "mp"):
        # GSPMD alone refuses the kernel ...
        with pytest.raises(Exception, match="shard_map"):
            jax.jit(pa._ragged_attention_local).lower(
                *walk_args[:6], None).compile()
        # ... per device it compiles, and the one collective is the
        # all-gather of the head-sharded output
        text = jax.jit(lambda *a: jax.lax.with_sharding_constraint(
            pa.ragged_paged_attention_grouped(*a),
            NamedSharding(mesh, whole))).lower(*walk_args) \
            .compile().as_text()
        assert text.count("tpu_custom_call") >= 2
        assert "all-gather" in text and "all-reduce" not in text
        text = jax.jit(lambda x, w, b: fnorm._ln_fwd(x, w, b, 1, 1e-5)) \
            .lower(shaped(((B, CHUNK, 2048), BF16), whole),
                   shaped(((2048,), BF16), whole),
                   shaped(((2048,), BF16), whole)).compile().as_text()
        assert "tpu_custom_call" in text


# -- trace names: every pallas_call site carries `ptk:<name>` -------------
# The device trace names a Mosaic call by its HLO text. `name=` reaches
# the instruction's name, `metadata=` its `kernel_metadata`; the
# benchmark's per-kernel shares search the event text for `ptk:<name>`.

def _shaped(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "shape") else x, tree)


def _tiny_gpt(hidden, heads, positions):
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=hidden, num_hidden_layers=2,
        num_attention_heads=heads, max_position_embeddings=positions,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    model.to(dtype="bfloat16")
    return model


def test_kernel_names_are_distinct_and_on_every_site():
    import re
    tables = {mod: mod.KERNELS for mod in (pa, fa, pln, moe, mla, sp)}
    names = [n for t in tables.values() for n in t]
    assert len(names) == len(set(names)) == 18
    assert not [(a, b) for a in names for b in names
                if a != b and a in b]
    for mod, table in tables.items():
        with open(mod.__file__) as f:
            src = f.read()
        # a site names its kernel, or picks one of its variants' names
        # (the page walk: `ragged_walk`, `split_walk`, `sink_walk`)
        sites = re.findall(r'\*\*KERNELS\[([^\]]+)\]', src)
        named = [n for site in sites for n in re.findall(r'"(\w+)"', site)]
        assert sorted(named) == sorted(table)       # each name, one site
        assert src.count("pl.pallas_call(") == len(sites)
        for name, kw in table.items():
            assert kw["name"] == name
            assert kw["metadata"]["kernel"] == "ptk:" + name
            # the kernel function's own name stays in the lowered text
            # (chip_smoke.py and the benchmark's train check count it)
            assert callable(getattr(mod, kw["metadata"]["fn"]))


def _lower_unified_step(one_chip, monkeypatch, **engine_kw):
    """The serving step of a tiny GPT (pages and head size as served),
    lowered for the described v5e as the chip traces it."""
    import numpy as np
    from paddle_tpu.nn.functional import norm as fnorm
    from paddle_tpu.serving import ServingEngine, SamplingParams
    model = _tiny_gpt(256, 2, 256)
    model.eval()
    # one step on the CPU (jnp references) fixes the operands' shapes
    monkeypatch.setattr(pa, "_use_kernel", lambda: False)
    eng = ServingEngine(model, num_slots=8, max_len=256, page_size=16,
                        chunk_len=128, attn_impl="kernel", **engine_kw)
    eng.add_request(np.arange(1, 40, dtype=np.int64),
                    SamplingParams(max_new_tokens=2))
    eng.run()
    # a fresh program, traced as the chip traces it
    monkeypatch.setattr(pa, "_use_kernel", lambda: True)
    monkeypatch.setattr(fnorm, "_use_pallas_ln", lambda: True)
    prog = eng._build_unified()
    return prog._jit.lower(*_shaped(
        (prog._state_vals, eng._ct, *eng._unified_args_tail),
        one_chip)), eng._ct[0][0].shape


def test_unified_step_names_its_kernels(one_chip, monkeypatch):
    """The serving step lowered for the described v5e: the walk's two
    phases and the fused LayerNorm carry their `ptk:` names and their
    kernel functions' names."""
    text = _lower_unified_step(one_chip, monkeypatch)[0].as_text()
    for name in ("ragged_walk", "grouped_phase1", "layer_norm_fwd"):
        assert f"ptk:{name}" in text, name
    for fn in ("_ragged_kernel", "_grouped_phase1_kernel",
               "_ln_fwd_kernel"):
        assert fn in text, fn


def test_unified_step_sizes_phase1_sweep_from_its_operands(one_chip,
                                                           monkeypatch):
    """Both phases of the grouped walk take their grids' lengths as
    operands (dynamic bounds: phase 1 the (q-block, group) items of the
    groups that share and the key blocks of the longest shared span,
    ONE step where none does; the walk proper the live (row, q-block)
    items and the key blocks of the longest live context), under no
    conditional; and reading the pools in place costs no copy of one:
    the compiled step copies no more pool-shaped arrays than the step
    of an engine without a prefix cache, whose walk has no phase 1."""
    import re
    lowered, pool = _lower_unified_step(one_chip, monkeypatch)
    text = lowered.as_text()
    assert "stablehlo.case" not in text
    calls = {name: [ln for ln in text.splitlines()
                    if f"ptk:{name}" in ln]
             for name in ("grouped_phase1", "ragged_walk")}
    # once a step program: the walk is a program of its own that both
    # layers call, so the step's set-up traces and lowers it once
    assert [len(v) for v in calls.values()] == [1, 1]
    assert text.count("call @_ragged_attention_local") == 2   # a layer
    # operand types close the line: two scalar bounds lead, then the
    # work items, pos, q_len and the flat page table
    for ln in calls["grouped_phase1"] + calls["ragged_walk"]:
        assert re.search(
            r": \(tensor<i32>, tensor<i32>(, tensor<8xi32>){4}, "
            r"tensor<128xi32>, ", ln)

    shape = ",".join(map(str, pool))
    pool_copy = re.compile(
        rf"= \w+\[{shape}\]\S* (copy|copy-start)\(")
    compiled = lowered.compile().as_text()
    assert "ptk:grouped_phase1" in compiled
    assert " conditional(" not in compiled
    ungrouped = _lower_unified_step(
        one_chip, monkeypatch,
        prefix_cache=False)[0].compile().as_text()
    assert "ptk:grouped_phase1" not in ungrouped
    assert len(pool_copy.findall(compiled)) \
        <= len(pool_copy.findall(ungrouped))


def test_unified_step_of_a_latent_model_compiles_with_its_kernels(
        one_chip, monkeypatch):
    """The serving step of a small DeepSeek-V2 in bfloat16 (real rope
    part and head sizes, a latent of 192 so that the cached row is
    padded 256, pages of 16, chunk 128, rows of 2048 keys), lowered and
    COMPILED for the described v5e as the chip traces it: the walk
    twice a layer (chunk rows, decoding rows), the expert kernel, no
    conditional, no ragged walk, and no `[slots, max_len, row]` view of
    the pool anywhere in it."""
    import warnings
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import DeepseekV2Config, DeepseekV2ForCausalLM
    from paddle_tpu.serving import ServingEngine, SamplingParams
    paddle.seed(0)
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=8, q_lora_rank=128,
        kv_lora_rank=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_routed_experts=16, num_experts_per_tok=3,
        n_group=4, topk_group=2, ep_size=4, dtype="bfloat16",
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096}))
    model.eval()
    for mod in (mla, moe):
        monkeypatch.setattr(mod, "_use_kernel", lambda: False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = ServingEngine(model, num_slots=8, max_len=2048, page_size=16,
                            chunk_len=128)
    eng.add_request(np.arange(1, 40, dtype=np.int64),
                    SamplingParams(max_new_tokens=2))
    eng.run()
    for mod in (mla, moe):
        monkeypatch.setattr(mod, "_use_kernel", lambda: True)
    prog = eng._build_unified()
    lowered = prog._jit.lower(*_shaped(
        (prog._state_vals, eng._ct, *eng._unified_args_tail), one_chip))
    text = lowered.as_text()
    assert "stablehlo.case" not in text
    for name, calls in (("mla_walk", 4), ("moe_experts", 1)):
        assert sum(f'ptk:{name}' in ln
                   for ln in text.splitlines()) == calls, name
    assert "ptk:ragged_walk" not in text and "ptk:mla_project" in text
    compiled = lowered.compile().as_text()
    assert " conditional(" not in compiled
    assert "%mla_walk." in compiled
    # the pool's pages are read in place: nothing gathers a slot's
    # max_len view of it (rows of 192 + 64 values fill 256)
    assert "bf16[8,2048,256]" not in compiled
    assert "bf16[8,128,16,256]" not in compiled


def test_unified_step_of_a_sparse_model_compiles_with_its_kernels(
        one_chip, monkeypatch):
    """The serving step of a small Keye-VL-2.0 language model in
    bfloat16 (real head and indexer sizes: 8 query heads to each of two
    kv heads of 128, an indexer of 16 heads x 64, topk 256; pages of 16,
    chunk 128, rows of 2048 keys), lowered and COMPILED for the
    described v5e as the chip traces it: the three kernels once a step
    program (a jit of their own, called by both layers), the expert
    kernel, no conditional, no ragged walk, and no `[slots, max_len,
    ...]` view of any of the three pools anywhere in it."""
    import warnings
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import KeyeVL2Config, KeyeVL2ForCausalLM
    from paddle_tpu.serving import ServingEngine, SamplingParams
    paddle.seed(0)
    model = KeyeVL2ForCausalLM(KeyeVL2Config(
        vocab_size=512, hidden_size=256, moe_intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=16, num_key_value_heads=2,
        head_dim=128, num_experts=16, num_experts_per_tok=3, ep_size=4,
        dtype="bfloat16",
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "topk": 256}))
    model.eval()
    for mod in (sp, moe):
        monkeypatch.setattr(mod, "_use_kernel", lambda: False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = ServingEngine(model, num_slots=8, max_len=2048, page_size=16,
                            chunk_len=128)
    eng.add_request(np.arange(1, 40, dtype=np.int64),
                    SamplingParams(max_new_tokens=2))
    eng.run()
    for mod in (sp, moe):
        monkeypatch.setattr(mod, "_use_kernel", lambda: True)
    prog = eng._build_unified()
    lowered = prog._jit.lower(*_shaped(
        (prog._state_vals, eng._ct, *eng._unified_args_tail), one_chip))
    text = lowered.as_text()
    assert "stablehlo.case" not in text
    for name, calls in (("sparse_index", 1), ("sparse_select", 1),
                        ("sparse_walk", 1), ("moe_experts", 2)):
        assert sum(f'ptk:{name}' in ln
                   for ln in text.splitlines()) == calls, name
    assert "ptk:ragged_walk" not in text and "ptk:mla_walk" not in text
    compiled = lowered.compile().as_text()
    assert " conditional(" not in compiled
    for name in ("sparse_index", "sparse_select", "sparse_walk"):
        assert f"%{name}." in compiled, name
    # the pools' pages are read in place: nothing gathers a slot's
    # max_len view of K, V or the indexer's rows
    assert "bf16[8,2048,2,128]" not in compiled
    assert "bf16[8,2048,128]" not in compiled
    assert "bf16[8,128,16,2,128]" not in compiled
    # ([8, 128, 16, 128] is also the indexer's padded queries: 8 slots
    # x 128 positions x 16 heads x 128 lanes)


def test_names_reach_the_compiled_instruction(one_chip, on_tpu_branch):
    """What the device trace shows is the COMPILED instruction: `name=`
    is its name (`%ragged_walk.N`, so `benchmark/trace.py` keys two
    kernels with one result shape apart), `metadata=` its
    `kernel_metadata`."""
    text = _compiles_to_kernel(
        pa.ragged_paged_attention_grouped, one_chip, _q(1), _pool(BF16),
        _pool(BF16), TABLE, ROW, ROW, ROW, ROW, ROW)
    for name in ("ragged_walk", "grouped_phase1"):
        assert f'"kernel":"ptk:{name}"' in text, name
        assert f"%{name}." in text, name


def test_scatter_write_is_named(one_chip):
    # the megakernel's KV write: not in the default engine's step
    text = jax.jit(pa._paged_scatter_kernel).lower(*_shaped(
        [jnp.zeros(s, d) for s, d in
         (_pool(BF16), ((B, 1, H, D), BF16), ROW, TABLE)],
        one_chip)).as_text()
    assert "ptk:scatter_write" in text and "_scatter_write_kernel" in text


def test_train_step_names_its_kernels(one_chip, monkeypatch):
    """The train step lowered for the described v5e holds flash
    attention forward, dq, dkv and fused LayerNorm forward and backward
    under their `ptk:` names, and the kernel functions' own names."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import jit
    from paddle_tpu.nn.functional import attention as fattn
    from paddle_tpu.nn.functional import norm as fnorm
    monkeypatch.setattr(fattn, "_use_pallas", lambda q_len, d: True)
    monkeypatch.setattr(fnorm, "_use_pallas_ln", lambda: True)
    model = _tiny_gpt(128, 2, 128)
    optimizer = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          weight_decay=0.01)
    step = jit.compile_train_step(
        lambda ids, labels: model(ids, labels=labels), model, optimizer)
    ids = np.zeros((2, 128), np.int64)
    args = ([p._value for p in step.params],
            [b._value for b in step.buffers], step.states, step.gstate,
            np.float32(1e-4), paddle.core.random.next_key_host(),
            jnp.asarray(ids), jnp.asarray(ids))
    text = step._step.lower(*_shaped(args, one_chip)).as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "layer_norm_fwd",
                 "layer_norm_bwd"):
        assert f"ptk:{name}" in text, name
    for fn in ("_fa_kernel", "_fa_dq_kernel", "_fa_dkv_kernel",
               "_ln_fwd_kernel", "_ln_bwd_kernel"):
        assert fn in text, fn


@pytest.mark.parametrize("kv_dtype, dt", [("fp", BF16), ("int8", I8)])
def test_spill_gather_at_its_widest(one_chip, kv_dtype, dt):
    """The host tier's gather (`ServingEngine._build_swap_out`) at the
    GPT-3 1.3B serving shape, 24 layers, its widest piece: one array a
    page comes out, in the pool's dtype, and the program's temporaries
    stay under the piece's own size (0.1 GB)."""
    import types
    from paddle_tpu.serving import engine as engine_mod
    width = engine_mod.SPILL_WIDTHS[0]
    so = engine_mod.ServingEngine._build_swap_out(
        types.SimpleNamespace(kv_dtype=kv_dtype))

    def shaped(spec):
        return jax.ShapeDtypeStruct(*spec, sharding=one_chip)
    scales = shaped(SCALES) if kv_dtype == "int8" else None
    ct = tuple((shaped(_pool(dt)), shaped(_pool(dt)), scales, scales)
               for _ in range(24))
    compiled = so.lower(ct, shaped(((width,), I32))).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    pages = [o for o in out if o.shape == (24, 2, PS, H, D)]
    assert len(pages) == width and all(o.dtype == dt for o in pages)
    assert len(out) == width * (2 if kv_dtype == "int8" else 1)
    page_bytes = 24 * 2 * PS * H * D * jnp.dtype(dt).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 1.1 * width * page_bytes
