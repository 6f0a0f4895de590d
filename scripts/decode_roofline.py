"""Decode roofline decomposition: where do the 2.95 ms/step go?

Times isolated compiled pieces of the GPT-124M decode step (bs16,
max_len 640) to attribute per-step time to weight streaming, KV-cache
attention, LM head, and while-loop/carry overhead. Prints a JSON report.

Reference analogue: the reference profiles its fused decoder with
nvprof over fused_multi_transformer_op.cu; here the XLA cost comes
apart the same way.
"""
from __future__ import annotations

import json
import time

import numpy as np


def timeit(fn, *args, reps=10, batches=5, warmup=3):
    """min-of-batches mean: the repo's convention for host timings
    that share the machine with other work (see decode_bench.py /
    op_bench.py)."""
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def timeit_varying(fn, make_args, reps=10, batches=5, warmup=3):
    """Per-call distinct args (no two timed calls are identical);
    args are pre-built outside the timed window."""
    import jax
    arg_sets = [make_args(i) for i in range(batches * reps + warmup)]
    jax.block_until_ready(arg_sets)
    it = iter(arg_sets)
    for _ in range(warmup):
        out = fn(*next(it))
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        outs = [fn(*next(it)) for _ in range(reps)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def timeit_chained(fn, x, cks, cvs, p, reps=10, batches=5, warmup=3):
    """For donated-cache steps: thread the output caches back in so the
    donated buffers stay alive across reps."""
    import jax
    for _ in range(warmup):
        out, cks, cvs = fn(x, cks, cvs, p)
    jax.block_until_ready((out, cks, cvs))
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(reps):
            out, cks, cvs = fn(x, cks, cvs, p)
        jax.block_until_ready((out, cks, cvs))
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def main():
    import jax
    import jax.numpy as jnp

    B, LMAX, H, NH, D, NL, V = 16, 640, 768, 12, 64, 12, 50304
    FF = 4 * H
    dt = jnp.bfloat16
    key = jax.random.PRNGKey(0)

    def rnd(*shape):
        nonlocal key
        key, k = jax.random.split(key)
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    # per-layer weights
    Wqkv = [rnd(H, 3 * H) for _ in range(NL)]
    Wout = [rnd(H, H) for _ in range(NL)]
    W1 = [rnd(H, FF) for _ in range(NL)]
    W2 = [rnd(FF, H) for _ in range(NL)]
    E = rnd(V, H)
    ck = [rnd(B, LMAX, NH, D) for _ in range(NL)]
    cv = [rnd(B, LMAX, NH, D) for _ in range(NL)]
    x0 = rnd(B, 1, H)
    pos = jnp.int32(400)

    def ln(x):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + 1e-5)).astype(x.dtype)

    def attend(q, k_buf, v_buf, p):
        # q [B,1,NH,D]; mask over cache axis
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        kf = k_buf.transpose(0, 2, 3, 1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhdk->bhqk", qf, kf) / np.sqrt(D)
        j = jnp.arange(LMAX)[None, None, None, :]
        s = jnp.where(j <= p, s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        vf = v_buf.transpose(0, 2, 1, 3).astype(jnp.float32)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, vf)
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    def layer_step(x, i, cks, cvs, p, with_attn=True):
        h = ln(x)
        qkv = h.reshape(B, H) @ Wqkv[i]
        q, kn, vn = jnp.split(qkv.reshape(B, 1, NH, 3 * D), 3, axis=-1)
        ckb = jax.lax.dynamic_update_slice(
            cks[i], kn, (0, p.astype(jnp.int32), 0, 0))
        cvb = jax.lax.dynamic_update_slice(
            cvs[i], vn, (0, p.astype(jnp.int32), 0, 0))
        if with_attn:
            o = attend(q, ckb, cvb, p)
        else:
            o = q
        x = x + (o.reshape(B, H) @ Wout[i]).reshape(B, 1, H)
        h = ln(x)
        y = jax.nn.gelu(h.reshape(B, H) @ W1[i], approximate=True)
        x = x + (y @ W2[i]).reshape(B, 1, H)
        return x, ckb, cvb

    def full_step(x, cks, cvs, p):
        ncks, ncvs = [], []
        for i in range(NL):
            x, a, b = layer_step(x, i, cks, cvs, p)
            ncks.append(a)
            ncvs.append(b)
        logits = (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1)
        return nxt, ncks, ncvs

    def noattn_step(x, cks, cvs, p):
        ncks, ncvs = [], []
        for i in range(NL):
            x, a, b = layer_step(x, i, cks, cvs, p, with_attn=False)
            ncks.append(a)
            ncvs.append(b)
        logits = (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1)
        return nxt, ncks, ncvs

    def mlp_only(x, step):
        # step varies per call, so no two timed calls are identical
        x = x + step.astype(x.dtype) * 0
        for i in range(NL):
            h = ln(x)
            qkv = h.reshape(B, H) @ Wqkv[i]
            x = x + (qkv[:, :H]).reshape(B, 1, H)
            h = ln(x)
            y = jax.nn.gelu(h.reshape(B, H) @ W1[i], approximate=True)
            x = x + (y @ W2[i]).reshape(B, 1, H)
        return (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)

    def attn_only(cks, cvs, p, step):
        q = (x0 + step.astype(x0.dtype) * 0).reshape(B, 1, NH, D)
        outs = []
        for i in range(NL):
            outs.append(attend(q, cks[i], cvs[i], p))
        return sum(outs)

    import sys
    only = sys.argv[1] if len(sys.argv) > 1 else None
    report = {}

    def note(k, v):
        report[k] = v
        print(f"  {k}: {v}", flush=True)

    if only != "layout":
        # (1) standalone full step, donated caches (true in-place)
        step_d = jax.jit(full_step, donate_argnums=(1, 2))
        t = timeit_chained(step_d, x0, [jnp.copy(a) for a in ck],
                           [jnp.copy(a) for a in cv], pos)
        note("standalone_step_donated_ms", round(t * 1e3, 3))

        # (2) standalone step, no donation (forces full cache copies)
        step_nd = jax.jit(full_step)
        t = timeit(step_nd, x0, list(ck), list(cv), pos)
        note("standalone_step_undonated_ms", round(t * 1e3, 3))

        # (3) weights-only (no attention, no cache read)
        t = timeit_chained(jax.jit(noattn_step, donate_argnums=(1, 2)),
                           x0, [jnp.copy(a) for a in ck],
                           [jnp.copy(a) for a in cv], pos)
        note("step_no_attention_ms", round(t * 1e3, 3))

        # (4) matmuls only (no cache update at all)
        mfn = jax.jit(mlp_only)
        t = timeit_varying(mfn, lambda i: (x0, jnp.float32(i)))
        note("matmuls_only_ms", round(t * 1e3, 3))

        # (5) attention reads only
        afn = jax.jit(attn_only)
        t = timeit_varying(afn, lambda i: (ck, cv, pos, jnp.float32(i)),
                           reps=6, batches=5)
        note("attention_only_ms", round(t * 1e3, 3))

        # (6) loop of 64 steps as one program (the real decode shape)
        def loop64(x, cks, cvs, p):
            cks = list(cks)
            cvs = list(cvs)

            def body(carry, _):
                x, cks, cvs, p = carry
                nxt, cks, cvs = full_step(x, tuple(cks), tuple(cvs), p)
                # feed a token-derived x back in (as real decode does via the
                # embedding) so no layer work is loop-invariant
                x2 = jnp.broadcast_to(
                    ((nxt % 997).astype(jnp.float32) * 1e-3)
                    .astype(x.dtype)[:, None, None], x.shape)
                return (x2, tuple(cks), tuple(cvs), p + 1), nxt

            (x, cks, cvs, p), toks = jax.lax.scan(
                body, (x, tuple(cks), tuple(cvs), p), None, length=64)
            return toks, list(cks), list(cvs)

        t = timeit_chained(jax.jit(loop64, donate_argnums=(1, 2)),
                           x0, [jnp.copy(a) for a in ck],
                           [jnp.copy(a) for a in cv], pos, reps=5)
        note("loop64_per_step_ms", round(t / 64 * 1e3, 3))

        # (7) weights as ARGUMENTS (the generator's shape: state passed to
        # jit, not closed over) — isolates constant-layout specialization
        Wflat = Wqkv + Wout + W1 + W2 + [E]

        def loop64_args(ws, x, cks, cvs, p):
            wqkv, wout, w1, w2 = (ws[:NL], ws[NL:2 * NL], ws[2 * NL:3 * NL],
                                  ws[3 * NL:4 * NL])
            e = ws[-1]

            def layer(x, i, cks, cvs, p):
                h = ln(x)
                qkv = h.reshape(B, H) @ wqkv[i]
                q, kn, vn = jnp.split(qkv.reshape(B, 1, NH, 3 * D), 3,
                                      axis=-1)
                ckb = jax.lax.dynamic_update_slice(
                    cks[i], kn, (0, p.astype(jnp.int32), 0, 0))
                cvb = jax.lax.dynamic_update_slice(
                    cvs[i], vn, (0, p.astype(jnp.int32), 0, 0))
                o = attend(q, ckb, cvb, p)
                x = x + (o.reshape(B, H) @ wout[i]).reshape(B, 1, H)
                h = ln(x)
                y = jax.nn.gelu(h.reshape(B, H) @ w1[i], approximate=True)
                x = x + (y @ w2[i]).reshape(B, 1, H)
                return x, ckb, cvb

            def body(carry, _):
                x, cks, cvs, p = carry
                ncks, ncvs = [], []
                for i in range(NL):
                    x, a_, b_ = layer(x, i, cks, cvs, p)
                    ncks.append(a_)
                    ncvs.append(b_)
                logits = (ln(x).reshape(B, H) @ e.T).astype(jnp.float32)
                nxt = jnp.argmax(logits, axis=-1)
                x2 = jnp.broadcast_to(
                    ((nxt % 997).astype(jnp.float32) * 1e-3)
                    .astype(x.dtype)[:, None, None], x.shape)
                return (x2, tuple(ncks), tuple(ncvs), p + 1), nxt

            (x, cks, cvs, p), toks = jax.lax.scan(
                body, (x, tuple(cks), tuple(cvs), p), None, length=64)
            return toks, list(cks), list(cvs)

        fn7 = jax.jit(loop64_args, donate_argnums=(2, 3))
        cks7 = [jnp.copy(a) for a in ck]
        cvs7 = [jnp.copy(a) for a in cv]
        for _ in range(2):
            toks, cks7, cvs7 = fn7(Wflat, x0, cks7, cvs7, pos)
        jax.block_until_ready((toks, cks7, cvs7))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            toks, cks7, cvs7 = fn7(Wflat, x0, cks7, cvs7, pos)
            jax.block_until_ready((toks, cks7, cvs7))
            best = min(best, time.perf_counter() - t0)
        note("loop64_weights_as_args_per_step_ms", round(best / 64 * 1e3, 3))

        # (8) logits head alone in the two layouts: [H,V] constant vs
        # [V,H] argument with transpose (the generator's tied embedding)
        h_in = rnd(B, H)

        def head_t(w, h, i):
            return ((h + i.astype(h.dtype) * 0) @ w.T).astype(jnp.float32)

        Evh = rnd(V, H)
        fn8 = jax.jit(head_t)
        t = timeit_varying(fn8, lambda i: (Evh, h_in, jnp.float32(i)))
        note("lm_head_arg_transposed_ms", round(t * 1e3, 3))

        Ehv = rnd(H, V)

        def head_n(w, h, i):
            return ((h + i.astype(h.dtype) * 0) @ w).astype(jnp.float32)

        fn8b = jax.jit(head_n)
        t = timeit_varying(fn8b, lambda i: (Ehv, h_in, jnp.float32(i)))
        note("lm_head_arg_contiguous_ms", round(t * 1e3, 3))

    # (9) cache layout variant: K/V stored [B, H, L, D] (attention
    # contracts over L; no transposed reads) — candidate layout for
    # nlp/generation.py if it beats the [B, L, H, D] baseline
    ck9 = [jnp.transpose(a, (0, 2, 1, 3)) for a in ck]   # [B,H,L,D]
    cv9 = [jnp.transpose(a, (0, 2, 1, 3)) for a in cv]

    def attend_bhld(q, k_buf, v_buf, p):
        # q [B,1,NH,D] -> [B,H,1,D]; cache already [B,H,L,D]
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       k_buf.astype(jnp.float32)) / np.sqrt(D)
        j = jnp.arange(LMAX)[None, None, None, :]
        s = jnp.where(j <= p, s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", a,
                       v_buf.astype(jnp.float32))
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    def loop64_bhld(x, cks, cvs, p):
        def layer(x, i, cks, cvs, p):
            h = ln(x)
            qkv = h.reshape(B, H) @ Wqkv[i]
            q, kn, vn = jnp.split(qkv.reshape(B, 1, NH, 3 * D), 3,
                                  axis=-1)
            kn = kn.transpose(0, 2, 1, 3)   # [B,H,1,D]
            vn = vn.transpose(0, 2, 1, 3)
            ckb = jax.lax.dynamic_update_slice(
                cks[i], kn.astype(cks[i].dtype),
                (0, 0, p.astype(jnp.int32), 0))
            cvb = jax.lax.dynamic_update_slice(
                cvs[i], vn.astype(cvs[i].dtype),
                (0, 0, p.astype(jnp.int32), 0))
            o = attend_bhld(q, ckb, cvb, p)
            x = x + (o.reshape(B, H) @ Wout[i]).reshape(B, 1, H)
            h = ln(x)
            y = jax.nn.gelu(h.reshape(B, H) @ W1[i], approximate=True)
            x = x + (y @ W2[i]).reshape(B, 1, H)
            return x, ckb, cvb

        def body(carry, _):
            x, cks, cvs, p = carry
            ncks, ncvs = [], []
            for i in range(NL):
                x, a_, b_ = layer(x, i, cks, cvs, p)
                ncks.append(a_)
                ncvs.append(b_)
            logits = (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)
            nxt = jnp.argmax(logits, axis=-1)
            x2 = jnp.broadcast_to(
                ((nxt % 997).astype(jnp.float32) * 1e-3)
                .astype(x.dtype)[:, None, None], x.shape)
            return (x2, tuple(ncks), tuple(ncvs), p + 1), nxt

        (x, cks, cvs, p), toks = jax.lax.scan(
            body, (x, tuple(cks), tuple(cvs), p), None, length=64)
        return toks, list(cks), list(cvs)

    t = timeit_chained(jax.jit(loop64_bhld, donate_argnums=(1, 2)),
                       x0, [jnp.copy(a) for a in ck9],
                       [jnp.copy(a) for a in cv9], pos, reps=5)
    note("loop64_bhld_layout_per_step_ms", round(t / 64 * 1e3, 3))

    # (10) int8 K/V with in-einsum dequant at [B,H,L,D] (does XLA fuse
    # the convert into the attention reads when the layout is direct?)
    ck10 = [jnp.clip(jnp.round(a.astype(jnp.float32) * 64), -127,
                     127).astype(jnp.int8) for a in ck9]
    cv10 = [jnp.clip(jnp.round(a.astype(jnp.float32) * 64), -127,
                     127).astype(jnp.int8) for a in cv9]

    svec_h = (jnp.full((NH,), 1.0 / 64, jnp.float32)
              .reshape(1, NH, 1, 1))   # per-head consts, [1,H,1,1]

    def attend_q8(q, k8, v8, p):
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       k8.astype(jnp.float32) * svec_h) / np.sqrt(D)
        j = jnp.arange(LMAX)[None, None, None, :]
        s = jnp.where(j <= p, s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", a,
                       v8.astype(jnp.float32) * svec_h)
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    def loop64_q8(x, cks, cvs, p):
        def layer(x, i, cks, cvs, p):
            h = ln(x)
            qkv = h.reshape(B, H) @ Wqkv[i]
            q, kn, vn = jnp.split(qkv.reshape(B, 1, NH, 3 * D), 3,
                                  axis=-1)
            kn8 = jnp.clip(jnp.round(
                kn.transpose(0, 2, 1, 3).astype(jnp.float32) * 64),
                -127, 127).astype(jnp.int8)
            vn8 = jnp.clip(jnp.round(
                vn.transpose(0, 2, 1, 3).astype(jnp.float32) * 64),
                -127, 127).astype(jnp.int8)
            ckb = jax.lax.dynamic_update_slice(
                cks[i], kn8, (0, 0, p.astype(jnp.int32), 0))
            cvb = jax.lax.dynamic_update_slice(
                cvs[i], vn8, (0, 0, p.astype(jnp.int32), 0))
            o = attend_q8(q, ckb, cvb, p)
            x = x + (o.reshape(B, H) @ Wout[i]).reshape(B, 1, H)
            h = ln(x)
            y = jax.nn.gelu(h.reshape(B, H) @ W1[i], approximate=True)
            x = x + (y @ W2[i]).reshape(B, 1, H)
            return x, ckb, cvb

        def body(carry, _):
            x, cks, cvs, p = carry
            ncks, ncvs = [], []
            for i in range(NL):
                x, a_, b_ = layer(x, i, cks, cvs, p)
                ncks.append(a_)
                ncvs.append(b_)
            logits = (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)
            nxt = jnp.argmax(logits, axis=-1)
            x2 = jnp.broadcast_to(
                ((nxt % 997).astype(jnp.float32) * 1e-3)
                .astype(x.dtype)[:, None, None], x.shape)
            return (x2, tuple(ncks), tuple(ncvs), p + 1), nxt

        (x, cks, cvs, p), toks = jax.lax.scan(
            body, (x, tuple(cks), tuple(cvs), p), None, length=64)
        return toks, list(cks), list(cvs)

    t = timeit_chained(jax.jit(loop64_q8, donate_argnums=(1, 2)),
                       x0, ck10, cv10, pos, reps=5)
    note("loop64_kv_int8_bhld_headscale_per_step_ms", round(t / 64 * 1e3, 3))

    # (11) int8 K/V in the ORIGINAL [B,L,H,D] layout with a constant
    # per-head scale vector (the production shape: does the dequant
    # still fuse when the scale is a [H] constant broadcast?)
    svec = jnp.full((NH,), 1.0 / 64, jnp.float32)   # per-head consts
    ck11 = [jnp.clip(jnp.round(a.astype(jnp.float32) * 64), -127,
                     127).astype(jnp.int8) for a in ck]
    cv11 = [jnp.clip(jnp.round(a.astype(jnp.float32) * 64), -127,
                     127).astype(jnp.int8) for a in cv]

    def attend_q8_blhd(q, k8, v8, p):
        kf = (k8.astype(jnp.float32)
              * svec[None, None, :, None]).transpose(0, 2, 3, 1)
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhdk->bhqk", qf, kf) / np.sqrt(D)
        j = jnp.arange(LMAX)[None, None, None, :]
        s = jnp.where(j <= p, s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        vf = (v8.astype(jnp.float32)
              * svec[None, None, :, None]).transpose(0, 2, 1, 3)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, vf)
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    def loop64_q8_blhd(x, cks, cvs, p):
        def layer(x, i, cks, cvs, p):
            h = ln(x)
            qkv = h.reshape(B, H) @ Wqkv[i]
            q, kn, vn = jnp.split(qkv.reshape(B, 1, NH, 3 * D), 3,
                                  axis=-1)
            kn8 = jnp.clip(jnp.round(
                kn.astype(jnp.float32)
                / svec[None, None, :, None]), -127,
                127).astype(jnp.int8)
            vn8 = jnp.clip(jnp.round(
                vn.astype(jnp.float32)
                / svec[None, None, :, None]), -127,
                127).astype(jnp.int8)
            ckb = jax.lax.dynamic_update_slice(
                cks[i], kn8, (0, p.astype(jnp.int32), 0, 0))
            cvb = jax.lax.dynamic_update_slice(
                cvs[i], vn8, (0, p.astype(jnp.int32), 0, 0))
            o = attend_q8_blhd(q, ckb, cvb, p)
            x = x + (o.reshape(B, H) @ Wout[i]).reshape(B, 1, H)
            h = ln(x)
            y = jax.nn.gelu(h.reshape(B, H) @ W1[i], approximate=True)
            x = x + (y @ W2[i]).reshape(B, 1, H)
            return x, ckb, cvb

        def body(carry, _):
            x, cks, cvs, p = carry
            ncks, ncvs = [], []
            for i in range(NL):
                x, a_, b_ = layer(x, i, cks, cvs, p)
                ncks.append(a_)
                ncvs.append(b_)
            logits = (ln(x).reshape(B, H) @ E.T).astype(jnp.float32)
            nxt = jnp.argmax(logits, axis=-1)
            x2 = jnp.broadcast_to(
                ((nxt % 997).astype(jnp.float32) * 1e-3)
                .astype(x.dtype)[:, None, None], x.shape)
            return (x2, tuple(ncks), tuple(ncvs), p + 1), nxt

        (x, cks, cvs, p), toks = jax.lax.scan(
            body, (x, tuple(cks), tuple(cvs), p), None, length=64)
        return toks, list(cks), list(cvs)

    t = timeit_chained(jax.jit(loop64_q8_blhd, donate_argnums=(1, 2)),
                       x0, ck11, cv11, pos, reps=5)
    note("loop64_kv_int8_blhd_headscale_per_step_ms",
         round(t / 64 * 1e3, 3))

    # (12) paged decode attention A/B at the same shapes: the gather
    # impl materializes each row's [max_pages * page_size] logical view
    # per layer; the ragged kernel walks the page table and streams
    # only live pages (on CPU this times its pure-JAX reference — run
    # on the chip for the real number)
    from paddle_tpu.ops.pallas.paged_attention import \
        paged_decode_attention
    from paddle_tpu.nlp.generation import _paged_gather_fwd
    PS = 16
    MP = LMAX // PS
    NPAGES = B * MP + 1
    kpool = rnd(NPAGES, PS, NH, D)
    vpool = rnd(NPAGES, PS, NH, D)
    ptab = jnp.asarray(
        np.arange(1, B * MP + 1, dtype=np.int32).reshape(B, MP))
    posv = jnp.full((B,), 400, jnp.int32)
    qrow = rnd(B, 1, NH, D)

    def paged_gather_attend(q, kp_, vp_, pt_, p_):
        kf = _paged_gather_fwd(kp_, pt_)
        vf = _paged_gather_fwd(vp_, pt_)
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)
        s = jnp.einsum("bhqd,bkhd->bhqk", qf,
                       kf.astype(jnp.float32)) / np.sqrt(D)
        j = jnp.arange(MP * PS)[None, None, None, :]
        s = jnp.where(j <= p_[:, None, None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", a, vf.astype(jnp.float32))
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    t = timeit(jax.jit(paged_gather_attend), qrow, kpool, vpool, ptab,
               posv)
    note("paged_attn_gather_ms", round(t * 1e3, 3))
    t = timeit(jax.jit(paged_decode_attention), qrow, kpool, vpool,
               ptab, posv)
    note("paged_attn_kernel_ms", round(t * 1e3, 3))

    # (13) ragged-mix A/B — the unified-step attention shape: half the
    # batch decoding (q_len 1), half mid-prefill (q_len = W), over the
    # same pools. "unified" is ONE ragged invocation; "alternating" is
    # the old two-family shape — the single-token kernel over the
    # decode rows plus one batch-1 chunk attend per prefill row (what
    # an engine step used to dispatch). On CPU this times the pure-JAX
    # references; run on the chip for the kernel's dead-block skipping.
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention
    W = 16
    qlen_mix = np.ones((B,), np.int32)
    qlen_mix[B // 2:] = W
    qlen_mixv = jnp.asarray(qlen_mix)
    qrag = rnd(B, W, NH, D)

    t = timeit(jax.jit(ragged_paged_attention), qrag, kpool, vpool,
               ptab, posv, qlen_mixv)
    note("ragged_mix_unified_ms", round(t * 1e3, 3))

    def alternating(qr, kp_, vp_, pt_, p_):
        # decode family: one single-token kernel call over the
        # decoding half; prefill family: one batch-1 W-wide gathered
        # attend per mid-prefill row (timing shape of the old chunk
        # programs — the window math differs per query but the cost
        # does not)
        outs = [paged_decode_attention(
            qr[:B // 2, :1], kp_, vp_, pt_[:B // 2], p_[:B // 2])]
        for b in range(B // 2, B):
            outs.append(paged_gather_attend(
                qr[b:b + 1], kp_, vp_, pt_[b:b + 1], p_[b:b + 1]))
        return outs

    t = timeit(jax.jit(alternating), qrag, kpool, vpool, ptab, posv)
    note("ragged_mix_alternating_ms", round(t * 1e3, 3))

    # (14) quantized-pool A/B at the same ragged mix: the int8 lane
    # streams HALF the KV bytes per page (codes + rowwise scales vs
    # fp16/32 values) with dequant fused into the softmax loop — on
    # HBM-bound hardware the decode step's dominant stream halves. On
    # CPU this times the pure-JAX q8 reference (gather + dequantize),
    # so treat the CPU delta as op overhead, not the HBM win; run on
    # the chip for the real number.
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention_q8
    from paddle_tpu.nlp.generation import quantize_kv_rowwise
    kcodes, kscales = quantize_kv_rowwise(kpool)
    vcodes, vscales = quantize_kv_rowwise(vpool)
    t = timeit(jax.jit(ragged_paged_attention_q8), qrag, kcodes,
               vcodes, kscales, vscales, ptab, posv, qlen_mixv)
    note("ragged_mix_unified_int8_ms", round(t * 1e3, 3))

    # (15) grouped-vs-flat walk at a HIGH-PREFIX-SHARE decode mix:
    # every row decodes (q_len 1) and ALL rows share their first
    # MP//2 physical pages (one group — the system-prompt shape). The
    # flat walk streams the shared span B times per step, the grouped
    # walk once: on HBM-bound hardware the delta approaches
    # (B-1)/B x shared-fraction of the KV stream. On CPU both time
    # the SAME pure-JAX reference (grouping is an HBM hint, not a
    # math change), so the CPU delta is op overhead; run on the chip
    # for the real number.
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention_grouped
    SHARED = MP // 2
    ptab_sh = np.asarray(ptab).copy()
    ptab_sh[:, :SHARED] = ptab_sh[0, :SHARED]
    ptab_shv = jnp.asarray(ptab_sh)
    qlen_dec = jnp.ones((B,), jnp.int32)
    gid = jnp.zeros((B,), jnp.int32)
    gld = jnp.zeros((B,), jnp.int32)
    gcn = jnp.asarray([SHARED] + [0] * (B - 1), jnp.int32)
    t = timeit(jax.jit(ragged_paged_attention), qrag[:, :1], kpool,
               vpool, ptab_shv, posv, qlen_dec)
    note("shared_prefix_flat_ms", round(t * 1e3, 3))
    t = timeit(jax.jit(ragged_paged_attention_grouped), qrag[:, :1],
               kpool, vpool, ptab_shv, posv, qlen_dec, gid, gld, gcn)
    note("shared_prefix_grouped_ms", round(t * 1e3, 3))

    # roofline bookkeeping
    wbytes = sum(int(np.prod(w.shape)) for w in Wqkv + Wout + W1 + W2) * 2
    ebytes = int(np.prod(E.shape)) * 2
    kvbytes = 2 * NL * B * LMAX * NH * D * 2
    report["weight_bytes_mb"] = round((wbytes + ebytes) / 1e6, 1)
    report["kv_bytes_mb"] = round(kvbytes / 1e6, 1)
    report["hbm_ideal_ms"] = round(
        (wbytes + ebytes + kvbytes) / 819e9 * 1e3, 3)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
