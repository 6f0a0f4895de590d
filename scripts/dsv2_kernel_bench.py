"""Device times of one DeepSeek-V2 layer's latent attention at the cell's
shapes (16 slots x chunk 128, 128 heads over cached rows of 640 = 512 +
64 + the zeros that fill the tile, rows of 16384 keys in pages of 16 of
a pool of 16,385), the pieces PR 34 chose between:

    chiprun -- python scripts/dsv2_kernel_bench.py [--what check,walk,project,experts]

- `check`: the kernel against the dense form ON THE CHIP at a small
  size (real DMAs, which interpret mode never runs).
- `walk`: `latent_attend` (the absorbed walk, pages in place) for a
  chunk of 128 at contexts 2k / 8k / 16k beside 15 decoding rows, and
  for 16 decoding rows alone; beside it the dense absorbed form over a
  gathered `[slots, max_len, row]` view, and the EXPANDED form for the
  chunk row (k_nope and v rebuilt from the row's gathered latents, then
  ordinary attention of 128 heads of 192 / 128): the design question
  of ISSUE 34.
- `project`: the projections around the walk (W_qa, W_qb, W_kva, the
  two absorbs) at 2048 rows and at 16.
- `experts`: the expert layer (20 held experts of 160, group-limited
  top-6), kernel against ragged_dot.

Prints one JSON line a measurement (ms a call, median of 5 after 2).
Weights are ARGUMENTS of what is timed: as closed-over constants they
made each compile carry up to 2 GB (ten minutes of the first call).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="check,walk,project,experts")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes: a rehearsal of this script on "
                    "the CPU (JAX_PLATFORMS=cpu), no measurement")
    args = ap.parse_args()
    what = args.what.split(",")
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import mla, moe
    from paddle_tpu.ops.pallas.paged_attention import paged_scatter
    from paddle_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(0)

    def rnd(shape, dt=bf, scale=1.0):
        nonlocal key
        key, k = jax.random.split(key)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def timed(name, fn, *args, **note):
        f = jax.jit(fn)
        for _ in range(2):
            jax.block_until_ready(f(*args))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(1e3 * (time.perf_counter() - t0))
        print(json.dumps(dict(what=name, ms=sorted(ts)[2], **note)),
              flush=True)

    s, l, h, row, lat, rope, nope, dv, n = 16, 128, 128, 640, 512, 64, 128, \
        128, 16384
    pages, ps = 16385, 16
    hid, qr, f, held, e = 5120, 1536, 1536, 20, 160
    contexts = (2048, 8192, 16384)
    if args.tiny:
        s, l, h, row, lat, rope, nope, dv, n = 4, 16, 4, 128, 32, 8, 16, 16, \
            2048
        pages, hid, qr, f, held, e = 4 * 128 + 1, 64, 48, 32, 4, 32
        contexts = (1024, 2048)
    i32 = jnp.int32
    scale = 0.1147
    kw = dict(d_v=lat, scale=scale)

    def table_of(rows_, per, total, seed=0):
        """Each row's pages, distinct, in a shuffled order."""
        return jnp.asarray(np.random.default_rng(seed).permutation(
            total - 1)[:rows_ * per].reshape(rows_, per) + 1, i32)

    if "check" in what:
        # the kernel against the dense form, on the chip: 3 rows of 2048
        # keys, a chunk of 16 at 1500, a decoding row at 700, an idle row
        cb, cl, cn = 3, 16, 2048
        cq = rnd((cb, cl, h, row), scale=0.3)
        cpool = rnd((400, ps, row))
        ctab = table_of(cb, cn // ps, 400, seed=1)
        cpos = jnp.asarray([1500, 700, 0], i32)
        cql = jnp.asarray([16, 1, 0], i32)
        got = jax.jit(lambda *a: mla.latent_attend(*a, **kw))(
            cq, cpool, ctab, cpos, cql)
        want = jax.jit(lambda q, v, p, n_: mla.latent_attend_reference(
            q, v, p, n_, **kw))(cq, mla.gather_view(cpool, ctab), cpos, cql)
        diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        print(json.dumps(dict(
            what="kernel - dense form (bf16 rows)",
            max_abs=float(diff.max()), mean_abs=float(diff.mean()),
            mean_abs_want=float(jnp.abs(want.astype(jnp.float32)).mean()))),
            flush=True)
    if "walk" in what:
        q = rnd((s, l, h, row), scale=0.3)
        pool = rnd((pages, ps, row))
        table = table_of(s, n // ps, pages)
        w_kvb = rnd((lat, h, nope + dv), scale=0.05)

        def rows(ctx, decode):
            """One slot prefills a chunk that ends at context `ctx`,
            `decode` rows decode at contexts spread up to 16k."""
            pos = np.zeros(s, np.int32)
            ql = np.zeros(s, np.int32)
            pos[0], ql[0] = ctx - l, l
            for i in range(decode):
                pos[1 + i] = 1024 + (n - 1100) * (i + 1) // decode
                ql[1 + i] = 1
            return jnp.asarray(pos, i32), jnp.asarray(ql, i32)

        def dense_absorbed(q, pool, table, pos, ql):
            return mla.latent_attend_reference(
                q, mla.gather_view(pool, table), pos, ql, **kw)

        def expanded_chunk(q_nope, q_rope, pool, table, pos, w_kvb):
            """The expanded form for ONE chunk row (slot 0): gather the
            row's latents, rebuild k_nope and v for every head, ordinary
            causal attention (bf16 products, f32 softmax). Keys up to
            the static `ctx` only: the least this form could read."""
            view = mla.gather_view(pool, table[:1])[0]          # [n, row]
            c, k_rope = view[:, :lat], view[:, lat:lat + rope]
            k_nope = jnp.einsum("sc,chd->shd", c, w_kvb[..., :nope],
                                preferred_element_type=bf)
            v = jnp.einsum("sc,chd->shd", c, w_kvb[..., nope:],
                           preferred_element_type=bf)
            sc = (jnp.einsum("thd,shd->hts", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("thd,sd->hts", q_rope, k_rope,
                               preferred_element_type=jnp.float32)) * scale
            seen = jnp.arange(view.shape[0])[None, :] \
                <= pos[0] + jnp.arange(l)[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), axis=-1)
            return jnp.einsum("hts,shd->thd", p.astype(bf), v,
                              preferred_element_type=bf)

        for ctx in contexts:
            pos, ql = rows(ctx, s - 1)
            note = dict(ctx=ctx, decode_rows=s - 1)
            timed("latent_attend (absorbed walk, pages in place; chunk at "
                  "a block of 8, decode rows at a block of 1)",
                  lambda *a: mla.latent_attend(*a, **kw), q, pool, table,
                  pos, ql, **note)
            only = jnp.where(jnp.arange(s) == 0, ql, 0)
            timed("  the chunk row alone", lambda *a: mla.mla_walk(*a, **kw),
                  q, pool, table, pos, only, ctx=ctx)
            # the expanded form reads ctx keys: slice the table to them
            timed("  expanded form for the chunk row (gather + rebuild "
                  "k_nope, v + attention)", expanded_chunk,
                  rnd((l, h, nope), scale=0.3), rnd((l, h, rope), scale=0.3),
                  pool, table[:, :ctx // ps], pos, w_kvb, ctx=ctx)
        # (the dense form over a chunk of 128 x 128 heads x 16384 keys
        # a slot would hold 17 GB of scores: timed for decoding rows)
        # decode-only step: 16 rows
        pos = jnp.asarray(np.linspace(1024, n - 100, s).astype(np.int32))
        ql = jnp.ones((s,), i32)
        timed("latent_attend, 16 decoding rows alone",
              lambda *a: mla.latent_attend(*a, **kw), q, pool, table, pos,
              ql)
        timed("dense absorbed form, 16 decoding rows alone",
              dense_absorbed, q[:, :1], pool, table, pos, ql)
        timed("view gather alone (pool[page_table])", mla.gather_view,
              pool, table)
        pos0 = jnp.asarray(np.arange(s) * 1000, i32)
        timed("XLA scatter of 2048 rows into the pool (copy included)",
              paged_scatter, pool, rnd((s, l, row)), pos0, table)
    if "project" in what:
        w_qa, w_qb = rnd((hid, qr), scale=0.02), \
            rnd((qr, h * (nope + rope)), scale=0.02)
        w_kva = rnd((hid, lat + rope), scale=0.02)
        w_kvb = rnd((lat, h, nope + dv), scale=0.05)
        w_o = rnd((h * dv, hid), scale=0.02)

        def project(x, w_qa, w_qb, w_kva, w_kvb):
            c_q = x @ w_qa
            qq = (c_q @ w_qb).reshape(x.shape[0], h, nope + rope)
            kv = x @ w_kva
            q_lat = jnp.einsum("thd,chd->thc", qq[..., :nope],
                               w_kvb[..., :nope],
                               preferred_element_type=bf)
            return q_lat, qq[..., nope:], kv

        def expand(o, w_kvb, w_o):
            o_h = jnp.einsum("thc,chd->thd", o, w_kvb[..., nope:],
                             preferred_element_type=bf)
            return o_h.reshape(o.shape[0], h * dv) @ w_o
        for t in (s * l, s):
            timed("projections before the walk (q_a, q_b, kv_a, absorb q)",
                  project, rnd((t, hid)), w_qa, w_qb, w_kva, w_kvb, rows=t)
            timed("projections after the walk (absorb v, o_proj)", expand,
                  rnd((t, h, lat)), w_kvb, w_o, rows=t)
    if "experts" in what:
        wr, wg, wu, wd = rnd((hid, e)), rnd((held, hid, f)), \
            rnd((held, hid, f)), rnd((held, f, hid))
        x = rnd((s * l, hid))
        for live in (s, s * l // 2, s * l):
            valid = jnp.arange(s * l) < live
            choice = dict(top_k=6, scale=16.0, norm_topk=False, first=0,
                          n_group=8, topk_group=3)

            def kernel(x, valid, wr, wg, wu, wd):
                return moe.routed_experts(x, valid, wr, wg, wu, wd,
                                          **choice)[0]

            def ragged(x, valid, wr, wg, wu, wd):
                route = moe.moe_route(x, wr, valid, n_local=held, **choice)
                return moe.moe_experts_ragged_dot(x, route, wg, wu, wd)

            def route_only(x, valid, wr):
                return moe.moe_route(x, wr, valid, n_local=held,
                                     **choice)["dest"]
            timed("routed experts, Pallas kernel (route + experts + "
                  "combine)", kernel, x, valid, wr, wg, wu, wd,
                  live_rows=live)
            timed("routed experts, ragged_dot (route + 3 products + order)",
                  ragged, x, valid, wr, wg, wu, wd, live_rows=live)
            timed("  moe_route alone", route_only, x, valid, wr,
                  live_rows=live)
    print(json.dumps({"device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
