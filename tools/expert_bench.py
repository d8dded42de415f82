"""Microbenchmark of the ops of the held-experts serving cells, at those
cells' shapes (benchmarks/configs/mistral-small-4-119b-l6-ep4.json,
granite-4.0-h-small-l10-ep2.json), on the chip:

    chiprun -- python3 tools/expert_bench.py [--ops experts,latent,ssm2]
        [--shape mistral4|granite4h] [--rows 128,384] [--layers 6]
        [--iters 20] [--contexts 1600,2600]
        [--variants as_is,copies_only,compute_only]

``experts``: the held experts' part of a layer — sort the (token, expert)
pairs, three grouped products over the 32 held experts' ``[32, 4096, 2048]``
stacks, weight and un-sort — for ``--rows`` rows routed top-4 over 128
experts by a seeded router (about a quarter of the pairs are held), with the
grouped product as ``jax.lax.ragged_dot`` (``xla``) and as the megablox
kernel (``pallas``): ``--layers`` layers of DISTINCT weights in one program
(one dispatch, and no layer finds its weights in a cache), microseconds a
layer beside the time the read of the active experts' matrices alone takes at
the bandwidth peak. This is the reading ``ops/select.py``'s
``GROUPED_MATMUL_ON_TPU`` was set from (PERF.md, PR 33). ``--shape
granite4h``: 36 held of 72 experts of ``[4096, 768]``, top 10 by the softmax
rule (half of the pairs are held; ``--rows 96,256`` are the cell's tick and
chunk: ~480 and ~1,280 held pairs; PERF.md, PR 35).

``ssm2``: Mamba-2's one-step state update over 96 slots of 128 x 64 x 128
float32 (``ops/ssm2.py``): ``--layers`` layers of DISTINCT states in one
program, each layer's input made from the one before, the states donated;
microseconds a layer and the share of the memory roofline (the state read
and written once) — the reading a hand-written kernel has to beat (PR 35's
read 61.9 % against the composition's 78.7 %, and was deleted). Then the
chunk scan of one slot (256 tokens, chunked form) from a state to a state,
microseconds a layer beside its 2.2 GFLOP at the bf16 peak (PERF.md, PR 35).

``latent``: decode attention over the paged latent pool, 128 rows at
lognormal contexts (sigma 0.6, scaled to each mean of ``--contexts``; the
cell's window sees 1.6k -> 3.2k, mean 2.6k), 6 dependent calls in one program;
microseconds a call and a row and the share of the memory roofline (640 bytes
a position, as ``benchmarks/roofline/latent_attention.py`` counts them), at
each ``--group-tokens`` and ``--block-sizes`` (the pool's block: one DMA
descriptor a block), in each of ``--variants``: the kernel ``as_is``, its
``copies_only`` (no matmul, no softmax) and its ``compute_only`` (no copy
started or waited for) — made HERE, by swapping the kernel module's two
helpers while it is traced; nothing serves them (PERF.md, PR 34).

One JSON line per row on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# hidden, expert width, router width, experts held, experts a token, rule
SHAPES = {"mistral4": (4096, 2048, 128, 32, 4, "deepseek_v3"),
          "granite4h": (4096, 768, 72, 36, 10, "softmax_of_chosen")}


def _timed(fn, args, iters):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def experts(rows_list, layers, iters, peaks, shape="mistral4"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.incubate.distributed.models.moe import held_experts as he
    from paddle_tpu.ops import select

    held_expert_sum = he.held_expert_sum
    H, F, E, HELD, K, rule = SHAPES[shape]

    def route(x, router, bias):
        logits = jnp.matmul(x, router, preferred_element_type=jnp.float32)
        if rule == "deepseek_v3":
            return he.deepseek_v3_rule()(logits, K, bias)
        return he.softmax_of_chosen(logits, K)

    key = jax.random.key(0)
    bf = jnp.bfloat16
    make = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                     * 0.02).astype(bf), static_argnums=1)
    ws = [tuple(make(jax.random.fold_in(key, 10 * i + j), s)
                for j, s in enumerate(((HELD, H, F), (HELD, H, F),
                                       (HELD, F, H))))
          for i in range(layers)]
    router = make(jax.random.fold_in(key, 999), (H, E))
    bias = jnp.zeros((E,), bf)
    for rows in rows_list:
        x = make(jax.random.fold_in(key, rows), (rows, H)) * 50
        outs = {}
        for impl in ("xla", "pallas"):
            select.GROUPED_MATMUL_ON_TPU = impl

            def prog(x, ws, router):
                y, counts = x, []
                for wg, wu, wd in ws:
                    idx, w = route(y, router, bias)
                    part, c = held_expert_sum(y, idx, w, wg, wu, wd, 0)
                    # the next layer's input depends on this one's output
                    # (normed, as a layer's input is)
                    y = x + part
                    y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                           )).astype(bf)
                    counts.append(c)
                return y, jnp.stack(counts)

            s, (y, counts) = _timed(jax.jit(prog), (x, ws, router), iters)
            counts = np.asarray(counts)
            active = int((counts[:, :HELD] > 0).sum())
            held = int(counts[:, :HELD].sum())
            read_us = active * 3 * H * F * 2 / peaks["hbm_bytes_per_s"] \
                / layers * 1e6
            outs[impl] = np.asarray(y, np.float32)
            # the first layer alone against a dense product, expert by expert
            idx, w = route(x, router, bias)
            part, _ = jax.jit(held_expert_sum, static_argnums=6)(
                x, idx, w, *ws[0], 0)
            want = jnp.zeros((rows, H), jnp.float32)
            for e in range(HELD):
                col = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
                h1 = (jax.nn.silu(x @ ws[0][0][e]) * (x @ ws[0][1][e]))
                want = want + col[:, None] * (h1 @ ws[0][2][e]).astype(
                    jnp.float32)
            part, want = np.asarray(part), np.asarray(want)
            print(json.dumps({
                "op": "experts_check", "shape": shape, "impl": impl,
                "rows": rows,
                "finite": bool(np.isfinite(part).all()),
                "max_abs_diff_vs_dense": float(np.nanmax(np.abs(part - want))),
                "max_abs": float(np.abs(want).max())}), flush=True)
            print(json.dumps({
                "op": "experts", "shape": shape, "impl": impl, "rows": rows,
                "pairs": rows * K,
                "pairs_held_per_layer": held / layers,
                "experts_active_per_layer": active / layers,
                "load_max_per_layer": float(counts[:, :HELD].max(1).mean()),
                "us_per_layer": round(s / layers * 1e6, 1),
                "weight_read_us": round(read_us, 1),
                "read_share_pct": round(100 * read_us / (s / layers * 1e6),
                                        1)}), flush=True)
        print(json.dumps({"op": "experts", "rows": rows, "max_abs_diff":
                          float(np.abs(outs["xla"] - outs["pallas"]).max()),
                          "max_abs": float(np.abs(outs["xla"]).max())}),
              flush=True)


def ssm2(rows, layers, iters, peaks, heads=128, P=64, N=128, tokens=256):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import select
    from paddle_tpu.ops import ssm2 as op

    f = jnp.float32
    key = jax.random.key(2)

    def draw(i, shape, scale=1.0):
        return jax.jit(lambda k: jax.random.normal(k, shape, f) * scale)(
            jax.random.fold_in(key, i))

    A = -jnp.exp(draw(0, (heads,), 0.5))
    D = jnp.ones((heads,), f)
    x = draw(1, (rows, heads, P))
    dt = jnp.abs(draw(2, (rows, heads), 0.05)) + 1e-3
    Bm, Cm = draw(3, (rows, N)), draw(4, (rows, N))
    states = [draw(10 + i, (rows, heads, P, N)) for i in range(layers)]

    def prog(x, states):
        new = []
        for h in states:
            y, h = op.ssm2_step(x, dt, A, Bm, Cm, D, h)
            # the next layer's input depends on this one's output
            x = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
            new.append(h)
        return x, new

    fn = jax.jit(prog, donate_argnums=(1,))
    x1, states = fn(x, states)
    jax.block_until_ready(x1)
    t0 = time.perf_counter()
    for _ in range(iters):
        x1, states = fn(x, states)
    jax.block_until_ready(x1)
    us = (time.perf_counter() - t0) / iters / layers * 1e6
    nbytes = 2.0 * rows * heads * P * N * 4
    print(json.dumps({
        "op": "ssm2_step", "rows": rows,
        "selected": select.selected().get("ssm2_step"),
        "state_mb_per_row": heads * P * N * 4 / 1e6,
        "us_per_layer": round(us, 1),
        "roofline_pct": round(
            100 * nbytes / peaks["hbm_bytes_per_s"] / (us * 1e-6), 1)}),
        flush=True)
    del states

    xs = draw(5, (tokens, heads, P))
    dts = jnp.abs(draw(6, (tokens, heads), 0.05)) + 1e-3
    Bs, Cs = draw(7, (tokens, N)), draw(8, (tokens, N))
    h0 = [draw(30 + i, (heads, P, N)) for i in range(layers)]

    def chunks(scan):
        def prog(x, h0):
            new = []
            for h in h0:
                y, h = scan(x, dts, A, Bs, Cs, D, h)
                x = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                      + 1e-5)
                new.append(h)
            return x, new
        return jax.jit(prog)

    got = {}
    for name, scan in (("chunked", op.ssd_chunk),
                       ("sequential", op.ssd_chunk_ref)):
        s, (y, hs) = _timed(chunks(scan), (xs, h0), max(iters // 4, 2))
        got[name] = (np.asarray(y), np.asarray(hs[0]))
        flops = 2.0 * heads * P * (tokens * tokens + 2 * tokens * N) \
            + 2.0 * tokens * tokens * N
        print(json.dumps({
            "op": "ssd_chunk", "form": name, "tokens": tokens,
            "us_per_layer": round(s / layers * 1e6, 1),
            "gflop_per_layer": round(flops / 1e9, 2),
            "bf16_peak_pct": round(100 * flops / peaks["bf16_flops"]
                                   / (s / layers), 1)}), flush=True)
    print(json.dumps({"op": "ssd_chunk", "max_abs_diff_y": float(np.abs(
        got["chunked"][0] - got["sequential"][0]).max()),
        "max_abs_diff_state": float(np.abs(
            got["chunked"][1] - got["sequential"][1]).max()),
        "max_abs_state": float(np.abs(got["sequential"][1]).max())}),
        flush=True)


# the three readings of the latent kernel: as it is; its copies alone (the
# group update patched out: no matmul, no softmax); its arithmetic alone (no
# copy is started or waited for: the tile is whatever the slot holds)
LATENT_VARIANTS = {
    "as_is": {},
    "copies_only": {"_attend_group": lambda *a, **k: None},
    "compute_only": {"_dma": lambda *a, **k: None},
}


def latent(groups, contexts, variants, iters, peaks, bs=16, B=128,
           tokens=640016, heads=32, D=384, Dv=256, calls=6):
    M, N = (12288 + 256) // bs, tokens // bs
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import latent_attention_pallas as lk

    # one lognormal sample of B contexts (sigma 0.6, as the cell's rows are
    # spread), scaled to each asked mean; unscaled its mean is ~3.7k
    base = np.clip(np.random.default_rng(0).lognormal(np.log(3072), 0.6, B),
                   300, 12000)
    key = jax.random.key(1)
    pool = jax.jit(lambda k: jax.random.normal(k, (N, bs, D), jnp.bfloat16))(
        key)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, 1, heads, D),
                          jnp.bfloat16) * 0.3
    for mean in contexts:
        ctx = np.clip(base * (mean / base.mean()), 64, 12000).astype(np.int32)
        tables = np.zeros((B, M), np.int32)
        nxt = 1
        for b in range(B):
            n = -(-int(ctx[b] + 1) // bs)
            tables[b, :n] = np.arange(nxt, nxt + n)
            nxt += n
        assert nxt <= N, nxt
        tbl, pos = jnp.asarray(tables), jnp.asarray(ctx)
        nbytes = float(ctx.sum() + B) * 640
        for g in groups:
            for variant in variants:
                def prog(q, pool, tables, pos):
                    out = q
                    for _ in range(calls):
                        o = lk.latent_attention(
                            out, pool, tables, pos, v_width=Dv, scale=0.195,
                            qscale=(0.1, 8192))
                        out = jnp.pad(o, ((0, 0),) * 3 + ((0, D - Dv),))
                    return out

                # the kernel's own jit would hand back another variant's trace
                jax.clear_caches()
                with mock.patch.multiple(lk, _GROUP_TOKENS=g,
                                         **LATENT_VARIANTS[variant]):
                    s, _ = _timed(jax.jit(prog), (q, pool, tbl, pos), iters)
                us = s / calls * 1e6
                print(json.dumps({
                    "op": "latent_decode", "variant": variant, "B": B,
                    "block_size": bs, "group_tokens": g,
                    "context_mean": round(float(ctx.mean()), 1),
                    "positions": int(ctx.sum() + B),
                    "us_per_call": round(us, 1),
                    "us_per_row": round(us / B, 3),
                    "roofline_pct": round(
                        100 * nbytes / peaks["hbm_bytes_per_s"] / (us * 1e-6),
                        1)}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ops", default="experts,latent")
    ap.add_argument("--shape", default="mistral4", choices=sorted(SHAPES))
    ap.add_argument("--slots", type=int, default=96,
                    help="rows of the ssm2 one-step update")
    ap.add_argument("--rows", default="128,384")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--group-tokens", default="1024,2048")
    ap.add_argument("--block-sizes", default="16,64")
    ap.add_argument("--contexts", default="3700",
                    help="mean live context of the 128 decode rows")
    ap.add_argument("--variants", default="as_is",
                    help="of " + ",".join(LATENT_VARIANTS))
    args = ap.parse_args()
    import jax

    kind = jax.devices()[0].device_kind
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"no peaks for device_kind {kind!r}: this tool "
                         f"measures on the chip")
    print(json.dumps({"device_kind": kind}), flush=True)
    ops = args.ops.split(",")
    if "experts" in ops:
        experts([int(r) for r in args.rows.split(",")], args.layers,
                args.iters, table[kind], args.shape)
    if "ssm2" in ops:
        ssm2(args.slots, args.layers, args.iters, table[kind])
    if "latent" in ops:
        for bs in (int(b) for b in args.block_sizes.split(",")):
            latent([int(g) for g in args.group_tokens.split(",")],
                   [int(c) for c in args.contexts.split(",")],
                   args.variants.split(","), args.iters, table[kind], bs=bs)


if __name__ == "__main__":
    main()
