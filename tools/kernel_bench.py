"""Per-op microbenchmark: reference jnp paged attention vs the Pallas
kernels (ops/paged_attention_pallas.py), decode / verify / prefill, fp and
int8, across (B, M, bs) shapes.

Each combo times BOTH dispatch paths on identical inputs, checks parity
(max abs diff — the online softmax is ~1e-6 off the two-pass reference),
and reports tokens/s plus the speedup. On a TPU backend the Pallas numbers
are the real Mosaic kernels; elsewhere they run in interpret mode (slower
than the reference — the point there is parity and plumbing, not speed,
which is why the suite's perf gate only reads the speedup on hardware).

``--tp N`` additionally runs every combo under ``jax.jit`` +
``shard_map`` over an N-way "tp" mesh with the SERVING shard layout
(q/KV pools split on the head axis, int8 scales with their heads,
tables/pos replicated — parallel/serving_mesh.py's pool_spec): each
shard executes the same Pallas kernel on its head slice, exactly what
the multi-chip serving tick lowers to. The row gains ``tp_tok_s`` /
``tp_max_abs_diff`` / ``tp_parity``, and the parity gate covers the
sharded output against the unsharded reference too (attention has no
cross-head reduction, so sharding must not move the result). On CPU
(JAX_PLATFORMS=cpu) the tool forces N XLA host devices for the dryrun.

``--ops ragged`` adds the SERVING-CELL rows (independent of ``--shapes``):
the decode kernel at the benchmark cells' own shapes — ``--ragged-rows``
slots (64) of which ``--ragged-live`` hold a request (``64,9``: the decode
and the prefill cell's tick), a ``--ragged-table`` wide table (256 blocks
of 16 tokens), a 4096-block bf16 pool, 32/8 heads of 128, lognormal
lengths that sum to ``--ragged-tokens`` (44000, scaled by live/rows) — and
the 128-token chunk at starts 0 / 1920 / 3840. Each row is 16 dependent
calls in ONE program (a tick's 16 layers: one dispatch, so the host's
launch cost is not in the number) and prints microseconds a call and the
share of the roofline: memory for decode, bytes as
``benchmarks/roofline/paged_attention.py`` counts them; compute for the
chunk, FLOPs as ``benchmarks/roofline/prefill_attention.py`` counts them;
peaks from ``benchmarks/peaks.json`` by ``device_kind`` (no share off the
chip). ``--ragged-depths 0,8,32`` repeats each row at explicit blocks per
group (``PagedAttentionGeometry.kv_block_depth``; 0 = derived), which is
how the group width in ``ops/paged_attention_pallas.py`` was chosen.

``--ops rows`` adds the WEIGHT-STREAMING rows: what the Mistral cells'
widest matmul, bf16 ``[M, 4096] x [4096, 14336]``, and the whole SwiGLU MLP
around it cost at ``--rows-m`` rows (64 = the decode tick, 128 = a prompt
chunk, 192 = both in one program), over 8 layers of distinct weights in
ONE program: us a layer and the share of the time the weight read alone
takes at the chip's bandwidth peak.

Usage:
    python tools/kernel_bench.py [--json] [--iters 10]
        [--shapes 2,4,8;4,8,16] [--window 4] [--heads 8] [--kv-heads 2]
        [--head-dim 128]
        [--ops decode,verify,prefill,ragged,rows] [--quant fp,int8] [--tp N]
    python tools/kernel_bench.py --ops ragged [--ragged-depths 0,8,32]
    python tools/kernel_bench.py --ops rows [--rows-m 64,128,192]

One JSON line per (op, quant, B, M, bs) combo under --json (bench.py
style); a human table otherwise.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_shapes(spec):
    out = []
    for part in spec.split(";"):
        b, m, bs = (int(x) for x in part.split(","))
        out.append((b, m, bs))
    return out


def make_inputs(rng, jnp, B, M, bs, H, KV, D, W, quant):
    """Block pools + tables + pos with realistic structure: partial final
    blocks (pos mid-block), scratch block 0 on table tails."""
    import numpy as np

    N = max(B * M + 1, 2)
    pos = np.minimum(M * bs - W, np.maximum(
        0, rng.randint(bs // 2, M * bs - W + 1, (B,)))).astype(np.int32)
    tables = np.zeros((B, M), np.int32)
    free = rng.permutation(np.arange(1, N))
    took = 0
    for b in range(B):
        nblk = (pos[b] + W - 1) // bs + 1
        tables[b, :nblk] = free[took:took + nblk]
        took += nblk
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    kv = rng.randn(2, N, bs, KV, D).astype(np.float32)
    tables = jnp.asarray(tables)
    pos = jnp.asarray(pos)
    if quant == "int8":
        from paddle_tpu.ops.paged_attention import quantize_block_kv

        kq, ks = quantize_block_kv(jnp.asarray(kv[0]))
        vq, vs = quantize_block_kv(jnp.asarray(kv[1]))
        return q, (kq, ks, vq, vs), tables, pos
    return q, (jnp.asarray(kv[0]), jnp.asarray(kv[1])), tables, pos


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="2,4,8;4,8,16;8,16,16",
                    help="semicolon list of B,M,bs (batch, table width, "
                         "block size)")
    ap.add_argument("--window", type=int, default=4,
                    help="verify window W (decode is W=1; prefill chunk is "
                         "2 blocks)")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--ops", default="decode,verify,prefill",
                    help="comma list of decode,verify,prefill,ragged,rows")
    ap.add_argument("--quant", default="fp,int8")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--ragged-rows", type=int, default=64)
    ap.add_argument("--ragged-live", default="64,9",
                    help="comma list: live rows of --ragged-rows (the "
                         "rest idle: zero table, position 0)")
    ap.add_argument("--ragged-table", type=int, default=256)
    ap.add_argument("--ragged-tokens", type=int, default=44000)
    ap.add_argument("--ragged-depths", default="0",
                    help="comma list of blocks per group (0 = derived)")
    ap.add_argument("--rows-m", default="64,128,192",
                    help="comma list: rows of the weight-streaming matmul")
    ap.add_argument("--tp", type=int, default=1,
                    help="also run every combo sharded over an N-way "
                         "'tp' mesh (shard_map, serving shard layout) "
                         "and gate parity vs the unsharded reference")
    ap.add_argument("--sweep-geometry", action="store_true",
                    help="per-op kernel-geometry tier: sweep the "
                         "bit-exact schedule candidates on every paged "
                         "row (plus one rung per fused-op family), "
                         "hard-reject parity mismatches, report each "
                         "row's winner + speedup vs default, and collect "
                         "winners into a GeometryCache (--emit-cache)")
    ap.add_argument("--seed", type=int, default=0,
                    help="input rng seed (sweeps under --clock counting "
                         "are byte-reproducible per seed)")
    ap.add_argument("--clock", default="real",
                    choices=("real", "counting"),
                    help="counting = deterministic injectable clock "
                         "(autotuner discipline): timings count calls, "
                         "so two runs at the same seed are byte-identical")
    ap.add_argument("--emit-cache", default=None, metavar="PATH",
                    help="write the swept GeometryCache JSON (the "
                         "artifact TunedProfile v3 / serving_benchmark "
                         "--geometry-cache consume)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.emit_cache and not args.sweep_geometry:
        ap.error("--emit-cache requires --sweep-geometry")
    if args.tp > 1:
        if args.heads % args.tp or args.kv_heads % args.tp:
            ap.error("--tp must divide --heads and --kv-heads (the mesh "
                     "shards the head axis)")
        if os.environ.get("JAX_PLATFORMS", "") == "cpu" \
                and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            # CPU dryrun mesh needs tp host devices; only effective
            # before the jax import below
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count"
                  f"={args.tp}").strip()

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import ops
    from paddle_tpu.autotune.kernel_geometry import resolve_geometry
    from paddle_tpu.ops import paged_attention as pa
    backend = jax.default_backend()
    on_tpu = backend == "tpu"

    mesh = None
    if args.tp > 1:
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        if len(jax.devices()) < args.tp:
            sys.exit(f"--tp {args.tp} needs {args.tp} devices, have "
                     f"{len(jax.devices())}")
        mesh = Mesh(np.array(jax.devices()[:args.tp]), ("tp",))
        # the serving shard layout (parallel/serving_mesh.pool_spec):
        # 4-D pool/q tensors split on the kv-/q-head axis, 2-D int8
        # scale tensors with their heads, block tables and positions
        # replicated
        _HEADS = P(None, None, "tp", None)
        _SCALES = P(None, "tp")

        def tp_specs(op, quant):
            if quant == "int8":
                pool = (_HEADS, _SCALES, _HEADS, _SCALES)
            else:
                pool = (_HEADS, _HEADS)
            if op == "prefill":                  # (q, *pools, table)
                return (_HEADS, *pool, P())
            return (_HEADS, *pool, P(), P())     # (q, *pools, tables, pos)

    if args.clock == "counting":
        # injectable counting clock (GL012 discipline, same as the
        # autotuner's TrialRunner): every read advances by one, so a
        # "duration" is a call count — two sweeps at one seed produce
        # byte-identical rows and winner tables
        _count = [0.0]

        def clk():
            _count[0] += 1.0
            return _count[0]
    else:
        clk = time.perf_counter

    def timed(fn, fn_args):
        # fresh lambda: jax's tracing cache is keyed on function identity,
        # so re-jitting `fn` itself after a kernel-mode flip (any rung of
        # the auto/pallas/reference enum) OR a kernel-geometry
        # re-bind (installing a different winner cache is invisible to the
        # cache key, exactly like the mode flag) would silently reuse the
        # other configuration's jaxpr
        jf = jax.jit(lambda *a: fn(*a))
        out = jf(*fn_args)
        out.block_until_ready()
        t0 = clk()
        for _ in range(args.iters):
            out = jf(*fn_args)
        out.block_until_ready()
        return (clk() - t0) / args.iters, out

    # ---------------------------------------------- kernel-geometry tier
    sweep_cache = None
    if args.sweep_geometry:
        from paddle_tpu.autotune import GeometryCache
        from paddle_tpu.autotune.kernel_geometry import local_device_kind

        sweep_cache = GeometryCache()
        device_kind = local_device_kind()

    def run_sweep(measure, op, dtype, key, **kw):
        """One deterministic sweep rung: measure every candidate under a
        fresh jit (geometry re-binds MUST re-trace — see timed), bitwise
        parity-gate vs the default's output, cache the winner."""
        from paddle_tpu.autotune import sweep_kernel_geometry

        return sweep_kernel_geometry(measure, op, dtype=dtype, key=key,
                                     device_kind=device_kind,
                                     cache=sweep_cache, **kw)

    def installed_measure(fn, fn_args, op, dtype, key):
        """measure() for ops whose geometry rides the process-wide seam
        (paged attention, flash): install a one-entry cache, fresh-jit,
        restore. The restore matters — the sweep must not leak its last
        candidate into the next row's timing."""
        from paddle_tpu.autotune import GeometryCache, install_geometry_cache
        from paddle_tpu.autotune.kernel_geometry import (
            active_geometry_cache, active_geometry_source)

        def measure(geom):
            prev, prev_src = active_geometry_cache(), \
                active_geometry_source()
            c = GeometryCache()
            c.put(op, dtype, key, device_kind, geom)
            install_geometry_cache(c, "swept")
            try:
                secs, out = timed(fn, fn_args)
            finally:
                install_geometry_cache(
                    prev, prev_src if prev is not None else "swept")
            return np.asarray(out), secs
        return measure

    def sweep_summary(res):
        return {
            "winner_geometry": res.winner,
            "geometry_speedup": round(res.speedup, 3),
            "geometry_candidates": len(res.trials),
            "geometry_parity_rejects": sum(
                1 for t in res.trials if not t.accepted),
        }

    def family_sweep_rows():
        """One sweep rung per fused-op family (fp, fixed microbench
        shapes) — the per-op tier beyond the paged rows. The LoRA/norm/
        CE candidates are bit-exact by design, so a parity reject there
        fails the run like a paged parity failure would; flash block_q
        is row-independent but its BITWISE equality is backend-dependent
        (host BLAS may regroup the contraction by tile shape), so flash
        rejects are a graceful result — the reject count is reported and
        the rejected schedule simply never wins the cell."""
        from paddle_tpu.autotune.kernel_geometry import geometry_candidates
        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        from paddle_tpu.ops.fused_norm import _rms_pallas
        from paddle_tpu.ops.paged_attention_pallas import fused_lora_matmul
        from paddle_tpu.ops.flash_attention import flash_attention

        rng = np.random.RandomState(args.seed)
        out_rows = []

        def add_row(fam, key, res):
            strict = fam != "flash_attention"   # see docstring above
            out_rows.append({
                "metric": "geometry_sweep", "op": fam, "quant": "fp",
                "dtype": "float32", "key": key, "backend": backend,
                "pallas_mode": "mosaic" if on_tpu else "interpret",
                "parity": (all(t.accepted for t in res.trials) if strict
                           else res.trials[res.winner_index].exact),
                **sweep_summary(res)})

        mode = ops.kernel_mode()
        try:
            ops.set_kernel_mode("pallas")
            # fused LoRA: geometry is a direct trace-time argument
            B, S, IN, OUT, R = 2, 8, 256, 256, 8
            x = jnp.asarray(rng.randn(B, S, IN).astype(np.float32))
            w = jnp.asarray(rng.randn(IN, OUT).astype(np.float32) * 0.05)
            a = jnp.asarray(rng.randn(B, IN, R).astype(np.float32) * 0.05)
            b = jnp.asarray(rng.randn(B, R, OUT).astype(np.float32) * 0.05)
            s = jnp.asarray(np.array([0.5, 0.0], np.float32))

            def lora_measure(geom):
                secs, out = timed(
                    lambda *t: fused_lora_matmul(*t, geometry=geom),
                    (x, w, a, b, s))
                return np.asarray(out), secs

            add_row("fused_lora", R, run_sweep(
                lora_measure, "fused_lora", "float32", R,
                shape={"seq": S, "in_dim": IN, "out_dim": OUT, "rank": R}))

            # fused norm: direct geometry, interpret off-TPU
            xr = jnp.asarray(rng.randn(256, 512).astype(np.float32))
            wr = jnp.asarray(rng.randn(512).astype(np.float32))

            def norm_measure(geom):
                secs, out = timed(
                    lambda *t: _rms_pallas(*t, 1e-6, geometry=geom,
                                           interpret=not on_tpu),
                    (xr, wr))
                return np.asarray(out), secs

            add_row("fused_norm", 512, run_sweep(
                norm_measure, "fused_norm", "float32", 512,
                shape={"rows_total": 256, "width": 512}))

            # fused CE: jnp composition, geometry sub-tiles the forward
            h = jnp.asarray(rng.randn(64, 128).astype(np.float32))
            wv = jnp.asarray(rng.randn(128, 512).astype(np.float32) * 0.1)
            lab = jnp.asarray(rng.randint(0, 512, (64,)).astype(np.int32))

            def ce_measure(geom):
                secs, out = timed(
                    lambda *t: fused_linear_cross_entropy(
                        *t, chunk_size=32, geometry=geom),
                    (h, wv, lab))
                return np.asarray(out), secs

            add_row("fused_ce", 128, run_sweep(
                ce_measure, "fused_ce", "float32", 128,
                shape={"rows_total": 64, "hidden": 128, "vocab": 512}))

            # flash attention: rides the seam like paged attention;
            # block_kv stays excluded from candidates (not parity-exact)
            D = args.head_dim
            qf = jnp.asarray(rng.randn(1, 2, 256, D).astype(np.float32))
            kf = jnp.asarray(rng.randn(1, 2, 256, D).astype(np.float32))
            vf = jnp.asarray(rng.randn(1, 2, 256, D).astype(np.float32))
            add_row("flash_attention", D, run_sweep(
                installed_measure(
                    lambda *t: flash_attention(*t, causal=True),
                    (qf, kf, vf), "flash_attention", "float32", D),
                "flash_attention", "float32", D,
                shape={"head_dim": D, "seq_q": 256, "seq_k": 256}))
        finally:
            ops.set_kernel_mode(mode)
        return out_rows

    def chip_peaks():
        """The benchmark's published peaks of this device, None off a chip
        it lists."""
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                               "peaks.json")) as f:
            return json.load(f).get(jax.devices()[0].device_kind)

    def ragged_rows():
        """The decode kernel and the chunk at the serving cells' shapes:
        us a call and the share of the roofline (module docstring)."""
        from benchmarks.roofline import paged_attention as dec_work
        from benchmarks.roofline import prefill_attention as chunk_work
        from paddle_tpu.autotune.kernel_geometry import \
            PagedAttentionGeometry
        from paddle_tpu.ops import paged_attention_pallas as pk

        H, KV, D, bs, N, C, calls = 32, 8, 128, 16, 4096, 128, 16
        B, M = args.ragged_rows, args.ragged_table
        peak = chip_peaks()
        rng = np.random.RandomState(args.seed)
        pool = [jnp.asarray(rng.randn(N, bs, KV, D).astype(np.float32),
                            jnp.bfloat16) for _ in range(2)]

        def case(lens, W):
            tables = np.zeros((len(lens), M), np.int32)
            free, took = rng.permutation(np.arange(1, N)), 0
            for b, n in enumerate(lens):
                if n:
                    nb = (n + W - 1) // bs + 1
                    tables[b, :nb] = free[took:took + nb]
                    took += nb
            q = jnp.asarray(rng.randn(len(lens), W, H, D).astype(np.float32),
                            jnp.bfloat16)
            return q, jnp.asarray(tables), jnp.asarray(
                np.asarray(lens, np.int32))

        def chained(depth):
            geom = PagedAttentionGeometry(kv_block_depth=depth)

            def run(q, t, p, k, v):
                def layer(c, _):
                    o = pk.paged_attention(c, k, v, t, p, geometry=geom)
                    return c + (o * 0).astype(c.dtype), o
                return jax.lax.scan(layer, q, None, length=calls)[1][-1]
            return run

        def one(label, q, t, p, depth, work, peak_key):
            mode = ops.kernel_mode()
            try:
                ops.set_kernel_mode("reference")
                ref = jax.jit(pa.paged_verify_attention)(q, *pool, t, p)
                ops.set_kernel_mode("pallas")
                secs, out = timed(chained(depth), (q, t, p, *pool))
            finally:
                ops.set_kernel_mode(mode)
            live = np.asarray(p) > 0 if q.shape[0] > 1 else slice(None)
            diff = float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32))[live]))
            W = q.shape[1]
            G, all_heads = pk.group_plan(W * H // KV, KV, bs, D, M, 2, depth)
            return {
                "metric": "paged_ragged_kernel_us", "op": label,
                "quant": "fp", "B": q.shape[0], "M": M, "bs": bs, "W": W,
                "backend": backend,
                "pallas_mode": "mosaic" if on_tpu else "interpret",
                "blocks_per_group": G,
                "body": "all_heads" if all_heads else "per_head",
                "us_per_call": round(secs / calls * 1e6, 1),
                "roofline_pct": (
                    round(100 * work / peak[peak_key] * calls / secs, 2)
                    if peak and on_tpu else None),
                "max_abs_diff": diff, "parity": diff < 2e-2,
            }

        out = []
        depths = [int(d) for d in args.ragged_depths.split(",")]
        for live in (int(x) for x in args.ragged_live.split(",")):
            lens = np.zeros(B, int)
            draw = np.clip(rng.lognormal(np.log(620), 0.55, live), 64,
                           M * bs - 1)
            lens[rng.permutation(B)[:live]] = np.clip(
                draw * args.ragged_tokens * live / B / draw.sum(), 1,
                M * bs - 1).astype(int)
            q, t, p = case(lens, 1)
            ctx = int(lens.sum() + B)
            for depth in depths:
                out.append(one(
                    f"decode{live}of{B}", q, t, p, depth,
                    dec_work.nbytes(H, KV, D, ctx, B, 1), "hbm_bytes_per_s"))
                out[-1]["ctx_tokens"] = ctx
        q, t, _ = case([M * bs - C], C)
        for start in dict.fromkeys(
                (0, (M * bs // 2 - C) // bs * bs, M * bs - 2 * C)):
            for depth in depths:
                out.append(one(
                    f"chunk@{start}", q, t, jnp.full((1,), start, jnp.int32),
                    depth, chunk_work.flops(H, D, [(start, C)], 1),
                    "bf16_flops"))
        return out

    def streaming_rows():
        """The 4096 x 14336 matmul and the SwiGLU MLP at M rows (module
        docstring): the weights are read once whatever M is, so the time
        says what the extra rows cost."""
        hid, ffn, layers = 4096, 14336, 8
        peak = chip_peaks()
        rng = np.random.RandomState(args.seed)

        def weights(shape):
            return [jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.02,
                                jnp.bfloat16) for _ in range(layers)]

        wg, wu, wd = (weights((hid, ffn)), weights((hid, ffn)),
                      weights((ffn, hid)))

        def up(x, *ws):
            for w in ws:        # each layer's input hangs on the one before
                # (a sum over every column: a slice would let the compiler
                # read only the columns kept)
                x = x + (jnp.sum(jnp.matmul(x, w), axis=1, keepdims=True)
                         * 0).astype(x.dtype)
            return x

        def mlp(x, *ws):
            for g, u, d in zip(ws[:layers], ws[layers:2 * layers],
                               ws[2 * layers:]):
                x = x + jnp.matmul(jax.nn.silu(jnp.matmul(x, g))
                                   * jnp.matmul(x, u), d)
            return x

        out = []
        for M in (int(m) for m in args.rows_m.split(",")):
            x = jnp.asarray(rng.randn(M, hid).astype(np.float32),
                            jnp.bfloat16)
            for label, fn, ws, n_w in (("matmul", up, wu, 1),
                                       ("mlp", mlp, wg + wu + wd, 3)):
                secs, _ = timed(fn, (x, *ws))
                read_s = n_w * hid * ffn * 2 / peak["hbm_bytes_per_s"] \
                    if peak and on_tpu else None
                out.append({
                    "metric": "weight_stream_us", "op": label, "M": M,
                    "backend": backend,
                    "us_per_layer": round(secs / layers * 1e6, 1),
                    "weight_read_us": (round(read_s * 1e6, 1)
                                       if read_s else None),
                    "read_share_pct": (round(100 * read_s * layers / secs, 2)
                                       if read_s else None),
                    "parity": True})
        return out

    rows = []
    op_list = args.ops.split(",")
    if "ragged" in op_list:
        op_list.remove("ragged")
        rows += ragged_rows()
    if "rows" in op_list:
        op_list.remove("rows")
        rows += streaming_rows()
    for B, M, bs in parse_shapes(args.shapes):
        for quant in args.quant.split(","):
            rng = np.random.RandomState(args.seed)
            for op in op_list:
                W = {"decode": 1, "verify": args.window,
                     "prefill": 2 * bs}[op]
                if op == "prefill":
                    # prefill is the verify kernel at B=1, W=chunk
                    q, pools, tables, pos = make_inputs(
                        rng, jnp, 1, M, bs, args.heads, args.kv_heads,
                        args.head_dim, W, quant)
                    tbl, start = tables[0], int(pos[0]) // bs * bs
                    if quant == "int8":
                        fn = lambda qq, kq, ks, vq, vs, t: \
                            pa.paged_prefill_attention_q(
                                qq, kq, ks, vq, vs, t, start)
                    else:
                        fn = lambda qq, kp, vp, t: \
                            pa.paged_prefill_attention(
                                qq, kp, vp, t, start)
                    fn_args = (q, *pools, tbl)
                    tok = W
                else:
                    q, pools, tables, pos = make_inputs(
                        rng, jnp, B, M, bs, args.heads, args.kv_heads,
                        args.head_dim, W, quant)
                    fn = (pa.paged_verify_attention_q if quant == "int8"
                          else pa.paged_verify_attention)
                    fn_args = (q, *pools, tables, pos)
                    tok = B * W
                mode = ops.kernel_mode()
                tp_s, tp_out = None, None
                sweep_res = None
                try:
                    ops.set_kernel_mode("reference")
                    ref_s, ref_out = timed(fn, fn_args)
                    ops.set_kernel_mode("pallas")
                    pal_s, pal_out = timed(fn, fn_args)
                    if args.sweep_geometry:
                        pa_dtype = ("int8" if quant == "int8"
                                    else "float32")
                        sweep_res = run_sweep(
                            installed_measure(
                                fn, fn_args, "paged_attention",
                                pa_dtype, args.head_dim),
                            "paged_attention", pa_dtype,
                            args.head_dim,
                            quantized=quant == "int8",
                            shape={"head_dim": args.head_dim,
                                   "block_size": bs, "window": W,
                                   "rep": args.heads // args.kv_heads,
                                   "blocks": M})
                    if mesh is not None:
                        # same kernel, per-shard head slices: jit a
                        # fresh shard_map lambda (cache is keyed on
                        # function identity — see timed) over
                        # explicitly sharded inputs so the GSPMD
                        # lowering is what gets measured
                        specs = tp_specs(op, quant)
                        sfn = jax.shard_map(fn, mesh=mesh,
                                            in_specs=specs,
                                            out_specs=_HEADS,
                                            check_vma=False)
                        sargs = tuple(
                            jax.device_put(a, NamedSharding(mesh, s))
                            for a, s in zip(fn_args, specs))
                        tp_s, tp_out = timed(sfn, sargs)
                finally:
                    ops.set_kernel_mode(mode)
                diff = float(jnp.max(jnp.abs(
                    ref_out.astype(jnp.float32) -
                    pal_out.astype(jnp.float32))))
                rows.append({
                    "metric": f"paged_{op}_kernel_tok_s",
                    "op": op, "quant": quant,
                    "B": B, "M": M, "bs": bs, "W": W,
                    "heads": args.heads, "kv_heads": args.kv_heads,
                    "head_dim": args.head_dim,
                    "backend": backend,
                    "pallas_mode": "mosaic" if on_tpu else "interpret",
                    "ref_tok_s": round(tok / ref_s, 1),
                    "pallas_tok_s": round(tok / pal_s, 1),
                    "speedup": round(ref_s / pal_s, 3),
                    "max_abs_diff": diff,
                    "parity": diff < 2e-5,
                })
                # which schedule the pallas timing above actually
                # ran (the trace-time resolution, not a guess)
                g_act, g_src = resolve_geometry(
                    "paged_attention",
                    "int8" if quant == "int8" else "float32",
                    args.head_dim)
                rows[-1]["geometry"] = g_act.asdict()
                rows[-1]["geometry_source"] = g_src
                if sweep_res is not None:
                    rows[-1]["parity"] = bool(
                        rows[-1]["parity"] and all(
                            t.accepted for t in sweep_res.trials))
                    rows[-1].update(sweep_summary(sweep_res))
                if tp_out is not None:
                    tp_diff = float(jnp.max(jnp.abs(
                        ref_out.astype(jnp.float32) -
                        tp_out.astype(jnp.float32))))
                    rows[-1].update({
                        "tp": args.tp,
                        "tp_tok_s": round(tok / tp_s, 1),
                        "tp_max_abs_diff": tp_diff,
                        "tp_parity": tp_diff < 2e-5,
                    })
    if args.sweep_geometry:
        rows += family_sweep_rows()

    ok = all(r["parity"] and r.get("tp_parity", True) for r in rows)
    if args.json:
        for r in rows:
            print(json.dumps(r))
    else:
        hdr = (f"{'op':8} {'quant':5} {'B':>3} {'M':>3} {'bs':>3} "
               f"{'ref tok/s':>12} {'pallas tok/s':>13} {'speedup':>8} "
               f"{'max|diff|':>10}")
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            if r["metric"] == "paged_ragged_kernel_us":
                print(f"{r['op']:14} B={r['B']:<3} W={r['W']:<4} "
                      f"G={r['blocks_per_group']:<3} {r['body']:9} "
                      f"{r['us_per_call']:>9} us/call  roofline "
                      f"{r['roofline_pct']} %  max|diff| "
                      f"{r['max_abs_diff']:.2e}")
                continue
            if r["metric"] == "weight_stream_us":
                print(f"{r['op']:8} M={r['M']:<4} {r['us_per_layer']:>8} "
                      f"us/layer  weight read alone {r['weight_read_us']} "
                      f"us = {r['read_share_pct']} %")
                continue
            if r["metric"] == "geometry_sweep":
                print(f"{r['op']:8} sweep  winner="
                      f"{json.dumps(r['winner_geometry'], sort_keys=True)} "
                      f"x{r['geometry_speedup']} "
                      f"({r['geometry_candidates']} candidates, "
                      f"{r['geometry_parity_rejects']} parity rejects)")
                continue
            line = (f"{r['op']:8} {r['quant']:5} {r['B']:>3} {r['M']:>3} "
                    f"{r['bs']:>3} {r['ref_tok_s']:>12} "
                    f"{r['pallas_tok_s']:>13} {r['speedup']:>8} "
                    f"{r['max_abs_diff']:>10.2e}")
            if "winner_geometry" in r:
                line += (f"  winner="
                         f"{json.dumps(r['winner_geometry'], sort_keys=True)}"
                         f" x{r['geometry_speedup']}")
            print(line)
        print(f"\nbackend={backend} "
              f"({'mosaic' if on_tpu else 'interpret'} pallas), "
              f"parity={'OK' if ok else 'FAIL'}")
    if args.emit_cache:
        with open(args.emit_cache, "w") as f:
            json.dump(sweep_cache.to_dict(), f, sort_keys=True, indent=2)
            f.write("\n")
        if not args.json:
            print(f"geometry cache ({len(sweep_cache)} entries) -> "
                  f"{args.emit_cache}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
