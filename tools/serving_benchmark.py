"""GenerationServer under-load benchmark — continuous batching on chip.

VERDICT r3 item 7: the serving engine (slot pool, mid-flight refill — the
AnalysisPredictor-equivalent deployment story, ref
inference/api/analysis_predictor.cc:929) had CPU tests but no on-chip
throughput-under-load number; tools/decode_benchmark.py measures only raw
``generate``. This driver submits a burst of mixed-prompt-length requests
against a slot pool smaller than the burst (so refill churns), and reports
generated tok/s + per-request completion latency p50/p95.

``--paged`` switches the server to the block-table KV pool (chunked
prefill + prefix caching, docs/serving.md): the JSON line then also
carries ``peak_kv_blocks``/``kv_blocks_total``/``kv_block_size`` so the
memory-proportionality claim (peak blocks ~ active tokens, not
``slots·max_len``) is measured, not asserted. ``--json`` emits exactly ONE
machine-readable JSON line on stdout (bench.py style); without it the same
line is printed plus a human-readable summary on stderr.

Sync honesty: every server tick pulls next-token ids to host
(np.asarray in ``step``), so wall-clock over the drain IS device time.

``--spec K`` stacks speculative decoding on the paged server (drafter →
one fused k+1-wide verify program, exact acceptance): the JSON line gains
``acceptance_rate`` and ``draft_tokens_proposed/accepted``;
``--repeat-suffix`` switches to the repeated-suffix workload where
prompt-lookup drafting shines.

Overload / scheduling (docs/serving.md "Scheduling and host KV offload"):
``--arrival-rate R`` switches from the submit-everything burst to an
OPEN-LOOP bursty generator — requests arrive in ``--burst``-sized clumps
on a pre-drawn timeline (exponential inter-burst gaps at R req/s overall)
that does NOT wait for the server, so queueing delay shows up in TTFT
instead of being hidden by closed-loop self-pacing. The whole traffic
trace (lengths, arrival times, priorities) is drawn up front from
``--seed``, so a run is reproducible end to end. ``--pool-frac F``
shrinks the KV pool to F× dense parity (demand > pool → swap-preemption
fires), ``--scheduler priority --mixed-priority`` splits traffic across
priority classes/tenants, and the JSON line gains
``ttft_p50_s/ttft_p95_s`` (plus per-class splits), ``tpot_p50_ms/
tpot_p95_ms``, and the preemption/swap counters.

Chaos soak (docs/serving.md "Fault tolerance and degradation"):
``--chaos`` runs the seeded traffic twice — a fault-free reference pass,
then the measured pass with ``FaultPlan.chaos(--seed)`` injected into
the paged substrate (allocator exhaustion, tick faults, drafter
failures, bit-flipped swap payloads) and the scheduler clock wrapped by
the injector. Pool conservation is asserted after every tick; the JSON
line gains ``faults_injected/quarantined/token_mismatches/ref_tok_s``.
``--strict`` turns telemetry on and exits non-zero on any watchdog
finding (under ``--chaos``, on the post-plan recovery burst, which must
come back clean).

Fleet (docs/serving.md "Fleet: routing, failover, migration"):
``--fleet N`` routes the same seeded traffic through a ``FleetRouter``
of N replica engines (prefix-aware routing, health-checked membership)
and the JSON line gains one row per replica (``fleet_metrics()``).
Under ``--chaos`` the reference pass becomes an UNDISTURBED single
engine and the measured fleet runs ``FaultPlan.fleet_chaos(--seed)``:
one replica is killed mid-decode and the line reports
``fleet_deaths / token_mismatches / quarantined`` plus
``ref_drain_recompiles / drain_recompiles`` — the failover drain is
held to the twin's compile budget by the same jit-cache guard.

Multi-chip (docs/serving.md "Multi-chip serving"): ``--mesh tp=N``
serves the same workload with params, KV block pool, int8 scales, and
LoRA pages sharded over an N-way tensor-parallel mesh (placement-only
GSPMD sharding — the compiled programs are unchanged and tokens are
bit-identical to tp=1). The JSON line gains ``tp`` / ``mesh`` /
``tok_s_per_chip`` (= value / (tp x replicas)) and a
``tokens_fingerprint`` hash of every output sequence, so a suite gate
can assert token-equality across mesh widths from the lines alone. On
CPU (JAX_PLATFORMS=cpu) the tool forces enough XLA host devices for the
dryrun mesh. ``--disagg`` (with ``--fleet N``) specializes the replicas
into floor(N/2) prefill-class + the rest decode-class engines: fresh
prompts route to the prefill class, finished prefills hand off over the
CRC-verified migration path, and the line gains ``prefill_replicas`` /
``decode_replicas`` / ``handoffs`` / ``handoff_requests`` plus
migration-latency percentiles. Under ``--chaos`` the disaggregated
fleet runs a seeded PREFILL-replica kill (``FaultPlan.disagg_chaos``)
instead of the generic fleet plan, so the salvage-onto-decode-class
path is what the twin comparison exercises.

Long-context (docs/serving.md "Long-context serving"):
``--long-context`` draws prompts from a log-spaced 8k-128k ladder
(``--lc-min/--lc-max`` rescale it for CPU dryruns), ``--shared-prefix F``
overlays one shared per-seed prefix on every prompt (the cross-request
prefix-cache workload), and ``--mesh tp=NxCp=M`` adds a context-parallel
axis that shards the chunked prefill's sequence dimension — tokens stay
bit-identical to cp=1. ``--tier-demote LOW:HIGH`` turns on
watermark-driven hot->warm KV demotion (``--warm-pool-mb`` caps the warm
tier; over budget, demotions fall to cold re-prefill). The paged JSON
line then reports ``prefill_tok_s_per_chip`` and ``tier_hit_rate``
{hot, warm, cold} alongside the demotion/promotion counters.

Every JSON line carries ``schema_version`` plus ``config_fingerprint``
(a stable hash of the resolved workload/config knobs, reporting-only
flags excluded) so downstream tooling can both detect schema drift and
refuse to diff lines that measured different configurations.

Traffic is decoupled from the serving config: the measured requests
(and the open-loop schedule) come from one ``RandomState(--seed)``
stream, while the config-scaled warmup bursts draw from a DISJOINT
xor-seeded stream with their own request counter — so any two configs
at the same ``--seed`` serve byte-identical traffic. The line's
``traffic_fingerprint`` hashes the measured submit timeline directly
(and token-exact serving then makes ``tokens_fingerprint`` match
across configs too — the autotuner's correctness gate rides on this).

Autotuning (docs/autotuning.md): ``--profile PATH`` replays a tuned
profile (``paddle_tpu.autotune`` JSON) — the server is built via
``GenerationServer(profile=...)`` and the profile's knobs override the
per-knob flags; the line gains ``profile_fingerprint`` /
``profile_workload_match``. ``--tune BUDGET`` runs the cost-model
search over THIS benchmark's seeded workload first, replays the
winning config as the measured run, and (with ``--profile PATH``)
saves the winner there; the line gains ``tuned`` / ``tune_budget`` /
``tune_baseline_tok_s`` / ``tune_trials``.

Usage: python tools/serving_benchmark.py [--requests 48] [--slots 8]
       [--seed 0] [--arrival-rate R --burst B]
       [--scheduler fifo|priority|wfq [--mixed-priority]]
       [--paged [--block-size 16] [--num-blocks N] [--pool-frac F]
        [--host-pool-mb M] [--prefill-chunk 64]
        [--spec 4 [--spec-drafter ngram|model] [--repeat-suffix]]
        [--long-context [--lc-min A --lc-max B] [--shared-prefix F]]
        [--tier-demote L:H [--warm-pool-mb M]]
        [--mesh tp=N[xcp=M]] [--fleet N [--disagg]] [--chaos [--strict]]
        [--profile PATH | --tune BUDGET [--profile OUT]]]
       [--json]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: Bump when the JSON line's keys change meaning or go away (adding keys
#: is compatible and does NOT bump): 2 = schema_version/config_fingerprint
#: introduced alongside the --fleet rows; 3 = ``value`` is still FLEET-WIDE
#: tok/s but the normalized figure moved to the new ``tok_s_per_chip``
#: (value / (tp x replicas)) — readers that treated the fleet ``value`` as
#: a per-chip number must switch keys. Every v2 key is still present.
#: 4 = long-context serving: ``mesh`` strings may now carry a cp axis
#: (``tpNcpM``) and per-chip figures divide by tp x cp; paged lines gain
#: ``prefill_tok_s_per_chip`` and ``tier_hit_rate`` {hot, warm, cold}.
#: Every v3 key is still present with its v3 meaning at cp=1.
#: 5 = ``kernels`` is stamped on EVERY line (v4 only stamped it on paged
#: microbench lines — readers keying dispatch mode off its presence must
#: read its value instead). (The fourth ``--kernels`` choice v5 added, and
#: the keys only it emitted, left with that kernel: no line of a remaining
#: choice changed, so no bump.)
#: 6 = fleet scale: ``--fleet`` lines now carry ``slo`` (per-tenant
#: TTFT/TPOT attainment + burn rate from the router's roll-up) on every
#: line, not only under --strict; the new ``--sim`` mode emits a
#: ``serving_fleetsim_sessions_s`` line (discrete-event day simulation,
#: no engine) with ``sim_sessions`` / ``sim_virtual_hours`` /
#: ``replica_hours`` / ``autoscale_events`` / per-tenant ``slo``.
#: Every v5 key is still present with its v5 meaning.
SCHEMA_VERSION = 6


def config_fingerprint(args) -> str:
    """Stable hash of every resolved knob that defines the measured
    configuration (reporting-only flags excluded). Two JSON lines with
    the same fingerprint measured the same setup — the suite gate and
    regression tooling refuse to diff lines whose fingerprints differ."""
    skip = {"json", "telemetry_out", "strict"}
    src = {k: v for k, v in sorted(vars(args).items()) if k not in skip}
    return hashlib.sha256(
        json.dumps(src, sort_keys=True, default=str).encode()
    ).hexdigest()[:12]


def kernel_microbench(server, cfg, args, iters: int = 10):
    """Per-op dispatch timing of the paged decode-attention program at the
    SERVER'S shapes (slots, table width, block size, kv heads) — the
    kernel-level tok/s figure behind the end-to-end line. Times the active
    dispatch (``kernel_tok_s`` / ``kernel_dispatch_us``) and the pinned jnp
    reference (``kernel_ref_tok_s``) so the win is measured, not asserted.
    Runs AFTER the measured drain — it jits two fresh closures and must not
    count against the steady-state recompile guard."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import ops
    from paddle_tpu.framework.dtype import convert_dtype
    from paddle_tpu.ops import paged_attention as pa

    B = args.slots
    bs = args.block_size
    H = cfg.num_attention_heads
    KV = cfg.num_key_value_heads
    D = cfg.hidden_size // H
    M = server._table_width
    N = server.alloc.num_blocks
    dt = convert_dtype(cfg.dtype)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, 1, H, D), dt)
    tables = jnp.asarray(
        rng.randint(1, max(N, 2), (B, M)).astype(np.int32))
    pos = jnp.full((B,), min(args.max_len - 1, M * bs - 1), jnp.int32)
    if args.kv_quant == "int8":
        kq = jnp.asarray(rng.randint(-127, 128, (N, bs, KV, D)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.randn(N, KV)).astype(np.float32))
        op_args = (q, kq, ks, kq, ks, tables, pos)
        op = pa.paged_decode_attention_q
    else:
        kp = jnp.asarray(rng.randn(N, bs, KV, D), dt)
        op_args = (q, kp, kp, tables, pos)
        op = pa.paged_decode_attention

    def timed(fn):
        jf = jax.jit(fn)
        jf(*op_args)[0].block_until_ready()        # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jf(*op_args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    mode = ops.kernel_mode()
    try:
        active_s = timed(lambda *a: op(*a))
        ops.set_kernel_mode("reference")
        ref_s = timed(lambda *a: op(*a))
    finally:
        ops.set_kernel_mode(mode)
    return {"kernel_tok_s": round(B / active_s, 1),
            "kernel_ref_tok_s": round(B / ref_s, 1),
            "kernel_dispatch_us": round(active_s * 1e6, 1)}


def sim_main(args):
    """--sim: the discrete-event day simulation (paddle_tpu.fleetsim) —
    a million seeded session arrivals against the analytic replica
    model under the elastic autoscaler, in virtual time. Emits one
    ``serving_fleetsim_sessions_s`` JSON line whose payload (including
    ``autoscale_events`` and per-tenant ``slo``) is byte-identical per
    seed; ``value`` is the only wall-time-dependent key (simulator
    throughput, sessions per wall second)."""
    from paddle_tpu.fleetsim import (DayTrafficSpec, FleetSimulation,
                                     ReplicaServiceModel, draw_day)
    from paddle_tpu.inference.autoscale import (AutoscalePolicy,
                                                ElasticAutoscaler,
                                                verify_replay)

    spec = DayTrafficSpec(sessions=args.sim_sessions, seed=args.seed)
    cap = float(args.sim_capacity)
    policy = AutoscalePolicy(min_replicas=1,
                             max_replicas=args.sim_max_replicas,
                             up_cooldown_s=120.0, down_cooldown_s=1200.0)
    engine = ElasticAutoscaler(cap, policy=policy)
    model = ReplicaServiceModel(decode_tok_s=cap, prefill_tok_s=8.0 * cap,
                                slots=16, spawn_delay_s=30.0)
    t0 = time.perf_counter()
    trace = draw_day(spec)
    report = FleetSimulation(trace, model, autoscaler=engine,
                             initial_replicas=2,
                             control_interval_s=60.0,
                             forecast_horizon_s=900.0).run()
    wall = time.perf_counter() - t0
    # the journal must replay before it is reported — an event log that
    # does not reproduce its own decisions is a log of accidents
    verify_replay(report["autoscale_events"], cap, policy=policy)
    line = {"metric": "serving_fleetsim_sessions_s",
            "value": round(args.sim_sessions / wall, 1),
            "unit": f"simulated sessions / wall second "
                    f"({args.sim_sessions} sessions, "
                    f"{report['sim_virtual_hours']}h virtual, "
                    f"cap={cap:g} tok/s, "
                    f"max={args.sim_max_replicas} replicas)",
            "sim_sessions": report["sim_sessions"],
            "sim_virtual_hours": report["sim_virtual_hours"],
            "replica_hours": report["replica_hours"],
            "static_replicas": report["static_replicas"],
            "static_replica_hours": report["static_replica_hours"],
            "elastic_beats_static": report["elastic_beats_static"],
            "autoscale_events": report["autoscale_event_count"],
            "scale_ups": report["scale_ups"],
            "scale_downs": report["scale_downs"],
            "peak_replicas": report["peak_replicas"],
            "completed": report["completed"],
            "mean_ttft_s": report["mean_ttft_s"],
            "tokens_served": report["tokens_served"],
            "slo": report["slo"],
            "slo_attained": report["slo_attained"],
            "slo_target": report["slo_target"],
            "traffic_signature": report["traffic_signature"],
            "wall_s": round(wall, 2),
            "seed": args.seed,
            "schema_version": SCHEMA_VERSION,
            "kernels": args.kernels,
            "config_fingerprint": config_fingerprint(args)}
    print(json.dumps(line))
    if not args.json:
        print(f"[fleetsim] {args.sim_sessions} sessions / "
              f"{report['sim_virtual_hours']}h virtual in {wall:.2f}s "
              f"wall; elastic {report['replica_hours']}h vs static "
              f"{report['static_replica_hours']}h replica-hours "
              f"({report['scale_ups']} ups, {report['scale_downs']} "
              f"downs, peak {report['peak_replicas']}), SLO attained: "
              f"{report['slo_attained']}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=None,
                    help="generated tokens per request (default 64; 128 "
                         "under --repeat-suffix, whose long-form "
                         "repetitive generations are the point)")
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--tick-window", type=int, default=None,
                    help="decode ticks per host round trip (amortizes the "
                         "d2h sync; 1 = exact per-token semantics). Default "
                         "16; 4 under --spec, where each window already "
                         "advances up to k+1 tokens so fewer windows per "
                         "trip keep per-trip emission comparable while "
                         "cutting surplus verify work past finished "
                         "requests")
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 (model.quantize_int8()) under "
                         "the same load — composes the decode win with "
                         "the tick-window server")
    ap.add_argument("--long-prompts", action="store_true",
                    help="mixed prompts 64-512 over buckets (64,128,256,"
                         "512); raises max-len to 768 unless given")
    ap.add_argument("--long-context", action="store_true",
                    help="long-context preset (paged only): prompt "
                         "lengths drawn from a log-spaced ladder "
                         "--lc-min..--lc-max (5 rungs, rounded to block "
                         "multiples); raises max-len to lc-max + max-new "
                         "unless given. Combine with --shared-prefix / "
                         "--tier-demote to exercise the hot/warm/cold "
                         "KV ladder (docs/serving.md)")
    ap.add_argument("--lc-min", type=int, default=8192,
                    help="shortest long-context prompt rung (default 8k; "
                         "shrink for CPU dryruns)")
    ap.add_argument("--lc-max", type=int, default=131072,
                    help="longest long-context prompt rung (default 128k)")
    ap.add_argument("--shared-prefix", type=float, default=0.0, metavar="F",
                    help="fraction [0,1] of every prompt replaced by ONE "
                         "shared token prefix (drawn once per seed) — the "
                         "cross-request prefix-cache / warm-tier workload")
    ap.add_argument("--tier-demote", default=None, metavar="LOW:HIGH",
                    help="enable watermark-driven hot->warm KV demotion "
                         "(paged only): when the free-block fraction "
                         "falls below LOW, cached blocks demote to the "
                         "host warm tier until HIGH is free again "
                         "(e.g. 0.1:0.3)")
    ap.add_argument("--warm-pool-mb", type=float, default=None,
                    help="cap the warm-tier byte budget (default "
                         "unbounded); over-budget demotions fall to the "
                         "cold tier (re-prefill from replay)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block-table pool + chunked "
                         "prefill + prefix caching (cache='paged')")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged only)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="total KV blocks in the pool (paged only; default "
                         "sizes for dense parity)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="tokens per chunked-prefill program (paged only)")
    ap.add_argument("--kv-quant", choices=("none", "int8"), default="none",
                    help="KV pool storage (paged only): int8 stores blocks "
                         "as int8 codes + per-block-per-head f32 scales "
                         "with dequant fused into the attention programs. "
                         "Without --num-blocks, the pool is sized to the "
                         "SAME byte budget the fp pool would get, so the "
                         "JSON's kv_blocks_total shows the capacity win "
                         "directly (~2x bf16 / ~4x f32)")
    ap.add_argument("--lora-adapters", type=int, default=0, metavar="N",
                    help="multi-tenant LoRA workload (paged only): register "
                         "N random adapters, assign requests round-robin "
                         "(request i uses adapter a{i%%N}, tenant t{i%%N}) "
                         "so adapter residency churns; JSON line gains "
                         "adapter_pool_bytes / adapter_hit_rate / "
                         "adapter_uploads + the per-tenant TTFT/TPOT "
                         "breakdown")
    ap.add_argument("--lora-rank", type=int, default=8,
                    help="rank of every generated adapter (and the pool's "
                         "max_rank)")
    ap.add_argument("--lora-live", type=int, default=None, metavar="M",
                    help="adapter-pool pages = max concurrently-resident "
                         "adapters (default min(N, slots)); N > M forces "
                         "LRU eviction + re-upload churn")
    ap.add_argument("--kernels",
                    choices=("auto", "pallas", "reference"),
                    default="auto",
                    help="attention/projection kernel dispatch for the "
                         "compiled serving programs: auto = Pallas on TPU / "
                         "jnp reference elsewhere, pallas = force the "
                         "Pallas kernels (interpret mode off-TPU), "
                         "reference = pin the jnp compositions")
    ap.add_argument("--guard-recompiles", action="store_true",
                    help="wrap the measured drain in jit_cache_guard: any "
                         "steady-state recompile after warmup fails the "
                         "run (exit 1)")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding with K drafts per verify "
                         "window (paged only). The ngram drafter runs "
                         "in-program, so tick-window verify windows fuse "
                         "into one compiled scan per host trip; the model "
                         "drafter forces tick-window=1. JSON line gains "
                         "acceptance_rate + draft_tokens_proposed/accepted")
    ap.add_argument("--spec-drafter", choices=("ngram", "model"),
                    default="ngram",
                    help="drafter: prompt-lookup n-gram (hermetic) or a "
                         "small draft llama sharing the tokenizer")
    ap.add_argument("--repeat-suffix", action="store_true",
                    help="repeated-suffix workload: prompts tile a short "
                         "motif, so generation loops the drafter can "
                         "predict — the speculative showcase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the whole traffic trace (prompt lengths, "
                         "contents, arrival times, priority assignment) — "
                         "same seed, same workload, run to run")
    ap.add_argument("--arrival-rate", type=float, default=None, metavar="R",
                    help="open-loop arrivals at R requests/s overall, in "
                         "--burst clumps with exponential inter-burst gaps "
                         "(drawn from --seed). Without it, all requests "
                         "are submitted up front (closed-loop burst)")
    ap.add_argument("--burst", type=int, default=4,
                    help="requests per arrival clump in open-loop mode")
    ap.add_argument("--scheduler", choices=("fifo", "priority", "wfq"),
                    default="fifo",
                    help="GenerationServer policy= (inference/scheduler.py)")
    ap.add_argument("--mixed-priority", action="store_true",
                    help="assign priorities round-robin (high/normal/low) "
                         "and tenants (a/b) so --scheduler priority|wfq "
                         "has classes to separate; the JSON line then "
                         "splits TTFT percentiles per class")
    ap.add_argument("--pool-frac", type=float, default=None, metavar="F",
                    help="shrink the paged pool to F x dense parity so "
                         "demand exceeds the pool and swap-preemption "
                         "fires (overload mode; paged only)")
    ap.add_argument("--host-pool-mb", type=float, default=None,
                    help="cap the host swap pool (default unbounded); "
                         "0 disables swapping — victims stall instead")
    ap.add_argument("--telemetry-out", metavar="PATH", default=None,
                    help="enable serving telemetry (span tracer + flight "
                         "recorder) and dump PATH.metrics.json (registry "
                         "snapshot + watchdog findings), PATH.trace.json "
                         "(chrome trace: one timeline row per request), "
                         "and PATH.flight.json (per-tick flight ring) "
                         "after the drain. The TTFT/TPOT percentiles in "
                         "the JSON line come from the same registry "
                         "histograms either way")
    ap.add_argument("--mesh", default=None, metavar="tp=N[xcp=M]",
                    help="serve over a device mesh (paged only): 'tp=N' "
                         "shards params, KV block pool, int8 scales, and "
                         "LoRA pages over an N-way tensor-parallel axis; "
                         "'cp=M' / 'tp=NxCp=M' adds an M-way "
                         "context-parallel axis that shards the chunked "
                         "prefill's sequence dimension (long-context "
                         "prefill scaling). Tokens stay bit-identical to "
                         "tp=1/cp=1 (the line's tokens_fingerprint "
                         "proves it) and the line gains "
                         "tp/cp/tok_s_per_chip/prefill_tok_s_per_chip. "
                         "Accepts 'tp=N', 'cp=M', 'tp=NxCp=M', or a bare "
                         "int (tp). On CPU the tool forces NxM XLA host "
                         "devices for the dryrun")
    ap.add_argument("--disagg", action="store_true",
                    help="with --fleet N: specialize the replicas into "
                         "floor(N/2) prefill-class + the rest "
                         "decode-class engines — fresh prompts route to "
                         "the prefill class, finished prefills hand off "
                         "to the decode class over the CRC-verified "
                         "migration path; the line gains "
                         "prefill_replicas/decode_replicas/handoffs + "
                         "migration-latency percentiles. With --chaos "
                         "the seeded plan kills a PREFILL replica so "
                         "the decode-class salvage path is exercised")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="route the traffic through a FleetRouter of N "
                         "replica engines (paged only, N >= 2): "
                         "prefix-aware routing + health-checked "
                         "membership; the JSON line gains per-replica "
                         "rows. With --chaos the reference is an "
                         "UNDISTURBED single engine and the fleet runs "
                         "FaultPlan.fleet_chaos(--seed) — one replica "
                         "dies mid-decode; the line reports fleet_deaths"
                         "/token_mismatches/quarantined and the failover "
                         "drain is held to the twin's compile budget")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos soak (paged only): run the seeded traffic "
                         "twice — a fault-free reference pass, then the "
                         "measured pass with FaultPlan.chaos(--seed) "
                         "injected (pool exhaustion, tick faults, drafter "
                         "failures, swap corruption) and the scheduler "
                         "clock injector-wrapped. Pool conservation is "
                         "asserted after EVERY tick; the JSON line gains "
                         "faults_injected / quarantined / "
                         "token_mismatches (non-quarantined outputs vs "
                         "the reference) / ref_tok_s")
    ap.add_argument("--profile", metavar="PATH", default=None,
                    help="apply a tuned serving profile (paddle_tpu."
                         "autotune JSON): the server is built via "
                         "GenerationServer(profile=...) and the profile's "
                         "knobs OVERRIDE the per-knob flags (--block-size/"
                         "--tick-window/--kv-quant/--scheduler/...). With "
                         "--tune, PATH is where the freshly tuned profile "
                         "is written before the measured replay")
    ap.add_argument("--geometry-cache", metavar="PATH", default=None,
                    help="install a swept kernel-geometry winner cache "
                         "(kernel_bench.py --sweep-geometry --emit-cache "
                         "JSON) before the server is built: every kernel "
                         "trace resolves its schedule from the cache "
                         "(source 'swept'); a --profile with its own "
                         "kernel_geometry takes precedence")
    ap.add_argument("--tune", type=int, default=None, metavar="BUDGET",
                    help="run the cost-model autotuner (paddle_tpu."
                         "autotune) over this benchmark's seeded workload "
                         "with BUDGET measured candidate trials, then "
                         "replay the WINNING config as the measured run; "
                         "--profile PATH saves the winner")
    ap.add_argument("--strict", action="store_true",
                    help="enable telemetry and exit non-zero on any "
                         "watchdog finding — over the measured drain, or "
                         "(under --chaos) over a post-plan recovery burst, "
                         "which must come back clean")
    ap.add_argument("--sim", action="store_true",
                    help="discrete-event fleet simulation instead of an "
                         "engine run: draw a seeded day of traffic "
                         "(paddle_tpu.fleetsim), replay it against the "
                         "analytic replica model under the elastic "
                         "autoscaler in fast-time, and emit one "
                         "serving_fleetsim_sessions_s line — no model, "
                         "no chip, byte-identical per --seed")
    ap.add_argument("--sim-sessions", type=int, default=1_000_000,
                    help="sessions in the simulated day (default 1M)")
    ap.add_argument("--sim-capacity", type=float, default=400.0,
                    metavar="TOK_S",
                    help="analytic per-replica decode capacity for --sim "
                         "(tokens/s; the cost model supplies this on a "
                         "real deployment via capacity_tok_s)")
    ap.add_argument("--sim-max-replicas", type=int, default=12,
                    help="autoscaler ceiling for --sim")
    ap.add_argument("--json", action="store_true",
                    help="emit exactly one machine-readable JSON line "
                         "(bench.py style) on stdout and nothing else")
    args = ap.parse_args()
    if args.sim:
        if args.fleet or args.chaos or args.paged or args.spec \
                or args.tune is not None or args.profile is not None:
            ap.error("--sim is the pure fast-time simulator — it takes "
                     "no engine knobs (--paged/--fleet/--chaos/--spec/"
                     "--profile/--tune); size it with --sim-sessions/"
                     "--sim-capacity/--sim-max-replicas and --seed")
        sim_main(args)
        return
    if args.chaos and not args.paged:
        ap.error("--chaos requires --paged (the fault sites live in the "
                 "paged substrate)")
    if args.fleet:
        if not args.paged:
            ap.error("--fleet requires --paged (migration rides the "
                     "per-request KV capture)")
        if args.fleet < 2:
            ap.error("--fleet needs N >= 2 — failover has to have "
                     "somewhere to go")
        if args.spec:
            ap.error("--fleet is incompatible with --spec (one knob at "
                     "a time; spec state does migrate, but the fleet "
                     "benchmark measures routing/failover)")
        if args.arrival_rate is not None:
            ap.error("--fleet uses the closed-loop burst (seeded bursty "
                     "traffic); --arrival-rate is not modeled for it")
    if args.disagg and not args.fleet:
        ap.error("--disagg requires --fleet N (N >= 2): prefill and "
                 "decode classes need separate replicas")
    if args.profile is not None or args.tune is not None:
        if not args.paged:
            ap.error("--profile/--tune require --paged (every tuned "
                     "config serves from the paged substrate)")
        if args.fleet or args.chaos:
            ap.error("--profile/--tune are incompatible with --fleet/"
                     "--chaos (tune the single-engine config; fleet "
                     "knobs ride the profile's fleet_* entries)")
        if args.tune is not None and args.tune < 1:
            ap.error("--tune BUDGET must be >= 1")
        if args.tune is not None and args.lora_adapters:
            ap.error("--tune does not model the adapter pool yet — "
                     "tune the base-engine knobs without --lora-adapters, "
                     "then replay the profile WITH them")
    tp, cp = 1, 1
    if args.mesh is not None:
        if not args.paged:
            ap.error("--mesh requires --paged (the sharded pools ARE the "
                     "paged substrate)")
        # mirrors paddle_tpu.parallel.serving_mesh.parse_mesh, but WITHOUT
        # importing it: the XLA host-device-count flag below only takes
        # effect if set before the first jax import
        m = str(args.mesh).strip().lower()
        try:
            if "=" not in m:
                tp = int(m)
            else:
                for part in m.split("x"):
                    k, _, v = part.partition("=")
                    if k.strip() == "tp":
                        tp = int(v)
                    elif k.strip() == "cp":
                        cp = int(v)
                    else:
                        raise ValueError(part)
        except ValueError:
            ap.error("--mesh must be an int tp degree, 'tp=N', 'cp=M', "
                     "or 'tp=NxCp=M'")
        if tp < 1 or cp < 1:
            ap.error("--mesh axis degrees must be >= 1")
        if tp * cp > 1 and os.environ.get("JAX_PLATFORMS", "") == "cpu" \
                and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            # CPU dryrun: the mesh needs tp*cp host devices, and the flag
            # only takes effect if set BEFORE jax is imported (which is
            # why the jax imports below sit under main())
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count"
                + f"={tp * cp}").strip()
    if args.pool_frac is not None and not args.paged:
        ap.error("--pool-frac requires --paged")
    if args.host_pool_mb is not None and not args.paged:
        ap.error("--host-pool-mb requires --paged")
    if args.warm_pool_mb is not None and not args.paged:
        ap.error("--warm-pool-mb requires --paged (the warm tier parks "
                 "paged KV blocks)")
    tier_low = tier_high = None
    if args.tier_demote is not None:
        if not args.paged:
            ap.error("--tier-demote requires --paged (only block-pool KV "
                     "demotes)")
        try:
            lo, _, hi = args.tier_demote.partition(":")
            tier_low, tier_high = float(lo), float(hi)
        except ValueError:
            ap.error("--tier-demote must be LOW:HIGH (two floats, e.g. "
                     "0.1:0.3)")
    if args.long_context:
        if not args.paged:
            ap.error("--long-context requires --paged (chunked prefill + "
                     "the block pool are the long-context substrate)")
        if args.long_prompts or args.repeat_suffix:
            ap.error("--long-context replaces the prompt ladder; drop "
                     "--long-prompts/--repeat-suffix")
        if not (0 < args.lc_min <= args.lc_max):
            ap.error("--lc-min/--lc-max must satisfy 0 < min <= max")
    if not (0.0 <= args.shared_prefix <= 1.0):
        ap.error("--shared-prefix must be a fraction in [0, 1]")
    if args.burst < 1:
        ap.error("--burst must be >= 1")
    if args.max_new is None:
        args.max_new = 128 if args.repeat_suffix else 64
    if args.max_len is None:
        if args.long_context:
            args.max_len = args.lc_max + args.max_new
        else:
            args.max_len = 768 if args.long_prompts else 256
            if args.repeat_suffix:
                args.max_len = max(args.max_len, 128 + args.max_new)
    if args.kv_quant != "none" and not args.paged:
        ap.error("--kv-quant requires --paged (the int8 pool is the "
                 "block pool)")
    if args.lora_adapters:
        if not args.paged:
            ap.error("--lora-adapters requires --paged (the adapter pool "
                     "shares the paged slot machinery)")
        if args.int8:
            ap.error("--lora-adapters is incompatible with --int8 weights "
                     "(serve LoRA over fp base weights; --kv-quant int8 "
                     "is fine)")
        if args.lora_rank < 1:
            ap.error("--lora-rank must be >= 1")
    if args.spec:
        if not args.paged:
            ap.error("--spec requires --paged (the verify op is paged)")
        if args.spec_drafter == "model":
            args.tick_window = 1  # host-side drafter: one window per trip
        elif args.tick_window is None:
            args.tick_window = 4
    if args.tick_window is None:
        args.tick_window = 16

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import GenerationServer
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=8,
                          num_attention_heads=16, num_key_value_heads=8,
                          max_position_embeddings=args.max_len,
                          dtype="bfloat16", use_flash_attention=True)
    else:
        # CPU stand-in: hidden 128 keeps the decode tick matmul-bound —
        # at hidden 64 per-op overhead swamps compute and every serving
        # ratio (tick-window, spec verify width) measures dispatch, not
        # the design
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=args.max_len,
                          dtype="float32", use_flash_attention=False)
    paddle.seed(0)   # model weights are part of the benchmark definition
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # --seed governs TRAFFIC only: same weights, different load trace
    rng = np.random.RandomState(args.seed)
    # warmup draws come from a DISJOINT stream (xor'd seed, own counter):
    # warmup sizing scales with --slots/--pool-frac, and if it shared the
    # measured stream the measured traffic would shift whenever a serving
    # knob changed — the autotuner's cross-config token-fingerprint gate
    # (and any two-line diff at one seed) needs byte-identical traffic
    wrng = np.random.RandomState((args.seed ^ 0x5EED) & 0x7FFFFFFF)
    _warm_state = wrng.get_state()

    motif = rng.randint(1, cfg.vocab_size, 8).tolist()
    lc_lens = None
    if args.long_context:
        # log-spaced rungs, rounded DOWN to block multiples so tier
        # demotion/promotion always moves whole blocks (the last-token
        # rule then leaves exactly the final block uncacheable)
        raw = np.geomspace(args.lc_min, args.lc_max, 5)
        lc_lens = sorted({max(args.block_size,
                              int(v) // args.block_size * args.block_size)
                          for v in raw})
    shared_tokens = None
    if args.shared_prefix > 0.0:
        # one shared prefix per seed, from its OWN stream — enabling the
        # knob must not shift the measured traffic draws
        srng = np.random.RandomState((args.seed + 0x5AFE) & 0x7FFFFFFF)
        shared_tokens = srng.randint(1, cfg.vocab_size, args.max_len)
    _counter = [0]
    _wcounter = [0]
    prios = {}
    # measured-pass submit timeline (prompts, priorities, tenants,
    # adapters + the pre-drawn open-loop schedule) — hashed into the
    # line's traffic_fingerprint so traffic/config decoupling is
    # checkable from the JSON alone
    _trace = []
    _sched_trace = []

    tuned_profile, wspec = None, None
    if args.tune is not None or args.profile is not None:
        from paddle_tpu.autotune import (TrialRunner, TunedProfile,
                                         WorkloadSpec)
        from paddle_tpu.autotune import autotune as run_autotune
        from paddle_tpu.autotune.workload import (LONG_PROMPT_LADDER,
                                                  SHORT_PROMPT_LADDER)

        wspec = WorkloadSpec(
            requests=args.requests, max_new=args.max_new,
            prompt_ladder=(tuple(lc_lens) if args.long_context
                           else LONG_PROMPT_LADDER if args.long_prompts
                           else SHORT_PROMPT_LADDER),
            vocab_size=cfg.vocab_size, repeat_suffix=args.repeat_suffix,
            mixed_priority=args.mixed_priority,
            lora_adapters=args.lora_adapters,
            arrival_rate=args.arrival_rate, burst=args.burst,
            long_context=args.long_context,
            shared_prefix_frac=args.shared_prefix,
            seed=args.seed)
        if args.tune is not None:
            runner = TrialRunner(model, wspec, max_batch=args.slots,
                                 max_len=args.max_len)
            tlog = (None if args.json
                    else (lambda s: print(f"[tune] {s}", file=sys.stderr)))
            tuned_profile, _trials = run_autotune(
                runner, budget=args.tune, seed=args.seed, log=tlog)
            if args.profile:
                tuned_profile.save(args.profile, now=time.time())
        else:
            tuned_profile = TunedProfile.load(args.profile)
        # sync the reporting knobs (unit string, kernel-microbench
        # shapes, config_fingerprint) to what the profile actually pins
        _pc = tuned_profile.config
        args.block_size = int(_pc["block_size"])
        args.tick_window = int(_pc["tick_window"])
        args.prefill_chunk = int(_pc["prefill_chunk"])
        args.kv_quant = str(_pc["kv_quant"])
        args.scheduler = str(_pc["policy"])
        args.spec = int(_pc.get("draft_k", 0))
        args.spec_drafter = "ngram"
        _pf = float(_pc.get("pool_frac", 1.0))
        args.pool_frac = _pf if _pf < 1.0 else None
        args.host_pool_mb = _pc.get("host_pool_mb")
        args.num_blocks = None

    if args.geometry_cache is not None:
        # installed BEFORE any server build so every kernel trace sees
        # it; a profile carrying its own kernel_geometry re-installs
        # with source "profile" inside the GenerationServer ctor
        from paddle_tpu.autotune.kernel_geometry import (GeometryCache,
                                                         install_geometry_cache)

        with open(args.geometry_cache) as f:
            install_geometry_cache(GeometryCache.from_dict(json.load(f)),
                                   source="swept")

    lora_cfg, lora_live = None, 0
    if args.lora_adapters:
        from paddle_tpu.inference.lora import (LORA_TARGETS, AdapterRegistry,
                                               LoRAConfig, target_dims)

        # adapter factors ride the traffic seed: same seed, same tenants'
        # weights — the model stays the fixed benchmark-definition model
        arng = np.random.RandomState(args.seed + 17)
        dims = target_dims(cfg)
        reg = AdapterRegistry()
        for a in range(args.lora_adapters):
            w = {}
            for layer in range(cfg.num_hidden_layers):
                for t in LORA_TARGETS:
                    fi, fo = dims[t]
                    w[(layer, t)] = (
                        arng.normal(0, 0.02, (fi, args.lora_rank))
                        .astype(np.float32),
                        arng.normal(0, 0.02, (args.lora_rank, fo))
                        .astype(np.float32))
            reg.register(f"a{a}", w, rank=args.lora_rank,
                         alpha=2.0 * args.lora_rank)
        lora_live = args.lora_live or min(args.lora_adapters, args.slots)
        lora_cfg = LoRAConfig(reg, max_live_adapters=lora_live,
                              max_rank=args.lora_rank)

    def burst(server, n, warm=False):
        """Mixed prompt lengths across the bucket ladder; round-robin
        priority classes + tenants under --mixed-priority. ``warm``
        bursts draw from the disjoint warmup stream (own counter) so
        config-scaled warmup never perturbs the measured traffic."""
        r = wrng if warm else rng
        ctr = _wcounter if warm else _counter
        if args.long_context:
            lens = r.choice(lc_lens, size=n)
        else:
            lens = r.choice([64, 128, 256, 400, 512] if args.long_prompts
                            else [16, 30, 64, 100, 128], size=n)
        rids = {}
        for ln in lens:
            if args.repeat_suffix:
                # tile one shared motif: greedy decoding locks onto the
                # repetition, which prompt-lookup drafts perfectly — and
                # the shared prefix exercises the prefix cache
                prompt = (motif * (int(ln) // len(motif) + 1))[:int(ln)]
            else:
                prompt = r.randint(1, cfg.vocab_size, int(ln)).tolist()
            if shared_tokens is not None:
                # overlay the seed's shared prefix — the cross-request
                # prefix-cache (and warm-tier re-hit) workload
                k = int(int(ln) * args.shared_prefix)
                prompt[:k] = shared_tokens[:k].tolist()
            i = ctr[0]
            ctr[0] += 1
            prio, tenant, adapter = 1, "default", None
            if args.mixed_priority:
                prio = (0, 1, 2)[i % 3]
                tenant = ("a", "b")[i % 2]
            if args.lora_adapters:
                # one tenant per adapter: the WFQ share → adapter
                # residency coupling is what the workload exercises
                adapter = f"a{i % args.lora_adapters}"
                tenant = f"t{i % args.lora_adapters}"
            rid = server.submit(prompt, max_new_tokens=args.max_new,
                                priority=prio, tenant=tenant,
                                adapter=adapter)
            rids[rid] = int(ln)
            prios[rid] = prio
            if not warm:
                _trace.append([prompt, int(args.max_new), prio, tenant,
                               adapter or ""])
        return rids

    import contextlib

    from paddle_tpu.analysis.recompile_guard import jit_cache_guard

    def make_server(faults=None, sched=None, role="any"):
        if tuned_profile is not None:
            # tuned path: the profile pins every engine knob through
            # GenerationServer(profile=...); only workload inputs
            # (model/slots/max_len) and reporting plumbing stay on args
            return GenerationServer(
                model, max_batch=args.slots, max_len=args.max_len,
                profile=tuned_profile, lora=lora_cfg, faults=faults,
                telemetry=bool(args.telemetry_out) or args.strict,
                kernels=args.kernels, role=role, mesh=args.mesh)
        if args.paged:
            spec = None
            if args.spec:
                from paddle_tpu.inference.speculative import SpecConfig

                draft_model = None
                if args.spec_drafter == "model":
                    paddle.seed(1)
                    dcfg = LlamaConfig(
                        vocab_size=cfg.vocab_size,
                        hidden_size=cfg.hidden_size // 2,
                        intermediate_size=cfg.intermediate_size // 2,
                        num_hidden_layers=max(cfg.num_hidden_layers // 4, 1),
                        num_attention_heads=max(
                            cfg.num_attention_heads // 2, 1),
                        num_key_value_heads=max(
                            cfg.num_key_value_heads // 2, 1),
                        max_position_embeddings=args.max_len,
                        dtype=cfg.dtype,
                        use_flash_attention=cfg.use_flash_attention)
                    draft_model = LlamaForCausalLM(dcfg)
                spec = SpecConfig(k=args.spec, drafter=args.spec_drafter,
                                  draft_model=draft_model)
            host_pool = (None if args.host_pool_mb is None
                         else int(args.host_pool_mb * 1e6))
            pool_bytes = None
            num_blocks = args.num_blocks
            if args.kv_quant != "none" and num_blocks is None:
                # equal-HBM comparison: hand the int8 server the byte
                # budget the DEFAULT fp pool would occupy (dense parity:
                # slots*ceil(max_len/bs)+1 blocks) and let it derive its
                # block count — kv_blocks_total then reports the capacity
                # win at constant memory instead of constant blocks
                from paddle_tpu.inference.serving import kv_block_bytes

                bs = args.block_size
                fp_blocks = args.slots * (-(-args.max_len // bs)) + 1
                pool_bytes = fp_blocks * kv_block_bytes(cfg, bs, "none")
            if args.pool_frac is not None:
                # overload mode: pool sized BELOW peak demand, so the
                # scheduler must preempt (swap KV to host) to make room
                if pool_bytes is not None:
                    pool_bytes = max(1, int(pool_bytes * args.pool_frac))
                elif num_blocks is None:
                    parity = args.slots * (-(-args.max_len
                                             // args.block_size)) + 1
                    num_blocks = max(4, int(parity * args.pool_frac))
            return GenerationServer(
                model, max_batch=args.slots, max_len=args.max_len,
                tick_window=args.tick_window, cache="paged",
                block_size=args.block_size, num_blocks=num_blocks,
                prefill_chunk=args.prefill_chunk, spec=spec,
                kv_quant=args.kv_quant, pool_bytes=pool_bytes,
                policy=sched if sched is not None else args.scheduler,
                host_pool_bytes=host_pool,
                warm_pool_bytes=(None if args.warm_pool_mb is None
                                 else int(args.warm_pool_mb * 1e6)),
                tier_demote_low=tier_low, tier_demote_high=tier_high,
                lora=lora_cfg, faults=faults,
                telemetry=bool(args.telemetry_out) or args.strict,
                kernels=args.kernels, role=role, mesh=args.mesh)
        return GenerationServer(model, max_batch=args.slots,
                                max_len=args.max_len,
                                prompt_buckets=((64, 128, 256, 512)
                                                if args.long_prompts
                                                else (32, 64, 128)),
                                tick_window=args.tick_window,
                                policy=args.scheduler,
                                telemetry=bool(args.telemetry_out)
                                or args.strict,
                                kernels=args.kernels)

    prefill_tokens0 = {}      # id(server) -> prompt tokens before its drain

    def run_pass(server, chaos_inj=None, allowed_compiles=0):
        """Warmup + the measured drain against the seeded traffic.

        The caller resets the traffic rng/counters before each pass, so
        two passes submit identical requests in identical order (and
        thus identical rids) — the chaos comparison relies on it (which
        is also why every warmup decision keys off args, never off
        which pass this is). Returns the drain's backend-compile count
        alongside the results: the chaos pass is held to the reference
        pass's compile budget — injected faults must not add a single
        program beyond what the fault-free drain compiles."""
        from paddle_tpu.analysis.recompile_guard import compile_count

        # warmup drain: compiles the decode tick + the prefill program(s)
        burst(server, min(args.slots, 4), warm=True)
        server.run()
        if (args.pool_frac is not None or tier_low is not None) \
                and (args.chaos or args.guard_recompiles):
            # overload warmup wave: churn so the swap gather/scatter
            # programs — which the tier ladder's demotion gather and
            # promotion scatter share shapes with — get a chance to
            # compile BEFORE the measured window (first preemption
            # after it still counts against the budget — hence the
            # reference-pass allowance)
            burst(server, args.slots * 2 + 2, warm=True)
            server.run()
        # warmup boundary: drop histogram samples, spans, and flight
        # ticks so registry percentiles (and any --telemetry-out dump)
        # cover the measured drain only; counters keep lifetime totals.
        # The reset folds warmup program keys into flight.warm_progs, so
        # the post-drain watchdog neither resurfaces a warmup compile as
        # a steady_state_recompile finding nor blanket-excuses a warm
        # program recompiling inside the first measured ticks
        server.telemetry.reset()
        if args.paged:
            # scope the prefill-throughput and cold-refill figures to
            # the measured drain (warmup churn demotes too)
            # (the token count is the lifetime serving_prefill_tokens
            # counter: keep where the drain starts)
            prefill_tokens0[id(server)] = server._prefill_tokens
            server._prefill_wall_s = 0.0
            server._cold_refills = 0
        if chaos_inj is not None:
            chaos_inj.enabled = True   # plan ordinals start at the drain

        # pre-draw the whole open-loop arrival timeline from the seeded
        # rng — the trace is fixed before the clock starts, so it cannot
        # react to server speed (open loop) and replays exactly per seed
        schedule = []
        if args.arrival_rate is not None:
            t, left = 0.0, args.requests
            while left > 0:
                n = min(args.burst, left)
                schedule.append((t, n))
                left -= n
                t += float(rng.exponential(args.burst / args.arrival_rate))
        _sched_trace[:] = [[t, n] for t, n in schedule]
        rids = {} if schedule else burst(server, args.requests)
        if chaos_inj is not None:
            guard = jit_cache_guard("chaos measured drain",
                                    allowed=allowed_compiles)
        elif args.guard_recompiles:
            guard = jit_cache_guard("serving_benchmark measured drain")
        else:
            guard = contextlib.nullcontext()
        c0 = compile_count()
        with guard:
            t0 = time.perf_counter()
            done_at = {}
            pending = list(schedule)
            while True:
                now = time.perf_counter() - t0
                while pending and pending[0][0] <= now:
                    rids.update(burst(server, pending.pop(0)[1]))
                remaining = server.step()
                if chaos_inj is not None:
                    # soak invariant: pool conservation after EVERY tick
                    server.assert_conserved()
                now = time.perf_counter() - t0
                for rid in list(server._results):
                    if rid not in done_at:
                        done_at[rid] = now
                if remaining == 0:
                    if not pending:
                        break
                    # open-loop lull: nothing in flight, next clump later
                    time.sleep(max(0.0, min(pending[0][0] - now, 0.01)))
            dt = time.perf_counter() - t0
        return rids, server._results, done_at, dt, compile_count() - c0

    def fleet_pass():
        """--fleet N: the seeded burst through a FleetRouter. Under
        --chaos the reference is an UNDISTURBED single engine over the
        identical traffic (rng state + request counter reset before each
        measured burst, so the trace matches request-for-request) and the
        fleet's failover drain is guarded at the twin's compile budget.
        Returns (json line, watchdog findings or None)."""
        from paddle_tpu.analysis.recompile_guard import compile_count
        from paddle_tpu.inference.fleet import FleetRouter

        traffic_state = rng.get_state()

        def reset_traffic():
            rng.set_state(traffic_state)
            wrng.set_state(_warm_state)
            _counter[0] = 0
            _wcounter[0] = 0
            prios.clear()
            del _trace[:]
            del _sched_trace[:]

        # reference twin: warm, then the measured drain
        ref_server = make_server()
        burst(ref_server, min(args.slots, 4), warm=True)
        ref_server.run()
        reset_traffic()
        ref_rids = burst(ref_server, args.requests)
        c0 = compile_count()
        t0 = time.perf_counter()
        ref_out = ref_server.run()
        ref_dt = time.perf_counter() - t0
        ref_compiles = compile_count() - c0
        ref_order = list(ref_rids)
        del ref_server

        # --disagg: floor(N/2) prefill-class replicas first, the rest
        # decode-class — index order matters, the chaos plan below aims
        # its seeded kill at a prefill index
        n_prefill = args.fleet // 2 if args.disagg else 0
        roles = (["prefill"] * n_prefill
                 + ["decode"] * (args.fleet - n_prefill)
                 if args.disagg else ["any"] * args.fleet)
        inj = None
        if args.chaos:
            from paddle_tpu.inference.faults import FaultInjector, FaultPlan

            plan = (FaultPlan.disagg_chaos(args.seed, replicas=args.fleet,
                                           prefill=n_prefill)
                    if args.disagg
                    else FaultPlan.fleet_chaos(args.seed,
                                               replicas=args.fleet))
            inj = FaultInjector(plan)
            inj.enabled = False    # hooks wire now, plan fires at the drain
        fleet = FleetRouter([make_server(role=r) for r in roles],
                            faults=inj)
        # warm EVERY replica's prefill/decode (routing spreads the warmup
        # burst by load), then replay the identical measured traffic
        burst(fleet, args.fleet * min(args.slots, 4), warm=True)
        if args.disagg:
            # the router only hands decode replicas KV payloads, so their
            # chunk-prefill programs never compile through routed warmup
            # — submit to them directly so the post-kill re-prefill
            # salvage path compiles nothing new inside the guarded drain
            for rep in fleet._replicas:
                if rep.role == "decode":
                    burst(rep.server, min(args.slots, 4), warm=True)
        fleet.run()
        for rep in fleet._replicas:
            rep.server.telemetry.reset()
        reset_traffic()
        if inj is not None:
            inj.enabled = True
        rids = burst(fleet, args.requests)
        done_at = {}
        guard = (jit_cache_guard("fleet measured drain",
                                 allowed=ref_compiles)
                 if (args.chaos or args.guard_recompiles)
                 else contextlib.nullcontext())
        c0 = compile_count()
        with guard:
            t0 = time.perf_counter()
            while True:
                remaining = fleet.step()
                if args.chaos:
                    # soak invariant, fleet-wide: every engine conserves
                    fleet.assert_conserved()
                now = time.perf_counter() - t0
                for rid in list(fleet._results):
                    if rid not in done_at:
                        done_at[rid] = now
                if remaining == 0:
                    break
            dt = time.perf_counter() - t0
        drain_compiles = compile_count() - c0
        out = fleet.run()
        fm = fleet.fleet_metrics()

        gen_tokens = sum(len(v) - rids[r]
                         for r, v in out.items() if r in rids)
        lats = sorted(done_at[r] for r in rids if r in done_at)
        roles_note = (f" ({n_prefill} prefill + "
                      f"{args.fleet - n_prefill} decode)"
                      if args.disagg else "")
        line = {"metric": "serving_fleet_tok_s_1chip",
                "value": round(gen_tokens / dt, 1),
                "unit": f"generated tok/s ({args.requests} reqs, "
                        f"{args.fleet} replicas{roles_note} x "
                        f"{args.slots} slots, max_new={args.max_new}, "
                        f"params={n_params/1e6:.0f}M)",
                "kv_cache": "paged", "fleet": args.fleet,
                "tp": tp, "cp": cp,
                "mesh": f"tp{tp}" if cp == 1 else f"tp{tp}cp{cp}",
                "tok_s_per_chip": round(
                    gen_tokens / dt / (tp * cp * args.fleet), 1),
                "tokens_fingerprint": hashlib.sha256(json.dumps(
                    [out[r] for r in sorted(rids)
                     if r in out]).encode()).hexdigest()[:16],
                "traffic_fingerprint": hashlib.sha256(json.dumps(
                    {"schedule": _sched_trace,
                     "requests": _trace}).encode()).hexdigest()[:16],
                "disagg": bool(args.disagg),
                "prefill_replicas": fm["prefill_replicas"],
                "decode_replicas": fm["decode_replicas"],
                "handoffs": fm["handoffs"],
                "handoff_requests": fm["handoff_requests"],
                "migration_latency_p50_s": round(
                    fm["migration_latency_p50_s"], 6),
                "migration_latency_p95_s": round(
                    fm["migration_latency_p95_s"], 6),
                "migration_latency_samples":
                    fm["migration_latency_samples"],
                "p50_s": round(lats[len(lats) // 2], 3) if lats else 0.0,
                "p95_s": round(lats[min(len(lats) - 1,
                                        int(len(lats) * 0.95))], 3)
                if lats else 0.0,
                "wall_s": round(dt, 2),
                "seed": args.seed, "scheduler": args.scheduler,
                "kv_quant": args.kv_quant,
                "fleet_states": fm["states"],
                "fleet_routed": fm["routed"],
                "fleet_misroutes": fm["misroutes"],
                "fleet_migrations": fm["migrations"],
                "fleet_migrated_requests": fm["migrated_requests"],
                "fleet_migrated_kv": fm["migrated_kv"],
                "fleet_deaths": fm["deaths"],
                "fleet_heartbeat_stalls": fm["heartbeat_stalls"],
                "quarantined": fm["quarantined"],
                "replicas": fm["replicas"],
                # schema v6: per-tenant SLO attainment on EVERY fleet
                # line (the roll-up the canary gate and the autoscaler's
                # burn-rate input both read)
                "slo": {tenant: {
                    "target": row["target"],
                    "ttft": {"attainment": round(
                                 row["ttft"]["attainment"], 6),
                             "burn_rate": round(
                                 row["ttft"]["burn_rate"], 6),
                             "samples": row["ttft"]["samples"]},
                    "tpot": {"attainment": round(
                                 row["tpot"]["attainment"], 6),
                             "burn_rate": round(
                                 row["tpot"]["burn_rate"], 6),
                             "samples": row["tpot"]["samples"]}}
                    for tenant, row in fm["slo"].items()}}
        strict = None
        if args.chaos:
            failed = [r for r in rids if fleet.status(r) == "failed"]
            mismatch = sum(
                1 for a, b in zip(ref_order, list(rids))
                if b not in failed and out.get(b) != ref_out.get(a))
            ref_gen = sum(len(v) - ref_rids[r]
                          for r, v in ref_out.items() if r in ref_rids)
            line["chaos"] = True
            st = inj.stats()
            line["faults_injected"] = st["fired"]
            line["fault_sites"] = st["fired_sites"]
            line["token_mismatches"] = mismatch
            line["ref_tok_s"] = round(ref_gen / ref_dt, 1)
            line["ref_drain_recompiles"] = ref_compiles
            line["drain_recompiles"] = drain_compiles
            if args.strict or args.telemetry_out:
                # recovery tail on the survivors: a fresh burst with the
                # plan spent must come back watchdog-clean
                for rep in fleet._replicas:
                    rep.server.telemetry.reset()
                burst(fleet, min(args.slots, 4), warm=True)
                fleet.run()
                strict = []
                for rep in fleet._replicas:
                    if rep.state in ("live", "degraded"):
                        strict.extend(rep.server.telemetry.watchdog())
                line["watchdog_after_recovery"] = len(strict)
        elif args.strict:
            strict = []
            for rep in fleet._replicas:
                if rep.state in ("live", "degraded"):
                    strict.extend(rep.server.telemetry.watchdog())
            line["watchdog_findings"] = len(strict)
        return line, strict

    if args.int8:
        model.quantize_int8()
    if args.fleet:
        line, strict_findings = fleet_pass()
        line["schema_version"] = SCHEMA_VERSION
        line["kernels"] = args.kernels
        line["config_fingerprint"] = config_fingerprint(args)
        print(json.dumps(line))
        if args.strict and strict_findings:
            for f in strict_findings:
                print(f"watchdog: {f}", file=sys.stderr)
            sys.exit(1)
        if not args.json:
            print(f"[fleet x{args.fleet}] {line['value']} tok/s, "
                  f"p50 {line['p50_s']}s, p95 {line['p95_s']}s over "
                  f"{line['wall_s']}s, states {line['fleet_states']}"
                  + (f", mismatches {line['token_mismatches']}"
                     if args.chaos else ""),
                  file=sys.stderr)
        return
    traffic_state = rng.get_state()
    inj, ref_out, ref_tok_s, ref_compiles = None, None, None, 0
    if args.chaos:
        from paddle_tpu.inference.faults import FaultInjector, FaultPlan
        from paddle_tpu.inference.scheduler import Scheduler

        ref_server = make_server()
        ref_rids, ref_out, _, ref_dt, ref_compiles = run_pass(ref_server)
        ref_tok_s = sum(len(v) - ref_rids[r]
                        for r, v in ref_out.items() if r in ref_rids) \
            / ref_dt
        del ref_server
        # identical traffic for the measured pass: same rng state,
        # same rid counter -> rid-for-rid comparable outputs
        rng.set_state(traffic_state)
        wrng.set_state(_warm_state)
        _counter[0] = 0
        _wcounter[0] = 0
        prios.clear()
        del _trace[:]
        del _sched_trace[:]
        inj = FaultInjector(FaultPlan.chaos(args.seed))
        inj.enabled = False        # hooks wire now, plan fires later
        sched = Scheduler(policy=args.scheduler,
                          clock=inj.wrap_clock(time.monotonic))
        server = make_server(faults=inj, sched=sched)
    else:
        server = make_server()
    rids, out, done_at, dt, drain_compiles = run_pass(
        server, chaos_inj=inj, allowed_compiles=ref_compiles)
    gen_tokens = sum(len(v) - rids[r] for r, v in out.items() if r in rids)
    lats = sorted(done_at[r] for r in rids if r in done_at)
    p50 = lats[len(lats) // 2]
    p95 = lats[min(len(lats) - 1, int(len(lats) * 0.95))]

    # TTFT (submit -> first generated token, queue wait included) and
    # per-token decode latency — read from the registry histograms the
    # server feeds in _emit_result (telemetry.MetricsRegistry is the one
    # source of truth; the warmup reset above scoped the samples to the
    # measured drain, so no per-rid filtering is needed here)
    reg = server.telemetry.registry

    def hpct(name, q, **where):
        v = reg.percentile(name, q, where=where or None)
        return v if v is not None else 0.0

    line = {"metric": "serving_continuous_batching_tok_s_1chip",
            "value": round(gen_tokens / dt, 1),
            "unit": f"generated tok/s ({args.requests} reqs, {args.slots} "
                    f"slots, max_new={args.max_new}, mixed prompts "
                    f"{'64-512' if args.long_prompts else '16-128'}, "
                    f"tick_window={args.tick_window}, "
                    f"{'int8' if args.int8 else 'bf16'} weights, "
                    f"params={n_params/1e6:.0f}M)",
            "kv_cache": "paged" if args.paged else "dense",
            "tp": tp, "cp": cp,
            "mesh": f"tp{tp}" if cp == 1 else f"tp{tp}cp{cp}",
            "tok_s_per_chip": round(gen_tokens / dt / (tp * cp), 1),
            "tokens_fingerprint": hashlib.sha256(json.dumps(
                [out[r] for r in sorted(rids)
                 if r in out]).encode()).hexdigest()[:16],
            "traffic_fingerprint": hashlib.sha256(json.dumps(
                {"schedule": _sched_trace,
                 "requests": _trace}).encode()).hexdigest()[:16],
            "p50_s": round(p50, 3), "p95_s": round(p95, 3),
            "wall_s": round(dt, 2),
            "seed": args.seed, "scheduler": args.scheduler,
            "ttft_p50_s": round(hpct("serving_ttft_s", 50), 4),
            "ttft_p95_s": round(hpct("serving_ttft_s", 95), 4),
            "tpot_p50_ms": round(hpct("serving_tpot_ms", 50), 3),
            "tpot_p95_ms": round(hpct("serving_tpot_ms", 95), 3)}
    if args.arrival_rate is not None:
        line["arrival_rate"] = args.arrival_rate
        line["burst"] = args.burst
    if args.mixed_priority:
        for cls, name in ((0, "high"), (1, "normal"), (2, "low")):
            line[f"ttft_p95_s_{name}"] = round(
                hpct("serving_ttft_s", 95, priority=str(cls)), 4)
    sm = server.sched_metrics()
    if sm["preemptions"] or sm["prefill_aborts"] or sm["expired"] \
            or args.pool_frac is not None or args.scheduler != "fifo":
        line["preemptions"] = sm["preemptions"]
        line["prefill_aborts"] = sm["prefill_aborts"]
        line["resumes"] = sm["resumes"]
        line["expired"] = sm["expired"]
        if args.paged:
            ks = server.kv_stats()
            line["swap_out_blocks"] = ks["swap_out_blocks"]
            line["swap_in_blocks"] = ks["swap_in_blocks"]
            line["host_bytes_peak"] = ks["host_bytes_peak"]
    if args.paged:
        stats = server.kv_stats()
        line["peak_kv_blocks"] = stats["peak_blocks_in_use"]
        line["kv_blocks_total"] = stats["num_blocks"]
        line["kv_block_size"] = stats["block_size"]
        line["prefix_hit_blocks"] = stats["prefix_hit_blocks"]
        line["prefill_chunk"] = server.prefill_chunk
        line["kv_quant"] = args.kv_quant
        # bytes one cached token costs across all layers (K+V, incl.
        # scale rows amortized over the block) — the bandwidth/capacity
        # figure the int8 pool halves vs bf16 (quarters vs f32)
        line["kv_bytes_per_token"] = round(
            stats["bytes_per_block"] / stats["block_size"], 2)
        line["kv_pool_bytes"] = stats["bytes_per_block"] * stats["num_blocks"]
        # chunked-prefill throughput over the measured drain, normalized
        # per chip (tp x cp) — the figure the cp axis is meant to scale
        line["prefill_tok_s_per_chip"] = round(
            (server._prefill_tokens - prefill_tokens0.get(id(server), 0))
            / max(server._prefill_wall_s, 1e-9) / (tp * cp), 1)
        # hot/warm rates are block-level fractions of prefix-cache
        # lookups; cold is re-prefill-over-demoted-content events per
        # measured request (the re-prefill IS the cold tier, so a
        # preempted-and-resumed request can legitimately count twice)
        looked = max(stats["prefix_lookup_blocks"], 1)
        line["tier_hit_rate"] = {
            "hot": round(stats["prefix_hit_blocks"] / looked, 4),
            "warm": round(stats["warm_hit_blocks"] / looked, 4),
            "cold": round(stats["cold_refills"] / max(len(rids), 1), 4)}
        line["tier_demotions"] = stats["warm_demoted_blocks"]
        line["tier_promotions"] = stats["warm_promoted_blocks"]
        line["warm_bytes_peak"] = stats["warm_bytes_peak"]
        if args.long_context:
            line["long_context"] = True
            line["lc_lens"] = lc_lens
        if args.shared_prefix:
            line["shared_prefix"] = args.shared_prefix
        line.update(kernel_microbench(server, cfg, args))
    if args.lora_adapters:
        am = server.sched_metrics()
        line["lora_adapters"] = args.lora_adapters
        line["lora_rank"] = args.lora_rank
        line["lora_live"] = lora_live
        line["adapter_pool_bytes"] = am["adapter_pool_bytes"]
        line["adapter_hit_rate"] = round(am["adapter_hit_rate"], 4)
        line["adapter_uploads"] = am["adapter_uploads"]
        line["adapter_evictions"] = am["adapter_evictions"]
        line["tenants"] = am["tenants"]
    if args.spec:
        sm = server.spec_metrics()
        line["spec_k"] = args.spec
        line["spec_drafter"] = args.spec_drafter
        line["acceptance_rate"] = round(sm["acceptance_rate"], 4)
        line["draft_tokens_proposed"] = sm["draft_tokens_proposed"]
        line["draft_tokens_accepted"] = sm["draft_tokens_accepted"]
    kg = getattr(server, "kernel_geometry", None)
    if kg and any(src != "default" for _, src in kg.values()):
        line["kernel_geometry_source"] = {op: src
                                          for op, (_, src) in kg.items()}
        line["kernel_geometry"] = {op: g.asdict()
                                   for op, (g, src) in kg.items()
                                   if src != "default"}
    if tuned_profile is not None:
        line["profile_fingerprint"] = tuned_profile.config_fingerprint
        line["profile_workload_match"] = bool(
            tuned_profile.workload == wspec.to_dict())
        if args.tune is not None:
            line["tuned"] = True
            line["tune_budget"] = args.tune
            line["tune_trials"] = tuned_profile.search["trials"]
            line["tune_baseline_tok_s"] = round(
                float(tuned_profile.baseline["tok_s"]), 1)
    strict_findings = None
    if args.chaos:
        st = inj.stats()
        failed = [r for r in rids if server.status(r) == "failed"]
        mismatch = sum(1 for r in rids
                       if r not in failed and out.get(r) != ref_out.get(r))
        server.assert_conserved()
        line["chaos"] = True
        line["faults_injected"] = st["fired"]
        line["fault_sites"] = st["fired_sites"]
        line["tick_retries"] = server._tick_faults
        line["quarantined"] = len(failed)
        line["token_mismatches"] = mismatch
        line["ref_tok_s"] = round(ref_tok_s, 1)
        # the jit_cache_guard in run_pass already hard-failed if the
        # chaos drain compiled MORE than the fault-free reference; the
        # counts land in the line so the suite gate can record them
        line["ref_drain_recompiles"] = ref_compiles
        line["drain_recompiles"] = drain_compiles
        if server.telemetry.enabled:
            # recovery tail: with the plan spent, a fresh burst must run
            # with a CLEAN watchdog — degradation is a response, not a
            # new steady state
            server.telemetry.reset()
            burst(server, min(args.slots, 4), warm=True)
            server.run()
            strict_findings = server.telemetry.watchdog()
            line["watchdog_after_recovery"] = len(strict_findings)
    elif args.strict:
        strict_findings = server.telemetry.watchdog()
        line["watchdog_findings"] = len(strict_findings)
    if args.telemetry_out:
        base = args.telemetry_out
        d = os.path.dirname(base)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(base + ".metrics.json", "w") as f:
            json.dump(server.telemetry_snapshot(), f, indent=1)
        server.export_chrome_trace(base + ".trace.json")
        with open(base + ".flight.json", "w") as f:
            json.dump({"ticks": server.telemetry.flight.dump(),
                       "warm_progs": sorted(
                           server.telemetry.flight.warm_progs),
                       "watchdog": server.telemetry.watchdog()}, f, indent=1)
        line["telemetry_out"] = base
    line["schema_version"] = SCHEMA_VERSION
    line["kernels"] = args.kernels
    line["config_fingerprint"] = config_fingerprint(args)
    print(json.dumps(line))
    if args.strict and strict_findings:
        for f in strict_findings:
            print(f"watchdog: {f}", file=sys.stderr)
        sys.exit(1)
    if not args.json:
        mode = "paged" if args.paged else "dense"
        if args.spec:
            mode += f"+spec{args.spec}:{args.spec_drafter}"
        if args.lora_adapters:
            mode += (f"+lora{args.lora_adapters}r{args.lora_rank}"
                     f"/{lora_live}live")
        extra = (f", peak blocks {line.get('peak_kv_blocks')}/"
                 f"{line.get('kv_blocks_total')}" if args.paged else "")
        if args.spec:
            extra += f", accept {line['acceptance_rate']:.2f}"
        print(f"[{mode}] {line['value']} tok/s, p50 {line['p50_s']}s, "
              f"p95 {line['p95_s']}s over {line['wall_s']}s{extra}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
