#!/bin/bash
# One-command on-hardware sequence — run from the repo root on a host that
# holds a TPU chip.  Each stage is independent; results land on stdout and
# under /tmp/tpu_runs.
#
# A chip belongs to one process at a time: stages run one after another, and
# two copies of this script must not run in parallel.
set -u
mkdir -p /tmp/tpu_runs
cd "$(dirname "$0")/.."

echo "== 0. graftlint (tracing-safety gate; fails on NEW findings only) =="
if ! python tools/graftlint.py paddle_tpu > /tmp/tpu_runs/graftlint.log 2>&1; then
  tail -10 /tmp/tpu_runs/graftlint.log
  echo "graftlint found new tracer-unsafe code — fix or baseline before burning chip time"
  exit 1
fi
tail -2 /tmp/tpu_runs/graftlint.log

echo "== 1. probe =="
timeout 120 python -c "import jax; ds=jax.devices(); print('DEVOK', ds[0].platform, len(ds))" \
  || { echo "TPU unreachable — aborting"; exit 1; }

echo "== 2. compiled-Mosaic kernel tier (tests_tpu/) =="
python -m pytest tests_tpu/ -q 2>&1 | tee /tmp/tpu_runs/tests_tpu.log | tail -3

echo "== 3. flash block-size sweeps (fwd winners -> _BLOCK_REGIMES_FWD /"
echo "      PT_FLASH_BLOCKS; bwd winners -> _BLOCK_REGIMES_BWD /"
echo "      PT_FLASH_BLOCKS_BWD — the env vars are direction-specific) =="
python tools/bench_flash_sweep.py --shapes small 2>&1 | tee /tmp/tpu_runs/sweep_small.log | tail -12
python tools/bench_flash_sweep.py --shapes small --bwd 2>&1 | tee /tmp/tpu_runs/sweep_small_bwd.log | tail -12
python tools/bench_flash_sweep.py --shapes mid 2>&1 | tee /tmp/tpu_runs/sweep_mid.log | tail -12
python tools/bench_flash_sweep.py --shapes mid --bwd 2>&1 | tee /tmp/tpu_runs/sweep_mid_bwd.log | tail -12
python tools/bench_flash_sweep.py --shapes long 2>&1 | tee /tmp/tpu_runs/sweep_long.log | tail -12
python tools/bench_flash_sweep.py --shapes long --bwd 2>&1 | tee /tmp/tpu_runs/sweep_long_bwd.log | tail -12

echo "== 3b. drift-robust ranking of close sweep winners (the chip's"
echo "       throughput drifts ~40% between quiet windows; trust medians) =="
python tools/bench_flash_pairwise.py \
  --configs "512x512:512x512,512x1024:512x512,512x1024:512x1024" --rounds 3 \
  2>&1 | tee /tmp/tpu_runs/pairwise.log | tail -8

echo "== 4. train-step bench (509M MFU, then the ~0.9B row) =="
python bench.py 2>/tmp/tpu_runs/bench_err.log | tee /tmp/tpu_runs/bench.json
BENCH_LAYERS=16 BENCH_B=2 python bench.py 2>/dev/null | tee /tmp/tpu_runs/bench_0p9b.json

echo "== 5. explicit long-context rows =="
BENCH_B=2 BENCH_S=8192 python bench.py 2>/dev/null | tee /tmp/tpu_runs/bench_s8192.json
BENCH_B=1 BENCH_S=16384 python bench.py 2>/dev/null | tee /tmp/tpu_runs/bench_s16384.json

echo "== 6. decode + conv-path model benchmarks =="
python tools/decode_benchmark.py 2>/dev/null | tee /tmp/tpu_runs/decode_bf16.json
python tools/decode_benchmark.py --int8 2>/dev/null | tee /tmp/tpu_runs/decode_int8.json
python tools/model_benchmark.py -o /tmp/tpu_runs/model_bench.json 2>/dev/null | tail -3

echo "== 7. serving under load (continuous batching; paged + speculative) =="
python tools/serving_benchmark.py --json 2>/dev/null | tee /tmp/tpu_runs/serving_dense.json
python tools/serving_benchmark.py --paged --json 2>/dev/null | tee /tmp/tpu_runs/serving_paged.json
python tools/serving_benchmark.py --paged --repeat-suffix --json 2>/dev/null | tee /tmp/tpu_runs/serving_paged_rs.json
python tools/serving_benchmark.py --paged --spec 4 --repeat-suffix --json 2>/dev/null | tee /tmp/tpu_runs/serving_spec.json
python tools/serving_benchmark.py --paged --kv-quant int8 --guard-recompiles --json 2>/dev/null | tee /tmp/tpu_runs/serving_paged_int8.json \
  || { echo "int8 KV serving pass FAILED (recompile guard or crash)"; exit 1; }
python - <<'PY'
# int8 KV gate: equal byte budget must hold >=1.8x the blocks of the fp
# pool (the bandwidth/capacity claim), and tok/s must not regress >20%
# (drift margin; the two runs share a chip minutes apart)
import json
q = json.load(open("/tmp/tpu_runs/serving_paged_int8.json"))
fp = json.load(open("/tmp/tpu_runs/serving_paged.json"))
blocks_ratio = q["kv_blocks_total"] / fp["kv_blocks_total"]
tok_ratio = q["value"] / fp["value"]
print(f"int8/fp blocks at equal budget: {blocks_ratio:.2f}x, "
      f"tok/s ratio: {tok_ratio:.2f} "
      f"(kv_bytes_per_token {q['kv_bytes_per_token']} vs "
      f"{fp['kv_bytes_per_token']})")
assert blocks_ratio >= 1.8, "int8 pool capacity win below 1.8x"
if tok_ratio < 0.8:
    raise SystemExit("int8 KV serving slower than fp paged beyond drift "
                     "margin — check the fused-dequant programs")
PY
python - <<'PY'
# spec smoke gate: the speculative line must carry a sane acceptance_rate
# and beat the paged repeat-suffix baseline (same workload, same chip)
import json
spec = json.load(open("/tmp/tpu_runs/serving_spec.json"))
base = json.load(open("/tmp/tpu_runs/serving_paged_rs.json"))
assert 0.0 <= spec["acceptance_rate"] <= 1.0, spec
ratio = spec["value"] / base["value"]
print(f"spec/paged repeat-suffix ratio: {ratio:.2f} "
      f"(accept {spec['acceptance_rate']:.2f})")
if ratio < 1.0:
    raise SystemExit("speculative decoding SLOWER than paged baseline — "
                     "check the gate (SpecConfig.gate_low) before shipping")
PY

echo "== 7b. overload smoke (scheduler + swap-preemption under pressure) =="
python tools/serving_benchmark.py --paged --pool-frac 0.35 --scheduler priority \
  --mixed-priority --arrival-rate 400 --burst 4 --seed 3 --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_overload.json \
  || { echo "overload serving pass FAILED (deadlock or crash)"; exit 1; }
python - <<'PY'
# overload gate: the starved pool must actually exercise swap-preemption,
# and priority scheduling must keep high-priority TTFT below low-priority
# (the whole point of the scheduler) with an absolute ceiling as a
# deadlock/livelock tripwire
import json
r = json.load(open("/tmp/tpu_runs/serving_overload.json"))
print(f"preemptions {r['preemptions']} aborts {r['prefill_aborts']} "
      f"swap {r['swap_out_blocks']}/{r['swap_in_blocks']} blocks, "
      f"ttft_p95 high {r['ttft_p95_s_high']:.3f}s low {r['ttft_p95_s_low']:.3f}s")
assert r["swap_out_blocks"] > 0, "pool never pressured — no swap exercised"
assert r["ttft_p95_s_high"] <= r["ttft_p95_s_low"], \
    "priority inversion: high-priority TTFT above low-priority"
if r["ttft_p95_s_high"] > 30.0:
    raise SystemExit("high-priority p95 TTFT unbounded under overload — "
                     "scheduler wedged or preemption not firing")
PY

echo "== 7c. multi-tenant LoRA smoke (adapter churn + per-tenant fairness) =="
python tools/serving_benchmark.py --paged --lora-adapters 8 --lora-rank 8 \
  --lora-live 4 --scheduler wfq --mixed-priority --guard-recompiles --json \
  2>/dev/null | tee /tmp/tpu_runs/serving_lora.json \
  || { echo "LoRA serving pass FAILED (recompile guard or crash)"; exit 1; }
python - <<'PY'
# LoRA gate: 8 adapters over a 4-page pool must churn (uploads beyond the
# first fill, evictions firing) WITHOUT recompiles (guard above), every
# tenant must complete work, and the multi-adapter path must hold >=80%
# of no-adapter paged throughput (BGMV delta cost bound)
import json
r = json.load(open("/tmp/tpu_runs/serving_lora.json"))
base = json.load(open("/tmp/tpu_runs/serving_paged.json"))
ratio = r["value"] / base["value"]
print(f"lora/paged tok/s ratio: {ratio:.2f} "
      f"(uploads {r['adapter_uploads']}, evictions "
      f"{r['adapter_evictions']}, hit-rate {r['adapter_hit_rate']:.2f}, "
      f"pool {r['adapter_pool_bytes']} B)")
assert r["lora_adapters"] == 8 and r["lora_live"] == 4, r
assert r["adapter_uploads"] >= 8, "every adapter should upload at least once"
assert r["adapter_evictions"] > 0, "8 adapters over 4 pages never evicted"
assert r["adapter_pool_bytes"] > 0, r
assert len(r["tenants"]) == 8 and all(
    t["completed"] > 0 for t in r["tenants"].values()), r["tenants"]
if ratio < 0.8:
    raise SystemExit("multi-adapter serving below 80% of paged baseline — "
                     "BGMV delta or adapter gather regressed")
PY

echo "== 7d. telemetry smoke (span trace + flight recorder under bursty LoRA+spec) =="
python tools/serving_benchmark.py --paged --spec 4 --repeat-suffix \
  --kv-quant int8 --lora-adapters 4 --lora-rank 4 --lora-live 2 \
  --scheduler wfq --arrival-rate 400 --burst 4 --seed 5 \
  --telemetry-out /tmp/tpu_runs/telemetry --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_telemetry.json \
  || { echo "telemetry serving pass FAILED (crash)"; exit 1; }
python tools/serving_benchmark.py --paged --spec 4 --repeat-suffix \
  --kv-quant int8 --lora-adapters 4 --lora-rank 4 --lora-live 2 \
  --scheduler wfq --arrival-rate 400 --burst 4 --seed 5 --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_telemetry_off.json
python - <<'PY'
# telemetry gate: the chrome trace must parse non-empty, the flight
# watchdog must report ZERO steady-state recompiles on the full stack
# (spec + int8 KV + LoRA + WFQ under bursty arrivals), and telemetry-on
# tok/s must hold >=95% of the telemetry-off run — the overhead contract
# (host-side spans/ring only, nothing inside compiled programs)
import json
on = json.load(open("/tmp/tpu_runs/serving_telemetry.json"))
off = json.load(open("/tmp/tpu_runs/serving_telemetry_off.json"))
trace = json.load(open("/tmp/tpu_runs/telemetry.trace.json"))
flight = json.load(open("/tmp/tpu_runs/telemetry.flight.json"))
assert trace["traceEvents"], "chrome trace empty — spans never recorded"
bad = [f for f in flight["watchdog"]
       if f["kind"] == "steady_state_recompile"]
assert not bad, f"steady-state recompiles under telemetry: {bad}"
ratio = on["value"] / off["value"]
print(f"telemetry-on/off tok/s ratio: {ratio:.3f} "
      f"({len(trace['traceEvents'])} trace events, "
      f"{len(flight['ticks'])} flight ticks, "
      f"watchdog findings: {[f['kind'] for f in flight['watchdog']]})")
if ratio < 0.95:
    raise SystemExit("telemetry overhead above 5% — the span/ring path is "
                     "leaking work into the measured drain")
PY

echo "== 7e. chaos soak (seeded fault plan vs fault-free twin, strict watchdog) =="
python tools/serving_benchmark.py --paged --chaos --strict --pool-frac 0.35 \
  --scheduler priority --mixed-priority --arrival-rate 400 --burst 4 \
  --seed 3 --telemetry-out /tmp/tpu_runs/chaos --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_chaos.json \
  || { echo "chaos soak FAILED (engine crash, chaos-pass recompiles above the"\
       "fault-free budget, or dirty watchdog after recovery)"; exit 1; }
python - <<'PY'
# chaos gate: the seeded plan must actually fire, every non-quarantined
# request must match its fault-free twin token-for-token, the chaos pass
# must not compile a single program beyond the fault-free reference
# (enforced in-process by the jit guard; re-checked here from the line),
# recovery must leave a clean watchdog (--strict already exits non-zero
# on findings), and degraded throughput must hold >=90% of the twin
import json
r = json.load(open("/tmp/tpu_runs/serving_chaos.json"))
print(f"faults {r['faults_injected']} at {r['fault_sites']}, "
      f"tick retries {r['tick_retries']}, quarantined {r['quarantined']}, "
      f"mismatches {r['token_mismatches']}, recompiles "
      f"{r['drain_recompiles']}/{r['ref_drain_recompiles']} (chaos/ref), "
      f"tok/s {r['value']} vs ref {r['ref_tok_s']}")
assert r["faults_injected"] > 0, "fault plan never fired — chaos soak vacuous"
assert r["token_mismatches"] == 0, \
    "surviving request diverged from its fault-free twin"
assert r["drain_recompiles"] <= r["ref_drain_recompiles"], \
    "fault handling compiled new programs during the soak"
assert r["watchdog_after_recovery"] == 0, \
    "watchdog findings after the plan was spent — degradation stuck on"
if r["value"] < 0.9 * r["ref_tok_s"]:
    raise SystemExit("chaos throughput below 90% of the fault-free twin — "
                     "retry/backoff ladder costs too much steady-state")
PY

echo "== 7f. fleet failover gate (2 replicas, seeded kill mid-decode vs undisturbed twin) =="
python tools/serving_benchmark.py --paged --fleet 2 --chaos --strict \
  --requests 24 --slots 4 --max-new 48 --tick-window 4 \
  --seed 3 --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_fleet.json \
  || { echo "fleet gate FAILED (failover drain above the twin's compile"\
       "budget, or dirty watchdog after recovery)"; exit 1; }
python - <<'PY'
# fleet gate: the seeded plan must kill exactly one of the two replicas
# mid-decode; every non-quarantined request must finish token-identical
# to the UNDISTURBED single-engine twin; the failover drain must stay
# within the twin's compile budget (enforced in-process by the jit
# guard; re-checked from the line); recovery on the survivor must leave
# a clean watchdog; and the line must carry the schema/fingerprint
# contract downstream tooling keys on
import json
r = json.load(open("/tmp/tpu_runs/serving_fleet.json"))
print(f"deaths {r['fleet_deaths']} (states {r['fleet_states']}), "
      f"salvaged {r['fleet_migrated_requests']} "
      f"(kv {r['fleet_migrated_kv']}), quarantined {r['quarantined']}, "
      f"mismatches {r['token_mismatches']}, recompiles "
      f"{r['drain_recompiles']}/{r['ref_drain_recompiles']} (fleet/ref), "
      f"tok/s {r['value']} vs twin {r['ref_tok_s']}")
assert r.get("schema_version") == 6, "benchmark schema drifted"
assert r.get("config_fingerprint"), "missing config fingerprint"
assert r["fleet_deaths"] == 1, "seeded kill never landed — gate vacuous"
assert r["fleet_states"]["dead"] == 1 and r["fleet_states"]["live"] == 1
assert r["fleet_migrated_requests"] >= 1, \
    "kill landed after the decode finished — nothing was salvaged"
assert r["token_mismatches"] == 0, \
    "non-quarantined request diverged from the undisturbed twin"
assert r["quarantined"] == 0, \
    "requests quarantined with a live survivor available"
assert r["drain_recompiles"] <= r["ref_drain_recompiles"], \
    "failover migration compiled beyond the twin's drain budget"
assert r["watchdog_after_recovery"] == 0, \
    "survivor watchdog dirty after the plan was spent"
assert len(r["replicas"]) == 2, "per-replica rows missing"
PY

echo "== 7g. Pallas serving-kernel gate (parity + mega-kernel tok/s vs jnp reference) =="
# interpret-mode parity first: same kernels the TPU runs, executed on the
# host interpreter — catches masking/dequant/LoRA-fusion bugs cheaply
JAX_PLATFORMS=cpu python -m pytest tests/test_paged_pallas.py -q \
  || { echo "kernel parity suite FAILED (Pallas diverged from the jnp"\
       "reference in interpret mode)"; exit 1; }
python tools/kernel_bench.py --json | tee /tmp/tpu_runs/kernel_bench.json \
  || { echo "kernel bench FAILED (per-op parity above tolerance)"; exit 1; }
python tools/serving_benchmark.py --paged --kv-quant int8 --kernels pallas \
  --guard-recompiles --requests 16 --slots 4 --max-new 32 --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_pallas.json \
  || { echo "pallas serving gate FAILED (recompile budget or tick"\
       "divergence with kernels=pallas)"; exit 1; }
python - <<'PY'
# kernel gate: every (op, quant, shape) combo must hold parity with the
# jnp reference; on real hardware the Mosaic kernels must also beat the
# gather-based reference per op AND end-to-end (kernel_tok_s from the
# serving line) — in interpret mode the speedup clause is skipped, the
# kernels run thousands of times slower by design
import json
rows = [json.loads(l) for l in open("/tmp/tpu_runs/kernel_bench.json")]
srv = json.load(open("/tmp/tpu_runs/serving_pallas.json"))
on_tpu = rows[0]["backend"] == "tpu"
assert rows and all(r["parity"] for r in rows), "kernel parity FAILED"
assert srv.get("kernels") == "pallas" and "kernel_tok_s" in srv, srv
print(f"{len(rows)} kernel combos parity-clean "
      f"({rows[0]['pallas_mode']} mode); serving kernel "
      f"{srv['kernel_tok_s']} vs ref {srv['kernel_ref_tok_s']} tok/s, "
      f"dispatch {srv['kernel_dispatch_us']}us")
if on_tpu:
    slow = [r for r in rows if r["speedup"] < 1.0]
    assert not slow, f"Mosaic kernels slower than reference: {slow}"
    assert srv["kernel_tok_s"] >= srv["kernel_ref_tok_s"], \
        "fused decode attention lost to the gather reference on TPU"
PY

echo "== 7h. multi-chip serving gate (tp=2 dryrun token-equal to single-chip; disaggregated 1+1 fleet with seeded prefill kill) =="
# CPU dryrun mesh ON PURPOSE (JAX_PLATFORMS=cpu + forced host devices):
# the TP claim being gated is TOKEN equality + zero steady-state
# recompiles under GSPMD sharding, which the host backend proves without
# burning chip time; on-chip tp throughput is a pod-slice measurement,
# not a single-chip suite stage
JAX_PLATFORMS=cpu python -m pytest tests/test_tp_serving.py tests/test_fleet_disagg.py -q \
  || { echo "multi-chip serving suite FAILED (TP token divergence or"\
       "disagg handoff regression)"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_tp1_dryrun.json \
  || { echo "tp=1 dryrun FAILED"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --mesh tp=2 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_tp2_dryrun.json \
  || { echo "tp=2 dryrun FAILED (recompile guard tripped or the mesh"\
       "path crashed)"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --fleet 2 \
  --disagg --chaos --strict --requests 24 --slots 4 --max-new 48 \
  --tick-window 4 --seed 3 --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_disagg.json \
  || { echo "disaggregated fleet gate FAILED (prefill-kill salvage drain"\
       "above the twin's compile budget, or dirty watchdog)"; exit 1; }
JAX_PLATFORMS=cpu python tools/kernel_bench.py --tp 2 --shapes 2,4,8 \
  --iters 2 --json | tee /tmp/tpu_runs/kernel_bench_tp.json \
  || { echo "sharded kernel parity FAILED (shard_map head-slice output"\
       "diverged from the unsharded reference)"; exit 1; }
python - <<'PY'
# multi-chip gate: the tp=2 line must be TOKEN-IDENTICAL to the tp=1
# line (same seed, same traffic — the fingerprint hashes every output
# sequence), carry the per-chip normalization, and hold the v4 schema;
# the disaggregated run must kill exactly the prefill replica, salvage
# every in-flight request onto the decode class token-exact, and come
# back watchdog-clean
import json
t1 = json.load(open("/tmp/tpu_runs/serving_tp1_dryrun.json"))
t2 = json.load(open("/tmp/tpu_runs/serving_tp2_dryrun.json"))
dg = json.load(open("/tmp/tpu_runs/serving_disagg.json"))
print(f"tp1 {t1['value']} tok/s vs tp2 {t2['value']} "
      f"({t2['tok_s_per_chip']}/chip), fingerprints "
      f"{t1['tokens_fingerprint']}/{t2['tokens_fingerprint']}; disagg "
      f"deaths {dg['fleet_deaths']} (states {dg['fleet_states']}), "
      f"handoffs {dg['handoffs']}, salvage lat p95 "
      f"{dg['migration_latency_p95_s']}s, mismatches "
      f"{dg['token_mismatches']}")
assert t1.get("schema_version") == t2.get("schema_version") == 6
assert t1["tp"] == 1 and t2["tp"] == 2 and t2["mesh"] == "tp2"
assert t1["tokens_fingerprint"] == t2["tokens_fingerprint"], \
    "tp=2 serving diverged from single-chip tokens"
assert abs(t2["tok_s_per_chip"] - t2["value"] / 2) < 0.1
assert dg["disagg"] is True and dg["fleet_deaths"] == 1
assert dg["fleet_states"]["dead"] == 1 and dg["fleet_states"]["live"] == 1
assert dg["prefill_replicas"] == 0, \
    "the seeded kill missed the prefill class"
assert dg["decode_replicas"] == 1
assert dg["token_mismatches"] == 0 and dg["quarantined"] == 0, \
    "prefill-kill salvage lost or diverged a request"
assert dg["migration_latency_samples"] >= 1
assert dg["migration_latency_p95_s"] >= dg["migration_latency_p50_s"] >= 0
assert dg["watchdog_after_recovery"] == 0, \
    "decode-class survivor dirty after recovery"
PY

echo "== 7i. long-context serving gate (cp=2 prefill token-equal to cp=1; tiered hot/warm/cold KV token-exact under forced demotion) =="
# CPU dryrun ON PURPOSE (same rationale as 7h): the claims gated here
# are token equality + zero steady-state recompiles under the cp mesh
# and the tier ladder, which the host backend proves without chip time
JAX_PLATFORMS=cpu python -m pytest tests/test_tiered_kv.py -q \
  || { echo "tiered-KV / context-parallel suite FAILED"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --long-context --lc-min 128 --lc-max 512 \
  --shared-prefix 0.5 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_cp1_dryrun.json \
  || { echo "cp=1 long-context dryrun FAILED"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --long-context --lc-min 128 --lc-max 512 \
  --shared-prefix 0.5 --mesh cp=2 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_cp2_dryrun.json \
  || { echo "cp=2 long-context dryrun FAILED (recompile guard tripped or"\
       "the cp mesh path crashed)"; exit 1; }
# int8 KV + LoRA over the cp axis: sharded chunked prefill must stay
# token-exact when fused dequant + adapter deltas ride the same program
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 8 \
  --slots 4 --max-new 16 --kv-quant int8 --lora-adapters 2 --lora-rank 4 \
  --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_cp1_int8lora.json \
  || { echo "cp=1 int8+LoRA dryrun FAILED"; exit 1; }
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 8 \
  --slots 4 --max-new 16 --kv-quant int8 --lora-adapters 2 --lora-rank 4 \
  --mesh cp=2 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_cp2_int8lora.json \
  || { echo "cp=2 int8+LoRA dryrun FAILED"; exit 1; }
# forced-demotion pass: a pool too small for the shared-prefix workload
# must spill through the warm tier (and cold re-prefill) yet finish
# token-identical to the big-pool cp=1 twin above, recompile-clean
# (--guard-recompiles) and watchdog-clean (--strict)
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --long-context --lc-min 128 --lc-max 512 \
  --shared-prefix 0.5 --num-blocks 48 --tier-demote 0.2:0.45 \
  --guard-recompiles --strict --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_tiered_dryrun.json \
  || { echo "tiered-KV dryrun FAILED (steady-state recompile, watchdog"\
       "finding, or crash under forced demotion)"; exit 1; }
python - <<'PY'
# long-context gate: the cp=2 lines must be TOKEN-IDENTICAL to their
# cp=1 twins (fp, and int8+LoRA — the fingerprint hashes every output
# sequence) and carry the cp-aware mesh/per-chip normalization; the
# starved-pool run must actually exercise the tier ladder (demotions,
# warm-tier prefix hits, cold re-prefills all > 0) and still match the
# big-pool twin token-for-token — the hierarchy is a capacity ladder,
# never a semantics change
import json
c1 = json.load(open("/tmp/tpu_runs/serving_cp1_dryrun.json"))
c2 = json.load(open("/tmp/tpu_runs/serving_cp2_dryrun.json"))
q1 = json.load(open("/tmp/tpu_runs/serving_cp1_int8lora.json"))
q2 = json.load(open("/tmp/tpu_runs/serving_cp2_int8lora.json"))
td = json.load(open("/tmp/tpu_runs/serving_tiered_dryrun.json"))
print(f"cp1 {c1['value']} tok/s vs cp2 {c2['value']} "
      f"(prefill {c2['prefill_tok_s_per_chip']}/chip), fingerprints "
      f"{c1['tokens_fingerprint']}/{c2['tokens_fingerprint']}; tiered "
      f"dem {td['tier_demotions']} pro {td['tier_promotions']}, "
      f"hit rates {td['tier_hit_rate']}")
assert all(x.get("schema_version") == 6 for x in (c1, c2, q1, q2, td)), \
    "benchmark schema drifted"
assert c1["cp"] == 1 and c2["cp"] == 2 and c2["mesh"] == "tp1cp2"
assert c1["tokens_fingerprint"] == c2["tokens_fingerprint"], \
    "cp=2 chunked prefill diverged from single-chip tokens"
assert q1["tokens_fingerprint"] == q2["tokens_fingerprint"], \
    "cp=2 int8+LoRA serving diverged from single-chip tokens"
assert c2["prefill_tok_s_per_chip"] > 0
assert abs(c2["tok_s_per_chip"] - c2["value"] / 2) < 0.1
assert td["tokens_fingerprint"] == c1["tokens_fingerprint"], \
    "tier ladder changed tokens vs the all-HBM big-pool twin"
assert td["tier_demotions"] > 0, \
    "starved pool never demoted — tier gate vacuous"
assert td["tier_hit_rate"]["warm"] > 0, \
    "shared prefix never re-hit the warm tier"
assert td["tier_hit_rate"]["cold"] > 0, \
    "no cold re-prefill exercised — shrink the pool or the warm budget"
assert td["tier_promotions"] > 0, "warm hits never promoted back to HBM"
PY

echo "== 7k. kernel-geometry gate (per-op schedule sweep: bit-exact candidates, deterministic winners, swept serving token-equal to default) =="
# interpret-mode parity first (same rationale as 7g): every supported
# geometry must be BIT-exact vs the default schedule, fp and int8
JAX_PLATFORMS=cpu python -m pytest tests/test_kernel_geometry.py -q \
  || { echo "kernel-geometry parity suite FAILED (a schedule candidate"\
       "diverged bitwise from the default kernel)"; exit 1; }
# determinism: two sweeps at one seed under the injectable counting
# clock must be byte-identical — rows AND the emitted winner cache
python tools/kernel_bench.py --shapes 2,4,8 --ops decode --quant fp,int8 \
  --iters 2 --sweep-geometry --seed 11 --clock counting --json \
  --emit-cache /tmp/tpu_runs/geometry_cache_a.json \
  | tee /tmp/tpu_runs/kernel_bench_sweep_a.json \
  || { echo "geometry sweep FAILED (candidate crashed or parity reject"\
       "took the winner slot)"; exit 1; }
python tools/kernel_bench.py --shapes 2,4,8 --ops decode --quant fp,int8 \
  --iters 2 --sweep-geometry --seed 11 --clock counting --json \
  --emit-cache /tmp/tpu_runs/geometry_cache_b.json \
  > /tmp/tpu_runs/kernel_bench_sweep_b.json \
  || { echo "geometry sweep rerun FAILED"; exit 1; }
cmp /tmp/tpu_runs/kernel_bench_sweep_a.json \
    /tmp/tpu_runs/kernel_bench_sweep_b.json \
  || { echo "geometry sweep NONDETERMINISTIC (two runs at one seed under"\
       "the counting clock differ)"; exit 1; }
cmp /tmp/tpu_runs/geometry_cache_a.json /tmp/tpu_runs/geometry_cache_b.json \
  || { echo "geometry winner cache NONDETERMINISTIC across reruns"; exit 1; }
# real-clock sweep: the row the speed clauses read (winner + speedup
# vs default; Mosaic clauses gated on_tpu below, same rationale as 7g)
python tools/kernel_bench.py --shapes 2,4,8 --ops decode --quant fp,int8 \
  --iters 3 --sweep-geometry --seed 11 --json \
  | tee /tmp/tpu_runs/kernel_bench_sweep.json \
  || { echo "real-clock geometry sweep FAILED"; exit 1; }
# serving twin: same seed + traffic, default geometry vs a swept cache
# installed before the server builds — tokens must be IDENTICAL (the
# whole point: geometry moves the schedule, never the math). CPU dryrun
# on purpose (token equality is backend-independent, 7h rationale), so
# the cache is keyed to the CPU stand-in model dims.
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --guard-recompiles --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_geo_ref.json \
  || { echo "default-geometry twin FAILED"; exit 1; }
JAX_PLATFORMS=cpu python - <<'PY'
# non-default winners for the CPU stand-in serving model (hidden 128,
# 4 heads -> head_dim 32, float32): exercises the swept source end to
# end without depending on what the real sweep above happened to pick
import json
from paddle_tpu.autotune.kernel_geometry import (CEGeometry, GeometryCache,
                                                 NormGeometry,
                                                 PagedAttentionGeometry,
                                                 local_device_kind)
c = GeometryCache()
kind = local_device_kind()
c.put("paged_attention", "float32", 32, kind,
      PagedAttentionGeometry(kv_block_depth=2, grid_order="gbm"))
c.put("fused_norm", "float32", 128, kind, NormGeometry(rows=8))
c.put("fused_ce", "float32", 128, kind, CEGeometry(rows=64))
with open("/tmp/tpu_runs/serving_geometry_cache.json", "w") as f:
    json.dump(c.to_dict(), f)
PY
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --paged --requests 12 \
  --slots 4 --max-new 24 --guard-recompiles --json \
  --geometry-cache /tmp/tpu_runs/serving_geometry_cache.json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_geo.json \
  || { echo "swept-geometry serving FAILED (recompile budget tripped or"\
       "a non-default schedule crashed the tick)"; exit 1; }
python - <<'PY'
# geometry gate: every sweep row must be parity-clean, with ZERO
# rejected candidates for the families whose schedules are bit-exact
# by design (paged attention, LoRA, norm, CE) and >= 3 candidates (a
# 1-candidate sweep is vacuous). Flash block_q is row-independent but
# its bitwise equality is backend-dependent (host BLAS may regroup the
# contraction by tile shape), so flash rejects are legal — the bitwise
# gate rejecting them IS the mechanism, and the winner stays exact.
# The swept serving line must actually engage the cache (source
# 'swept') and be TOKEN-IDENTICAL to the default twin; on real
# hardware the winner must not lose to the default it was picked over
import json
rows = [json.loads(l)
        for l in open("/tmp/tpu_runs/kernel_bench_sweep.json")]
ref = json.load(open("/tmp/tpu_runs/serving_geo_ref.json"))
srv = json.load(open("/tmp/tpu_runs/serving_geo.json"))
on_tpu = rows[0]["backend"] == "tpu"
swept = [r for r in rows if "winner_geometry" in r]
assert swept, "no sweep rows emitted — gate vacuous"
assert all(r["parity"] for r in rows), "geometry sweep parity FAILED"
strict = [r for r in swept if r.get("op") != "flash_attention"]
assert all(r["geometry_parity_rejects"] == 0 for r in strict), \
    "a bit-exact-by-design geometry candidate diverged from default"
assert all(r["geometry_candidates"] >= 3 for r in swept), \
    "sweep ran with fewer than 3 candidates — gate vacuous"
fams = {r["op"] for r in rows if r.get("metric") == "geometry_sweep"}
assert {"fused_lora", "fused_norm", "fused_ce",
        "flash_attention"} <= fams, f"family rungs missing: {fams}"
src = srv.get("kernel_geometry_source") or {}
assert any(s == "swept" for s in src.values()), \
    "swept cache never engaged in serving — twin vacuous"
assert srv["tokens_fingerprint"] == ref["tokens_fingerprint"], \
    "swept geometry CHANGED serving tokens (schedule leaked into math)"
print(f"{len(swept)} sweep rows parity-clean "
      f"({rows[0]['pallas_mode']} mode), "
      f"{sum(r['geometry_candidates'] for r in swept)} candidates, "
      f"0 parity rejects; swept serving token-equal to default twin "
      f"(sources {src})")
if on_tpu:
    slow = [r for r in swept if r["geometry_speedup"] < 1.0]
    assert not slow, f"geometry winner slower than default on TPU: {slow}"
PY

echo "== 7l. fleet-at-scale gate (2-process socket fleet, fast-time slice, mid-run kill vs in-process twin; 1M-session simulated day) =="
# deliberately pinned to CPU: cross-process token-exactness needs both
# sides of the twin on one backend, and the gate must not serialize on
# the chip lock — the fleet layer under test is backend-agnostic
JAX_PLATFORMS=cpu python tools/fleet_sim.py --execute-slice 10 \
  --transport subprocess --kill-tick 3 --seed 0 --json 2>/dev/null \
  | tee /tmp/tpu_runs/fleet_slice.json \
  || { echo "fleet slice FAILED (transport, salvage, or twin divergence)"; exit 1; }
JAX_PLATFORMS=cpu python tools/fleet_sim.py --execute-slice 10 \
  --transport subprocess --kill-tick 3 --seed 0 --json 2>/dev/null \
  > /tmp/tpu_runs/fleet_slice_2.json
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --sim \
  --sim-sessions 1000000 --seed 0 --json 2>/dev/null \
  | tee /tmp/tpu_runs/fleetsim_day.json
JAX_PLATFORMS=cpu python tools/serving_benchmark.py --sim \
  --sim-sessions 1000000 --seed 0 --json 2>/dev/null \
  > /tmp/tpu_runs/fleetsim_day_2.json
python - <<'PY'
# fleet-at-scale gate: the measured fleet (real OS processes over the
# socket transport, one SIGKILL mid-decode, autoscaler forced through a
# scale-up and a drain) must be token-exact against the undisturbed
# in-process twin, watchdog-clean, and byte-identical across two
# same-seed runs; the simulated day must clear 1M sessions with the
# elastic fleet beating the static peak-sized fleet on replica-hours
# while every tenant holds its SLO
import json
a = open("/tmp/tpu_runs/fleet_slice.json").read()
b = open("/tmp/tpu_runs/fleet_slice_2.json").read()
assert a == b, "same-seed fleet slice runs are not byte-identical"
r = json.loads(a)
day = json.load(open("/tmp/tpu_runs/fleetsim_day.json"))
assert r["transport"] == "subprocess"
assert r["deaths"] == 1, "scripted kill never landed — gate vacuous"
assert r["token_mismatches"] == 0, \
    "process kill / autoscale drain changed tokens vs twin"
assert r["migrated_requests"] >= 1, "kill salvaged nothing — vacuous"
assert r["scale_ups"] >= 1 and r["scale_downs"] >= 1, \
    "autoscaler never exercised both directions"
assert r["watchdog_findings"] == 0, "watchdog not clean after slice"
assert r["heartbeat_stalls"] == 0, \
    "transport round-trips tripped the heartbeat"
assert day["schema_version"] == 6 and day["sim_sessions"] == 1000000
# the day line is byte-identical per seed modulo the two documented
# wall-time keys (value = simulator wall throughput, wall_s)
day2 = json.load(open("/tmp/tpu_runs/fleetsim_day_2.json"))
strip = lambda d: json.dumps(
    {k: v for k, v in d.items() if k not in ("value", "wall_s")},
    sort_keys=True)
assert strip(day) == strip(day2), \
    "same-seed simulated days diverged beyond wall-time keys"
assert day["slo_attained"], "simulated day violated a tenant SLO"
assert day["elastic_beats_static"], \
    "elastic fleet used more replica-hours than the static peak fleet"
print(f"slice: {r['sessions']} sessions over {r['transport']}, "
      f"mismatches {r['token_mismatches']}, deaths {r['deaths']}, "
      f"salvaged {r['migrated_requests']}, ups {r['scale_ups']} / "
      f"downs {r['scale_downs']}, watchdog {r['watchdog_findings']}; "
      f"day: {day['sim_sessions']} sessions in {day['wall_s']}s wall, "
      f"elastic {day['replica_hours']}h vs static "
      f"{day['static_replica_hours']}h ({day['scale_ups']} ups, "
      f"{day['scale_downs']} downs)")
PY

echo "== 8. training chaos gate (seeded kills + torn writes + bit-flip reads vs unkilled twin) =="
python tools/train_chaos.py --steps 12 --kills 2 --seed 3 --json 2>/dev/null \
  | tee /tmp/tpu_runs/train_chaos.json \
  || { echo "training chaos gate FAILED (resume diverged from the twin,"\
       "a corruption went undetected, or a kill was never recovered)"; exit 1; }
python - <<'PY'
# training chaos gate: every scripted kill must be DETECTED by the
# elastic monitor (lease expiry -> RESTART) and recovered via
# restore-latest-valid; every replayed + continued step loss and the
# final params/opt-state must be bit-exact against the unkilled
# fault-free twin; every injected on-disk corruption must be caught by
# the CRC32 manifest and absorbed by generation fallback (zero
# undetected corruptions); torn writes must be absorbed by the retry
# rung without a single dropped save
import json
r = json.load(open("/tmp/tpu_runs/train_chaos.json"))
print(f"faults {r['faults_injected']} at {r['fault_sites']}, "
      f"kills {r['detected_kills']}/{r['restarts']} restarts, "
      f"mismatches {r['loss_mismatches']}, bitexact {r['params_bitexact']}, "
      f"corrupt reads {r['corrupt_reads_detected']}/{r['ckpt_read_fired']}, "
      f"torn-write retries {r['save_retries']} "
      f"(dropped {r['save_failures']})")
assert r["faults_injected"] > 0, "fault plan never fired — gate vacuous"
assert r["completed"], "chaos run never reached the final step"
assert r["detected_kills"] == r["restarts"] >= 1, \
    "a kill was missed by the elastic monitor or never injected"
assert r["loss_mismatches"] == 0, \
    "resumed trajectory diverged from the unkilled twin"
assert r["params_bitexact"], \
    "final params/opt-state differ from the unkilled twin"
assert r["corrupt_reads_detected"] >= r["ckpt_read_fired"], \
    "an injected on-disk corruption went UNDETECTED by the manifest"
assert r["ckpt_read_fired"] >= 1 and r["generation_fallbacks"] >= 1, \
    "corrupt-read rung never exercised — gate vacuous"
assert r["save_failures"] == 0, \
    "a torn write exhausted its retries and dropped the generation"
# goodput accounting riding the same artifact: the unkilled twin books
# every step productive (exactly 1.0 — integer step indices, no float
# residue) while the chaos run must dip below 1.0 IFF a kill forced
# replayed steps + a recovery segment
assert r["twin_goodput_ratio"] == 1.0, \
    "fault-free twin booked lost work — goodput ledger is broken"
assert (r["train_goodput_ratio"] < 1.0) == (r["detected_kills"] >= 1), \
    "goodput ratio disagrees with the kill count"
PY

echo "== 8b. train-telemetry overhead gate (instrumented vs bare step time; fault-free goodput + clean watchdog) =="
JAX_PLATFORMS=cpu python tools/train_telemetry_bench.py --json \
  --out /tmp/tpu_runs/train_telemetry 2>/dev/null \
  | tee /tmp/tpu_runs/train_telemetry.json \
  || { echo "train telemetry bench FAILED (missing spans or non-unit"\
       "fault-free goodput)"; exit 1; }
python - <<'PY'
# overhead gate: recording AROUND the compiled step (GL010) must cost
# at most ~5% even on a model small enough that the hooks are maximally
# visible; the instrumented fault-free run must leave a full train
# timeline (one train_step span per step), a clean watchdog and a
# goodput ledger of exactly 1.0
import json
r = json.load(open("/tmp/tpu_runs/train_telemetry.json"))
print(f"overhead ratio {r['overhead_ratio']:.3f} "
      f"(bare {r['median_step_bare_s'] * 1e3:.2f}ms vs instrumented "
      f"{r['median_step_instrumented_s'] * 1e3:.2f}ms), "
      f"{r['train_step_spans']} train_step spans, "
      f"{r['watchdog_findings']} watchdog findings, "
      f"goodput {r['train_goodput_ratio']}")
assert r["overhead_ratio"] >= 0.95, \
    f"train telemetry overhead above 5%: ratio {r['overhead_ratio']:.3f}"
assert r["train_step_spans"] == r["steps"] > 0, \
    "train timeline is missing steps — spans were dropped or never cut"
assert r["flight_ticks"] == r["steps"], "flight ring missed steps"
assert r["watchdog_findings"] == 0, \
    f"fault-free run tripped the watchdog: {r['watchdog']}"
assert r["train_goodput_ratio"] == 1.0, \
    "fault-free goodput is not exactly 1.0 — phantom lost work"
ev = json.load(open("/tmp/tpu_runs/train_telemetry.trace.json"))
ev = ev["traceEvents"] if isinstance(ev, dict) else ev
kinds = {e["name"] for e in ev if e.get("ph") == "X"}
assert {"train_step", "host_to_device", "dispatch",
        "device_wait"} <= kinds, f"train trace missing phases: {kinds}"
PY
# artifact tooling smoke: the dump CLI must render both artifact kinds
python tools/telemetry_dump.py /tmp/tpu_runs/train_telemetry.metrics.json \
  > /dev/null || { echo "telemetry_dump FAILED on metrics artifact"; exit 1; }
python tools/telemetry_dump.py /tmp/tpu_runs/train_telemetry.flight.json \
  > /dev/null || { echo "telemetry_dump FAILED on flight artifact"; exit 1; }

echo "== 9. serving autotune gate (short-budget search; tuned profile must hold the default's throughput on identical traffic, recompile-clean) =="
python tools/serving_benchmark.py --paged --repeat-suffix --requests 16 \
  --slots 4 --max-new 24 --seed 7 --tune 8 \
  --profile /tmp/tpu_runs/tuned_profile.json --json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_tune.json \
  || { echo "autotune search FAILED (trial crash or profile save)"; exit 1; }
python tools/serving_benchmark.py --paged --repeat-suffix --requests 16 \
  --slots 4 --max-new 24 --seed 7 --guard-recompiles --strict --json \
  2>/dev/null | tee /tmp/tpu_runs/serving_default_replay.json \
  || { echo "default replay FAILED (recompile guard or watchdog)"; exit 1; }
python tools/serving_benchmark.py --paged --repeat-suffix --requests 16 \
  --slots 4 --max-new 24 --seed 7 --guard-recompiles --strict --json \
  --profile /tmp/tpu_runs/tuned_profile.json 2>/dev/null \
  | tee /tmp/tpu_runs/serving_tuned_replay.json \
  || { echo "tuned replay FAILED (steady-state recompile or watchdog"\
       "finding under the tuned config)"; exit 1; }
python - <<'PY'
# autotune gate: the search line must record a real multi-trial search
# whose winner beat its own measured baseline; the tuned replay must see
# BYTE-IDENTICAL traffic to the default replay (the decoupling contract)
# and produce IDENTICAL tokens (greedy serving is config-invariant);
# --strict/--guard-recompiles above already enforce clean watchdog +
# zero steady-state recompiles; and tuned throughput must hold the
# default's within the chip's drift margin (the search already proved
# winner >= default on its own measured traffic)
import json
tune = json.load(open("/tmp/tpu_runs/serving_tune.json"))
dft = json.load(open("/tmp/tpu_runs/serving_default_replay.json"))
tuned = json.load(open("/tmp/tpu_runs/serving_tuned_replay.json"))
prof = json.load(open("/tmp/tpu_runs/tuned_profile.json"))
ratio = tuned["value"] / dft["value"]
print(f"tuned {tuned['value']} vs default {dft['value']} tok/s "
      f"(ratio {ratio:.2f}); search: {tune['tune_trials']} trials, "
      f"winner cfg {tune['profile_fingerprint']} "
      f"{prof['metrics']['tok_s']:.1f} vs baseline "
      f"{tune['tune_baseline_tok_s']} tok/s, "
      f"{len(prof['search']['rejected'])} rejected")
assert tune["tuned"] is True and tune["tune_budget"] == 8, tune
assert tune["tune_trials"] >= 4, "search never ran its trial plan"
assert tuned["profile_fingerprint"] == prof["config_fingerprint"]
assert tuned["profile_workload_match"] is True, \
    "replay workload drifted from the one the profile was tuned on"
assert tuned["traffic_fingerprint"] == dft["traffic_fingerprint"], \
    "tuned replay saw different traffic — config leaked into the draw"
assert tuned["tokens_fingerprint"] == dft["tokens_fingerprint"], \
    "tuned config changed the tokens — a reject gate is leaking"
assert prof["metrics"]["tok_s"] >= prof["baseline"]["tok_s"], \
    "search crowned a winner below its own measured baseline"
if ratio < 0.95:
    raise SystemExit("tuned profile below 95% of the default replay — "
                     "tuning regressed throughput beyond drift margin")
PY

echo "== done: JSON lines + sweep winners are under /tmp/tpu_runs =="
