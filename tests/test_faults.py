"""Fault-tolerant serving (inference/faults.py + the GenerationServer
degradation ladder): deterministic seeded fault injection, per-request
retry/backoff/quarantine, checksum-verified swaps with re-prefill
fallback, crash-safe snapshot/restore that resumes every in-flight
request token-identically, and per-tick pool conservation. Quick tier
on CPU."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import AdapterRegistry, LoRAConfig
from paddle_tpu.inference.faults import (NULL_INJECTOR, EngineFailedError,
                                         FaultInjector, FaultPlan,
                                         FaultSpec, TickFault)
from paddle_tpu.inference.scheduler import PRIORITY_HIGH, Scheduler
from paddle_tpu.inference.serving import GenerationServer
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _model(max_pos=160):
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=max_pos,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(7)
    return LlamaForCausalLM(cfg), cfg


def _prompts(cfg, lens=(18, 11, 7)):
    rng = np.random.RandomState(11)
    return [rng.randint(1, cfg.vocab_size, (n,)).tolist() for n in lens]


# --------------------------------------------------------------------------
# Injector unit tests (pure host, no model)
# --------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="site"):
        FaultSpec("warp_core")
    with pytest.raises(ValueError, match="at"):
        FaultSpec("tick", at=-1)
    with pytest.raises(ValueError, match="count"):
        FaultSpec("tick", count=0)
    assert issubclass(EngineFailedError, RuntimeError)
    assert issubclass(TickFault, RuntimeError)


def test_injector_determinism_and_null_fast_path():
    # same seed -> same plan -> same firing sequence, call for call
    pa, pb = FaultPlan.chaos(9), FaultPlan.chaos(9)
    assert pa.specs == pb.specs
    assert FaultPlan.chaos(10).specs != pa.specs
    ia, ib = FaultInjector(pa), FaultInjector(pb)
    sites = ["alloc", "tick", "drafter", "swap_corrupt", "host_put"] * 60
    fired_a = [(s, ia.fire(s) is not None) for s in sites]
    fired_b = [(s, ib.fire(s) is not None) for s in sites]
    assert fired_a == fired_b
    assert ia.fired == ib.fired and len(ia.fired) > 0
    # the disabled injector is inert and permanently so
    assert not NULL_INJECTOR.enabled
    assert all(NULL_INJECTOR.fire(s) is None for s in sites)
    assert NULL_INJECTOR.fired == []


def test_corrupt_flips_exactly_one_bit_deterministically():
    base = np.arange(64, dtype=np.float32).reshape(8, 8)
    outs = []
    for _ in range(2):
        inj = FaultInjector(FaultPlan([FaultSpec("swap_corrupt")], seed=5))
        arr = base.copy()
        inj.corrupt([arr])
        outs.append(arr)
    assert np.array_equal(outs[0], outs[1])          # seeded -> replayable
    diff = (outs[0].view(np.uint32) ^ base.view(np.uint32))
    assert bin(int(diff.sum())).count("1") == 1      # exactly one bit


def test_wrap_clock_stall_and_jump_back():
    t = [100.0]
    plan = FaultPlan([FaultSpec("clock", at=1, count=1, kind="stall"),
                      FaultSpec("clock", at=3, count=1, kind="jump_back",
                                magnitude=50.0)])
    clock = FaultInjector(plan).wrap_clock(lambda: t[0])
    assert clock() == 100.0
    t[0] = 110.0
    assert clock() == 100.0          # stall: last value repeats
    assert clock() == 110.0
    assert clock() == 60.0           # jump_back: t - magnitude
    t[0] = 120.0
    assert clock() == 120.0


def test_scheduler_clock_monotonic_clamp():
    """Regression for the injectable-clock hazard: a backwards-jumping
    clock must not corrupt TTL ordering — now() clamps to the high-water
    mark, so a jump degrades to 'time stands still' and nothing queued
    after the jump expires before its elders."""
    t = [100.0]
    s = Scheduler("priority", clock=lambda: t[0])
    s.submit("a", 0, ttl_s=30.0)                     # deadline 130
    assert s.now() == 100.0
    t[0] = 40.0                                      # clock jumps back
    assert s.now() == 100.0                          # clamped
    s.submit("b", 1, ttl_s=5.0)                      # deadline 105, not 45
    assert [e.rid for e in s.waiting()] == [1, 0]
    assert s.expire() == []                          # nothing mis-expires
    t[0] = 106.0
    assert [e.rid for e in s.expire()] == [1]        # real passage of time
    assert s.now() == 106.0


# --------------------------------------------------------------------------
# Degradation ladder on the serving engine
# --------------------------------------------------------------------------

def test_tick_fault_retry_token_identical():
    """Transient tick faults ride the retry/backoff rung: the faulting
    trips re-dispatch verbatim (faults fire before compiled dispatch, so
    donated pools are intact) and the run's output is token-identical to
    a fault-free twin."""
    model, cfg = _model()
    prompts = _prompts(cfg)

    clean = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16)
    rc = [clean.submit(p, max_new_tokens=10) for p in prompts]
    base = clean.run()

    inj = FaultInjector(FaultPlan([FaultSpec("tick", at=2, count=2)]))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, faults=inj)
    rs = [srv.submit(p, max_new_tokens=10) for p in prompts]
    out = srv.run()
    assert srv._tick_faults == 2
    assert ("tick", 2) in inj.fired and ("tick", 3) in inj.fired
    for a, b in zip(rc, rs):
        assert b in out
        assert out[b] == base[a], "retried run diverged from fault-free twin"
    srv.assert_conserved()


def test_poison_request_quarantined_engine_survives():
    """A rid-attributed fault that keeps striking one request quarantines
    exactly that request to terminal `failed` after fault_retries
    strikes; everyone else finishes token-identical and the engine stays
    serviceable."""
    model, cfg = _model()
    prompts = _prompts(cfg)

    clean = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16)
    rc = [clean.submit(p, max_new_tokens=10) for p in prompts]
    base = clean.run()

    # rid 0 takes 4 strikes (> fault_retries=3) -> quarantine on the 4th
    inj = FaultInjector(FaultPlan(
        [FaultSpec("tick", at=1, count=4, rid=0)]))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, faults=inj,
                           telemetry=True)
    rs = [srv.submit(p, max_new_tokens=10) for p in prompts]
    while srv.step():
        srv.assert_conserved()
    out = srv.run()
    assert srv.status(rs[0]) == "failed"
    assert rs[0] not in out
    assert srv._quarantined == 1
    # its timeline row says so: the decode bracket closed as failed, then
    # the terminal marker; the ticks it sat out are on the flight ring
    row = srv.telemetry.tracer.spans(rs[0])
    assert row[-1]["name"] == "failed" and row[-1].get("instant")
    (dec,) = [s for s in row if s["name"] == "decode"]
    assert dec["args"]["outcome"] == "failed"
    assert srv.telemetry.tracer.open_spans(rs[0]) == []
    assert any(t["prog"] == "backoff"
               for t in srv.telemetry.flight.dump())
    for a, b in list(zip(rc, rs))[1:]:
        assert out[b] == base[a]
    # the engine is alive: a fresh request completes normally
    extra = srv.submit(prompts[1], max_new_tokens=4)
    fin = srv.run()
    assert fin[extra] == base[rc[1]][:len(prompts[1]) + 4]
    srv.assert_conserved()


def test_fatal_fault_terminal_state_and_submit_refuses():
    """A fault escaping the retry ladder (kind='fatal' models an
    exception after compiled dispatch: donated buffers gone) flips the
    server into a terminal failed state — the original error propagates
    and submit() refuses with EngineFailedError."""
    model, cfg = _model()
    inj = FaultInjector(FaultPlan([FaultSpec("tick", at=0, kind="fatal")]))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, faults=inj)
    srv.submit(_prompts(cfg)[0], max_new_tokens=8)
    with pytest.raises(RuntimeError, match="injected fatal"):
        srv.run()
    with pytest.raises(EngineFailedError, match="terminal failed state"):
        srv.submit(_prompts(cfg)[1], max_new_tokens=4)


def test_alloc_exhaustion_fault_recovers_token_identical():
    """Injected allocator exhaustion rides the EXISTING preemption/stall
    ladder (alloc failures were already a handled domain — the injector
    just makes them schedulable): the run completes token-identical to
    the fault-free twin."""
    model, cfg = _model()
    prompts = _prompts(cfg)

    clean = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16)
    rc = [clean.submit(p, max_new_tokens=10) for p in prompts]
    base = clean.run()

    inj = FaultInjector(FaultPlan([FaultSpec("alloc", at=6, count=2)]))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, faults=inj)
    rs = [srv.submit(p, max_new_tokens=10) for p in prompts]
    while srv.step():
        srv.assert_conserved()
    out = srv.run()
    assert any(site == "alloc" for site, _ in inj.fired)
    for a, b in zip(rc, rs):
        assert out[b] == base[a]
    srv.assert_conserved()


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_swap_corruption_falls_back_to_reprefill(kv_quant):
    """Checksum rung: a bit-flipped swap-in payload fails its CRC, the
    blocks roll back, and the request re-prefills prompt+generated[:-1]
    through the token-exact chunked-prefill program — output identical
    to the uncorrupted twin, fp and int8 pools alike."""
    model, cfg = _model()
    prompts = _prompts(cfg, (18, 11))

    ample = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16,
                             kv_quant=kv_quant)
    ra = [ample.submit(p, max_new_tokens=12) for p in prompts]
    base = ample.run()

    # tight pool + priority churn forces a decode-phase swap; the first
    # swap-in payload comes back corrupted
    inj = FaultInjector(FaultPlan([FaultSpec("swap_corrupt", at=0)]))
    tight = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16, num_blocks=7,
                             policy="priority", kv_quant=kv_quant,
                             faults=inj)
    rt = [tight.submit(p, max_new_tokens=12, priority=i % 2)
          for i, p in enumerate(prompts)]
    out = tight.run()
    sm = tight.sched_metrics()
    assert sm["preemptions"] > 0, "setup failed to force a swap"
    assert ("swap_corrupt", 0) in inj.fired, "no swap-in happened"
    for a, b in zip(ra, rt):
        assert out[b] == base[a], "re-prefill recovery diverged"
    tight.assert_conserved()
    assert tight.kv_stats()["host_bytes_in_use"] == 0


def test_assert_conserved_detects_leaks():
    model, cfg = _model()
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16)
    srv.submit(_prompts(cfg)[0], max_new_tokens=4)
    srv.run()
    audit = srv.assert_conserved()
    assert audit["blocks_in_use"] == 0 and audit["host_bytes_in_use"] == 0
    leaked = srv.alloc.alloc()          # a block no table accounts for
    with pytest.raises(AssertionError, match="refcount audit"):
        srv.assert_conserved()
    srv.alloc.free(leaked)
    srv.assert_conserved()


# --------------------------------------------------------------------------
# Snapshot / restore — the drain/migrate primitive
# --------------------------------------------------------------------------

def _mid_flight_server(model, cfg, prompts, kv_quant="none", lora=None,
                       adapters=None):
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16,
                           kv_quant=kv_quant, lora=lora)
    kw = [{"adapter": a} for a in (adapters or [None] * len(prompts))]
    rids = [srv.submit(p, max_new_tokens=12, **k)
            for p, k in zip(prompts, kw)]
    for _ in range(4):      # a mix: decoding slots + a queued request
        srv.step()
    assert any(srv.status(r) in ("running", "prefilling") for r in rids)
    return srv, rids


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_snapshot_restore_token_identical(kv_quant):
    """snapshot() on a mid-flight server, restore() into a FRESH server:
    every in-flight request continues to exactly the tokens the captured
    server goes on to produce (it keeps running — snapshot is
    non-destructive), fp and int8 pools alike. A second restore into the
    warmed server then replays under the jit-cache guard: resuming from
    a snapshot costs zero steady-state recompiles."""
    from paddle_tpu.analysis import jit_cache_guard

    model, cfg = _model()
    prompts = _prompts(cfg)
    srv, rids = _mid_flight_server(model, cfg, prompts, kv_quant)
    snap = srv.snapshot()
    base = srv.run()        # the captured server's own continuation

    fresh = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16,
                             kv_quant=kv_quant)
    assert fresh.restore(snap) == len(rids)
    out = fresh.run()
    for r in rids:
        assert out[r] == base[r], "restored run diverged from original"
    fresh.assert_conserved()

    # warm server, same snapshot again: the resume path must reuse every
    # compiled program (drain/migrate cannot pay a recompile storm)
    assert fresh.restore(snap) == len(rids)
    with jit_cache_guard("snapshot-resume") as g:
        out2 = fresh.run()
    assert g.compiles == 0
    for r in rids:
        assert out2[r] == base[r]


def test_snapshot_restore_with_lora_adapters():
    """Adapter residency survives the round trip: requests pinned to
    different-rank adapters restore into a fresh server and finish
    token-identical."""
    from tests.test_lora_serving import _adapter_weights

    model, cfg = _model()
    reg = AdapterRegistry()
    reg.register("a1", _adapter_weights(cfg, 4, seed=1), rank=4, alpha=8.0)
    reg.register("a2", _adapter_weights(cfg, 2, seed=2), rank=2, alpha=2.0)
    lora = dict(max_live_adapters=4, max_rank=4)
    prompts = _prompts(cfg)
    srv, rids = _mid_flight_server(
        model, cfg, prompts, lora=LoRAConfig(reg, **lora),
        adapters=["a1", "a2", None])
    snap = srv.snapshot()
    base = srv.run()

    fresh = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16,
                             lora=LoRAConfig(reg, **lora))
    assert fresh.restore(snap) == len(rids)
    out = fresh.run()
    for r in rids:
        assert out[r] == base[r]
    fresh.assert_conserved()


def test_restore_refuses_bad_targets():
    model, cfg = _model()
    prompts = _prompts(cfg)
    srv, rids = _mid_flight_server(model, cfg, prompts)
    snap = srv.snapshot()
    # busy server: slots/queue must be empty
    with pytest.raises(ValueError, match="idle"):
        srv.restore(snap)
    # config mismatch: the compiled programs' shapes would differ
    other = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=4, prefill_chunk=16)
    with pytest.raises(ValueError, match="block_size"):
        other.restore(snap)
    # dense servers have no per-request KV capture
    dense = GenerationServer(model, max_batch=2, max_len=96,
                             prompt_buckets=(32,))
    with pytest.raises(ValueError, match="paged"):
        dense.snapshot()
    srv.run()


def test_restore_validation_ladder_rejects_without_corrupting_target():
    """The negative rungs of the restore ladder — kv_quant mismatch,
    shrunk block pool, missing adapter — each raise a clear error and
    leave the refusing target untouched: conserved, idle, and still able
    to serve. The same snapshot then restores cleanly into a proper
    target, token-identical (the failed attempts corrupted nothing)."""
    from tests.test_lora_serving import _adapter_weights

    model, cfg = _model()
    prompts = _prompts(cfg)
    reg = AdapterRegistry()
    reg.register("a1", _adapter_weights(cfg, 4, seed=1), rank=4, alpha=8.0)
    lora = dict(max_live_adapters=4, max_rank=4)
    mk = dict(max_batch=2, max_len=96, cache="paged", block_size=8,
              prefill_chunk=16)
    srv = GenerationServer(model, num_blocks=24,
                           lora=LoRAConfig(reg, **lora), **mk)
    rids = [srv.submit(p, max_new_tokens=12,
                       adapter="a1" if i == 0 else None)
            for i, p in enumerate(prompts)]
    for _ in range(4):
        srv.step()
    snap = srv.snapshot()
    base = srv.run()

    def rejects(target, match):
        with pytest.raises(ValueError, match=match):
            target.restore(snap)
        audit = target.assert_conserved()
        assert audit["blocks_in_use"] == 0, "rejected restore leaked blocks"
        assert audit["host_bytes_in_use"] == 0, "rejected restore leaked host"
        assert target.load_metrics()["queue_depth"] == 0, \
            "rejected restore left requests behind"
        r = target.submit(prompts[2], max_new_tokens=4)   # still serves
        assert r in target.run()

    # kv_quant mismatch: the payloads' dtype/scale layout would not parse
    rejects(GenerationServer(model, num_blocks=24, kv_quant="int8",
                             lora=LoRAConfig(reg, **lora), **mk),
            "kv_quant")
    # shrunk pool: captured requests may no longer be feasible
    rejects(GenerationServer(model, num_blocks=12,
                             lora=LoRAConfig(reg, **lora), **mk),
            "blocks")
    # no LoRA stack at all: config fingerprint refuses up front
    rejects(GenerationServer(model, num_blocks=24, **mk), "lora")
    # LoRA stack present but the adapter is unknown: the per-request
    # pre-flight refuses BEFORE any state mutates (a mid-loop rejection
    # would be a partial restore — corruption, not an error)
    rejects(GenerationServer(model, num_blocks=24,
                             lora=LoRAConfig(AdapterRegistry(), **lora),
                             **mk),
            "unknown adapter")

    good = GenerationServer(model, num_blocks=24,
                            lora=LoRAConfig(reg, **lora), **mk)
    assert good.restore(snap) == len(rids)
    out = good.run()
    for r in rids:
        assert out[r] == base[r], "snapshot was damaged by failed restores"
    good.assert_conserved()


def test_restore_under_live_fault_injection():
    """Chaos during drain: the receiving server restores a snapshot
    while its own seeded fault plan is live — swap-in corruption on the
    migrated payloads, allocator exhaustion, a tick fault. The ladder
    and the restore path compose: every non-quarantined request finishes
    token-identical to the captured server's own continuation, the CRC
    rung demonstrably fired, and conservation holds after every tick."""
    model, cfg = _model()
    prompts = _prompts(cfg)
    srv, rids = _mid_flight_server(model, cfg, prompts)
    snap = srv.snapshot()
    base = srv.run()

    inj = FaultInjector(FaultPlan([
        FaultSpec("swap_corrupt", at=0, count=2),
        FaultSpec("tick", at=1, count=1),
        FaultSpec("alloc", at=2, count=2),
    ], seed=13))
    fresh = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16, faults=inj)
    assert fresh.restore(snap) == len(rids)
    steps = 0
    while fresh.step():
        fresh.assert_conserved()
        steps += 1
        assert steps < 5000, "restore-under-chaos wedged"
    out = fresh.run()
    assert len(inj.fired) > 0, "plan never fired — proved nothing"
    assert fresh.telemetry.registry.counter(
        "serving_swap_reprefills", "").total() >= 1, \
        "corrupted restore payload never hit the CRC re-prefill rung"
    for r in rids:
        if fresh.status(r) == "failed":
            assert r not in out
        else:
            assert out[r] == base[r], "restored-under-chaos run diverged"
    fresh.assert_conserved()


# --------------------------------------------------------------------------
# Chaos soak: a seeded plan against a bursty workload
# --------------------------------------------------------------------------

def test_chaos_soak_engine_never_dies():
    """FaultPlan.chaos under pool pressure: the engine survives the whole
    plan, every non-quarantined request finishes token-identical to the
    fault-free twin, and pool conservation holds after every tick."""
    model, cfg = _model()
    rng = np.random.RandomState(23)
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).tolist()
               for n in (18, 9, 13, 7, 11)]

    clean = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                             block_size=8, prefill_chunk=16, num_blocks=10,
                             policy="priority")
    rc = [clean.submit(p, max_new_tokens=8,
                       priority=PRIORITY_HIGH if i == 2 else 1)
          for i, p in enumerate(prompts)]
    base = clean.run()

    inj = FaultInjector(FaultPlan.chaos(3, horizon=40))
    srv = GenerationServer(model, max_batch=2, max_len=96, cache="paged",
                           block_size=8, prefill_chunk=16, num_blocks=10,
                           policy="priority", faults=inj)
    rs = [srv.submit(p, max_new_tokens=8,
                     priority=PRIORITY_HIGH if i == 2 else 1)
          for i, p in enumerate(prompts)]
    steps = 0
    while srv.step():
        srv.assert_conserved()
        steps += 1
        assert steps < 5000, "chaos soak wedged"
    out = srv.run()
    assert len(inj.fired) > 0, "plan never fired — soak proved nothing"
    for a, b in zip(rc, rs):
        if srv.status(b) == "failed":
            assert b not in out
        else:
            assert out[b] == base[a], "non-quarantined request diverged"
    srv.assert_conserved()
