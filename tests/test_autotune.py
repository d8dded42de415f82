"""Autotuner tests (paddle_tpu/autotune/): config space validity and
seeded sampling, analytic cost-model sanity (monotonicity, the PR 3
speculative break-even, calibration), workload draw determinism and
warmup-stream disjointness, end-to-end search byte-determinism under a
counting clock, the hard reject gates (watchdog findings, token
fingerprint mismatch), tuned-profile round-trip/tamper detection, and
the serving_benchmark traffic-decoupling regression (two configs at one
seed must see byte-identical traffic)."""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.autotune.cost import (ACCEPT_P_RANDOM, ServingCostModel,
                                      expected_acceptance)
from paddle_tpu.autotune.features import FeatureVector
from paddle_tpu.autotune.profile import (TunedProfile, config_server_kwargs,
                                         resolve_profile)
from paddle_tpu.autotune.search import TrialRunner, autotune
from paddle_tpu.autotune.space import ALL_KNOBS, ConfigSpace, engine_space
from paddle_tpu.autotune.workload import (WorkloadSpec, draw_traffic,
                                          warmup_traffic)
from paddle_tpu.cost_model import (REF_DECODING, PagedTickCostModel,
                                   TickShape)

REPO = pathlib.Path(__file__).resolve().parents[1]


# ======================================================================
# config space
# ======================================================================

class TestConfigSpace:
    def test_default_is_valid_and_canonical(self):
        space = ConfigSpace(ALL_KNOBS)
        cfg = space.default()
        assert space.is_valid(cfg)
        assert cfg == space.canonicalize(cfg)
        assert set(cfg) == {k.name for k in ALL_KNOBS}

    def test_kernel_tier_is_the_dispatch_mode_alone(self):
        """18 knobs, none of a whole-tick kernel's geometry (PR 31), and
        the ``kernels`` knob offers what ``ops.set_kernel_mode`` takes."""
        from paddle_tpu.ops import KERNEL_MODES

        for space in (ConfigSpace(ALL_KNOBS), engine_space(max_len=256)):
            assert len(space.knobs) == 18
            assert not [k.name for k in space.knobs
                        if k.name.startswith("mk_")]
            assert space.knob("kernels").choices == KERNEL_MODES \
                == ("auto", "pallas", "reference")
            rng = np.random.RandomState(31)
            for _ in range(200):
                space.validate(space.sample(rng))

    def test_sample_deterministic_per_seed(self):
        space = engine_space(max_len=256)
        rng1, rng2 = np.random.RandomState(7), np.random.RandomState(7)
        seq1 = [space.sample(rng1) for _ in range(12)]
        seq2 = [space.sample(rng2) for _ in range(12)]
        assert seq1 == seq2

    def test_samples_respect_constraints(self):
        space = ConfigSpace(ALL_KNOBS)
        rng = np.random.RandomState(11)
        for _ in range(40):
            cfg = space.sample(rng)
            assert space.is_valid(cfg), space.errors(cfg)
            # cross-knob constraints can never leak out of sample()
            if cfg["pool_frac"] < 1.0:
                assert cfg["host_pool_mb"] != 0
            if cfg["draft_k"] > 0:
                assert cfg["tick_window"] <= 8

    def test_cross_knob_errors(self):
        space = ConfigSpace(ALL_KNOBS)
        starved = dict(space.default(), pool_frac=0.5, host_pool_mb=0)
        errs = space.errors(starved)
        assert any("host_pool_mb=0" in e for e in errs)
        wide_spec = dict(space.default(), draft_k=4, tick_window=16)
        errs = space.errors(wide_spec)
        assert any("tick_window > 8" in e for e in errs)
        with pytest.raises(ValueError, match="tick_window > 8"):
            space.validate(wide_spec)

    def test_schema_errors(self):
        space = ConfigSpace(ALL_KNOBS)
        cfg = space.default()
        assert any("unknown knob" in e
                   for e in space.errors(dict(cfg, bogus=1)))
        missing = dict(cfg)
        del missing["block_size"]
        assert any("missing knob" in e for e in space.errors(missing))
        assert any("not in" in e
                   for e in space.errors(dict(cfg, block_size=7)))

    def test_canonicalize_collapses_dead_knobs(self):
        space = ConfigSpace(ALL_KNOBS)
        base = space.default()
        # spec gate is dead without speculation -> one fingerprint
        a = dict(base, draft_k=0, spec_gate_low=0.5)
        b = dict(base, draft_k=0, spec_gate_low=4.0)
        assert space.fingerprint(a) == space.fingerprint(b)
        # ...but live once draft_k > 0 (cap the window to stay valid)
        a = dict(base, draft_k=4, tick_window=4, spec_gate_low=0.5)
        b = dict(base, draft_k=4, tick_window=4, spec_gate_low=4.0)
        assert space.fingerprint(a) != space.fingerprint(b)
        # fleet routing knobs are dead at one replica
        a = dict(base, fleet_replicas=1, prefix_weight=0.5)
        b = dict(base, fleet_replicas=1, prefix_weight=2.0)
        assert space.fingerprint(a) == space.fingerprint(b)

    def test_engine_space_pins_fleet_tier(self):
        space = engine_space(max_len=256, pins={"kv_quant": "int8"})
        rng = np.random.RandomState(3)
        for _ in range(10):
            cfg = space.sample(rng)
            assert cfg["fleet_replicas"] == 1
            assert cfg["kv_quant"] == "int8"
        bad = dict(space.default(), kv_quant="none")
        assert any("violates pin" in e for e in space.errors(bad))

    def test_max_len_bounds_block_size(self):
        space = ConfigSpace(ALL_KNOBS, max_len=12)
        assert space.knob("block_size").choices == (8,)
        assert space.default()["block_size"] == 8
        with pytest.raises(ValueError, match="no block_size choice"):
            ConfigSpace(ALL_KNOBS, max_len=4)

    def test_mutate_deterministic_valid_neighbor(self):
        space = engine_space(max_len=256)
        base = space.default()
        m1 = space.mutate(base, np.random.RandomState(5))
        m2 = space.mutate(base, np.random.RandomState(5))
        assert m1 == m2
        assert m1 != base
        assert space.is_valid(m1)


# ======================================================================
# cost model
# ======================================================================

class TestCostModel:
    def test_tick_cost_monotone_in_context(self):
        m = PagedTickCostModel()
        costs = [m.tick_seconds(TickShape(decoding=8, ctx_blocks=cb))
                 for cb in (1.0, 4.0, 16.0, 64.0)]
        assert costs == sorted(costs) and costs[0] < costs[-1]

    def test_trip_amortizes_round_trips(self):
        m = PagedTickCostModel()
        shape = TickShape(decoding=8)
        # one trip of w ticks beats w trips of 1 tick by (w-1) trip costs
        assert m.trip_seconds(shape, 16) < 16 * m.trip_seconds(shape, 1)
        # and the end-to-end model prefers wider tick windows, all else
        # equal (fewer host round trips for the same ticks)
        cm = ServingCostModel(None, max_batch=8)
        wl = WorkloadSpec(requests=16, max_new=32)
        cfg = engine_space(max_len=256).default()
        slow = cm.predict_seconds(dict(cfg, tick_window=1), wl)
        fast = cm.predict_seconds(dict(cfg, tick_window=16), wl)
        assert fast < slow

    def test_starved_pool_costs_more(self):
        cm = ServingCostModel(None, max_batch=8)
        wl = WorkloadSpec(requests=16, max_new=32)
        cfg = engine_space(max_len=256).default()
        parity = cm.predict_seconds(cfg, wl)
        starved = cm.predict_seconds(
            dict(cfg, pool_frac=0.5, host_pool_mb=16), wl)
        assert starved > parity

    def test_spec_break_even_matches_pr3_gate(self):
        """The uncalibrated prior reproduces the PR 3 measurement: the
        speculative break-even at the reference shape is k/2 accepted
        drafts per window — exactly the default dynamic-gate floor."""
        from paddle_tpu.inference.speculative import SpecConfig

        m = PagedTickCostModel()
        shape = TickShape(decoding=REF_DECODING)
        assert m.spec_break_even(4, shape) == pytest.approx(2.0)
        assert m.spec_break_even(4, shape) == pytest.approx(
            SpecConfig().gate_low)
        assert m.spec_break_even(2, shape) == pytest.approx(1.0)
        # ServingCostModel reaches the same number through the workload
        cm = ServingCostModel(None, max_batch=REF_DECODING)
        wl = WorkloadSpec(requests=REF_DECODING, max_new=32,
                          prompt_ladder=(48,))
        assert cm.spec_break_even(4, wl) == pytest.approx(2.0, abs=0.3)

    def test_expected_acceptance_geometric(self):
        assert expected_acceptance(4, 1.0) == pytest.approx(4.0)
        assert expected_acceptance(4, 0.0) == pytest.approx(0.0)
        e = expected_acceptance(4, ACCEPT_P_RANDOM)
        assert 0.0 < e < 1.0

    def test_calibration_reduces_error(self):
        """Ridge calibration from measured trials must beat the prior on
        a held-out config when the truth deviates from the prior."""
        prior = PagedTickCostModel()
        truth = PagedTickCostModel(prior.c_trip * 2.0, prior.c_tick * 0.5,
                                   prior.c_flop * 1.5, prior.c_byte * 0.7)
        cm = ServingCostModel(None, max_batch=8)
        wl = WorkloadSpec(requests=16, max_new=32)
        space = engine_space(max_len=256)
        rng = np.random.RandomState(0)
        configs = [space.default()] + [space.sample(rng) for _ in range(7)]
        held_out = space.sample(rng)
        for cfg in configs:
            a = cm.aggregates(cfg, wl)
            cm.observe(cfg, wl, truth.predict(a["trips"], a["ticks"],
                                              a["flops"], a["bytes"]))
        cm.recalibrate()
        a = cm.aggregates(held_out, wl)
        want = truth.predict(a["trips"], a["ticks"], a["flops"], a["bytes"])
        prior_err = abs(prior.predict(a["trips"], a["ticks"], a["flops"],
                                      a["bytes"]) - want)
        calib_err = abs(cm.tick_model.predict(
            a["trips"], a["ticks"], a["flops"], a["bytes"]) - want)
        assert calib_err < prior_err

    def test_tick_model_round_trip(self):
        m = PagedTickCostModel(1e-3, 2e-4, 3e-9, 4e-11)
        m2 = PagedTickCostModel.from_dict(m.to_dict())
        assert m2.to_dict() == m.to_dict()


# ======================================================================
# workload
# ======================================================================

class TestWorkload:
    def test_draw_deterministic_and_config_free(self):
        spec = WorkloadSpec(requests=8, max_new=8, seed=5)
        t1, t2 = draw_traffic(spec), draw_traffic(spec)
        assert t1.signature() == t2.signature()
        assert t1.requests == t2.requests

    def test_truncated_is_strict_prefix(self):
        spec = WorkloadSpec(requests=8, max_new=8, seed=5)
        full = draw_traffic(spec)
        short = draw_traffic(spec.truncated(3))
        assert short.requests == full.requests[:3]

    def test_warmup_stream_disjoint_from_measured(self):
        spec = WorkloadSpec(requests=4, max_new=8, seed=5)
        measured = draw_traffic(spec).requests
        warm = warmup_traffic(spec, 4)
        assert [w.prompt for w in warm] != \
            [m.prompt for m in measured[:4]]

    def test_repeat_suffix_tiles_shared_motif(self):
        spec = WorkloadSpec(requests=4, max_new=8, repeat_suffix=True,
                            seed=5)
        t = draw_traffic(spec)
        for r in t.requests:
            assert r.prompt[:len(t.motif)] == \
                t.motif[:len(r.prompt)] or len(r.prompt) < len(t.motif)
            assert r.prompt == tuple(
                (list(t.motif) * (len(r.prompt) // len(t.motif) + 1))
                [:len(r.prompt)])

    def test_open_loop_schedule_covers_all_requests(self):
        spec = WorkloadSpec(requests=10, max_new=8, arrival_rate=100.0,
                            burst=4, seed=1)
        t = draw_traffic(spec)
        assert sum(n for _, n in t.schedule) == 10
        times = [at for at, _ in t.schedule]
        assert times == sorted(times)

    def test_spec_round_trip(self):
        spec = WorkloadSpec(requests=8, max_new=8, mixed_priority=True,
                            arrival_rate=50.0, seed=9)
        assert WorkloadSpec.from_dict(spec.to_dict()) == spec


# ======================================================================
# reject gates (stub runner — no model, no jax programs)
# ======================================================================

class _StubRunner:
    """Duck-typed TrialRunner: instant measurements with scripted
    findings/fingerprints, so the gate logic is tested in isolation."""

    def __init__(self, workload, *, findings_for_nondefault=None,
                 wrong_tokens_for_nondefault=False):
        self.workload = workload
        self.max_len = 256
        self.max_batch = 4
        self.model = None
        self.space = engine_space(max_len=self.max_len)
        self._default_fp = self.space.fingerprint(self.space.default())
        self._findings = findings_for_nondefault or []
        self._wrong_tokens = wrong_tokens_for_nondefault

    def traffic_for(self, spec):
        return draw_traffic(spec)

    def run(self, config, workload=None):
        spec = workload if workload is not None else self.workload
        fp_cfg = self.space.fingerprint(config)
        default = fp_cfg == self._default_fp
        tokens = spec.requests * spec.max_new
        # non-default configs measure FASTER — the gates, not the
        # objective, must be what keeps them from winning
        seconds = 1.0 if default else 0.1
        fv = FeatureVector(tokens=tokens, seconds=seconds,
                           tok_s=tokens / seconds)
        tok_fp = "ref0" if (default or not self._wrong_tokens) \
            else f"bad-{fp_cfg}"
        findings = [] if default else list(self._findings)
        return fv, tok_fp, findings


class TestRejectGates:
    def _tune(self, runner, budget=4):
        return autotune(runner, budget=budget, seed=0,
                        space=runner.space,
                        cost=ServingCostModel(None,
                                              max_batch=runner.max_batch))

    def test_watchdog_finding_rejects_fast_config(self):
        wl = WorkloadSpec(requests=8, max_new=8, seed=0)
        runner = _StubRunner(
            wl, findings_for_nondefault=[
                {"kind": "preemption_storm", "detail": "stub"}])
        profile, trials = self._tune(runner)
        rejected = [t for t in trials if not t.accepted]
        assert rejected, "every non-default trial carries a finding"
        assert all(t.reject_reason.startswith("watchdog:preemption_storm")
                   for t in rejected)
        # the 10x-faster pathological configs never become the winner
        assert profile.config == runner.space.default()
        assert profile.search["winner_trial"] == 0
        assert {r["index"] for r in profile.search["rejected"]} == \
            {t.index for t in rejected}

    def test_token_fingerprint_mismatch_rejects(self):
        wl = WorkloadSpec(requests=8, max_new=8, seed=0)
        runner = _StubRunner(wl, wrong_tokens_for_nondefault=True)
        profile, trials = self._tune(runner)
        full_rejects = [t for t in trials
                        if t.rung == "full" and not t.accepted]
        assert full_rejects, "full-rung non-default trials must be gated"
        assert all(t.reject_reason.startswith("token_fingerprint_mismatch")
                   for t in full_rejects)
        # wrong-but-fast never wins; the reference stays the incumbent
        assert profile.config == runner.space.default()

    def test_trial_artifacts_feed_telemetry_dump(self, tmp_path, capsys):
        """TrialResult.to_dict() is the artifact telemetry_dump's trials
        mode consumes — keep the contract wired end to end."""
        sys.path.insert(0, str(REPO / "tools"))
        try:
            import telemetry_dump
        finally:
            sys.path.pop(0)
        wl = WorkloadSpec(requests=8, max_new=8, seed=0)
        runner = _StubRunner(wl, findings_for_nondefault=[
            {"kind": "pool_pressure", "detail": "stub"}])
        _, trials = self._tune(runner)
        paths = []
        for t in trials:
            p = tmp_path / f"trial_{t.index:02d}.json"
            p.write_text(json.dumps(t.to_dict()))
            paths.append(str(p))
        assert telemetry_dump.main(paths) == 0
        out = capsys.readouterr().out
        assert f"autotune trials ({len(trials)})" in out
        assert "REJECT watchdog" in out
        # mixing trials with another artifact kind is refused
        other = tmp_path / "metrics.json"
        other.write_text(json.dumps({"counters": {}}))
        assert telemetry_dump.main(paths + [str(other)]) == 2


# ======================================================================
# tuned profile
# ======================================================================

def _profile_for(space, config, workload):
    return TunedProfile(
        config=space.validate(config),
        config_fingerprint=space.fingerprint(config),
        workload=workload.to_dict(),
        workload_signature=draw_traffic(workload).signature(),
        metrics=FeatureVector().to_dict(),
        baseline=FeatureVector().to_dict(),
        search={"budget": 1, "seed": 0},
        cost_model=PagedTickCostModel().to_dict(),
    )


class TestTunedProfile:
    def test_round_trip(self, tmp_path):
        space = ConfigSpace(ALL_KNOBS)
        wl = WorkloadSpec(requests=4, max_new=8)
        prof = _profile_for(space, dict(space.default(), tick_window=4),
                            wl)
        path = str(tmp_path / "tuned.json")
        prof.save(path, now=123.0)
        back = TunedProfile.load(path)
        assert back.config == prof.config
        assert back.created_unix == 123.0
        assert back.canonical_json() == prof.canonical_json()
        assert back.workload_spec() == wl

    def test_tampered_config_fails_loudly(self, tmp_path):
        space = ConfigSpace(ALL_KNOBS)
        prof = _profile_for(space, space.default(),
                            WorkloadSpec(requests=4, max_new=8))
        d = prof.to_dict()
        d["config"]["tick_window"] = 4          # edited after tuning
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            TunedProfile.from_dict(d)
        d2 = prof.to_dict()
        d2["schema"] = 99
        with pytest.raises(ValueError, match="schema"):
            TunedProfile.from_dict(d2)

    def test_schema3_profile_naming_the_removed_kernel_is_refused(self):
        """A profile an older tree tuned onto the whole-tick kernel (a
        fourth ``kernels`` value plus its three ``mk_*`` knobs) is refused
        at load by the schema check — retuned, never applied in part."""
        space = ConfigSpace(ALL_KNOBS)
        d = _profile_for(space, space.default(),
                         WorkloadSpec(requests=4, max_new=8)).to_dict()
        assert d["schema"] == 4
        d["schema"] = 3
        d["config"].update({"kernels": "mega" "kernel",
                            **{f"mk_{k}": v for k, v in (
                                ("ffn_tile", 512), ("prefetch_depth", 2),
                                ("dequant", "scores"))}})
        with pytest.raises(ValueError, match="schema 3 != 4 — retune"):
            TunedProfile.from_dict(d)

    def test_resolve_profile_accepts_all_forms(self, tmp_path):
        space = ConfigSpace(ALL_KNOBS)
        prof = _profile_for(space, space.default(),
                            WorkloadSpec(requests=4, max_new=8))
        assert resolve_profile(None) is None
        assert resolve_profile(prof) is prof
        path = str(tmp_path / "p.json")
        prof.save(path)
        assert resolve_profile(path).config == prof.config
        assert resolve_profile(prof.to_dict()).config == prof.config
        with pytest.raises(ValueError, match="profile must be"):
            resolve_profile(42)

    def test_config_server_kwargs_pool_geometry(self):
        """pool_frac resolves against THIS geometry's fp-parity budget
        and host_pool_mb converts to bytes."""
        space = ConfigSpace(ALL_KNOBS)
        cfg = dict(space.default(), pool_frac=0.5, host_pool_mb=16,
                   kv_quant="int8", draft_k=4, tick_window=4)
        from paddle_tpu.models import LlamaConfig

        mcfg = LlamaConfig(vocab_size=64, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=1,
                           num_attention_heads=2, num_key_value_heads=1,
                           max_position_embeddings=256, dtype="float32",
                           use_flash_attention=False)
        kw = config_server_kwargs(space.validate(cfg), mcfg,
                                  max_batch=4, max_len=64)
        assert kw["cache"] == "paged"
        assert kw["kv_quant"] == "int8"
        assert kw["spec"].k == 4
        assert kw["pool_bytes"] >= 1
        assert kw["host_pool_bytes"] == 16 << 20
        # at parity no pool override is emitted at all
        kw2 = config_server_kwargs(space.default(), mcfg,
                                   max_batch=4, max_len=64)
        assert "pool_bytes" not in kw2 and "host_pool_bytes" not in kw2


# ======================================================================
# end-to-end search on a real (tiny) model
# ======================================================================

class _CountingClock:
    """Deterministic time source: every read advances one quantum, so
    measured durations count clock reads instead of wall time."""

    def __init__(self, quantum: float = 1e-4):
        self.t = 0.0
        self.quantum = quantum

    def __call__(self) -> float:
        self.t += self.quantum
        return self.t


@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=256,
                      dtype="float32", use_flash_attention=False)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


_TUNE_WL = dict(requests=6, max_new=8, prompt_ladder=(8, 12, 16),
                vocab_size=64, seed=0)


def _search(model, budget=3, seed=0):
    wl = WorkloadSpec(**_TUNE_WL)
    runner = TrialRunner(model, wl, max_batch=4, clock=_CountingClock())
    return autotune(runner, budget=budget, seed=seed)


class TestSearchEndToEnd:
    def test_same_seed_same_profile_bytes(self, tiny_model):
        """The determinism contract: two independent searches (fresh
        runner, fresh clock) at one seed produce byte-identical
        profiles and identical trial sequences."""
        p1, t1 = _search(tiny_model)
        p2, t2 = _search(tiny_model)
        assert p1.canonical_json() == p2.canonical_json()
        assert [(t.fingerprint, t.rung, t.accepted) for t in t1] == \
            [(t.fingerprint, t.rung, t.accepted) for t in t2]
        # the reference trial ran the default and was accepted
        assert t1[0].index == 0 and t1[0].rung == "full"
        assert t1[0].accepted
        # profile bookkeeping is consistent
        assert p1.search["trials"] == len(t1)
        win = t1[p1.search["winner_trial"]]
        assert win.accepted and win.rung == "full"
        assert p1.config == win.config
        assert p1.workload_signature == draw_traffic(
            WorkloadSpec(**_TUNE_WL)).signature()

    def test_profile_applies_to_server(self, tiny_model):
        """GenerationServer(profile=) adopts the tuned knobs wherever
        the ctor argument is still at its declared default — and an
        explicit caller argument always wins over the profile."""
        from paddle_tpu.inference.serving import GenerationServer

        space = ConfigSpace(ALL_KNOBS)
        cfg = dict(space.default(), tick_window=4, block_size=8,
                   kv_quant="int8")
        prof = _profile_for(space, cfg, WorkloadSpec(**_TUNE_WL))
        srv = GenerationServer(tiny_model, max_batch=2, max_len=64,
                               profile=prof)
        assert srv.profile is prof
        assert srv.cache_mode == "paged"
        assert srv.tick_window == 4
        assert srv.block_size == 8
        assert srv.kv_quant == "int8"
        # explicit NON-default ctor args beat the profile (an arg left
        # at its declared default is indistinguishable from "not
        # passed", so the profile fills it — kv_quant stays tuned)
        srv2 = GenerationServer(tiny_model, max_batch=2, max_len=64,
                                profile=prof, tick_window=2,
                                block_size=32)
        assert srv2.tick_window == 2
        assert srv2.block_size == 32
        assert srv2.kv_quant == "int8"   # untouched knob still tuned


# ======================================================================
# serving_benchmark traffic decoupling (subprocess regression)
# ======================================================================

def _bench(extra):
    proc = subprocess.run(
        [sys.executable, "tools/serving_benchmark.py", "--paged", "--json",
         "--requests", "6", "--max-new", "8", "--seed", "3"] + extra,
        capture_output=True, text=True, timeout=600, cwd=str(REPO),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_benchmark_traffic_decoupled_from_config():
    """Two different serving configs at one --seed must see
    byte-identical traffic (traffic_fingerprint) AND — greedy serving
    being config-invariant — produce identical tokens
    (tokens_fingerprint). This is the regression gate for the
    warmup-rng split: before it, warmup consumption shifted the
    measured trace under the config."""
    a = _bench(["--slots", "4"])
    b = _bench(["--slots", "3", "--tick-window", "4", "--block-size", "8"])
    assert a["traffic_fingerprint"] == b["traffic_fingerprint"]
    assert a["tokens_fingerprint"] == b["tokens_fingerprint"]
    assert a["traffic_fingerprint"] != a["tokens_fingerprint"]
