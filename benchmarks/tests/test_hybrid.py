"""The hybrid (SambaY) family's part of the benchmark: its plain reference
against a recurrence written out by hand, its required-FLOPs count against a
count by hand, and a CPU rehearsal of ``drivers/serve_hybrid.py`` with the
readers that take the program's by-kind counters."""
import importlib
import json
import os

import numpy as np
import pytest

from conftest import FIXTURES, ROOT

from benchmarks import run as R
from benchmarks.reference import sambay_lm as ref
from benchmarks.roofline import hybrid_attention, hybrid_step, ssm
from benchmarks.weights_sambay import make_weights, sambay_shapes

HYBRID = os.path.join(FIXTURES, "hybrid")
TINY = R.load_json(HYBRID, "bench", "configs", "tiny-sambay.json")


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_mamba_layer_is_the_two_token_recurrence_by_hand():
    """One Mamba layer of the reference over two tokens against the
    equations in numpy float64, every step written out."""
    import jax.numpy as jnp

    w32 = make_weights(sambay_shapes(TINY), 3, jnp.float32, std=0.3)
    w = {k[len("layers.0."):]: np.asarray(v, np.float64)
         for k, v in w32.items() if k.startswith("layers.0.")}
    z = ref.sizes(TINY)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, z["H"]))
    w0 = {k[len("layers.0."):]: v for k, v in w32.items()
          if k.startswith("layers.0.")}
    got_x, got_y, got_h1 = ref.mamba_layer(
        jnp.asarray(x, jnp.float32), w0, jnp.int32(2),
        S=z["S"], K=z["K"], r=z["r"], eps=z["eps"])
    # the state after ONE token, the second being padding
    got_h0 = ref.mamba_layer(jnp.asarray(x, jnp.float32), w0, jnp.int32(1),
                             S=z["S"], K=z["K"], r=z["r"], eps=z["eps"])[2]

    def ln(v, g, b):
        mu = v.mean(-1, keepdims=True)
        return (v - mu) / np.sqrt(((v - mu) ** 2).mean(-1, keepdims=True)
                                  + z["eps"]) * g + b

    u = ln(x, w["norm1_w"], w["norm1_b"])
    xz = u @ w["in_proj"]
    xs, gate = xz[:, :z["di"]], xz[:, z["di"]:]
    K = z["K"]
    # causal depthwise conv: token 0 sees itself only, token 1 sees both
    c0 = xs[0] * w["conv_w"][K - 1] + w["conv_b"]
    c1 = xs[0] * w["conv_w"][K - 2] + xs[1] * w["conv_w"][K - 1] + w["conv_b"]
    xc = np.stack([_silu(c0), _silu(c1)])
    dbc = xc @ w["x_proj"]
    r, S = z["r"], z["S"]
    dt = np.log1p(np.exp(dbc[:, :r] @ w["dt_proj"] + w["dt_bias"]))
    Bm, Cm = dbc[:, r:r + S], dbc[:, r + S:]
    A = -np.exp(w["A_log"])                                 # [S, di]
    h0 = (dt[0] * xc[0])[None, :] * Bm[0][:, None]          # from h = 0
    y0 = (h0 * Cm[0][:, None]).sum(0) + w["D"] * xc[0]
    h1 = np.exp(dt[1][None, :] * A) * h0 \
        + (dt[1] * xc[1])[None, :] * Bm[1][:, None]
    y1 = (h1 * Cm[1][:, None]).sum(0) + w["D"] * xc[1]
    y = np.stack([y0, y1])
    x1 = x + (y * _silu(gate)) @ w["out_proj"]
    hh = ln(x1, w["norm2_w"], w["norm2_b"])
    want = x1 + (_silu(hh @ w["w_gate"]) * (hh @ w["w_up"])) @ w["w_down"]
    np.testing.assert_allclose(np.asarray(got_y), y, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_h0), h0, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_h1), h1, rtol=2e-5, atol=2e-6)
    # (float32 against float64 at values of some tens)
    np.testing.assert_allclose(np.asarray(got_x), want, rtol=1e-4, atol=1e-4)


def test_differential_attention_by_hand():
    """Two tokens, the pairing of the docstring: adjacent heads pair, the
    value pair side by side, lambda from the four vectors, sub-norm, (1 -
    lambda_init)."""
    import jax.numpy as jnp

    d, P, G = 4, 2, 1
    rng = np.random.default_rng(1)
    q = rng.standard_normal((2, 2 * P, d))
    k = rng.standard_normal((2, 2 * G, d))
    v = rng.standard_normal((2, 2 * G, d))
    lam, lam0, eps = 0.37, ref.lambda_init(3), 1e-5
    sub = rng.standard_normal(2 * d)
    got = ref._diff_attention(*(jnp.asarray(a, jnp.float32)
                                for a in (q, k, v)), lam, lam0,
                              jnp.asarray(sub, jnp.float32), 0, d, eps)
    want = np.zeros((2, P, 2 * d))
    for t in range(2):
        for p in range(P):
            def probs(qh, kh):
                sc = np.array([q[t, qh] @ k[s, kh] / np.sqrt(d)
                               for s in range(t + 1)])
                e = np.exp(sc - sc.max())
                return e / e.sum()
            a = probs(2 * p, 0) - lam * probs(2 * p + 1, 1)
            o = sum(a[s] * np.concatenate([v[s, 0], v[s, 1]])
                    for s in range(t + 1))
            want[t, p] = o / np.sqrt((o * o).mean() + eps) * sub * (1 - lam0)
    np.testing.assert_allclose(np.asarray(got), want.reshape(2, -1),
                               rtol=2e-5, atol=2e-6)


def test_hybrid_step_flops_by_hand():
    """The tiny layout (8 layers: Mamba, window, Mamba, window, Mamba, full,
    GMU, cross), counted by hand."""
    H, F, di, S, K, r, V = 64, 128, 128, 16, 4, 4, 512
    nq, nkv, W = 8 * 8, 4 * 8, 24
    mlp = 3 * H * F
    mamba = mlp + H * 2 * di + K * di + di * (r + 2 * S) + r * di + di * H
    attn = mlp + 2 * H * nq + 2 * H * nkv
    cross, gmu = mlp + 2 * H * nq, mlp + 2 * H * di
    lower = 3 * mamba + 2 * attn + attn          # layers 0..5
    whole = lower + gmu + cross
    assert hybrid_step.params_passed(TINY) == (lower, whole)
    # one decoded token at context 30: window layers attend 24, the full
    # and the cross layer 30 each; three scans
    f = hybrid_step.serve_flops(TINY, [], [], 1, 24, 30)
    assert f == (2 * whole + 2 * H * V + 4 * nq * (2 * 24 + 30 + 30)
                 + 6 * di * S * 3)
    # a 40-token prompt in chunks of 16, 16, 8: 40 positions through the
    # lower layers, its last one through the rest and the head
    chunks = [(0, 16), (16, 16), (32, 8)]
    f2 = hybrid_step.serve_flops(TINY, chunks, [40], 0, 0, 0)
    win = sum(min(p + 1, W) for p in range(40))
    full = 40 * 41 // 2
    assert f2 == (2 * (lower * 40 + (whole - lower)) + 2 * H * V
                  + 4 * nq * (2 * win + full + 40) + 6 * di * S * 3 * 40)


def test_hybrid_attention_and_ssm_work_by_hand():
    cfg = R.load_json(ROOT, "benchmarks", "configs",
                      "phi-4-mini-flash-reasoning.json")
    # one row at context 2,300: 8 window layers at 512, 8 layers at 2,300
    assert hybrid_attention.flops(cfg, 512, 2300) == \
        4 * 40 * 64 * 8 * (512 + 2300)
    per_pos = 2 * 20 * 64 * 2                    # K and V, bf16: 5,120 B
    assert per_pos == 5120
    assert hybrid_attention.nbytes(cfg, 512, 2300, 1) == \
        8 * per_pos * (512 + 2300) + 16 * 2 * 40 * 64 * 2
    # the state update: 9 Mamba layers, float32 state 16 x 5120
    assert ssm.step_nbytes(cfg, 128, 1) == 9 * 4 * (
        128 * (2 * 16 * 5120 + 3 * 5120 + 2 * 16) + 16 * 5120 + 5120)
    assert ssm.step_flops(cfg, 1) == 9 * 7 * 5120 * 16


@pytest.fixture(scope="module")
def traced():
    return R.run_cell("tiny-hybrid-serve", 2**31 + 17, 3.0, True,
                      root=HYBRID, require_chip=False)


def test_rehearsal_reports_the_hybrid_metrics_and_is_correct(traced):
    res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"mfu.hybrid_decode",
                                   "cache_fill_peak.hybrid"}
    assert 0 < res["metrics"]["cache_fill_peak.hybrid"]["value"] <= 100
    assert res["metrics"]["mfu.hybrid_decode"]["value"] > 0
    assert res["compared"]["logit_gap_max"]["value"] <= 1e-3
    assert res["compared"]["state_drift_first"]["value"] <= 1e-5
    assert res["compared"]["state_drift_max"]["value"] <= 1e-5
    assert res["compared"]["state_slots_checked"]["value"] >= 1
    json.loads(json.dumps(res))


@pytest.fixture(scope="module")
def untraced():
    traffic = R.load_json(HYBRID, "bench", "traffic", "tiny-long.json")
    driver = importlib.import_module("benchmarks.drivers.serve_hybrid")
    ctx = R.Context(workload="tiny-hybrid-serve", seed=5, seconds=3.0,
                    trace=False, config=TINY, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir="/tmp")
    return driver, driver.run(ctx)


def test_the_state_in_bf16_fails_the_limit(untraced):
    """The second control of the hybrid cell: the reference with its SSM
    state rounded to bfloat16 after every token, put in the program's
    place, lies past the limit on ``state_drift_first``. (At this size, with
    an init range of 0.15, greedy tokens show it too; at the published
    widths they do not, which is why the cell compares the state itself.)"""
    from benchmarks import check_served

    driver, run = untraced
    limits = R.load_json(HYBRID, "bench", "limits", "tiny-hybrid-serve.json")
    ok, compared = driver.check(run, limits, 5)
    assert ok, compared
    assert 1 <= compared["state_slots_checked"]["value"] <= driver.PROBE_SLOTS
    limit = limits["state_drift_first"]
    assert compared["state_drift_first"]["value"] < limit / 10
    assert compared["state_drift_max"]["value"] < limits["state_drift_max"]
    control = driver.state_drifts(run, limits, mode="state_bf16")
    assert control["state_drift_first"] > 10 * limit
    # the untraced loop runs the server's own step(): no per-step record
    assert "hybrid_steps" not in run
    gap = check_served.control_gap(run, dict(limits, sample_requests=8), 5,
                                   mode="state_bf16")
    assert gap > limits["logit_gap_max"]


@pytest.mark.parametrize("fault", ["zeroed", "other_request", "stale_16",
                                   "no_slot"])
def test_a_state_fault_fails_the_limit(untraced, fault):
    """Faults planted in what the probe read (``tools/state_readings.py``
    plants the same on the chip): a state restored as zeros, the state of
    another request, a state that missed its last 16 tokens (not carried
    over a chunk's edge), and a run that probed no slot. The served tokens
    show none of them."""
    from benchmarks.tools.state_readings import planted

    driver, run = untraced
    limits = R.load_json(HYBRID, "bench", "limits", "tiny-hybrid-serve.json")
    probe = [] if fault == "no_slot" else [planted(run, limits["pad_to"], stale=16)[fault]]
    ok, compared = driver.check(dict(run, state_probe=probe), limits, 5)
    assert not ok
    assert compared["logit_gap_max"]["value"] <= limits["logit_gap_max"]
    bad = {k for k, c in compared.items()
           if not ((c["value"] >= c["limit"]) if c.get("at_least")
                   else (c["value"] <= c["limit"]))}
    assert bad == {"state_drift_first", "state_drift_max"} | (
        {"state_slots_checked"} if fault == "no_slot" else set())


def test_readers_return_nothing_for_a_run_without_the_counters():
    run = {"steps": [{"t0": 0.0, "t1": 0.1}], "seconds": 1.0,
           "traced_window": (0.0, 1.0), "requests": []}
    for name in ("mfu.hybrid_decode", "hybrid_attention_roofline",
                 "ssm_step_roofline", "cache_fill_peak.hybrid"):
        assert R.load_reader(name).read(run) is None


def test_repo_manifest_has_the_hybrid_cell_and_its_files():
    m = R.load_manifest(ROOT)
    cell = R.cell_of(m, "phi4flash-serve-long-decode")
    assert cell["chips"] == 1 and len(m["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    _, _, cfg, traffic, limits, _ = R.load_cell(ROOT, cell["name"])
    assert cfg["driver"] == "serve_hybrid" and cfg["reference"] == "sambay_lm"
    assert traffic["ramp"] == {"seconds": 20.0, "burst": 128}
    for name in ("mfu.hybrid_decode", "hybrid_attention_roofline",
                 "ssm_step_roofline", "cache_fill_peak.hybrid"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert met["workloads"] == [cell["name"]]
        assert met["moves"] == "serve_tok_s"
