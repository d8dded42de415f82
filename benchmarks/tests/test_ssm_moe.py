"""The Mamba-2 + attention + many-expert family's part of the benchmark: its
plain reference against the equations written out by hand, its work counts
against counts by hand at the published widths, and a CPU rehearsal of
``drivers/serve_ssm_moe.py`` with the readers that take the program's
counters."""
import importlib
import json
import os

import numpy as np
import pytest

from conftest import FIXTURES, ROOT

from benchmarks import check_served
from benchmarks import run as R
from benchmarks.reference import ssm_moe_lm as ref
from benchmarks.roofline import ssm2, ssm_moe_experts, ssm_moe_step
from benchmarks.weights_ssm_moe import make_weights, ssm_moe_shapes

FIX = os.path.join(FIXTURES, "ssm_moe")
TINY = R.load_json(FIX, "bench", "configs", "tiny-ssm-moe.json")
REAL = R.load_json(ROOT, "benchmarks", "configs",
                   "granite-4.0-h-small-l10-ep2.json")
CELL = "granite4h-serve-chat-decode"
LIMITS = R.load_json(FIX, "bench", "limits", "tiny-ssm-moe-serve.json")


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(v, g, eps):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * g


def test_one_mamba2_layer_is_the_equations_by_hand():
    """One Mamba-2 layer of the reference over two tokens against the
    equations in numpy float64, every step written out: the split of the
    input projection, the convolution, ONE decay a head, gate then norm, the
    router's softmax over the chosen logits, the held experts only, the
    shared MLP, the residual multiplier."""
    import jax.numpy as jnp

    w32 = make_weights(ssm_moe_shapes(TINY), 3, jnp.float32, std=0.3)
    w0 = ref.layer_weights(w32, 0)
    w = {k: np.asarray(v, np.float64) for k, v in w0.items()}
    z = ref.sizes(TINY)
    di, N, mh, P, K = z["di"], z["N"], z["mh"], z["P"], z["K"]
    x = np.random.default_rng(0).standard_normal((2, z["H"]))
    got, got_S = ref.layer_forward(jnp.asarray(x, jnp.float32), w0,
                                   jnp.int32(2), zs=ref._static(z),
                                   kind="mamba")
    got_S1 = ref.layer_forward(jnp.asarray(x, jnp.float32), w0, jnp.int32(1),
                               zs=ref._static(z), kind="mamba")[1]

    u = _rms(x, w["mix_norm"], z["eps"])
    p = u @ w["in_proj"]
    gate, xbc, dt = p[:, :di], p[:, di:2 * di + 2 * N], p[:, 2 * di + 2 * N:]
    assert dt.shape == (2, mh)
    # causal depthwise conv: token 0 sees itself only, token 1 sees both
    c0 = xbc[0] * w["conv_w"][K - 1] + w["conv_b"]
    c1 = (xbc[0] * w["conv_w"][K - 2] + xbc[1] * w["conv_w"][K - 1]
          + w["conv_b"])
    xc = np.stack([_silu(c0), _silu(c1)])
    xs = xc[:, :di].reshape(2, mh, P)
    Bm, Cm = xc[:, di:di + N], xc[:, di + N:]
    dt = np.logaddexp(0.0, dt + w["dt_bias"])
    A = -np.exp(w["A_log"])
    S = np.zeros((mh, P, N))
    ys, states = [], []
    for t in range(2):
        S = (np.exp(dt[t] * A)[:, None, None] * S
             + (dt[t][:, None] * xs[t])[:, :, None] * Bm[t][None, None, :])
        states.append(S)
        ys.append((S * Cm[t]).sum(-1) + w["D"][:, None] * xs[t])
    y = np.stack(ys).reshape(2, di)
    g = _rms(y * _silu(gate), w["ssm_norm"], z["eps"])
    x1 = x + 0.22 * (g @ w["out_proj"])
    v = _rms(x1, w["mlp_norm"], z["eps"])
    r = v @ w["router"]
    moe = np.zeros_like(v)
    for t in range(2):
        top = np.argsort(-r[t])[:3]
        wt = np.exp(r[t][top] - r[t][top].max())
        wt /= wt.sum()
        for e, a in zip(top, wt):
            if 2 <= e < 6:                       # the experts held here
                j = e - 2
                moe[t] += a * ((_silu(v[t] @ w["w_gate_e"][j])
                                * (v[t] @ w["w_up_e"][j])) @ w["w_down_e"][j])
    shared = (_silu(v @ w["ws_gate"]) * (v @ w["ws_up"])) @ w["ws_down"]
    want = x1 + 0.22 * (moe + shared)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_S), states[1], rtol=2e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_S1), states[0], rtol=2e-4,
                               atol=1e-5)


def test_the_attention_layer_has_no_positions_and_its_own_scale():
    """Attention by hand: score scale ``attention_multiplier`` (0.125 here,
    not 16^-1/2 = 0.25), grouped heads, causal — and swapping two EARLIER
    tokens leaves a later token's output unchanged (no position term)."""
    import jax.numpy as jnp

    w32 = make_weights(ssm_moe_shapes(TINY), 4, jnp.float32, std=0.3)
    w0 = ref.layer_weights(w32, 1)
    w = {k: np.asarray(v, np.float64) for k, v in w0.items()}
    z = ref.sizes(TINY)
    u = np.random.default_rng(1).standard_normal((5, z["H"]))
    got = np.asarray(ref.attention_part(jnp.asarray(u, jnp.float32), w0, z))
    nh, G, d = z["heads"], z["kv_heads"], z["d"]
    q = (u @ w["wq"]).reshape(5, nh, d)
    k = (u @ w["wk"]).reshape(5, G, d)
    v = (u @ w["wv"]).reshape(5, G, d)
    o = np.zeros((5, nh, d))
    for t in range(5):
        for h in range(nh):
            s = (k[:t + 1, h // (nh // G)] @ q[t, h]) * 0.125
            p = np.exp(s - s.max())
            o[t, h] = (p / p.sum()) @ v[:t + 1, h // (nh // G)]
    np.testing.assert_allclose(got, o.reshape(5, -1) @ w["wo"], rtol=2e-4,
                               atol=2e-4)
    swapped = u[[1, 0, 2, 3, 4]]
    got2 = np.asarray(ref.attention_part(jnp.asarray(swapped, jnp.float32),
                                         w0, z))
    np.testing.assert_allclose(got2[2:], got[2:], rtol=1e-4, atol=1e-5)
    rot = np.asarray(ref.attention_part(jnp.asarray(u, jnp.float32), w0, z,
                                        mode="rope"))
    assert np.abs(rot - got).max() > 1e-2


def test_work_counts_by_hand_at_the_published_widths():
    cfg = REAL
    per = ssm_moe_step.row_params(cfg)
    common = 4096 * 72 + 3 * 4096 * 1536
    assert per == {"mamba": common + 4096 * 16768 + 8192 * 4096 + 4 * 8448,
                   "attention": common + 2 * 4096 * 4096 + 2 * 4096 * 1024}
    # 96 decode rows at context 1,000 each, 480 held pairs a layer
    f = ssm_moe_step.serve_flops(cfg, [], 96, 96 * 1000, 96, 480 * 10)
    assert f == (2 * (9 * per["mamba"] + per["attention"]) * 96
                 + 6 * 4096 * 768 * 4800 + 6 * 8192 * 128 * 9 * 96
                 + 4 * 32 * 128 * 96_000 + 2 * 4096 * 50176 * 96)
    # a 300-token prompt in chunks of 256 + 44, no held pair, one logits row
    f2 = ssm_moe_step.serve_flops(cfg, [(0, 256), (256, 44)], 0, 0, 1, 0)
    assert f2 == (2 * (9 * per["mamba"] + per["attention"]) * 300
                  + 6 * 8192 * 128 * 9 * 300
                  + 4 * 32 * 128 * (300 * 301 // 2) + 2 * 4096 * 50176)
    # the state update: 9 Mamba-2 layers, float32 state 128 x 64 x 128
    assert ssm2.step_nbytes(cfg, 96, 1) == 9 * 4 * (
        96 * (2 * 8192 * 128 + 2 * 8192 + 128 + 2 * 128) + 2 * 128)
    assert 2 * 8192 * 128 * 4 == 2 * 4_194_304
    assert ssm2.step_flops(cfg, 1) == 9 * 6 * 8192 * 128
    assert ssm_moe_experts.flops(cfg, 480) == 6 * 4096 * 768 * 480
    assert ssm_moe_experts.nbytes(cfg, 480, 36) == (
        36 * 3 * 4096 * 768 * 2 + 480 * 2 * 4096 * 2)


@pytest.fixture(scope="module")
def traced():
    return R.run_cell("tiny-ssm-moe-serve", 2**31 + 35, 3.0, True,
                      root=FIX, require_chip=False)


def test_rehearsal_reports_the_metrics_and_is_correct(traced):
    res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"mfu.ssm_moe_decode",
                                   "cache_fill_peak.hybrid",
                                   "kv_blocks_peak.decode"}
    assert res["metrics"]["mfu.ssm_moe_decode"]["value"] > 0
    assert 0 < res["metrics"]["cache_fill_peak.hybrid"]["value"] <= 100
    assert 0 < res["metrics"]["kv_blocks_peak.decode"]["value"] <= 100
    c = res["compared"]
    assert c["logit_gap_max"]["value"] <= 5e-5
    assert c["state_drift_first"]["value"] <= 1e-5
    assert c["state_drift_max"]["value"] <= 5e-5
    assert c["state_slots_checked"]["value"] >= 1
    # three Mamba-2 layers: float32 state 8 x 16 x 128, conv tail 3 x 384
    assert c["state_bytes_per_slot"]["value"] == \
        c["state_bytes_per_slot"]["limit"] == 3 * (8 * 16 * 128 * 4
                                                   + 3 * 384 * 4)
    assert set(res["breakdown"]["selected"]["ssd_chunk"]) == {"xla"}
    assert "ssm2_step" in res["breakdown"]["selected"]
    json.loads(json.dumps(res))


@pytest.fixture(scope="module")
def untraced():
    traffic = R.load_json(FIX, "bench", "traffic", "tiny-turns.json")
    driver = importlib.import_module("benchmarks.drivers.serve_ssm_moe")
    ctx = R.Context(workload="tiny-ssm-moe-serve", seed=5, seconds=3.0,
                    trace=False, config=TINY, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir="/tmp")
    return driver, driver.run(ctx)


def _failing(compared):
    return {k for k, c in compared.items()
            if not ((c["value"] >= c["limit"]) if c.get("at_least")
                    else (c["value"] <= c["limit"]))}


def test_the_untraced_loop_runs_the_servers_own_step(untraced):
    driver, run = untraced
    ok, compared = driver.check(run, LIMITS, 5)
    assert ok, compared
    assert "moe_steps" not in run and "hybrid_steps" not in run
    assert driver.end_to_end(run)["serve_tok_s"] > 0
    assert 1 <= compared["state_slots_checked"]["value"] <= 2
    assert "load max over mean" not in driver.describe(run)


@pytest.mark.parametrize("mode", ["int8", "bf16", "top9", "softmax_all",
                                  "no_shared", "res_1", "scale_sqrt", "rope",
                                  "norm_then_gate"])
def test_a_control_or_a_planted_fault_reads_not_correct(untraced, mode):
    """The reference in a lower precision, or with a fault planted — one
    expert fewer a token, a softmax over all the router's logits, the shared
    MLP left out, the residual multiplier left at 1, a score scale of
    d^-1/2, a rotation, norm before gate — put in the program's place over
    the same prompts and served tokens: each lies past the limit the
    program passes under."""
    _, run = untraced
    gap = check_served.control_gap(run, dict(LIMITS, sample_requests=8), 5,
                                   mode=mode)
    assert gap > 10 * LIMITS["logit_gap_max"], gap


def test_the_state_in_bf16_fails_the_limit(untraced):
    driver, run = untraced
    control = driver.state_drifts(run, LIMITS, mode="state_bf16")
    assert control["state_drift_first"] > 10 * LIMITS["state_drift_first"]
    got = driver.state_drifts(run, LIMITS)
    assert got["state_drift_first"] < LIMITS["state_drift_first"] / 10


@pytest.mark.parametrize("fault", ["zeroed", "other_request", "stale_16",
                                   "no_slot"])
def test_a_state_fault_fails_the_limit(untraced, fault):
    """Faults planted in what the probe read (``tools/ssm_moe_readings.py``
    plants the same on the chip): a state restored as zeros, the state of
    another request, a state that missed its last 16 tokens, and a run that
    probed no slot. The served tokens show none of them."""
    from benchmarks.tools.ssm_moe_readings import planted

    driver, run = untraced
    probe = [] if fault == "no_slot" else [
        planted(run, LIMITS["pad_to"], stale=16)[fault]]
    ok, compared = driver.check(dict(run, state_probe=probe), LIMITS, 5)
    assert not ok
    assert _failing(compared) == {"state_drift_first", "state_drift_max"} | (
        {"state_slots_checked"} if fault == "no_slot" else set())


def test_an_altered_token_and_fewer_state_bytes_read_not_correct(untraced):
    driver, run = untraced
    idx = check_served.sample_finished(run, 5, 4)[0]
    seq = list(run["results"][idx])
    bad = dict(run, results=dict(run["results"]))
    bad["results"][idx] = seq[:-3] + [(seq[-3] + 1) % 512 or 1] + seq[-2:]
    ok, compared = driver.check(bad, LIMITS, 5)
    assert not ok and "logit_gap_max" in _failing(compared)
    bad["results"][idx] = seq[:-2]
    ok, compared = driver.check(bad, LIMITS, 5)
    assert not ok and compared["wrong_shape"]["value"] == 1
    # a state kept in the served type would be allotted half the bytes
    kv = dict(run["kv_stats"])
    kv["cache_bytes_state_allotted"] //= 2
    ok, compared = driver.check(dict(run, kv_stats=kv), LIMITS, 5)
    assert not ok and _failing(compared) == {"state_bytes_per_slot"}


def test_readers_return_nothing_for_a_run_without_the_counters():
    run = {"steps": [{"t0": 0.0, "t1": 0.1, "decode_rows": 1, "decode_ctx": 5,
                      "tokens": 1, "prefill_chunks": []}],
           "seconds": 1.0, "traced_window": (0.0, 1.0), "requests": [],
           "config": TINY}
    zeros = {"pairs_held": 0, "pairs_absent": 0, "experts_active": 0,
             "load_max": 0}
    for name in ("mfu.ssm_moe_decode", "expert_matmul_roofline.ssm_moe"):
        assert R.load_reader(name).read(run) is None
        assert R.load_reader(name).read(dict(run, moe_steps=[zeros])) is None
    assert R.load_reader("ssm2_step_roofline").read(run) is None
    assert R.load_reader("ssm2_step_roofline").read(
        dict(run, hybrid_steps=[{"decode_rows": 0}])) is None


def test_roofline_readers_match_the_kernels_by_structure():
    """Trace events named as the v5e names them (whole HLO text): the
    one-step update by the state it returns, the grouped products by the
    expert stacks they read; the chunk program's write of ONE slot's state,
    Mamba-1's update and a dense matmul match neither."""
    from benchmarks import trace_reduce as tr

    E = tr.Event
    step = E(("%ssm2_step.3 = (f32[96,4,64,32]{3,2,1,0}, f32[96,128,64,128]"
              "{3,2,1,0}) custom-call(f32[96,4,64,32]{3,2,1,0} %f.1, "
              "f32[96,128,64,128]{3,2,1,0} %p.9), custom_call_target="
              "\"tpu_custom_call\"", 0.0, 1e-3))
    fused = E(("%multiply_reduce_fusion = (f32[96,128,64]{2,1,0:T(8,128)S(1)}"
               ", f32[96,128,64,128]{3,2,1,0:T(8,128)}) fusion(f32[96,128] "
               "%g.217, f32[96,128,64,128] %slot_pools_0_.1), kind=kLoop", 0.0,
               1e-3))
    one = E(("%fusion.9 = f32[96,128,64,128]{3,2,1,0:T(8,128)} fusion(f32[96,128,64,"
             "128] %p, s32[] %s, f32[1,128,64,128] %u), kind=kLoop", 0.0,
             1e-3))
    mamba1 = E(("%custom-call.2 = (f32[128,1,5120]{2,1,0}, f32[128,16,5120]"
                "{2,1,0}) custom-call(f32[128,1,5120] %x), custom_call_target"
                "=\"tpu_custom_call\"", 0.0, 1e-3))
    gmm = E(("%gmm.3 = f32[1024,768]{1,0} custom-call(s32[36]{0} %c, "
             "bf16[1024,4096]{1,0} %g, bf16[36,4096,768]{2,1,0} %p.9)", 0.0,
             2e-3))
    dense = E(("%fusion.1 = bf16[96,4096]{1,0} fusion(bf16[96,4096] %a, "
               "bf16[4096,4096] %w)", 0.0, 1e-3))
    ops = [step, one, mamba1, gmm, dense]
    sr = R.load_reader("ssm2_step_roofline")
    em = R.load_reader("expert_matmul_roofline.ssm_moe")
    assert tr.match(ops, sr.patterns(REAL)[1:]) == [step]
    assert tr.match(ops, sr.patterns(REAL)) == [step]
    # the jnp composition's one fusion, as XLA:TPU names it
    assert tr.match([fused, one, mamba1], sr.patterns(REAL)) == [fused]
    assert tr.match(ops, em.patterns(REAL)) == [gmm]
    rec = {"t0": 0.0, "t1": 0.1, "decode_rows": 96, "decode_ctx": 96_000,
           "tokens": 96, "prefill_chunks": []}
    run = {"steps": [rec], "seconds": 1.0, "traced_window": (0.0, 1.0),
           "config": REAL, "device_ops": ops,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "hybrid_steps": [{"decode_rows": 96}],
           "moe_steps": [{"pairs_held": 4800, "pairs_absent": 4800,
                          "experts_active": 360, "load_max": 200}]}
    share = sr.read(run)
    # 96 rows x 9 layers x ~8.46 MB at 819 GB/s = 8.9 ms over 1 ms
    assert abs(share - 100 * ssm2.step_nbytes(REAL, 96, 1) / 819e9
               / 1e-3) < 1e-6
    assert run["roofline_bounds"]["ssm2_step"] == "memory"
    share = em.read(run)
    want = (360 * 3 * 4096 * 768 * 2 + 4800 * 2 * 4096 * 2) / 819e9 / 2e-3
    assert abs(share - 100 * want) < 1e-6
    assert run["roofline_bounds"]["expert_gmm"] == "memory"


def test_repo_manifest_has_the_cell_and_its_files():
    m = R.load_manifest(ROOT)
    cell = R.cell_of(m, CELL)
    assert cell["chips"] == 1 and len(m["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    _, _, cfg, traffic, limits, _ = R.load_cell(ROOT, CELL)
    assert cfg["driver"] == "serve_ssm_moe"
    assert cfg["reference"] == "ssm_moe_lm"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72, "vocab_size": 100352}
    assert cfg["experts_held"] == [0, cfg["num_local_experts"]] == [0, 36]
    assert traffic["ramp"] == {"seconds": 20.0, "burst": 96}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 128, "max": 2048}
    assert traffic["output"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 96, "max": 1024}
    assert traffic["knee_factor"] == 1.5 and not traffic["unfinished_fails"]
    assert cfg["served"]["max_len"] == \
        traffic["prompt"]["max"] + traffic["output"]["max"]
    assert cfg["served"]["max_batch"] == traffic["ramp"]["burst"] == 96
    assert limits["pad_to"] <= cfg["served"]["max_len"]
    for name in ("mfu.ssm_moe_decode", "ssm2_step_roofline",
                 "expert_matmul_roofline.ssm_moe"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert met["workloads"] == [CELL] and met["moves"] == "serve_tok_s"
    for name in ("gen_late_p95_ms.decode", "batch_occupancy.decode",
                 "queue_depth_min.decode", "tick_ms.decode",
                 "compiles_in_window.decode", "kv_blocks_peak.decode",
                 "device_idle.decode", "host_ms_per_tick.decode"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert met["workloads"][-1] == CELL
    assert next(e for e in m["end_to_end"]
                if e["name"] == "serve_tok_s")["workloads"][-1] == CELL


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row, under the same key, but the three
    keys in ``reduced`` (the catalog lives outside the repo: its numbers are
    written out here)."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_size": 4096,
        "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "shared_intermediate_size": 1536,
        "tie_word_embeddings": True, "position_embedding_type": "nope"}
    assert {k: REAL[k] for k in published} == published
    kinds = REAL["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    assert ref.layer_kinds(REAL) == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert (REAL["num_hidden_layers"], REAL["num_local_experts"],
            REAL["vocab_size"]) == (10, 36, 50176)
    # the guide's floors: a whole period, >= 8 routed experts, >= 1/8
    # of the vocabulary
    assert REAL["vocab_size"] * 8 >= REAL["published"]["vocab_size"]
    # the share's weights: 9.51 GB in bfloat16
    n = sum(int(np.prod(s)) for s, _ in ssm_moe_shapes(REAL).values())
    assert n == 4_757_211_776 and abs(n * 2 / 1e9 - 9.51) < 0.01
    for key in ("deployment", "assumed", "served", "experts_held"):
        assert REAL[key]


def test_a_seed_draws_no_router_bias_and_mamba2s_own_leaves():
    """No leaf of the router but its matrix (a seed must not change the
    work); ``A_log`` in log([1, 16]) and ``dt_bias`` = softplus^-1 of [1e-3,
    1e-1], one value a head."""
    import jax.numpy as jnp

    shapes = ssm_moe_shapes(TINY)
    assert not [n for n in shapes if "router" in n and not n.endswith(
        ".router")]
    w = make_weights(shapes, 9, jnp.float32, std=0.3)
    a = np.asarray(w["layers.0.A_log"])
    dt = np.logaddexp(0.0, np.asarray(w["layers.0.dt_bias"]))
    assert a.shape == dt.shape == (8,)
    assert 0.0 <= a.min() and a.max() <= np.log(16.0) + 1e-6
    assert 1e-3 - 1e-6 <= dt.min() and dt.max() <= 1e-1 + 1e-6
    assert np.asarray(w["layers.0.D"]).tolist() == [1.0] * 8
    assert "layers.1.wq" in w and "layers.1.in_proj" not in w
    assert abs(float(np.asarray(w["layers.0.router"]).std()) - 0.3) < 0.05
