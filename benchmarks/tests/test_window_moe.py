"""The window + full attention, dense-then-experts family's part of the
benchmark: its plain reference against the equations written out by hand,
its work counts against counts by hand at the published widths, and a CPU
rehearsal of ``drivers/serve_window_moe.py`` with the readers that take the
program's counters."""
import importlib
import json
import os

import numpy as np
import pytest

from conftest import FIXTURES, ROOT

from benchmarks import run as R
from benchmarks.reference import window_moe_lm as ref
from benchmarks.roofline import (window_moe_attention, window_moe_experts,
                                 window_moe_step)
from benchmarks.weights_window_moe import make_weights, window_moe_shapes

FIX = os.path.join(FIXTURES, "window_moe")
TINY = R.load_json(FIX, "bench", "configs", "tiny-window-moe.json")
REAL = R.load_json(ROOT, "benchmarks", "configs", "trinity-mini-l8-ep2.json")
CELL = "trinitymini-serve-long-context-decode"
LIMITS = R.load_json(FIX, "bench", "limits", "tiny-window-moe-serve.json")


def _rms(v, g, eps):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    T, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2) / d)
    ang = np.arange(T)[:, None] * inv[None]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@pytest.mark.parametrize("layer,W", [(0, 3), (3, 0)], ids=["window", "full"])
def test_the_attention_is_the_equations_by_hand(layer, W):
    """Attention by hand in float64: q/k norms per head, rope in the window
    layer only, a window of 3 (here: the fixture's 24 cut to 3 so that it
    binds over 6 tokens), grouped heads, causal, the sigmoid gate, W_o."""
    import jax.numpy as jnp

    w32 = make_weights(window_moe_shapes(TINY), 3, jnp.float32, std=0.3)
    w0 = ref.layer_weights(w32, layer)
    w = {k: np.asarray(v, np.float64) for k, v in w0.items()}
    z = dict(ref.sizes(TINY), W=3)
    kind = "window" if W else "full"
    u = np.random.default_rng(1).standard_normal((6, z["H"]))
    got = np.asarray(ref.attention_part(jnp.asarray(u, jnp.float32), w0, z,
                                        kind))
    nh, G, d, eps = z["heads"], z["kv_heads"], z["d"], z["eps"]
    q = _rms((u @ w["wq"]).reshape(6, nh, d), w["q_norm"], eps)
    k = _rms((u @ w["wk"]).reshape(6, G, d), w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(6, G, d)
    if W:
        q, k = _rope(q, z["theta"]), _rope(k, z["theta"])
    o = np.zeros((6, nh, d))
    for t in range(6):
        lo = max(0, t - W + 1) if W else 0
        for h in range(nh):
            s = (k[lo:t + 1, h // (nh // G)] @ q[t, h]) / np.sqrt(d)
            p = np.exp(s - s.max())
            o[t, h] = (p / p.sum()) @ v[lo:t + 1, h // (nh // G)]
    g = 1.0 / (1.0 + np.exp(-(u @ w["wg"])))
    np.testing.assert_allclose(got, (o.reshape(6, -1) * g) @ w["wo"],
                               rtol=2e-4, atol=2e-4)
    # a window layer sees where its tokens are; a full layer does not: a
    # swap of two EARLIER tokens moves a later token's output only there
    swapped = np.asarray(ref.attention_part(
        jnp.asarray(u[[1, 0, 2, 3, 4, 5]], jnp.float32), w0,
        dict(z, W=24), kind))
    whole = np.asarray(ref.attention_part(jnp.asarray(u, jnp.float32), w0,
                                          dict(z, W=24), kind))
    moved = np.abs(swapped[2:] - whole[2:]).max()
    assert (moved > 1e-3) if W else (moved < 1e-5)


def test_the_router_is_sigmoid_top_k_by_bias_normed_and_scaled():
    import jax.numpy as jnp

    z = ref.sizes(TINY)
    rng = np.random.default_rng(2)
    w = {"router": jnp.asarray(rng.standard_normal((64, 16)), jnp.float32),
         "router_bias": jnp.asarray(np.r_[[5.0, 5.0], np.zeros(14)],
                                    jnp.float32)}
    h = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    wts = np.asarray(ref.route(h, w, z))
    s = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64)
                              @ np.asarray(w["router"], np.float64))))
    for t in range(5):
        chosen = np.argsort(-(s[t] + np.asarray(w["router_bias"])))[:4]
        assert {0, 1} <= set(chosen.tolist())     # the bias chooses ...
        want = np.zeros(16)
        want[chosen] = s[t][chosen] / s[t][chosen].sum() * 2.826
        np.testing.assert_allclose(wts[t], want, rtol=1e-5, atol=1e-6)
    assert np.allclose(wts.sum(-1), 2.826)        # ... and never weighs


def test_work_counts_by_hand_at_the_published_widths():
    cfg = REAL
    per = window_moe_step.row_params(cfg)
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert per == {"dense": attn + 3 * 2048 * 6144,
                   "experts": attn + 2048 * 128 + 3 * 2048 * 1024}
    assert window_moe_step.layer_params(cfg) == \
        2 * per["dense"] + 6 * per["experts"]
    # 128 decode rows at context 6,000, 1,024 held pairs an expert layer
    pos = 128 * (6 * 2048 + 2 * 6000)
    f = window_moe_step.serve_flops(cfg, 128, pos, 128, 6 * 1024)
    assert f == (2 * (2 * per["dense"] + 6 * per["experts"]) * 128
                 + 6 * 2048 * 1024 * 6144 + 4 * 32 * 128 * pos
                 + 2 * 2048 * 100_096 * 128)
    assert window_moe_experts.nbytes(cfg, 1024, 64) == (
        64 * 3 * 2048 * 1024 * 2 + 1024 * 2 * 2048 * 2)
    # the window layers: a decode row reads 2,048 B a position; a chunk of
    # 256 at 4,096 reads the 2,048 positions before it and itself ONCE
    wpos = 6 * (128 * 2048 + 256 * 2048)
    assert window_moe_attention.chunk_positions(cfg, "window",
                                                [(4096, 256)]) == 256 * 2048
    assert window_moe_attention.nbytes(cfg, "window", wpos, 128,
                                       [(4096, 256)]) == (
        2048 * 6 * 128 * 2048 + 2048 * 6 * (2048 + 256)
        + 2 * 32 * 128 * 2 * 6 * (128 + 256))
    fpos = 2 * (128 * 6000 + 256 * 4096 + 256 * 257 // 2)
    assert window_moe_attention.nbytes(cfg, "full", fpos, 128,
                                       [(4096, 256)]) == (
        2048 * 2 * 128 * 6000 + 2048 * 2 * (4096 + 256)
        + 2 * 32 * 128 * 2 * 2 * (128 + 256))


@pytest.fixture(scope="module")
def traced():
    return R.run_cell("tiny-window-moe-serve", 2**31 + 39, 3.0, True,
                      root=FIX, require_chip=False)


def test_rehearsal_reports_the_metrics_and_is_correct(traced):
    res = traced
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert set(res["metrics"]) == {"mfu.window_moe_decode",
                                   "kv_blocks_peak.decode",
                                   "fused_chunk_share.decode"}
    assert res["metrics"]["mfu.window_moe_decode"]["value"] > 0
    assert 0 < res["metrics"]["kv_blocks_peak.decode"]["value"] <= 100
    assert res["metrics"]["fused_chunk_share.decode"]["value"] > 0
    c = res["compared"]
    assert c["logit_gap_max"]["value"] <= 1e-4
    assert c["served_tokens_checked"]["value"] >= 20
    assert set(res["breakdown"]["selected"]["window_chunk_attention"]) == \
        {"xla"}
    json.loads(json.dumps(res))


@pytest.fixture(scope="module")
def untraced():
    traffic = R.load_json(FIX, "bench", "traffic", "tiny-long.json")
    driver = importlib.import_module("benchmarks.drivers.serve_window_moe")
    ctx = R.Context(workload="tiny-window-moe-serve", seed=5, seconds=3.0,
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
    assert "load max over mean" not in driver.describe(run)


@pytest.mark.parametrize("mode", ["int8", "bf16"] + list(ref.FAULTS))
def test_a_control_or_a_planted_fault_reads_not_correct(untraced, mode):
    """The reference in a lower precision, or with a fault planted, put in
    the program's place over the same prompts and served tokens, at the
    positions compared: each lies past the limit the program passes under."""
    driver, run = untraced
    got = driver.served_gap_stats(run, dict(LIMITS, sample_requests=8), 5,
                                  mode=mode)
    assert got["max"] > 10 * LIMITS["logit_gap_max"], got


def test_only_positions_from_compare_from_on_are_compared(untraced):
    driver, run = untraced
    early = driver.served_gap_stats(run, dict(LIMITS, compare_from=0), 5)
    late = driver.served_gap_stats(run, LIMITS, 5)
    assert early["tokens"] > late["tokens"] > 0
    none = driver.served_gap_stats(run, dict(LIMITS, compare_from=10**6), 5)
    assert none["tokens"] == 0 and none["max"] == float("inf")


def test_an_altered_token_reads_not_correct(untraced):
    from benchmarks import check_served

    driver, run = untraced
    idx = check_served.sample_finished(run, 5, 4)[0]
    seq = list(run["results"][idx])
    bad = dict(run, results=dict(run["results"]))
    bad["results"][idx] = seq[:-3] + [(seq[-3] + 1) % 256 or 1] + seq[-2:]
    ok, compared = driver.check(bad, LIMITS, 5)
    assert not ok and "logit_gap_max" in _failing(compared)
    bad["results"][idx] = seq[:-2]
    ok, compared = driver.check(bad, LIMITS, 5)
    assert not ok and compared["wrong_shape"]["value"] == 1


def test_readers_return_nothing_for_a_run_without_the_counters():
    """A run with no by-kind record (``hybrid_steps``: an untraced run, or a
    program without the window counter), or with no work in it: every
    reader that needs one is silent."""
    run = {"steps": [{"t0": 0.0, "t1": 0.1, "decode_rows": 1, "decode_ctx": 5,
                      "tokens": 1, "prefill_chunks": []}],
           "seconds": 1.0, "traced_window": (0.0, 1.0), "requests": [],
           "config": TINY,
           "moe_steps": [{"pairs_held": 4, "pairs_absent": 4,
                          "experts_active": 4, "load_max": 1}]}
    idle = dict(run, steps=[dict(run["steps"][0], decode_ctx=0)],
                hybrid_steps=[{"decode_ctx_window": 0}])
    for name in ("mfu.window_moe_decode", "window_attention_roofline",
                 "full_attention_roofline"):
        assert R.load_reader(name).read(run) is None
        assert R.load_reader(name).read(idle) is None
    assert R.load_reader("expert_matmul_roofline.window_moe").read(
        dict(run, moe_steps=[dict(run["moe_steps"][0], pairs_held=0)])) \
        is None


def test_positions_by_kind_are_the_step_records_and_the_window_counter():
    """The full layers' positions are the step records' decode contexts and
    the chunks' whole contexts; the window layers' are the engine's capped
    decode count and the chunks' contexts capped at the window; each summed
    over the layers of its kind."""
    from benchmarks import window_moe_readers as wr

    W = REAL["sliding_window"]
    rec = {"t0": 0.0, "t1": 0.1, "decode_rows": 128,
           "decode_ctx": 128 * 6000, "tokens": 128,
           "prefill_chunks": [(0, 256), (4096, 256)]}
    run = {"steps": [rec], "config": REAL,
           "hybrid_steps": [{"decode_ctx_window": 128 * W}]}
    pairs = list(zip(run["steps"], run["hybrid_steps"]))
    chunk_full = sum(range(1, 257)) + sum(range(4097, 4353))
    chunk_win = sum(range(1, 257)) + 256 * W
    assert wr.positions(run, pairs, "full") == 2 * (128 * 6000 + chunk_full)
    assert wr.positions(run, pairs, "window") == 6 * (128 * W + chunk_win)


def test_roofline_readers_match_the_kernels_by_scope_and_structure():
    """Trace events named as the v5e names them (whole HLO text): each kind
    of attention by the scope the program traces it under, the grouped
    products by name or by the expert stacks they read; a dense matmul
    matches none."""
    from benchmarks import trace_reduce as tr

    E = tr.Event
    win = E(("%window_attend.3 = bf16[128,4,8,128]{3,2,1,0} custom-call("
             "s32[128,129]{1,0} %r, s32[128]{0} %p)", 0.0, 1e-3))
    win_chunk = E(("%fusion.12 = f32[4,8,256,2304]{3,2,1,0} fusion(bf16[256"
                   ",4,128] %q), kind=kLoop, metadata={op_name=\"jit(_decode"
                   "_chunk_fn)/window_attend/dot_general\"}", 0.0, 1e-4))
    full = E(("%full_attend.1 = bf16[128,4,8,128]{3,2,1,0} custom-call("
              "s32[128,1152]{1,0} %t, s32[128]{0} %p)", 0.0, 2e-3))
    gmm = E(("%gmm.3 = f32[1024,1024]{1,0} custom-call(s32[64]{0} %c, "
             "bf16[1024,2048]{1,0} %g, bf16[64,2048,1024]{2,1,0} %p.9)", 0.0,
             2e-3))
    dense = E(("%fusion.1 = bf16[128,2048]{1,0} fusion(bf16[128,2048] %a, "
               "bf16[2048,4096] %w)", 0.0, 1e-3))
    ops = [win, win_chunk, full, gmm, dense]
    em = R.load_reader("expert_matmul_roofline.window_moe")
    assert tr.match(ops, ["window_attend"]) == [win, win_chunk]
    assert tr.match(ops, ["full_attend"]) == [full]
    assert tr.match(ops, em.patterns(REAL)) == [gmm]
    assert tr.match([gmm, dense], em.patterns(REAL)[1:]) == [gmm]
    rec = {"t0": 0.0, "t1": 0.1, "decode_rows": 128,
           "decode_ctx": 128 * 6000, "tokens": 128, "prefill_chunks": []}
    run = {"steps": [rec], "seconds": 1.0, "traced_window": (0.0, 1.0),
           "config": REAL, "device_ops": ops,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "hybrid_steps": [{"decode_ctx_window": 128 * 2048}],
           "moe_steps": [{"pairs_held": 6144, "pairs_absent": 6144,
                          "experts_active": 384, "load_max": 120}]}
    share = R.load_reader("window_attention_roofline").read(run)
    want = window_moe_attention.nbytes(REAL, "window", 6 * 128 * 2048, 128,
                                       []) / 819e9 / 1.1e-3
    assert abs(share - 100 * want) < 1e-6
    assert run["roofline_bounds"]["window_attend"] == "memory"
    share = R.load_reader("full_attention_roofline").read(run)
    want = window_moe_attention.nbytes(REAL, "full", 2 * 128 * 6000, 128,
                                       []) / 819e9 / 2e-3
    assert abs(share - 100 * want) < 1e-6
    share = em.read(run)
    want = window_moe_experts.nbytes(REAL, 6144, 384) / 819e9 / 2e-3
    assert abs(share - 100 * want) < 1e-6


def test_repo_manifest_has_the_cell_and_its_files():
    m = R.load_manifest(ROOT)
    cell = R.cell_of(m, CELL)
    assert cell["chips"] == 1 and len(m["workloads"]) <= 24
    _, _, cfg, traffic, limits, _ = R.load_cell(ROOT, CELL)
    assert (cfg["driver"], cfg["reference"]) == ("serve_window_moe",
                                                 "window_moe_lm")
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 200192}
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 64]
    assert traffic["prompt"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.6, "min": 1024, "max": 12288}
    assert traffic["output"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.6, "min": 512, "max": 6144}
    assert traffic["knee_factor"] == 1.5 and not traffic["unfinished_fails"]
    assert cfg["served"]["max_len"] == \
        traffic["prompt"]["max"] + traffic["output"]["max"]
    assert cfg["served"]["max_batch"] == traffic["ramp"]["burst"] == 128
    assert limits["pad_to"] <= cfg["served"]["max_len"]
    assert limits["compare_from"] == 2 * cfg["sliding_window"]
    for name in ("mfu.window_moe_decode", "window_attention_roofline",
                 "full_attention_roofline",
                 "expert_matmul_roofline.window_moe"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert met["workloads"] == [CELL] and met["moves"] == "serve_tok_s"
    for name in ("gen_late_p95_ms.decode", "batch_occupancy.decode",
                 "queue_depth_min.decode", "tick_ms.decode",
                 "compiles_in_window.decode", "kv_blocks_peak.decode",
                 "device_idle.decode", "host_ms_per_tick.decode",
                 "device_starved.decode", "starved_after_first_token.decode",
                 "fused_chunk_share.decode"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert CELL in met["workloads"]
    for name in ("expert_load_max_over_mean.decode", "cache_fill_peak.hybrid"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert CELL not in met["workloads"]
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "serve_tok_s")["workloads"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row, under the same key, but the three
    keys in ``reduced`` (the catalog lives outside the repo: its numbers are
    written out here)."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "model_type": "afmoe"}
    assert {k: REAL[k] for k in published} == published
    kinds = REAL["layer_types"]
    assert len(kinds) == 32 and [i for i, k in enumerate(kinds)
                                 if k == "full_attention"] == list(
        range(3, 32, 4))
    assert ref.layer_kinds(REAL) == (["window"] * 3 + ["full"]) * 2
    assert (REAL["num_hidden_layers"], REAL["num_experts"],
            REAL["vocab_size"]) == (8, 64, 100096)
    # the guide's floors: a whole period, >= 4 layers after the dense ones,
    # >= 8 routed experts, >= 1/8 of the vocabulary
    assert REAL["num_hidden_layers"] - REAL["num_dense_layers"] >= 4
    assert REAL["vocab_size"] * 8 >= REAL["published"]["vocab_size"]
    n = sum(int(np.prod(s)) for s, _ in window_moe_shapes(REAL).values())
    assert n == 3_158_905_600 and abs(n * 2 / 1e9 - 6.32) < 0.01
    for key in ("deployment", "assumed", "served", "experts_held"):
        assert REAL[key]


def test_a_seed_draws_a_small_selection_bias_and_no_other_router_leaf():
    import jax.numpy as jnp

    shapes = window_moe_shapes(TINY)
    assert sorted(n for n in shapes if "router" in n and n.startswith(
        "layers.2.")) == ["layers.2.router", "layers.2.router_bias"]
    assert "layers.0.router" not in shapes and "layers.0.w_gate" in shapes
    w = make_weights(shapes, 9, jnp.float32, std=0.3, bias_std=0.03)
    assert abs(float(np.asarray(w["layers.2.router"]).std()) - 0.3) < 0.05
    assert float(np.abs(np.asarray(w["layers.2.router_bias"])).max()) < 0.15
    assert np.asarray(w["layers.2.q_norm"]).tolist() == [1.0] * 16
