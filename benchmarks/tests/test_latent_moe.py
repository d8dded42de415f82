"""The latent-attention + held-experts family's part of the benchmark: its
plain reference against a layer written out by hand, its required-work counts
against counts by hand, and a CPU rehearsal of ``drivers/
serve_latent_moe.py`` with its controls, its planted faults and the readers
that take the program's expert-layer counters."""
import importlib
import json
import math
import os

import numpy as np
import pytest

from conftest import FIXTURES, ROOT

from benchmarks import check_served
from benchmarks import run as R
from benchmarks.reference import latent_moe_lm as ref
from benchmarks.roofline import expert_matmul, latent_attention, latent_moe_step
from benchmarks.weights_latent_moe import latent_moe_shapes, make_weights

FIX = os.path.join(FIXTURES, "latent_moe")
TINY = R.load_json(FIX, "bench", "configs", "tiny-latent-moe.json")
CELL = "mistralsmall4-serve-long-decode"
REAL = R.load_json(ROOT, "benchmarks", "configs",
                   "mistral-small-4-119b-l6-ep4.json")


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_one_layer_is_the_equations_by_hand():
    """One layer of the reference over three tokens against the published
    equations in numpy float64, every step written out: interleaved YaRN
    rotation, one shared rope key, the query scale, sigmoid routing with a
    selection bias over all 8 experts, the sum over the held ones (2..5)
    only, the shared expert."""
    import jax.numpy as jnp

    z = ref.sizes(TINY)
    w32 = make_weights(latent_moe_shapes(TINY), 3, jnp.float32, std=0.3)
    w0 = ref.layer_weights(w32, 0)
    w = {k: np.asarray(v, np.float64) for k, v in w0.items()}
    T = 3
    # positions 0..2 are under the original length; shift the test to
    # positions 16..18 by hand below through a_t and the angles
    x = np.random.default_rng(0).standard_normal((T, z["H"]))
    inv = ref.yarn_inv_freq(z["dr"], z["theta"], z["factor"], z["orig"],
                            z["beta_fast"], z["beta_slow"])
    got = np.asarray(ref.layer_forward(
        jnp.asarray(x, jnp.float32), w0, jnp.asarray(inv),
        zs=ref._static(z)))

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + z["eps"]) * g

    def rope(v, t):                                   # v [..., dr]
        out = v.copy()
        for j in range(z["dr"] // 2):
            c, s = math.cos(t * inv[j]), math.sin(t * inv[j])
            a, b = v[..., 2 * j], v[..., 2 * j + 1]
            out[..., 2 * j], out[..., 2 * j + 1] = a * c - b * s, b * c + a * s
        return out

    h = rms(x, w["attn_norm"])
    nh, dn, dr, dv, rkv = z["heads"], z["dn"], z["dr"], z["dv"], z["rkv"]
    q = (rms(h @ w["w_dq"], w["q_norm"]) @ w["w_uq"]).reshape(T, nh, dn + dr)
    ckr = h @ w["w_dkv"]
    c = rms(ckr[:, :rkv], w["kv_norm"])
    k_r = np.stack([rope(ckr[t, rkv:], t) for t in range(T)])
    kv = (c @ w["w_ukv"]).reshape(T, nh, dn + dv)
    m = 0.1 * 1 * math.log(4.0) + 1
    sigma = (dn + dr) ** -0.5 * m * m
    o = np.zeros((T, nh, dv))
    for t in range(T):
        a_t = 1 + 0.1 * math.log(1 + t // 16)
        for i in range(nh):
            qr = rope(q[t, i, dn:], t)
            sc = np.array([(q[t, i, :dn] @ kv[u, i, :dn] + qr @ k_r[u])
                           * sigma * a_t for u in range(t + 1)])
            p = np.exp(sc - sc.max())
            p /= p.sum()
            o[t, i] = sum(p[u] * kv[u, i, dn:] for u in range(t + 1))
    x1 = x + o.reshape(T, -1) @ w["wo"]
    h2 = rms(x1, w["mlp_norm"])
    s = 1 / (1 + np.exp(-(h2 @ w["router"])))
    y = np.zeros_like(x1)
    for t in range(T):
        top = np.argsort(-(s[t] + w["router_bias"]))[:z["k"]]
        for e in top:
            if 2 <= e < 6:                            # held here
                we = s[t, e] / s[t, top].sum()
                y[t] += we * ((_silu(h2[t] @ w["w_gate_e"][e - 2])
                               * (h2[t] @ w["w_up_e"][e - 2]))
                              @ w["w_down_e"][e - 2])
    y += (_silu(h2 @ w["ws_gate"]) * (h2 @ w["ws_up"])) @ w["ws_down"]
    np.testing.assert_allclose(got, x1 + y, rtol=2e-4, atol=2e-4)


def test_yarn_frequencies_and_scales_at_the_published_values():
    z = ref.sizes(REAL)
    f = ref.yarn_inv_freq(z["dr"], z["theta"], z["factor"], z["orig"],
                          z["beta_fast"], z["beta_slow"])
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    # corr(32) = 64 ln(8192 / 64 pi) / (2 ln 10000) = 12.88 -> lo 12;
    # corr(1) = 24.9 -> hi 25: untouched below pair 12, / 128 from pair 25
    np.testing.assert_allclose(f[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(f[25:], plain[25:] / 128, rtol=1e-6)
    assert plain[18] / 128 < f[18] < plain[18]
    assert abs(ref.softmax_scale(z) - 128 ** -0.5 * 1.4852 ** 2) < 1e-4
    import jax.numpy as jnp

    a = np.asarray(ref.query_scale(jnp.asarray([0, 8191, 8192, 16383, 16384]),
                                   z["qscale_beta"], z["orig"]))
    np.testing.assert_allclose(a, [1, 1, 1 + 0.1 * math.log(2),
                                   1 + 0.1 * math.log(2),
                                   1 + 0.1 * math.log(3)], rtol=1e-6)


def test_work_counts_by_hand_at_the_published_widths():
    # one position attended in every one of the 6 layers: 2 * 32 * (320 +
    # 256) FLOPs over 640 bytes
    assert latent_attention.flops(REAL, 1) == 6 * 2 * 32 * 576
    assert latent_attention.nbytes(REAL, 1) == 6 * 640
    assert latent_attention.flops(REAL, 1) / latent_attention.nbytes(
        REAL, 1) == 57.6
    # one held pair: three products of 4096 x 2048; one active expert:
    # three matrices of 16.8 MB
    assert expert_matmul.flops(REAL, 1) == 6 * 4096 * 2048
    assert expert_matmul.nbytes(REAL, 0, 1) == 3 * 4096 * 2048 * 2
    assert expert_matmul.nbytes(REAL, 1, 0) == 2 * 4096 * 2
    # what a token passes in a layer outside the routed experts: 53.75 M
    attn = (4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144
            + 4096 * 4096)
    assert latent_moe_step.row_params(REAL) == \
        attn + 4096 * 128 + 3 * 4096 * 2048 == 53_739_520
    # one decoded token at context 3000 with one held pair, one logits row
    f = latent_moe_step.serve_flops(REAL, [], 1, 3000, 1, 1)
    assert f == (2 * 53_739_520 * 6 + 6 * 4096 * 2048
                 + 6 * 2 * 32 * 576 * 3000 + 2 * 4096 * 32768)
    # a 300-token prompt in chunks of 256 + 44, no held pair
    f2 = latent_moe_step.serve_flops(REAL, [(0, 256), (256, 44)], 0, 0, 1, 0)
    assert f2 == (2 * 53_739_520 * 6 * 300 + 6 * 2 * 32 * 576 * (300 * 301 // 2)
                  + 2 * 4096 * 32768)


@pytest.fixture(scope="module")
def traced():
    return R.run_cell("tiny-latent-moe-serve", 2**31 + 33, 3.0, True,
                      root=FIX, require_chip=False)


def test_rehearsal_reports_the_expert_metrics_and_is_correct(traced):
    res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"mfu.latent_moe_decode",
                                   "expert_load_max_over_mean.decode",
                                   "kv_blocks_peak.decode"}
    assert res["metrics"]["mfu.latent_moe_decode"]["value"] > 0
    # 4 held experts: the busiest has between the mean and 4 x the mean
    assert 1.0 <= res["metrics"]["expert_load_max_over_mean.decode"][
        "value"] <= 4.0
    assert 0 < res["metrics"]["kv_blocks_peak.decode"]["value"] <= 100
    assert res["compared"]["logit_gap_max"]["value"] <= 1e-3
    json.loads(json.dumps(res))


@pytest.fixture(scope="module")
def untraced():
    traffic = R.load_json(FIX, "bench", "traffic", "tiny-long.json")
    driver = importlib.import_module("benchmarks.drivers.serve_latent_moe")
    ctx = R.Context(workload="tiny-latent-moe-serve", seed=5, seconds=3.0,
                    trace=False, config=TINY, traffic=traffic, chips=1,
                    t_process_start=R.T_PROCESS_START, scratch_dir="/tmp")
    return driver, driver.run(ctx)


def test_the_untraced_loop_runs_the_servers_own_step(untraced):
    driver, run = untraced
    limits = R.load_json(FIX, "bench", "limits", "tiny-latent-moe-serve.json")
    ok, compared = driver.check(run, limits, 5)
    assert ok, compared
    assert "moe_steps" not in run
    assert driver.end_to_end(run)["serve_tok_s"] > 0
    # answers run past the YaRN original length (16): the query scale and
    # the ramped frequencies are in what was compared
    assert max(len(s) for s in run["results"].values()) > 32


@pytest.mark.parametrize("mode", ["int8", "bf16", "drop_1.25", "top3",
                                  "no_shared", "k_unrotated", "no_qscale"])
def test_a_control_or_a_planted_fault_reads_not_correct(untraced, mode):
    """The reference in a lower precision, or with a fault planted — tokens
    dropped over a capacity of 1.25, one expert fewer a token, the shared
    expert left out, the rope key cached unrotated, the query scale left at
    1 — put in the program's place over the same prompts and served tokens:
    each lies past the limit the program passes under."""
    _, run = untraced
    limits = R.load_json(FIX, "bench", "limits", "tiny-latent-moe-serve.json")
    gap = check_served.control_gap(run, dict(limits, sample_requests=8), 5,
                                   mode=mode)
    assert gap > 10 * limits["logit_gap_max"], gap


def test_an_altered_token_and_a_request_cut_short_read_not_correct(untraced):
    driver, run = untraced
    limits = R.load_json(FIX, "bench", "limits", "tiny-latent-moe-serve.json")
    idx = check_served.sample_finished(run, 5, 4)[0]
    seq = list(run["results"][idx])
    bad = dict(run, results=dict(run["results"]))
    bad["results"][idx] = seq[:-3] + [(seq[-3] + 1) % 512 or 1] + seq[-2:]
    ok, compared = driver.check(bad, limits, 5)
    assert not ok and compared["logit_gap_max"]["value"] > 0.001
    bad["results"][idx] = seq[:-2]
    ok, compared = driver.check(bad, limits, 5)
    assert not ok and compared["wrong_shape"]["value"] == 1


def test_readers_return_nothing_for_a_run_without_the_counters():
    run = {"steps": [{"t0": 0.0, "t1": 0.1, "decode_rows": 1, "decode_ctx": 5,
                      "tokens": 1, "prefill_chunks": []}],
           "seconds": 1.0, "traced_window": (0.0, 1.0), "requests": [],
           "config": TINY}
    zeros = {"pairs_held": 0, "pairs_absent": 0, "experts_active": 0,
             "load_max": 0}
    for name in ("mfu.latent_moe_decode", "expert_matmul_roofline",
                 "expert_load_max_over_mean.decode"):
        assert R.load_reader(name).read(run) is None
        assert R.load_reader(name).read(dict(run, moe_steps=[zeros])) is None
    dense = R.load_json(ROOT, "benchmarks", "configs",
                        "mistral-7b-v0.3-l16.json")
    assert R.load_reader("latent_attention_roofline").read(
        dict(run, config=dense)) is None


def test_roofline_readers_match_the_kernels_by_structure():
    """Trace events named as the v5e names them (whole HLO text): the
    latent attention custom-call by its operands, the grouped products by
    the expert stacks they read; the K/V-pool attention and a dense matmul
    match neither."""
    from benchmarks import trace_reduce as tr

    E = tr.Event
    latent = E(("%custom-call.7 = bf16[128,32,256]{2,1,0} custom-call(s32[128,"
                "784]{1,0} %p.1, s32[128]{0} %p.2, bf16[128,32,384]{2,1,0} "
                "%f.3, bf16[40001,16,384]{2,1,0} %p.4), custom_call_target="
                "\"tpu_custom_call\"", 0.0, 1e-3))
    gqa = E(("%custom-call.9 = bf16[64,8,4,128]{3,2,1,0} custom-call(s32[64,"
             "264]{1,0} %p.1, s32[64]{0} %p.2, bf16[64,8,4,128] %f), "
             "custom_call_target=\"tpu_custom_call\"", 0.0, 1e-3))
    gmm = E(("%ragged-dot.3 = f32[512,2048]{1,0} custom-call(bf16[512,4096]"
             "{1,0} %g, bf16[32,4096,2048]{2,1,0} %p.9, s32[32]{0} %c)",
             0.0, 2e-3))
    dense = E(("%fusion.1 = bf16[128,4096]{1,0} fusion(bf16[128,4096] %a, "
               "bf16[4096,4096] %w)", 0.0, 1e-3))
    ops = [latent, gqa, gmm, dense]
    la = R.load_reader("latent_attention_roofline")
    em = R.load_reader("expert_matmul_roofline")
    assert tr.match(ops, la.PATTERNS) == [latent]
    assert tr.match(ops, em.patterns(REAL)) == [gmm]
    step = {"t0": 0.0, "t1": 0.1, "decode_rows": 128,
            "decode_ctx": 128 * 3000, "tokens": 128, "prefill_chunks": []}
    run = {"steps": [step], "seconds": 1.0, "traced_window": (0.0, 1.0),
           "config": REAL, "device_ops": ops,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "moe_steps": [{"pairs_held": 6 * 128, "pairs_absent": 6 * 384,
                          "experts_active": 6 * 32, "load_max": 6 * 9}]}
    share = la.read(run)
    # 384k positions x 6 layers x 640 B at 819 GB/s = 1.8 ms over 1 ms
    assert abs(share - 100 * 384000 * 6 * 640 / 819e9 / 1e-3) < 1e-6
    assert run["roofline_bounds"]["latent_attend"] == "memory"
    share = em.read(run)
    want = (192 * 3 * 4096 * 2048 * 2 + 768 * 2 * 4096 * 2) / 819e9 / 2e-3
    assert abs(share - 100 * want) < 1e-6
    assert run["roofline_bounds"]["expert_gmm"] == "memory"
    assert R.load_reader("expert_load_max_over_mean.decode").read(
        dict(run, steps=[dict(step, t1=0.5)])) == 54 / (768 / 32)


def test_repo_manifest_has_the_cell_and_its_files():
    m = R.load_manifest(ROOT)
    cell = R.cell_of(m, CELL)
    assert cell["chips"] == 1 and len(m["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    _, _, cfg, traffic, limits, _ = R.load_cell(ROOT, CELL)
    assert cfg["driver"] == "serve_latent_moe"
    assert cfg["reference"] == "latent_moe_lm"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 36,
                                "n_routed_experts": 128, "vocab_size": 131072}
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 32]
    assert traffic["ramp"] == {"seconds": 20.0, "burst": 128}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.6, "min": 256, "max": 4096}
    assert traffic["output"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 0.6, "min": 768, "max": 8192}
    assert cfg["served"]["max_len"] == \
        traffic["prompt"]["max"] + traffic["output"]["max"]
    assert limits["pad_to"] <= cfg["served"]["max_len"]
    for name in ("mfu.latent_moe_decode", "latent_attention_roofline",
                 "expert_matmul_roofline",
                 "expert_load_max_over_mean.decode"):
        met = next(p for p in m["per_layer"] if p["name"] == name)
        assert met["workloads"] == [CELL] and met["moves"] == "serve_tok_s"
    for name in ("serve_tok_s",):
        assert CELL in next(e for e in m["end_to_end"]
                            if e["name"] == name)["workloads"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row, under the same key, but the three
    keys in ``reduced`` (the catalog lives outside the repo: its numbers are
    written out here)."""
    published = {
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 12288, "kv_lora_rank": 256,
        "max_position_embeddings": 1048576, "moe_intermediate_size": 2048,
        "n_group": 1, "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 32,
        "q_lora_rank": 1024, "qk_head_dim": 128, "qk_nope_head_dim": 64,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "routed_scaling_factor": 1, "topk_group": 1, "v_head_dim": 128}
    assert {k: REAL[k] for k in published} == published
    assert REAL["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    assert (REAL["num_hidden_layers"], REAL["n_routed_experts"],
            REAL["vocab_size"]) == (6, 32, 32768)
    # the guide's floors: >= 4 layers, >= 8 routed experts, >= 1/8 vocabulary
    assert REAL["vocab_size"] * 8 >= REAL["published"]["vocab_size"]
    # the share's weights: 10.85 GB in bfloat16
    n = sum(int(np.prod(s)) for s, _ in latent_moe_shapes(REAL).values())
    assert abs(n * 2 / 1e9 - 10.85) < 0.02


def test_router_bias_is_drawn_at_its_own_width():
    """Leaves of kind ``bias`` take ``bias_std`` (the configuration's
    ``router_bias_range``), every matrix keeps ``std``; without it the bias
    is as wide as the matrices. The real configuration states a bias a
    tenth as wide as its matrices: at the matrices' width each seed's bias
    chose that seed's popular experts, and the seed changed the work
    (PERF.md section 6, PR 33)."""
    import jax.numpy as jnp

    shapes = latent_moe_shapes(dict(TINY, n_routed_experts=4096,
                                    experts_held=[0, 2],
                                    published={"n_routed_experts": 4096}))
    assert shapes["layers.0.router_bias"] == ((4096,), "bias")
    wide = make_weights(shapes, 5, jnp.float32, std=0.02)
    narrow = make_weights(shapes, 5, jnp.float32, std=0.02, bias_std=0.002)
    for w, want in ((wide, 0.02), (narrow, 0.002)):
        assert np.std(np.asarray(w["layers.0.router_bias"])) == \
            pytest.approx(want, rel=0.05)
        assert np.std(np.asarray(w["layers.0.router"])) == \
            pytest.approx(0.02, rel=0.05)
    np.testing.assert_array_equal(np.asarray(wide["embed"]),
                                  np.asarray(narrow["embed"]))
    assert REAL["router_bias_range"] * 10 == \
        pytest.approx(REAL["initializer_range"])
