"""The plain reference against a hand-written case: the same equations in
numpy float64 with explicit loops over heads and positions."""
import numpy as np
import pytest

from benchmarks.reference import decoder_lm
from benchmarks.weights import decoder_shapes, make_weights

CFG = {"hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "vocab_size": 50, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "tie_word_embeddings": False}


def numpy_forward(w, cfg, tokens):
    H, nh, nkv, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    T = len(tokens)
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True)
                           + cfg["rms_norm_eps"]) * g

    def rope(v, t):                       # v [D] at position t
        out = v.copy()
        for i in range(D // 2):
            a = t / cfg["rope_theta"] ** (2 * i / D)
            out[i] = v[i] * np.cos(a) - v[i + D // 2] * np.sin(a)
            out[i + D // 2] = v[i + D // 2] * np.cos(a) + v[i] * np.sin(a)
        return out

    x = w["embed"][tokens]
    for layer in range(cfg["num_hidden_layers"]):
        p = f"layers.{layer}."
        h = norm(x, w[p + "attn_norm"])
        q = (h @ w[p + "wq"]).reshape(T, nh, D)
        k = (h @ w[p + "wk"]).reshape(T, nkv, D)
        v = (h @ w[p + "wv"]).reshape(T, nkv, D)
        att = np.zeros((T, nh, D))
        for head in range(nh):
            g = head // (nh // nkv)
            for t in range(T):
                qt = rope(q[t, head], t)
                sc = np.array([qt @ rope(k[s, g], s) / np.sqrt(D)
                               for s in range(t + 1)])
                pr = np.exp(sc - sc.max())
                pr /= pr.sum()
                att[t, head] = sum(pr[s] * v[s, g] for s in range(t + 1))
        x = x + att.reshape(T, nh * D) @ w[p + "wo"]
        h = norm(x, w[p + "mlp_norm"])
        gate = h @ w[p + "w_gate"]
        x = x + (gate / (1 + np.exp(-gate)) * (h @ w[p + "w_up"])) \
            @ w[p + "w_down"]
    return norm(x, w["final_norm"]) @ w["lm_head"]


@pytest.fixture(scope="module")
def weights():
    import jax.numpy as jnp

    return make_weights(decoder_shapes(CFG), 2**31 + 3, jnp.float32, std=0.3)


def test_reference_matches_the_hand_written_case(weights):
    tokens = [3, 17, 4, 44, 9, 21, 30]
    want = numpy_forward(weights, CFG, tokens)
    got = decoder_lm.logits_at(weights, CFG, tokens, list(range(7)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # right padding cannot reach back
    padded = decoder_lm.logits_at(weights, CFG, tokens, list(range(7)),
                                  pad_to=16)
    np.testing.assert_allclose(padded, got, rtol=2e-4, atol=2e-5)


def test_served_gaps_are_zero_for_the_argmax_and_positive_otherwise(weights):
    prompt = [3, 17, 4]
    lg = decoder_lm.logits_at(weights, CFG, prompt, [2])
    best = int(lg[0].argmax())
    gaps, _ = decoder_lm.served_gaps(weights, CFG, prompt, [best])
    assert gaps[0] == 0.0
    other = (best + 1) % CFG["vocab_size"]
    gaps, _ = decoder_lm.served_gaps(weights, CFG, prompt, [other])
    assert gaps[0] > 0.0


@pytest.mark.parametrize("mode", ["bf16", "int8", "fp8"])
def test_lower_precisions_differ_from_the_reference(weights, mode):
    tokens = [3, 17, 4, 44, 9]
    ref = decoder_lm.logits_at(weights, CFG, tokens, [4])
    low = decoder_lm.logits_at(weights, CFG, tokens, [4], mode=mode)
    err = np.abs(ref - low).max()
    assert 1e-5 < err < 3.0


def test_weights_same_seed_same_numbers():
    import jax.numpy as jnp

    a = make_weights(decoder_shapes(CFG), 2**31 + 3, jnp.float32)
    b = make_weights(decoder_shapes(CFG), 2**31 + 3, jnp.float32)
    c = make_weights(decoder_shapes(CFG), 4, jnp.float32)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["embed"], c["embed"])
    assert float(np.asarray(a["final_norm"]).min()) == 1.0
