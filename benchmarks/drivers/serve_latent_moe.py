"""Driver: a served decoder with latent attention and one chip's share of a
many-expert layer (Mistral-Small-4's language model) through
``GenerationServer`` (paged cache), open loop, one process.

The loop, the end-to-end metrics and the failure count are ``serve_paged``'s,
imported; this file brings the model (its own class and weight table), a
comparison of the served tokens that reads a high percentile of the gap
beside its maximum (:func:`check`: a router that chooses among many experts
makes the maximum a reading of its nearest tie, not of the arithmetic), and
one read of the program:

- **the expert layer's counters, per step, in the traced run only**
  (:func:`_record_steps`): ``serving_moe_pairs{held}`` ((token, expert)
  pairs of real rows, by whether the expert lives here),
  ``serving_moe_experts_active`` and ``serving_moe_load_max``. The program
  adds them where it reads a decode trip's tokens, so a step's difference is
  the work of the trip it harvested. They ride beside ``serve_paged``'s step
  records as ``run["moe_steps"]`` (one entry per step, same order); on a
  program without the counters every entry is zero and the readers return
  None. The untraced loop, which the end-to-end metric is judged from, runs
  ``srv.step`` as it is.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import stats
from ..check_served import sample_finished, wrong_shape
from ..weights_latent_moe import latent_moe_shapes, make_weights, n_params
from .serve_paged import (attempted_failed, build_server,  # noqa: F401
                          end_to_end, measure)
from .serve_paged import describe as _describe

# the benchmark's leaf names -> this program's parameter names
_LAYER_NAMES = {
    "attn_norm": "input_layernorm.weight",
    "w_dq": "self_attn.q_a_proj.weight",
    "q_norm": "self_attn.q_a_layernorm.weight",
    "w_uq": "self_attn.q_b_proj.weight",
    "w_dkv": "self_attn.kv_a_proj.weight",
    "kv_norm": "self_attn.kv_a_layernorm.weight",
    "w_ukv": "self_attn.kv_b_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "router": "mlp.router.weight", "router_bias": "mlp.router_bias",
    "w_gate_e": "mlp.experts_gate", "w_up_e": "mlp.experts_up",
    "w_down_e": "mlp.experts_down",
    "ws_gate": "mlp.shared.gate_proj.weight",
    "ws_up": "mlp.shared.up_proj.weight",
    "ws_down": "mlp.shared.down_proj.weight"}
_TOP_NAMES = {"embed": "model.embed_tokens.weight",
              "final_norm": "model.norm.weight", "lm_head": "lm_head.weight"}


def program_name(leaf: str) -> str:
    if leaf in _TOP_NAMES:
        return _TOP_NAMES[leaf]
    _, i, rest = leaf.split(".", 2)
    return f"model.layers.{i}.{_LAYER_NAMES[rest]}"


def model_config(cfg: dict):
    """The program's configuration of the file's sizes: the router at the
    PUBLISHED width, the experts held here, and a position limit no larger
    than the server needs (the published 1,048,576 is a promise about the
    frequencies, which come from ``rope_parameters``, not a table)."""
    from paddle_tpu.models.mistral4 import Mistral4Config

    rp = cfg["rope_parameters"]
    pub = cfg.get("published", {})
    return Mistral4Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=pub.get("n_routed_experts",
                                 cfg["n_routed_experts"]),
        experts_held=tuple(cfg.get("experts_held",
                                   (0, cfg["n_routed_experts"]))),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=rp["rope_theta"], rope_factor=rp["factor"],
        rope_original_max_position_embeddings=rp[
            "original_max_position_embeddings"],
        rope_beta_fast=rp["beta_fast"], rope_beta_slow=rp["beta_slow"],
        rope_mscale_all_dim=rp["mscale_all_dim"],
        llama_4_scaling_beta=rp["llama_4_scaling_beta"],
        initializer_range=cfg.get("initializer_range", 0.02),
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int):
    """The program's model class at the configuration's sizes, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models.mistral4 import Mistral4ForCausalLM

    model = Mistral4ForCausalLM(model_config(cfg))
    model.eval()
    shapes = latent_moe_shapes(cfg)
    own = dict(model.named_parameters())
    donate = {leaf: own[program_name(leaf)].value for leaf in shapes}
    weights = make_weights(shapes, seed, jnp.dtype(cfg["torch_dtype"]),
                           std=cfg.get("initializer_range", 0.02),
                           donate=donate,
                           bias_std=cfg.get("router_bias_range"))
    missing, unexpected = model.set_state_dict(
        {program_name(leaf): Tensor(w) for leaf, w in weights.items()})
    if missing or unexpected:
        raise RuntimeError(f"weights do not cover the model: missing "
                           f"{missing}, unexpected {unexpected}")
    return model, weights


def _record_steps(srv, out: list):
    """Wrap ``srv.step`` so that every step leaves, in ``out``, the running
    totals of the expert layer's counters (differences are taken after the
    loop)."""
    reg = srv.telemetry.registry
    pairs = reg.counter("serving_moe_pairs")
    active = reg.counter("serving_moe_experts_active")
    load_max = reg.counter("serving_moe_load_max")
    step = srv.step

    def stepped():
        remaining = step()
        out.append((pairs.value(held="1"), pairs.value(held="0"),
                    active.total(), load_max.total()))
        return remaining

    srv.step = stepped


def _moe_steps(totals: list, n_steps: int) -> list:
    """``_record_steps``'s totals as one record per step of the loop (the
    warm-up's steps came first; the loop's are the last ``n_steps``)."""
    at = len(totals) - n_steps
    out = []
    for i in range(at, len(totals)):
        now, was = totals[i], totals[i - 1] if i else (0,) * 4
        out.append(dict(zip(
            ("pairs_held", "pairs_absent", "experts_active", "load_max"),
            (int(a - b) for a, b in zip(now, was)))))
    return out


def _memory(tag: str) -> str:
    import jax

    m = jax.local_devices()[0].memory_stats() or {}
    return (f"{tag}: {m.get('bytes_in_use', 0) / 1e9:.2f} GB in use, peak "
            f"{m.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def run(ctx) -> dict:
    """Set up, warm up, measure ``ctx.seconds`` seconds, drain, and return
    the run record that the metric readers and the check consume."""
    import jax
    import paddle_tpu  # noqa: F401  (pins CPU numerics under tests)

    cfg = ctx.config
    t_build = time.monotonic()
    model, weights = build_model(cfg, ctx.seed)
    ctx.log(_memory("model built"))
    srv = build_server(model, cfg, telemetry=ctx.trace)
    ctx.log(_memory("server built"))
    ctx.log(f"model {n_params(latent_moe_shapes(cfg)) / 1e9:.3f}B params + "
            f"server built in {time.monotonic() - t_build:.1f}s; latent pool "
            f"{srv.alloc.num_blocks} blocks of {srv.block_size}, "
            f"{srv.alloc.bytes_per_block} bytes each")
    totals: list = []
    if ctx.trace:
        _record_steps(srv, totals)
    run_rec = measure(ctx, srv)
    if ctx.trace:
        run_rec["moe_steps"] = _moe_steps(totals, len(run_rec["steps"]))
    run_rec["weights"] = weights
    ctx.log(_memory("loop ended"))
    del srv, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return run_rec


def describe(run: dict) -> str:
    """``serve_paged``'s line, and the mean live context of a decode row in
    the window (the ramp fills every slot at once, so contexts grow through
    it)."""
    st = [s for s in run["steps"]
          if 0.0 <= s["t1"] < run["seconds"] and s["decode_rows"]]
    rows = sum(s["decode_rows"] for s in st)
    ctx = sum(s["decode_ctx"] for s in st)
    grow = ("" if not st else
            f" ({st[0]['decode_ctx'] / st[0]['decode_rows']:.0f} at its "
            f"first tick, {st[-1]['decode_ctx'] / st[-1]['decode_rows']:.0f} "
            f"at its last)")
    return (_describe(run) + f"; mean live context of a decode row "
            f"{ctx / rows if rows else 0:.0f}{grow}")


def served_gap_stats(run: dict, limits: dict, seed: int, mode: str = None,
                     log=None) -> dict:
    """Over a seeded sample of the finished requests (the longest among
    them), teacher-forced once through the float32 reference: the gap by
    which a token's reference logit lies below the reference's best at its
    position — of the tokens the program SERVED, or with ``mode`` of the
    tokens the reference in that arithmetic (or with that planted fault)
    puts first: the CONTROL put in the program's place. Returns the widest
    gap, nearest-rank percentiles of it, and the counts."""
    import importlib

    ref = importlib.import_module(
        f"benchmarks.reference.{run['config']['reference']}")
    cfg, weights = run["config"], run["weights"]
    pad_to = int(limits["pad_to"])
    picks = sample_finished(run, seed, int(limits["sample_requests"]))
    gaps = []
    for idx in picks:
        seq, p = run["results"][idx], run["prompts"][idx]
        served = seq[len(p):]
        g, lg = ref.served_gaps(weights, cfg, p, served, pad_to=pad_to)
        if mode is not None:
            _, low = ref.served_gaps(weights, cfg, p, served, pad_to=pad_to,
                                     mode=mode)
            g = lg.max(-1) - lg[np.arange(len(served)), low.argmax(-1)]
        gaps.append(np.where(np.isfinite(g), g, np.inf))
    g = np.concatenate(gaps) if gaps else np.asarray([np.inf])
    out = {"max": float(g.max()), "tokens": int(len(g)) if gaps else 0,
           "requests": len(picks), "argmax_share": float((g == 0).mean())}
    for q in (50, 90, 95, 97.5, 99, 99.9):
        out[f"p{q:g}"] = float(stats.percentile(g.tolist(), q))
    if log is not None:
        log(f"check: {len(picks)} finished requests, {out['tokens']} tokens "
            f"against the float32 reference: {out['argmax_share']:.1%} are "
            f"its argmax; gap p50 {out['p50']:.3f}, p90 {out['p90']:.3f}, "
            f"p95 {out['p95']:.3f}, p97.5 {out['p97.5']:.3f}, p99 "
            f"{out['p99']:.3f}, p99.9 {out['p99.9']:.3f}, max "
            f"{out['max']:.3f}")
    return out


def check(run: dict, limits: dict, seed: int, log=print):
    """The comparison that decides ``correct``: :func:`served_gap_stats` of
    the served tokens, the widest gap and the percentile the limits file
    names (``logit_gap_p95``) each under its own limit (``limits/<cell>.json`` says which readings each came
    from), that every finished request kept its prompt and got the number
    of tokens it asked for, and that enough tokens were compared."""
    got = served_gap_stats(run, limits, seed, log=log)
    compared = {
        "logit_gap_max": {"value": got["max"],
                          "limit": float(limits["logit_gap_max"])},
        "wrong_shape": {"value": float(wrong_shape(run)), "limit": 0.0},
        "served_tokens_checked": {"value": float(got["tokens"]),
                                  "limit": float(limits["min_tokens"]),
                                  "at_least": True}}
    for name, limit in limits.items():      # logit_gap_p95 and the like
        if name.startswith("logit_gap_p"):
            compared[name] = {"value": got[name[len("logit_gap_"):]],
                              "limit": float(limit)}
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return ok, compared
