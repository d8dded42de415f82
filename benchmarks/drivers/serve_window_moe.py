"""Driver: a served decoder of window and full attention layers and, after a
few dense layers, one chip's share of a many-expert layer (Trinity) through
``GenerationServer`` (paged cache), open loop, one process.

The loop, the end-to-end metrics and the failure count are ``serve_paged``'s,
imported, and the expert layer's counters per step (``run["moe_steps"]``) are
``serve_latent_moe``'s; this file brings the model (its own class and weight
table), one more record and a comparison of the served tokens:

- **attention's work by kind, in the traced run only**: the engine's
  ``serving_decode_ctx_window`` per step (``serve_hybrid``'s record,
  imported, kept as ``run["hybrid_steps"]`` as for the other drivers with
  window or state layers) beside ``serve_paged``'s step records, which hold
  the full contexts and the prompt chunks; ``window_moe_readers.py`` makes
  the positions attended by kind from the two. The untraced loop, which the
  end-to-end metric is judged from, runs ``srv.step`` as it is.
- **the comparison** (:func:`served_gap_stats`): a seeded sample of the
  longest finished requests, teacher-forced once through the float32
  reference, the gap by which each served token's reference logit lies below
  the reference's best — ONLY at positions from the limits file's
  ``compare_from`` (4,096) on, where every window layer's ring has wrapped
  at least once — read at a percentile beside the maximum, because a
  router that picks 8 of 128 experts has a near-tie in every request. The
  readings tool puts each control through the same comparison
  (:func:`gap_readings`, :func:`compare`).
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from .. import stats
from ..check_served import sample_finished, wrong_shape
from ..weights_window_moe import make_weights, n_params, window_moe_shapes
from . import serve_hybrid, serve_latent_moe
from .serve_latent_moe import describe as _describe
from .serve_paged import (attempted_failed, build_server,  # noqa: F401
                          end_to_end, measure)

# the benchmark's leaf names -> this program's parameter names
_LAYER_NAMES = {
    "attn_norm": "input_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wg": "self_attn.gate_proj.weight",
    "wo": "self_attn.o_proj.weight", "q_norm": "self_attn.q_norm.weight",
    "k_norm": "self_attn.k_norm.weight",
    "post_attn_norm": "post_attention_layernorm.weight",
    "pre_mlp_norm": "pre_mlp_layernorm.weight",
    "post_mlp_norm": "post_mlp_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
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
    """The program's configuration of the file's sizes, every published key
    under its published name: the router at the PUBLISHED width, the experts
    held here, and a position limit no larger than the server needs (the
    published 131,072 is a promise about lengths the model was trained to;
    nothing here is a table of that length)."""
    from paddle_tpu.models.afmoe import AfmoeConfig

    pub = cfg.get("published", {})
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
            "layer_types", "global_attn_every_n_layers", "sliding_window",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rope_theta", "num_experts_per_tok", "num_shared_experts",
            "score_func", "route_norm", "route_scale", "n_group",
            "topk_group", "mup_enabled", "rms_norm_eps",
            "max_position_embeddings", "tie_word_embeddings")
    return AfmoeConfig(
        **{k: cfg[k] for k in keys},
        num_experts=pub.get("num_experts", cfg["num_experts"]),
        experts_held=tuple(cfg.get("experts_held", (0, cfg["num_experts"]))),
        initializer_range=cfg.get("initializer_range", 0.02),
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int):
    """The program's model class at the configuration's sizes, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models.afmoe import AfmoeForCausalLM

    model = AfmoeForCausalLM(model_config(cfg))
    model.eval()
    shapes = window_moe_shapes(cfg)
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


def run(ctx) -> dict:
    """Set up, warm up, measure ``ctx.seconds`` seconds, drain, and return
    the run record that the metric readers and the check consume."""
    import jax
    import paddle_tpu  # noqa: F401  (pins CPU numerics under tests)

    cfg = ctx.config
    t_build = time.monotonic()
    model, weights = build_model(cfg, ctx.seed)
    ctx.log(serve_latent_moe._memory("model built"))
    srv = build_server(model, cfg, telemetry=ctx.trace)
    ctx.log(serve_latent_moe._memory("server built"))
    ctx.log(f"model {n_params(window_moe_shapes(cfg)) / 1e9:.3f}B params + "
            f"server built in {time.monotonic() - t_build:.1f}s; full pool "
            f"{srv.alloc.num_blocks} blocks of {srv.block_size}, "
            f"{srv.alloc.bytes_per_block} bytes each; per slot "
            f"{srv.cache_spec.slot_bytes(srv.block_size)} bytes")
    moe_totals, cache_totals = [], []
    if ctx.trace:
        serve_latent_moe._record_steps(srv, moe_totals)
        serve_hybrid._record_steps(srv, cache_totals)
    run_rec = measure(ctx, srv)
    if ctx.trace:
        n = len(run_rec["steps"])
        run_rec["moe_steps"] = serve_latent_moe._moe_steps(moe_totals, n)
        run_rec["hybrid_steps"] = serve_hybrid._hybrid_steps(
            cache_totals, run_rec["steps"],
            dict(srv.cache_spec.slot_bytes(srv.block_size),
                 block=srv.alloc.bytes_per_block))
    run_rec["weights"] = weights
    ctx.log(serve_latent_moe._memory("loop ended"))
    del srv, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return run_rec


def describe(run: dict) -> str:
    """``serve_latent_moe``'s line and, in the traced run, what the router
    did in the window: the held experts that got a row, of the held experts,
    an expert layer a program call, and the busiest held expert's rows over
    the mean (what a seed must not change)."""
    line = _describe(run)
    pairs = [(s, m) for s, m in zip(run["steps"], run.get("moe_steps") or [])
             if 0.0 <= s["t1"] < run["seconds"]]
    moe = [m for _, m in pairs if m["pairs_held"]]
    if moe:
        from ..reference.window_moe_lm import sizes

        z = sizes(run["config"])
        held = z["held"][1] - z["held"][0]
        calls = (z["L"] - z["dense"]) * sum(
            len(s["prefill_chunks"]) + bool(s["decode_rows"])
            for s, _ in pairs)
        n = sum(m["pairs_held"] for m in moe)
        line += (f"; held experts active an expert layer a program call "
                 f"{sum(m['experts_active'] for m in moe) / calls:.2f} of "
                 f"{held}; load max over mean "
                 f"{sum(m['load_max'] for m in moe) / (n / held):.3f}; "
                 f"held share of the pairs "
                 f"{n / (n + sum(m['pairs_absent'] for m in moe)):.3f}")
    return line


def gap_readings(run: dict, limits: dict, seed: int,
                 modes=(None,)) -> Dict[str, np.ndarray]:
    """Over a seeded sample of the finished requests (the longest among
    them), teacher-forced through the float32 reference (once a request,
    whatever the modes) at positions from ``limits["compare_from"]`` on:
    for each of ``modes`` the gaps by which a token's reference logit lies
    below the reference's best at its position — for ``None`` of the tokens
    the program SERVED, for a mode of the tokens the reference in that
    arithmetic (or with that planted fault) puts first: the CONTROL put in
    the program's place. A non-finite gap reads infinite."""
    from ..reference import window_moe_lm as ref

    cfg, weights = run["config"], run["weights"]
    kw = dict(pad_to=int(limits["pad_to"]),
              from_pos=int(limits["compare_from"]))
    picks = sample_finished(run, seed, int(limits["sample_requests"]))
    gaps = {m: [] for m in modes}
    for idx in picks:
        seq, p = run["results"][idx], run["prompts"][idx]
        g, lg, _ = ref.served_gaps(weights, cfg, p, seq[len(p):], **kw)
        for m in modes:
            if m is not None and len(g):
                _, low, _ = ref.served_gaps(weights, cfg, p, seq[len(p):],
                                            mode=m, **kw)
                gaps[m].append(lg.max(-1) - lg[np.arange(len(g)),
                                                 low.argmax(-1)])
            else:
                gaps[m].append(g)
    return {m: np.where(np.isfinite(c), c, np.inf) for m, c in
            ((m, np.concatenate(c) if c else np.zeros((0,)))
             for m, c in gaps.items())}


def gap_summary(g: np.ndarray) -> Dict[str, float]:
    """The widest gap, nearest-rank percentiles of the gaps, the share that
    is the reference's argmax, and the count (no gap reads infinite)."""
    n = int(len(g))
    g = g if n else np.asarray([np.inf])
    out = {"max": float(g.max()), "tokens": n,
           "argmax_share": float((g == 0).mean())}
    for q in (50, 90, 95, 97.5, 99, 99.9):
        out[f"p{q:g}"] = float(stats.percentile(g.tolist(), q))
    return out


def served_gap_stats(run: dict, limits: dict, seed: int, mode: str = None,
                     log=None) -> Dict[str, float]:
    """:func:`gap_summary` of :func:`gap_readings` of the served tokens (or
    of one control's), with the number of requests read."""
    out = gap_summary(gap_readings(run, limits, seed, (mode,))[mode])
    out["requests"] = len(sample_finished(run, seed,
                                          int(limits["sample_requests"])))
    if log is not None:
        log(f"check: {out['requests']} finished requests, {out['tokens']} "
            f"tokens at positions >= {int(limits['compare_from'])} against "
            f"the float32 reference: {out['argmax_share']:.1%} are its "
            f"argmax; gap p50 {out['p50']:.3f}, p90 {out['p90']:.3f}, p95 "
            f"{out['p95']:.3f}, p99 {out['p99']:.3f}, max {out['max']:.3f}")
    return out


def compare(got: Dict[str, float], run: dict, limits: dict):
    """Readings :func:`gap_summary` gives, each under its own limit: the
    widest gap, the percentile the limits file names (``logit_gap_p90``;
    ``limits/<cell>.json`` says which readings each limit came from), that
    every finished request kept its prompt and got the number of tokens it
    asked for, and that enough tokens were compared. Returns ``(ok,
    compared)``."""
    compared = {
        "logit_gap_max": {"value": got["max"],
                          "limit": float(limits["logit_gap_max"])},
        "wrong_shape": {"value": float(wrong_shape(run)), "limit": 0.0},
        "served_tokens_checked": {"value": float(got["tokens"]),
                                  "limit": float(limits["min_tokens"]),
                                  "at_least": True}}
    for name, limit in limits.items():      # logit_gap_p90 and the like
        if name.startswith("logit_gap_p"):
            compared[name] = {"value": got[name[len("logit_gap_"):]],
                              "limit": float(limit)}
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return ok, compared


def check(run: dict, limits: dict, seed: int, log=print):
    """The comparison that decides ``correct``: :func:`compare` of the
    :func:`served_gap_stats` of the served tokens."""
    return compare(served_gap_stats(run, limits, seed, log=log), run, limits)
