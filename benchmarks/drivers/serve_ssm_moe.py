"""Driver: a served decoder of Mamba-2 layers, an attention layer every so
often and one chip's share of a many-expert layer in every layer (Granite
4.0-H) through ``GenerationServer`` (paged cache), open loop, one process.

The loop, the end-to-end metrics and the failure count are ``serve_paged``'s,
imported; this file brings the model (its own class and weight table) and a
comparison that reads both of what this family can get wrong in silence:

- **the served tokens** as ``serve_latent_moe`` compares them
  (``served_gap_stats``, imported: it finds the reference by the
  configuration's ``reference``): a high percentile of the gap beside its
  maximum, because a router that chooses ten of 72 experts has a near-tie at
  some token of every request;
- **the recurrent state, once, after the loop** (``serve_hybrid.probe_state``,
  imported): the float32 SSM state that a few decoding slots hold when the
  loop ends, through the executor's ``save_slot``, against the reference's
  over the same tokens on the heads that remember longest
  (:func:`state_drifts`) — a greedy token cannot tell a bfloat16 state from a
  float32 one (PERF.md section 2).

In the traced run only, two records ride beside ``serve_paged``'s step
records, each kept by the driver that first needed it and imported here: the
expert layer's counters (``run["moe_steps"]``, ``serve_latent_moe``) and the
by-kind cache bytes and decode rows (``run["hybrid_steps"]``,
``serve_hybrid``). The untraced loop, which the end-to-end metric is judged
from, runs ``srv.step`` as it is.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

from ..weights_ssm_moe import make_weights, n_params, ssm_moe_shapes
from . import serve_hybrid, serve_latent_moe
from .serve_latent_moe import describe as _describe
from .serve_latent_moe import served_gap_stats  # noqa: F401  (the readings tool's)
from .serve_paged import (attempted_failed, build_server,  # noqa: F401
                          end_to_end, measure)

# the benchmark's leaf names -> this program's parameter names
_LAYER_NAMES = {
    "mix_norm": "input_layernorm.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "router": "mlp.router.weight",
    "w_gate_e": "mlp.experts_gate", "w_up_e": "mlp.experts_up",
    "w_down_e": "mlp.experts_down",
    "ws_gate": "mlp.shared.gate_proj.weight",
    "ws_up": "mlp.shared.up_proj.weight",
    "ws_down": "mlp.shared.down_proj.weight",
    "in_proj": "mixer.in_proj.weight", "conv_w": "mixer.conv_weight",
    "conv_b": "mixer.conv_bias", "dt_bias": "mixer.dt_bias",
    "A_log": "mixer.A_log", "D": "mixer.D", "ssm_norm": "mixer.norm_weight",
    "out_proj": "mixer.out_proj.weight",
    "wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
    "wv": "mixer.v_proj.weight", "wo": "mixer.o_proj.weight"}
_TOP_NAMES = {"embed": "model.embed_tokens.weight",
              "final_norm": "model.norm.weight"}


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
    from paddle_tpu.models.granitemoehybrid import GraniteMoeHybridConfig

    pub = cfg.get("published", {})
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "shared_intermediate_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_n_groups", "mamba_chunk_size", "mamba_conv_bias",
            "mamba_proj_bias", "attention_bias", "position_embedding_type",
            "num_experts_per_tok", "rms_norm_eps", "max_position_embeddings",
            "tie_word_embeddings")
    return GraniteMoeHybridConfig(
        **{k: cfg[k] for k in keys},
        num_local_experts=pub.get("num_local_experts",
                                  cfg["num_local_experts"]),
        experts_held=tuple(cfg.get("experts_held",
                                   (0, cfg["num_local_experts"]))),
        initializer_range=cfg.get("initializer_range", 0.02),
        dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int):
    """The program's model class at the configuration's sizes, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models.granitemoehybrid import GraniteMoeHybridForCausalLM

    model = GraniteMoeHybridForCausalLM(model_config(cfg))
    model.eval()
    shapes = ssm_moe_shapes(cfg)
    own = dict(model.named_parameters())
    donate = {leaf: own[program_name(leaf)].value for leaf in shapes}
    std = cfg.get("initializer_range", 0.02)
    weights = make_weights(shapes, seed, jnp.dtype(cfg["torch_dtype"]),
                           std=std, donate=donate,
                           embed_std=std / cfg["embedding_multiplier"])
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
    spec = srv.cache_spec
    per_slot = spec.slot_bytes(srv.block_size)
    ctx.log(f"model {n_params(ssm_moe_shapes(cfg)) / 1e9:.3f}B params + "
            f"server built in {time.monotonic() - t_build:.1f}s; attention "
            f"pool {srv.alloc.num_blocks} blocks of {srv.block_size}, "
            f"{srv.alloc.bytes_per_block} bytes each; per slot {per_slot} "
            f"bytes")
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
            dict(per_slot, block=srv.alloc.bytes_per_block))
    # (sequences up to the server's longest: the reference pads every
    # sequence to ONE compiled length, the limits file's ``pad_to``)
    run_rec["state_probe"] = serve_hybrid.probe_state(
        srv, serve_hybrid.PROBE_SLOTS, ctx.seed,
        int(cfg["served"]["max_len"]))
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
    a layer a program call (a decode trip or a chunk), and the busiest held
    expert's rows over the mean (what a seed must not change)."""
    line = _describe(run)
    pairs = [(s, m) for s, m in zip(run["steps"], run.get("moe_steps") or [])
             if 0.0 <= s["t1"] < run["seconds"]]
    moe = [m for _, m in pairs if m["pairs_held"]]
    if moe:
        from ..reference.ssm_moe_lm import sizes

        z = sizes(run["config"])
        held = z["held"][1] - z["held"][0]
        calls = z["L"] * sum(len(s["prefill_chunks"]) + bool(s["decode_rows"])
                             for s, _ in pairs)
        pairs = sum(m["pairs_held"] for m in moe)
        line += (f"; held experts active a layer a program call "
                 f"{sum(m['experts_active'] for m in moe) / calls:.2f} of "
                 f"{held}; load max over mean "
                 f"{sum(m['load_max'] for m in moe) / (pairs / held):.3f}; "
                 f"held share of the pairs "
                 f"{pairs / (pairs + sum(m['pairs_absent'] for m in moe)):.3f}")
    return line


def state_drifts(run: dict, limits: dict, mode: str = None,
                 log=None) -> Dict[str, float]:
    """Drift of the SSM state from the float32 reference's over the same
    tokens, on the ``state_slow_heads`` heads that remember longest
    (``reference.ssm_moe_lm.slow_heads``), widest
    over the probed slots: ``state_drift_first`` in the FIRST Mamba-2 layer,
    whose inputs the program has all but exactly (an embedding row and one
    RMSNorm), so that what piles up in the recurrence shows;
    ``state_drift_max`` over all Mamba-2 layers, where the bfloat16
    activations of the layers below move the inputs by percents — a bound
    for faults that move the state by much more. The state compared is the
    PROGRAM's; with ``mode``, the reference's own in that arithmetic put in
    the program's place (the CONTROL: ``state_bf16`` rounds it to bfloat16
    after every token). Infinite where no slot was probed."""
    from ..reference import ssm_moe_lm as ref

    cfg, weights = run["config"], run["weights"]
    pad_to, heads = int(limits["pad_to"]), int(limits["state_slow_heads"])
    first = worst = 0.0 if run["state_probe"] else float("inf")
    for p in run["state_probe"]:
        want = ref.states_at(weights, cfg, p["tokens"], pad_to)
        got = (p["h"] if mode is None else
               ref.states_at(weights, cfg, p["tokens"], pad_to, mode=mode))
        by_layer = [ref.state_drift(
            got[i], want[i], ref.slow_heads(weights, cfg, i, heads))
            for i in sorted(want)]
        if log is not None:
            log(f"check: slot {p['slot']}, {len(p['tokens'])} tokens "
                f"consumed, state drift by Mamba-2 layer: "
                + ", ".join(f"{d:.2e}" for d in by_layer))
        by_layer = [d if d == d else float("inf") for d in by_layer]
        first, worst = max(first, by_layer[0]), max([worst] + by_layer)
    return {"state_drift_first": first, "state_drift_max": worst}


def state_bytes_per_slot(cfg: dict) -> int:
    """What a slot's recurrent state is allotted, in closed form: per
    Mamba-2 layer the float32 state and the convolution's tail in the
    served type."""
    from ..reference.ssm_moe_lm import layer_kinds, sizes

    z = sizes(cfg)
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["torch_dtype"]]
    return layer_kinds(cfg).count("mamba") * (
        z["mh"] * z["P"] * z["N"] * 4
        + (z["K"] - 1) * (z["di"] + 2 * z["N"]) * itemsize)


def check(run: dict, limits: dict, seed: int, log=print):
    """The comparison that decides ``correct``: ``serve_latent_moe``'s of the
    served tokens — the widest gap and the percentile the limits file names
    (``logit_gap_p95``), each under its own limit —, the drift of the probed
    slots' SSM state (:func:`state_drifts`), the bytes a slot's state is
    allotted held to the float32 closed form, that every finished request
    kept its prompt and got the number of tokens it asked for, and that
    enough tokens and slots were compared. ``limits/<cell>.json`` says which
    readings each limit came from."""
    _, compared = serve_latent_moe.check(run, limits, seed, log=log)
    for name, value in state_drifts(run, limits, log=log).items():
        compared[name] = {"value": value, "limit": float(limits[name])}
    compared["state_slots_checked"] = {
        "value": float(len(run["state_probe"])), "limit": 1.0,
        "at_least": True}
    compared["state_bytes_per_slot"] = {
        "value": float(run["kv_stats"]["cache_bytes_state_allotted"]
                       / run["steps"][0]["slots_total"]),
        "limit": float(state_bytes_per_slot(run["config"])),
        "at_least": True}
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return ok, compared
