"""Driver: a served decoder-hybrid-decoder (SambaY: Phi-4-mini-flash) through
``GenerationServer`` (paged cache), open loop, one process.

The loop, the end-to-end metrics, the failure count and the comparison of
the served tokens are ``serve_paged``'s, imported; this file brings the model
(its own class and weight table) and two reads of the program:

- **the recurrent state, once, after the loop** (:func:`probe_state`): the
  float32 SSM state that a few decoding slots hold when the loop ends, with
  the tokens each has consumed, through the executor's ``save_slot`` (what a
  preemption carries). :func:`check` holds it to the reference's state over
  the same tokens — the comparison that moves with the state: a greedy token
  does not (PERF.md section 2).
- **the by-kind counters, per step, in the traced run only**
  (:func:`_record_steps`): ``serving_decode_rows``,
  ``serving_decode_ctx_window`` / ``_shared`` (positions attended per decode
  row, capped at the window / in the one full pool that eight layers read)
  and the allocator's blocks in use. ``serve_paged.measure`` keeps one
  ``decode_ctx`` a step and has no hook at the trace's ends, so these ride
  beside its step records as ``run["hybrid_steps"]`` (one entry per step,
  same order). The untraced loop, which the end-to-end metric is judged
  from, runs ``srv.step`` as it is.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

from ..weights_sambay import make_weights, n_params, sambay_shapes
from .serve_paged import (attempted_failed, build_server,  # noqa: F401
                          end_to_end, measure)
from .serve_paged import check as _check
from .serve_paged import describe as _describe

# the benchmark's leaf names -> this program's parameter names
_LAYER_NAMES = {
    "norm1_w": "input_layernorm.weight", "norm1_b": "input_layernorm.bias",
    "norm2_w": "post_attention_layernorm.weight",
    "norm2_b": "post_attention_layernorm.bias",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "in_proj": "mixer.in_proj.weight", "conv_w": "mixer.conv_weight",
    "conv_b": "mixer.conv_bias", "x_proj": "mixer.x_proj.weight",
    "dt_proj": "mixer.dt_proj.weight", "dt_bias": "mixer.dt_bias",
    "A_log": "mixer.A_log", "D": "mixer.D",
    "out_proj": "mixer.out_proj.weight",
    "w1": "mixer.in_proj.weight", "w2": "mixer.out_proj.weight",
    "wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
    "wv": "mixer.v_proj.weight", "wo": "mixer.o_proj.weight",
    "subln": "mixer.subln_weight", "lambda_q1": "mixer.lambda_q1",
    "lambda_k1": "mixer.lambda_k1", "lambda_q2": "mixer.lambda_q2",
    "lambda_k2": "mixer.lambda_k2"}
_TOP_NAMES = {"embed": "model.embed_tokens.weight",
              "final_norm_w": "model.norm.weight",
              "final_norm_b": "model.norm.bias"}


def program_name(leaf: str) -> str:
    if leaf in _TOP_NAMES:
        return _TOP_NAMES[leaf]
    _, i, rest = leaf.split(".", 2)
    return f"model.layers.{i}.{_LAYER_NAMES[rest]}"


def model_config(cfg: dict):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig

    ssm = cfg["ssm"]
    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        d_state=ssm["d_state"], d_conv=ssm["d_conv"], expand=ssm["expand"],
        dt_rank=ssm["dt_rank"], dtype=cfg["torch_dtype"])


def build_model(cfg: dict, seed: int):
    """The program's model class at the configuration's sizes, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    model = Phi4FlashForCausalLM(model_config(cfg))
    model.eval()
    shapes = sambay_shapes(cfg)
    own = dict(model.named_parameters())
    donate = {leaf: own[program_name(leaf)].value for leaf in shapes}
    weights = make_weights(shapes, seed, jnp.dtype(cfg["torch_dtype"]),
                           std=cfg.get("initializer_range", 0.02),
                           donate=donate)
    missing, unexpected = model.set_state_dict(
        {program_name(leaf): Tensor(w) for leaf, w in weights.items()})
    if missing or unexpected:
        raise RuntimeError(f"weights do not cover the model: missing "
                           f"{missing}, unexpected {unexpected}")
    return model, weights


PROBE_SLOTS = 2           # slots whose state the check reads after the loop

_COUNTERS = ("serving_decode_rows", "serving_decode_ctx_window",
             "serving_decode_ctx_shared")


def _record_steps(srv, out: list):
    """Wrap ``srv.step`` so that every step leaves, in ``out``, the running
    totals of the by-kind counters, the blocks of the full pool in use and
    the seconds spent waiting for the device (differences are taken after
    the loop)."""
    counters = [srv.telemetry.registry.counter(n) for n in _COUNTERS]
    tel, alloc, step = srv.telemetry, srv.alloc, srv.step

    def stepped():
        remaining = step()
        out.append((*(c.total() for c in counters), alloc.blocks_in_use,
                    tel.wait_s))
        return remaining

    srv.step = stepped


def _hybrid_steps(totals: list, steps: list, unit: dict) -> list:
    """``_record_steps``'s totals as one record per step of the loop (the
    warm-up's steps came first; the loop's are the last ``len(steps)``):
    what the step added to each counter, and the cache bytes in use after
    it by kind (blocks of the full pool; rings and state per occupied
    slot)."""
    at = len(totals) - len(steps)
    out = []
    for i, s in enumerate(steps, start=at):
        now, was = totals[i], totals[i - 1] if i else (0,) * 5
        out.append({
            "decode_rows": now[0] - was[0],
            "decode_ctx_window": now[1] - was[1],
            "decode_ctx_shared": now[2] - was[2],
            "wait_s": now[4] - was[4],
            "cache_bytes_full": now[3] * unit["block"],
            "cache_bytes_window": s["slots_occupied"] * unit["window"],
            "cache_bytes_state": s["slots_occupied"] * unit["state"]})
    return out


def probe_state(srv, k: int, seed: int, max_tokens: int) -> list:
    """The SSM state that ``k`` decoding slots hold now, each with the
    tokens it has consumed (at most ``max_tokens``): the slot that has
    consumed most, then others drawn from the seed. Call between steps."""
    import numpy as np

    spec = srv.cache_spec
    found = []
    for s, req in enumerate(srv._slots):
        if req is None or srv._prefilling[s]:
            continue
        seq = list(req.prompt) + list(req.generated)
        n = int(srv.pos[s])           # the last token is not fed yet
        if n == len(seq) - 1 and 0 < n <= max_tokens:
            found.append((n, s, seq[:n]))
    if not found:
        return []
    found.sort(key=lambda f: (-f[0], f[1]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 11])
    pick = rng.permutation(len(found) - 1)[:max(0, k - 1)]
    out = []
    for n, s, tokens in [found[0]] + [found[1 + j] for j in sorted(pick)]:
        arrays = iter(srv._exec.save_slot(s))      # ``slot_pools`` order
        h = {}
        for i in spec.slot_layers:
            l = spec.layers[i]
            if l.kind == "window":
                next(arrays), next(arrays)          # the K and the V ring
                continue
            for name, _, _ in l.shapes:
                a = next(arrays)
                if name == "ssm":
                    h[i] = np.asarray(a, np.float32)
        out.append({"slot": s, "tokens": tokens, "h": h})
    return out


def run(ctx) -> dict:
    """Set up, warm up, measure ``ctx.seconds`` seconds, drain, and return
    the run record that the metric readers and the check consume."""
    import jax
    import paddle_tpu  # noqa: F401  (pins CPU numerics under tests)

    cfg = ctx.config
    t_build = time.monotonic()
    model, weights = build_model(cfg, ctx.seed)
    srv = build_server(model, cfg, telemetry=ctx.trace)
    spec = srv.cache_spec
    per_slot = spec.slot_bytes(srv.block_size)
    ctx.log(f"model {n_params(sambay_shapes(cfg)) / 1e9:.3f}B params + "
            f"server built in {time.monotonic() - t_build:.1f}s; full pool "
            f"{srv.alloc.num_blocks} blocks of {srv.block_size}; per slot "
            f"{per_slot} bytes")
    totals: list = []
    if ctx.trace:
        _record_steps(srv, totals)
    run_rec = measure(ctx, srv)
    if ctx.trace:
        run_rec["hybrid_steps"] = _hybrid_steps(
            totals, run_rec["steps"],
            dict(per_slot, block=srv.alloc.bytes_per_block))
    # (sequences up to half the server's longest: the reference pads every
    # sequence to ONE compiled length, the limits file's ``pad_to``)
    run_rec["state_probe"] = probe_state(srv, PROBE_SLOTS, ctx.seed,
                                         int(cfg["served"]["max_len"]) // 2)
    run_rec["cache_layers"] = {k: len(spec.of_kind(k)) for k in
                               ("full", "window", "shared", "state", "none")}
    run_rec["weights"] = weights
    del srv, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return run_rec


def describe(run: dict) -> str:
    """``serve_paged``'s line, and the mean live context a decode row saw
    in the window (the ramp fills every slot at once, so contexts grow
    through the window). In the traced run also, for the steps that took
    more than three medians, how much of each the host spent waiting for
    the device (``wait_s`` of the program's telemetry)."""
    pairs = [(s, h) for s, h in zip(
        run["steps"], run.get("hybrid_steps") or [None] * len(run["steps"]))
        if 0.0 <= s["t1"] < run["seconds"]]
    st = [s for s, _ in pairs if s["decode_rows"]]
    rows = sum(s["decode_rows"] for s in st)
    ctx = sum(s["decode_ctx"] for s in st)
    grow = ("" if not st else
            f" ({st[0]['decode_ctx'] / st[0]['decode_rows']:.0f} at its "
            f"first tick, {st[-1]['decode_ctx'] / st[-1]['decode_rows']:.0f} "
            f"at its last)")
    line = (_describe(run) + f"; mean live context of a decode row "
            f"{ctx / rows if rows else 0:.0f}{grow}")
    dur = sorted(s["t1"] - s["t0"] for s, _ in pairs)
    slow = [(s, h) for s, h in pairs if h is not None and dur
            and s["t1"] - s["t0"] > 3 * dur[len(dur) // 2]]
    if slow:
        line += "; slow steps (ms, of which waiting for the device): " + \
            ", ".join(f"{(s['t1'] - s['t0']) * 1e3:.0f}/"
                      f"{h['wait_s'] * 1e3:.0f}@{s['t0']:.1f}s"
                      for s, h in slow[:8])
    return line


def state_drifts(run: dict, limits: dict, mode: str = None,
                 log=None) -> Dict[str, float]:
    """Drift of the SSM state from the float32 reference's over the same
    tokens, on the slow elements (``reference.sambay_lm.slow_elements``),
    widest over the probed slots: ``state_drift_first`` in the FIRST Mamba
    layer, whose inputs the program has all but exactly (an embedding row
    and one LayerNorm), so that what piles up in the recurrence shows;
    ``state_drift_max`` over all Mamba layers, where the bfloat16
    activations of the layers below move the inputs by percents — a bound
    for faults that move the state by much more. The state compared is the
    PROGRAM's; with ``mode``, the reference's own in that arithmetic put
    in the program's place (the CONTROL: ``state_bf16`` rounds it to
    bfloat16 after every token). Infinite where no slot was probed."""
    from ..reference import sambay_lm as ref

    cfg, weights = run["config"], run["weights"]
    pad_to, tokens = int(limits["pad_to"]), float(limits["state_slow_tokens"])
    first = worst = 0.0 if run["state_probe"] else float("inf")
    for p in run["state_probe"]:
        want = ref.states_at(weights, cfg, p["tokens"], pad_to)
        got = (p["h"] if mode is None else
               ref.states_at(weights, cfg, p["tokens"], pad_to, mode=mode))
        by_layer = [ref.state_drift(
            got[i], want[i], ref.slow_elements(weights, cfg, i, tokens))
            for i in sorted(want)]
        if log is not None:
            log(f"check: slot {p['slot']}, {len(p['tokens'])} tokens "
                f"consumed, state drift by Mamba layer: "
                + ", ".join(f"{d:.2e}" for d in by_layer))
        by_layer = [d if d == d else float("inf") for d in by_layer]
        first, worst = max(first, by_layer[0]), max([worst] + by_layer)
    return {"state_drift_first": first, "state_drift_max": worst}


def check(run: dict, limits: dict, seed: int, log=print):
    """``serve_paged``'s comparison of the served tokens with the float32
    reference, and the comparison that moves with the recurrent state: the
    float32 SSM state of ``PROBE_SLOTS`` slots when the loop ended against
    the reference's over the tokens they had consumed (:func:`state_drifts`:
    a state rounded to bfloat16 at every token lies past the limit on
    ``state_drift_first``; one dropped at a chunk's edge, restored as zeros
    or read from another slot past both limits; greedy tokens show none of
    these at the published widths — PERF.md section 2). Beside it, the
    bytes a slot's state is allotted, held to the float32 closed form."""
    from ..reference.sambay_lm import layer_kinds, sizes

    ok, compared = _check(run, limits, seed, log=log)
    for name, value in state_drifts(run, limits, log=log).items():
        compared[name] = {"value": value, "limit": float(limits[name])}
    compared["state_slots_checked"] = {
        "value": float(len(run["state_probe"])), "limit": 1.0,
        "at_least": True}
    cfg, kv = run["config"], run["kv_stats"]
    z = sizes(cfg)
    kv_itemsize = {"bfloat16": 2, "float32": 4}[cfg["torch_dtype"]]
    want = layer_kinds(cfg).count("mamba") * (
        z["S"] * z["di"] * 4 + (z["K"] - 1) * z["di"] * kv_itemsize)
    got = kv["cache_bytes_state_allotted"] / run["steps"][0]["slots_total"]
    compared["state_bytes_per_slot"] = {"value": float(got),
                                        "limit": float(want),
                                        "at_least": True}
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return ok, compared
