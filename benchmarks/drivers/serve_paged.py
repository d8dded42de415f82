"""Driver: one served cell through ``GenerationServer`` (paged cache), open
loop, one process.

What it takes from the program: the server (``submit`` / ``step`` /
``take_results``), its public marks and counters (``request_metrics``,
``load_metrics``, ``kv_stats``, ``telemetry.tracer.spans`` in the traced run,
``ops.select.selected``, ``recompile_guard.compile_count``) and — the one
read of engine state, in :func:`_probe` — how far each request in a slot has
got, because the server publishes a request's tokens only when it ends and
has no counter of tokens emitted (PERF.md, Open questions).

The cell passes only what an operator must state (the config file's
``served`` arguments); every other server argument is the program's default.
"""
from __future__ import annotations

import gc
import os
import shutil
import time
from typing import Dict, List

import numpy as np

from .. import stats
from ..weights import decoder_shapes, make_weights, n_params

TRACE_MARGIN_S = 12.0     # schedule kept going past the window for the trace

# the benchmark's leaf names -> this program's parameter names
_LAYER_NAMES = {
    "attn_norm": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "mlp_norm": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight"}


def program_name(leaf: str) -> str:
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "final_norm":
        return "model.norm.weight"
    if leaf == "lm_head":
        return "lm_head.weight"
    _, i, rest = leaf.split(".", 2)
    return f"model.layers.{i}.{_LAYER_NAMES[rest]}"


def build_model(cfg: dict, seed: int):
    """The program's model class at the configuration's sizes, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    mcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])
    model = LlamaForCausalLM(mcfg)
    model.eval()
    shapes = decoder_shapes(cfg)
    own = dict(model.named_parameters())
    # the model's own initial draw is donated: the seeded weights take over
    # its buffers, so the model is never held twice
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


def build_server(model, cfg: dict, telemetry: bool):
    from paddle_tpu.inference import GenerationServer

    served = dict(cfg["served"])
    return GenerationServer(model, telemetry=True if telemetry else None,
                            **served)


class _Progress:
    """Tokens emitted and attention work done, per step, from the engine's
    slot table (see module docstring)."""

    def __init__(self, srv):
        self.srv = srv
        self.gen: Dict[int, int] = {}        # rid -> tokens seen so far
        self.pf: Dict[int, int] = {}         # rid -> prompt tokens prefilled

    def _advance(self, rid, g, ctx, rec):
        d = g - self.gen.get(rid, 0)
        if d <= 0:
            return
        first = rid not in self.gen          # its first token came from prefill
        self.gen[rid] = g
        rec["tokens"] += d
        n_dec = d - (1 if first else 0)
        if n_dec > 0:
            rec["decode_rows"] += n_dec
            # each decode token attended every position up to its own
            rec["decode_ctx"] += sum(range(ctx - n_dec + 1, ctx + 1))

    def step(self, finished: Dict[int, List[int]], prompt_len: Dict[int, int],
             rec: dict) -> None:
        srv = self.srv
        C = srv.prefill_chunk
        for s, req in enumerate(srv._slots):
            if req is None:
                continue
            n = len(req.prompt)
            cur = min(req.pf_next, n) if srv._prefilling[s] else n
            self._prefill(req.rid, cur, C, rec)
            if not srv._prefilling[s]:
                g = min(len(req.generated), req.max_new_tokens)
                self._advance(req.rid, g, n + g - 1, rec)
        for rid, seq in finished.items():
            n = prompt_len[rid]
            self._prefill(rid, n, C, rec)
            g = len(seq) - n
            self._advance(rid, g, n + g - 1, rec)

    def _prefill(self, rid, cur, C, rec):
        seen = self.pf.get(rid, 0)
        while seen < cur:
            end = min(seen + C, cur)
            rec["prefill_chunks"].append((seen, end - seen))
            seen = end
        self.pf[rid] = seen


def run(ctx) -> dict:
    """Set up, warm up, measure ``ctx.seconds`` seconds, drain, and return
    the run record that the metric readers and the check consume."""
    import jax
    import paddle_tpu  # noqa: F401  (pins CPU numerics under tests)

    cfg = ctx.config
    t_build = time.monotonic()
    model, weights = build_model(cfg, ctx.seed)
    srv = build_server(model, cfg, telemetry=ctx.trace)
    ctx.log(f"model {n_params(decoder_shapes(cfg)) / 1e9:.3f}B params + "
            f"server built in {time.monotonic() - t_build:.1f}s; pool "
            f"{srv.alloc.num_blocks} blocks of {srv.block_size}")
    run_rec = measure(ctx, srv)
    run_rec["weights"] = weights
    # the program's state goes before the reference runs
    del srv, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return run_rec


def measure(ctx, srv) -> dict:
    """Warm up ``srv``, offer the schedule, measure the window, drain."""
    import jax

    from ..generators import load as load_generator
    from paddle_tpu.analysis.recompile_guard import compile_count
    from paddle_tpu.ops import select

    cfg, traffic = ctx.config, ctx.traffic
    gen = load_generator(traffic["generator"])
    # (the traced run's schedule runs on through the trace that follows)
    reqs = gen.schedule(traffic, cfg["vocab_size"], ctx.seed,
                        ctx.seconds + (TRACE_MARGIN_S if ctx.trace else 0.0),
                        rate_rps=ctx.rate_rps)
    temperature = float(traffic.get("temperature", 0.0))

    # warm-up: this cell's two programs (the prefill chunk and the decode
    # tick) and nothing else — two prompts longer than one chunk, a few
    # tokens each, drained
    t_warm = time.monotonic()
    rng = np.random.default_rng(0)
    for _ in range(2):
        srv.submit(rng.integers(1, cfg["vocab_size"],
                                size=srv.prefill_chunk + 9).tolist(),
                   max_new_tokens=4, temperature=temperature)
    srv.run()
    warm_rids = set(srv.request_metrics())
    ctx.log(f"warm-up drained in {time.monotonic() - t_warm:.1f}s")

    ramp_s = float(traffic.get("ramp", {}).get("seconds", 0.0))
    drain_s = float(traffic.get("drain_s", 0.0))
    seconds = float(ctx.seconds)
    # the traced run traces AFTER the window has closed, while the schedule's
    # arrivals go on: starting and stopping the profiler stalls the loop for
    # seconds, which must not fall inside the window the host-clock metrics
    # are taken from
    trace_s = float(traffic.get("trace_s", 4.0)) if ctx.trace else 0.0
    trace_dir = os.path.join(ctx.scratch_dir, "trace", ctx.workload)
    prog = _Progress(srv)
    rid_of: Dict[int, int] = {}           # request idx -> rid
    idx_of: Dict[int, int] = {}
    prompt_len: Dict[int, int] = {}
    submit_t: Dict[int, float] = {}
    refused: Dict[int, str] = {}
    results: Dict[int, List[int]] = {}
    steps: List[dict] = []
    due_in = [r for r in reqs if 0 <= r.due < seconds]
    nxt = 0
    tracing, traced = False, None
    gc.collect()
    clock = time.monotonic
    t0 = clock() + ramp_s                 # window start on the host clock
    compiles0 = None
    setup_s = None
    while True:
        now = clock() - t0
        if compiles0 is None and now >= 0:
            compiles0 = compile_count()
            setup_s = clock() - ctx.t_process_start   # process start -> window
        if ctx.trace and not tracing and traced is None and now >= seconds:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing, trace_t0 = True, clock() - t0
        while nxt < len(reqs) and reqs[nxt].due <= now:
            r = reqs[nxt]
            nxt += 1
            try:
                rid = srv.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                                 temperature=temperature)
            except Exception as e:  # noqa: BLE001 — a refusal is a failed request, counted
                refused[r.idx] = f"{type(e).__name__}: {e}"
                continue
            rid_of[r.idx], idx_of[rid] = rid, r.idx
            prompt_len[rid] = len(r.prompt)
            submit_t[r.idx] = clock() - t0
        rec = {"t0": clock() - t0, "tokens": 0, "decode_rows": 0,
               "decode_ctx": 0, "prefill_chunks": []}
        with jax.profiler.TraceAnnotation("bench.step"):
            remaining = srv.step()
        rec["t1"] = clock() - t0
        done = srv.take_results()
        results.update(done)
        prog.step(done, prompt_len, rec)
        lm = srv.load_metrics()
        rec["slots_occupied"] = lm["slots_occupied"]
        rec["slots_total"] = lm["slots_total"]
        rec["queue_depth"] = lm["queue_depth"]
        steps.append(rec)
        now = rec["t1"]
        if tracing and now >= trace_t0 + trace_s:
            jax.profiler.stop_trace()
            tracing, traced = False, (trace_t0, now)
        if now >= seconds and not tracing and (traced or not ctx.trace):
            if now >= seconds + drain_s or all(
                    r.idx in refused or rid_of.get(r.idx) in results
                    for r in due_in):
                break
        if remaining == 0 and nxt < len(reqs):
            # idle server: wait for the next arrival without spinning
            time.sleep(min(max(reqs[nxt].due - (clock() - t0), 0.0), 0.001))
    if tracing:
        jax.profiler.stop_trace()
        traced = (trace_t0, steps[-1]["t1"])
    compiles_in_window = compile_count() - compiles0
    t_end = clock() - t0

    marks = srv.request_metrics()
    requests = []
    for r in reqs:
        rid = rid_of.get(r.idx)
        m = marks.get(rid, {}) if rid is not None else {}
        requests.append({
            "idx": r.idx, "due": r.due, "prompt_len": len(r.prompt),
            "max_new_tokens": r.max_new_tokens,
            "submit_t": submit_t.get(r.idx),
            "first_token_t": (m["first_token_t"] - t0
                              if "first_token_t" in m else None),
            "done_t": m["done_t"] - t0 if "done_t" in m else None,
            "n_generated": int(m.get("n_generated", 0)),
            "refused": refused.get(r.idx),
            "status": ("refused" if rid is None else "done"
                       if rid in results else srv.status(rid))})
    spans = []
    if ctx.trace:
        tr = srv.telemetry.tracer
        spans = [{"rid": s["rid"], "name": s["name"], "t0": s["t0"],
                  "dur": s["dur"]} for s in tr.spans()
                 if s["rid"] not in warm_rids]
        if tr.dropped:
            ctx.log(f"the program's tracer dropped {tr.dropped} spans")
    peak = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()), default=0)
    return {
        "config": cfg,
        "traffic": traffic, "seconds": seconds, "t_end": t_end,
        "setup_s": setup_s, "steps": steps, "requests": requests,
        "results": {idx_of[rid]: seq for rid, seq in results.items()
                    if rid in idx_of},
        "prompts": {r.idx: r.prompt for r in reqs},
        "spans": spans,
        "compiles_in_window": compiles_in_window,
        "kv_stats": srv.kv_stats(), "selected": select.selected(),
        "memory_peak_bytes": peak,
        "traced_window": traced, "trace_dir": trace_dir if traced else None,
    }


def end_to_end(run: dict) -> Dict[str, float]:
    """Every end-to-end metric this kind of cell can report."""
    seconds = run["seconds"]
    cap_ms = run["t_end"] * 1e3
    tok = [(s["t1"], s["tokens"]) for s in run["steps"]]
    due = [r for r in run["requests"] if 0 <= r["due"] < seconds]
    ttft = [stats.ttft_ms(r["due"], r["first_token_t"]) for r in due]
    tpot = [t for t in (stats.tpot_ms(r["first_token_t"], r["done_t"],
                                      r["max_new_tokens"]) for r in due)
            if t is not None]
    out = {"serve_tok_s": stats.rate_in_window(tok, 0.0, seconds),
           "setup_s": run["setup_s"]}
    if ttft:
        out["ttft_p95_ms"] = stats.finite_or_cap(
            stats.percentile(ttft, 95), cap_ms)
    if tpot:
        out["tpot_p95_ms"] = stats.finite_or_cap(
            stats.percentile(tpot, 95), cap_ms)
    return out


def describe(run: dict) -> str:
    """One line for the log of every run: where the loop's time went, so
    that a far-off tail can be traced to its cause (a stalled step, a late
    generator, a pile-up of prompts)."""
    st = [s for s in run["steps"] if 0.0 <= s["t1"] < run["seconds"]]
    if not st:
        return "no step inside the window"
    dur = sorted((((s["t1"] - s["t0"]) * 1e3, s) for s in st),
                 key=lambda x: x[0])
    worst = [f"{d:.0f}ms@{s['t0']:.1f}s/{len(s['prefill_chunks'])}chunks"
             for d, s in dur[-3:]]
    gaps = [(b["t0"] - a["t1"]) * 1e3 for a, b in zip(st, st[1:])]
    late = [(r["submit_t"] - r["due"]) * 1e3 for r in run["requests"]
            if r["submit_t"] is not None and 0 <= r["due"] < run["seconds"]]
    return (f"steps {len(st)}, median {dur[len(dur) // 2][0]:.1f} ms, longest "
            f"{worst}; longest time between steps {max(gaps, default=0):.0f} "
            f"ms; most chunks in a step "
            f"{max(len(s['prefill_chunks']) for s in st)}; slots occupied "
            f"at most {max(s['slots_occupied'] for s in st)}; generator "
            f"latest {max(late, default=0):.0f} ms")


def attempted_failed(run: dict):
    seconds = run["seconds"]
    due = [r for r in run["requests"] if 0 <= r["due"] < seconds]
    unfinished_fails = bool(run["traffic"].get("unfinished_fails", True))
    failed = 0
    for r in due:
        if r["refused"] or r["status"] in ("failed", "expired", "cancelled",
                                           "unknown"):
            failed += 1
        elif unfinished_fails and r["done_t"] is None:
            failed += 1
    return len(due), failed


def check(run: dict, limits: dict, seed: int, log=print):
    from ..check_served import check as _check

    return _check(run, limits, seed, log=log)
