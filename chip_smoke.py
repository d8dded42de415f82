"""Does the main path still start on the chip?  One process, a few minutes.

    python3 chip_smoke.py                      # on a machine with a TPU
    python3 chip_smoke.py --cpu-rehearsal      # tiny sizes on CPU: the
                                               # control flow only, no claim

Drives the system through the entry points a user calls and checks what comes
out by the repo's own means. Legs:

* ``train``  one chip: ``ParallelEngine.train_batch`` on bench.py's 509M
  Llama proxy (AdamW state at Llama-3-8B width does not fit one 16 GB chip:
  13.85 GB for two layers) — loss finite and falling on a repeated batch.
  Closes with the sync question: do N chained steps closed by
  ``block_until_ready`` take as long as the same N closed by a scalar pull?
* ``serve``  one chip, full width: ``LlamaForCausalLM(llama3_8b_config(
  num_hidden_layers=16))`` in bf16 (9.1 GB of weights), ``GenerationServer(
  cache="paged", kernels="auto")`` with a 2 GiB pool, 13 requests of mixed
  prompt length through 8 slots, 64 new tokens each. Every request must
  finish with the right token count, and every generated token must sit
  within a bf16 tolerance of the top logit of the model's own non-paged
  forward over the same sequence (teacher-forced; random weights make
  token-exact a coin toss in bf16 — the CPU tier keeps that pin).
* ``four``   when >= 4 devices are visible: a train step at Llama-3-8B widths
  on a sharding=2 x tensor=2 mesh with ``fsdp=True``, and
  ``GenerationServer(mesh="tp=4")`` at the same widths, full depth. Fails
  unless parameter + optimizer bytes per device are within 1.3x of total/4.

For each leg it prints which implementation every hot op took
(``ops.select.selected``) and fails if any ran in the Pallas interpreter.

Contract: pins the platform, so no accelerator is an error (exit code 1, no
result line); prints ``platform``, ``device_kind`` and the device count first;
the last line of stdout is ``{"ok": true, "device": {...}}`` only if every leg
passed. Wall times printed here are set-up times (compilation included), not
speeds.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback


def _parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every leg at tiny sizes on (virtual) CPU "
                         "devices; prints no result line")
    ap.add_argument("--legs", default="train,serve,four",
                    help="comma-separated subset of train,serve,four")
    return ap.parse_args()


ARGS = _parse()
REHEARSAL = ARGS.cpu_rehearsal
# Pin the platform BEFORE jax is imported: an unset JAX_PLATFORMS falls back
# to the CPU with only a warning, and paddle_tpu switches to its CPU test
# numerics (x64, "highest" matmuls) when the variable says cpu.
if REHEARSAL:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
else:
    # the CPU backend rides along only as the host staging device for the
    # four-chip leg; an explicitly listed platform that fails to start raises
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T_START = time.time()


def log(msg=""):
    print(msg, flush=True)


def device_stamp():
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0)))
    return out


def report_selection(leg):
    """Print which implementation each hot op traced under during ``leg``
    and refuse interpret mode — on a chip that would be a hidden fallback."""
    from paddle_tpu.ops import select

    took = select.selected(reset=True)
    log(f"[{leg}] hot-op implementations (traces): "
        f"{json.dumps(took, sort_keys=True)}")
    if not REHEARSAL:
        interp = [op for op, impls in took.items()
                  if select.INTERPRET in impls]
        if interp:
            raise AssertionError(
                f"{interp} ran in the Pallas interpreter on a chip")
    return took


def free_device_memory():
    gc.collect()
    jax.clear_caches()
    gc.collect()


def host_device():
    return jax.devices("cpu")[0]


# --------------------------------------------------------------- train leg
def train_config():
    from paddle_tpu.models import LlamaConfig, llama_tiny_config

    if REHEARSAL:
        return llama_tiny_config(use_flash_attention=True), 4, 128
    # bench.py's 509M proxy: the largest no-remat config one v5e holds
    return LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5632, num_hidden_layers=8,
                       num_attention_heads=16, num_key_value_heads=8,
                       max_position_embeddings=2048, dtype="bfloat16",
                       use_flash_attention=True), 4, 2048


def random_batch(cfg, B, S):
    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    return (paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                             .astype("int32")),
            paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                             .astype("int64")))


def leg_train():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    cfg, B, S = train_config()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = ParallelEngine(model, optimizer=opt, loss_fn=None)
    log(f"[train] {n_params / 1e6:.0f}M params, hidden {cfg.hidden_size}, "
        f"L={cfg.num_hidden_layers}, B={B} x S={S}, dtype {cfg.dtype}; "
        f"no mesh given -> ParallelEngine took device "
        f"{[d.id for d in eng.mesh.devices.flat]} of {len(jax.devices())} "
        f"visible (mesh {dict(eng.mesh.shape)})")
    ids, lbl = random_batch(cfg, B, S)

    t0 = time.time()
    losses = [float(np.asarray(eng.train_batch(ids, lbl).value))]
    log(f"[train] first step (compile included): {time.time() - t0:.1f}s "
        f"set-up time; loss {losses[0]:.4f}")
    losses.append(float(np.asarray(eng.train_batch(ids, lbl).value)))

    # the sync question (ROADMAP A0): N steps chained through the donated
    # state, closed by block_until_ready vs closed by a scalar pull
    n = 3

    def chain(close):
        t = time.time()
        for _ in range(n):
            loss = eng.train_batch(ids, lbl)
        close(loss.value)
        return time.time() - t, loss

    t_block, loss = chain(jax.block_until_ready)
    losses.append(float(np.asarray(loss.value)))
    t_pull, loss = chain(lambda v: float(np.asarray(v)))
    losses.append(float(np.asarray(loss.value)))
    waits = t_block >= 0.7 * t_pull
    log(f"[train] sync check: {n} chained steps closed by block_until_ready "
        f"{t_block:.3f}s vs by a scalar pull {t_pull:.3f}s -> "
        f"block_until_ready {'WAITS' if waits else 'DOES NOT WAIT'} "
        f"(a sync check, not a speed)")
    log(f"[train] losses on a repeated batch: "
        f"{[round(x, 4) for x in losses]}")
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    took = report_selection("train")
    if not REHEARSAL:
        assert "pallas" in took.get("flash_attention", {}), took
        assert "pallas" in took.get("fused_norm", {}), took
    log(f"[train] peak bytes per device: {peak_bytes()}")


# --------------------------------------------------------------- serve leg
def serve_model_config(layers):
    from paddle_tpu.models import llama3_8b_config, llama_tiny_config

    if REHEARSAL:
        return llama_tiny_config(use_flash_attention=True)
    return llama3_8b_config(num_hidden_layers=layers,
                            max_position_embeddings=512)


def serve_requests(cfg, n_new):
    """13 prompts, 5..300 tokens: several longer than one prefill chunk
    (128), several shorter than a block, from a fixed seed."""
    rng = np.random.RandomState(1)
    lens = ([5, 17, 40, 150, 300, 9, 130, 64, 257, 33, 200, 12, 96]
            if not REHEARSAL else [5, 17, 40, 9, 33, 12, 25, 7, 44, 19, 3,
                                   30, 11])
    return [rng.randint(1, cfg.vocab_size, (n,)).tolist() for n in lens]


def check_against_forward(tag, model, params, results, prompts, rids, n_new,
                          pad_to, tol, mesh=None):
    """Teacher-forced check: one non-paged forward over each served sequence;
    every generated token must sit within ``tol`` of that position's top
    logit. Sequences are right-padded to one length (causal attention: the
    pad cannot reach back), so the reference compiles once."""
    import contextlib

    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.jit import functional_call

    ctx = contextlib.nullcontext
    if mesh is not None:
        from paddle_tpu.parallel import mesh_context

        def ctx():
            return mesh_context(mesh)

    def fwd(p, ids):
        with ctx():
            return functional_call(model, p, Tensor(ids)).value

    fwd = jax.jit(fwd)
    worst, exact, total = 0.0, 0, 0
    for rid, prompt in zip(rids, prompts):
        seq = results[rid]
        assert len(seq) == len(prompt) + n_new, \
            f"request {rid}: {len(seq)} tokens, expected " \
            f"{len(prompt) + n_new}"
        assert seq[:len(prompt)] == prompt, f"request {rid}: prompt altered"
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(seq)] = seq
        logits = np.asarray(fwd(params, jnp.asarray(ids))[0], np.float32)
        assert np.isfinite(logits[:len(seq)]).all(), \
            f"request {rid}: non-finite reference logits"
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        served = np.asarray(seq[len(prompt):])
        margin = logits[pos].max(-1) - logits[pos, served]
        worst = max(worst, float(margin.max()))
        exact += int((margin == 0).sum())
        total += len(served)
    log(f"[{tag}] {total} generated tokens vs the non-paged forward: "
        f"{exact} are its argmax, worst margin below the top logit "
        f"{worst:.4f} (tolerance {tol})")
    assert worst <= tol, f"served tokens disagree with the forward: " \
                         f"margin {worst:.4f} > {tol}"
    # bf16 logits tie or flip within an ulp often enough (top-2 gaps of
    # ~0.26 against ~0.03 resolution) that "most" is the honest bar here
    assert exact >= 0.5 * total, f"only {exact}/{total} tokens are the " \
                                 f"forward's argmax"


def serve_leg(tag, layers, tp=1):
    """Build the model, serve the 13 requests through a paged
    ``GenerationServer`` and check every token against the non-paged
    forward. ``tp`` > 1: ``mesh="tp=N"``, with model and server built under
    a HOST default device — the executor otherwise builds the whole pool
    (and the model its weights) on device 0 before placing them
    (inference/executor.py), and device 0 would cap the depth."""
    import contextlib

    import paddle_tpu as paddle
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.parallel import serving_mesh as sm

    cfg = serve_model_config(layers)
    n_new = 64 if not REHEARSAL else 8
    max_len = 512 if not REHEARSAL else 128
    t0 = time.time()
    with (jax.default_device(host_device()) if tp > 1
          else contextlib.nullcontext()):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        model.eval()
        srv = GenerationServer(
            model, cache="paged", kernels="auto", max_batch=8,
            max_len=max_len, block_size=16,
            prefill_chunk=128 if not REHEARSAL else 32,
            pool_bytes=(2 << 30) if not REHEARSAL else None,
            mesh=f"tp={tp}" if tp > 1 else None)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    log(f"[{tag}] {'Llama-3-8B' if not REHEARSAL else 'tiny rehearsal'} "
        f"widths (hidden {cfg.hidden_size}, heads "
        f"{cfg.num_attention_heads}/{cfg.num_key_value_heads}, FFN "
        f"{cfg.intermediate_size}, vocab {cfg.vocab_size}), "
        f"L={cfg.num_hidden_layers}: {n_params / 1e9:.2f}B params, "
        f"{cfg.dtype}, mesh tp={tp}; built "
        f"{'on the host and placed ' if tp > 1 else ''}in "
        f"{time.time() - t0:.1f}s")
    if tp > 1:
        check_state_is_sharded(tag, "serving params", [srv.params], tp)
    prompts = serve_requests(cfg, n_new)
    rids = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    t0 = time.time()
    results = srv.run()
    log(f"[{tag}] {len(rids)} requests through {srv.max_batch} slots, "
        f"{n_new} new tokens each, drained in {time.time() - t0:.1f}s "
        f"(compile included: set-up time, not a speed)")
    assert sorted(results) == sorted(rids), \
        f"finished {sorted(results)} of {sorted(rids)}"
    took = report_selection(tag)
    if not REHEARSAL:
        # one chip: the Pallas kernel; under the tp mesh the program is
        # GSPMD-partitioned and the rule answers xla
        want = "pallas" if tp == 1 else "xla"
        assert want in took.get("paged_attention", {}), took
    check_against_forward(tag, model, srv.params, results, prompts, rids,
                          n_new, pad_to=max_len,
                          tol=0.5 if cfg.dtype == "bfloat16" else 1e-2,
                          mesh=sm.build_serving_mesh(tp) if tp > 1 else None)
    report_selection(f"{tag}-reference")
    log(f"[{tag}] peak bytes per device: {peak_bytes()}")


# ----------------------------------------------------------- four-chip leg
def per_device_bytes(trees):
    per_dev, total = {}, 0
    for arr in jax.tree_util.tree_leaves(trees):
        total += arr.nbytes
        for sh in arr.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return per_dev, total


def check_state_is_sharded(tag, what, trees, n_dev):
    per_dev, total = per_device_bytes(trees)
    worst = max(per_dev.values())
    log(f"[{tag}] {what}: {total / 1e9:.2f} GB in all, per device "
        f"{ {d: round(b / 1e9, 2) for d, b in sorted(per_dev.items())} } GB "
        f"(even share {total / n_dev / 1e9:.2f})")
    assert worst <= 1.3 * total / n_dev, \
        f"{what}: a device holds {worst / 1e9:.2f} GB, more than 1.3x the " \
        f"even share {total / n_dev / 1e9:.2f} GB — state is not sharded"


def leg_four_train():
    import paddle_tpu as paddle
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.models import (LlamaForCausalLM, llama3_8b_config,
                                   llama_tiny_config)
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    if REHEARSAL:
        cfg, B, S = llama_tiny_config(use_flash_attention=True), 4, 128
    else:
        cfg, B, S = llama3_8b_config(num_hidden_layers=4,
                                     max_position_embeddings=2048), 4, 2048
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("sharding", "tensor"))
    t0 = time.time()
    # model and engine are built under a HOST default device: the engine
    # otherwise materialises the whole model and optimizer state on device
    # 0 before spreading it (parallel/engine.py _build_state), and device 0
    # would cap the depth
    with jax.default_device(host_device()):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
        eng = ParallelEngine(model, optimizer=opt, loss_fn=None, mesh=mesh,
                             fsdp=True, batch_spec=P(("data", "sharding")))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    log(f"[four-train] Llama-3-8B widths x L={cfg.num_hidden_layers} "
        f"({n_params / 1e9:.2f}B params), mesh {dict(mesh.shape)}, "
        f"fsdp=True, B={B} x S={S}; built on the host and placed in "
        f"{time.time() - t0:.1f}s")
    check_state_is_sharded("four-train", "params + optimizer state",
                           [eng.params, eng.opt_state], 4)
    ids, lbl = random_batch(cfg, B, S)
    t0 = time.time()
    losses = [float(np.asarray(eng.train_batch(ids, lbl).value))
              for _ in range(3)]
    log(f"[four-train] 3 steps in {time.time() - t0:.1f}s (compile "
        f"included), losses {[round(x, 4) for x in losses]}")
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    took = report_selection("four-train")
    if not REHEARSAL:
        assert "pallas" in took.get("flash_attention", {}), took
    log(f"[four-train] peak bytes per device: {peak_bytes()}")


# -------------------------------------------------------------------- main
def main() -> int:
    stamp = device_stamp()
    log(f"platform={stamp['platform']} device_kind={stamp['kind']} "
        f"devices={stamp['count']}")
    if REHEARSAL:
        log("CPU REHEARSAL: tiny sizes, control flow only — nothing below "
            "is a device result")
    elif stamp["platform"] != "tpu":
        log(f"chip_smoke.py needs a TPU; JAX found {stamp['platform']!r}")
        return 1

    from paddle_tpu.utils.compile_cache import (cache_stats,
                                                enable_compile_cache)

    log(f"compile cache: {enable_compile_cache()}")

    # one chip: depth cut to 16 layers (9.1 GB of bf16 weights beside a
    # 2 GiB pool); four chips: the full 32
    legs = {"train": [("train", leg_train)],
            "serve": [("serve", lambda: serve_leg("serve", 16))],
            "four": [("four-train", leg_four_train),
                     ("four-serve",
                      lambda: serve_leg("four-serve", 32, tp=4))]}
    failed = []
    for name in ARGS.legs.split(","):
        if name == "four" and stamp["count"] < 4:
            log(f"[four] skipped: {stamp['count']} device(s) visible, the "
                f"leg needs 4")
            continue
        for leg, fn in legs[name]:
            t0 = time.time()
            try:
                fn()
                log(f"[{leg}] PASSED in {time.time() - t0:.1f}s")
            except Exception:  # noqa: BLE001 — report every leg, then fail
                traceback.print_exc()
                log(f"[{leg}] FAILED after {time.time() - t0:.1f}s")
                failed.append(leg)
            free_device_memory()
    log(f"compile cache hits/misses this run: {cache_stats()}")
    log(f"wall time {time.time() - T_START:.1f}s (set-up time: start-up, "
        f"compilation and the runs together — not a speed)")
    if failed:
        log(f"FAILED legs: {failed}")
        return 1
    if REHEARSAL:
        log("rehearsal finished: every leg ran to the end on the CPU")
        return 0
    print(json.dumps({"ok": True, "device": stamp}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
