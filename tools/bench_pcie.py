"""PCIe roofline probe for the host-offload path.

Measures, through the same mechanism the offloaded optimizer compiles
(jitted device_put between memory kinds + optimization_barrier chains):

- ``h2d``: pinned_host→device bandwidth alone.
- ``roundtrip``: d2h then a barrier-chained h2d of the same payload —
  the serialized cost of one param's moment traffic.
- ``chain_w1`` / ``chain_w2``: an 8-block offload-pattern chain (h2d_i
  gated on h2d_{i-1} and on "update"_{i-W}; d2h_i after each tiny
  update) at window 1 (round-4 strict chain) vs window 2 (double
  buffered) — the directly decision-relevant number: if w2 beats w1,
  h2d/d2h overlap on the wire.

The offload ladder's floor: step_floor ≈ moment_bytes / chain_BW, with
moment traffic = 8 B/param EACH WAY for AdamW m+v (f32).

Usage: python tools/bench_pcie.py [--mb 256] [--blocks 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=256,
                    help="payload PER BLOCK, MiB")
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.utils.bench_timing import device_time_ms

    assert any(d.platform == "tpu" for d in jax.devices()), \
        "PCIe probe needs the TPU backend (pinned_host memory)"
    dev = jax.devices()[0]
    dev_s = SingleDeviceSharding(dev, memory_kind="device")
    host_s = SingleDeviceSharding(dev, memory_kind="pinned_host")
    n = args.mb * (1 << 20) // 4

    def token(v):
        return jax.lax.convert_element_type(v.ravel()[0], jnp.float32) * 0.0

    def h2d_fn(h):
        return token(jax.device_put(h, dev_s))

    def roundtrip_fn(d):
        h = jax.device_put(d, host_s)
        # gate the return h2d on the d2h having happened
        back = jax.device_put(
            jax.lax.optimization_barrier(h), dev_s)
        return token(back)

    def chain_fn(hosts, window):
        """The _offloaded_update schedule shape over k blocks."""
        h2d_tok = jnp.zeros((), jnp.float32)
        upd_toks = []
        outs = []
        for i, h in enumerate(hosts):
            gate = h2d_tok
            if i >= window:
                gate = gate + upd_toks[i - window]
            d = jax.device_put(
                jax.lax.optimization_barrier((h, gate))[0], dev_s)
            h2d_tok = token(d)
            upd = d * 1.0001 + 1.0  # stand-in elementwise optimizer math
            upd_toks.append(token(upd))
            outs.append(jax.device_put(upd, host_s))
        return sum(upd_toks), outs

    x_host = jax.device_put(np.zeros((n,), np.float32), host_s)
    x_dev = jax.device_put(jnp.zeros((n,), jnp.float32), dev_s)
    hosts = [jax.device_put(np.full((n,), float(i), np.float32), host_s)
             for i in range(args.blocks)]
    for a in (x_host, x_dev, *hosts):
        a.block_until_ready()

    h2d = jax.jit(h2d_fn)
    rt = jax.jit(roundtrip_fn)
    # the d2h outs MUST be jit OUTPUTS (host shardings): returning only
    # the scalar lets XLA dead-code-eliminate every d2h and the "chain"
    # measures h2d alone (r5 code-review catch — the first "full
    # duplex" rows were unsupported)
    chains = {}
    for w in (1, 2, 4):
        jitted = jax.jit(lambda hs, w=w: chain_fn(hs, w),
                         out_shardings=(dev_s, [host_s] * args.blocks))

        def run(jitted=jitted):
            s, _outs = jitted(hosts)
            return s

        chains[w] = run

    gib = args.mb / 1024.0
    res = {}
    ms = device_time_ms(lambda: h2d(x_host), reps=args.reps,
                        repeats=2, warmup=2)
    res["h2d"] = {"ms": round(ms, 2),
                  "gib_s": round(gib / (ms / 1e3), 2)}
    ms = device_time_ms(lambda: rt(x_dev), reps=args.reps,
                        repeats=2, warmup=2)
    res["roundtrip"] = {"ms": round(ms, 2),
                        "gib_s_each_way": round(2 * gib / (ms / 1e3), 2)}
    chain_gib = 2 * gib * args.blocks  # both directions, k blocks
    for w, fn in chains.items():
        ms = device_time_ms(fn, reps=args.reps, repeats=2, warmup=2)
        res[f"chain_w{w}"] = {
            "ms": round(ms, 2),
            "gib_s_total": round(chain_gib / (ms / 1e3), 2)}
    line = {"metric": "pcie_bandwidth_gib_s", "payload_mib": args.mb,
            "blocks": args.blocks, **res}
    print(json.dumps(line))


if __name__ == "__main__":
    main()
