"""The serving config space: every tunable knob, typed and constrained.

One :class:`ConfigSpace` declares the full knob surface the serving
stack has grown — paged-cache geometry, tick batching, speculative
decoding, KV quantization, pool sizing, scheduler policy, and the fleet
tier (replica count, routing weights, probe cadence). A *config* is a
plain ``{knob: value}`` dict over exactly these knobs, so it JSON
round-trips into tuned profiles unchanged.

Knobs interact, so validity is first-class:

- ``spec_gate_low`` is dead weight when ``draft_k == 0``; canonicalize
  rather than reject, so fingerprints never differ on a knob that
  cannot matter.
- ``pool_frac < 1`` (pool sized below demand) REQUIRES a host pool to
  swap victims into (``host_pool_mb != 0``); with swapping disabled the
  starved pool degrades to stall livelock, which no search should ever
  measure as a candidate.
- speculation caps the tick window (``draft_k > 0`` requires
  ``tick_window <= 8``): the fused verify scan compiles one program
  spanning ``tick_window`` windows of width ``k+1``, so wide windows
  blow up both program size (multi-minute XLA compiles) and the
  surplus verify work past finished requests — the same reason the
  benchmark drops its tick-window default to 4 under ``--spec``.
- the fleet knobs (``prefix_weight``/``load_weight``/``probe_every``/
  ``degrade_cooldown_s``) are dead at ``fleet_replicas == 1`` and
  canonicalize to their defaults.
- ``cp > 1`` (context-parallel prefill) requires a mesh the host can
  actually build (the space's ``devices`` bound) and must divide
  ``prefill_chunk`` — the chunk shards evenly by construction.
- the tier watermarks are one ladder: ``tier_demote_low`` without
  ``tier_demote_high`` (or an unordered pair) is invalid, and the high
  watermark canonicalizes to None when the low trigger is off.

Sampling and mutation take an explicit ``numpy.random.RandomState`` and
are fully deterministic per seed — the search's trial sequence replays
bit-for-bit (see tests/test_autotune.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable: a finite choice set plus the untuned default."""

    name: str
    choices: Tuple[Any, ...]
    default: Any
    help: str = ""

    def __post_init__(self):
        if self.default not in self.choices:
            raise ValueError(
                f"knob {self.name!r}: default {self.default!r} not in "
                f"choices {self.choices!r}")


#: the serving knob surface (engine tier first, fleet tier after).
#: Choice sets are small on purpose: the space is combinatorial anyway
#: (~1e5 engine-tier configs) and every value here is one the suite has
#: actually exercised.
ENGINE_KNOBS: Tuple[Knob, ...] = (
    Knob("block_size", (8, 16, 32), 16,
         "tokens per KV block (pool geometry + attention table width)"),
    Knob("tick_window", (1, 2, 4, 8, 16, 32), 16,
         "decode ticks fused per host round trip"),
    Knob("prefill_chunk", (32, 64, 128), 64,
         "tokens per chunked-prefill program"),
    Knob("draft_k", (0, 2, 4, 8), 0,
         "speculative drafts per verify window; 0 = speculation off"),
    Knob("spec_gate_low", (0.5, 1.0, 2.0, 4.0), 2.0,
         "dynamic-gate acceptance floor (accepted drafts/window)"),
    Knob("kv_quant", ("none", "int8"), "none",
         "KV pool storage: fp blocks or int8 codes + f32 scales"),
    Knob("pool_frac", (0.5, 0.75, 1.0), 1.0,
         "KV pool byte budget as a fraction of fp dense parity"),
    Knob("host_pool_mb", (None, 16, 64), None,
         "host swap-pool cap in MB; None = unbounded, 0 = no swapping"),
    Knob("policy", ("fifo", "priority", "wfq"), "fifo",
         "request scheduler (inference/scheduler.py)"),
    Knob("cp", (1, 2, 4), 1,
         "context-parallel mesh axis sharding the chunked prefill's "
         "sequence dimension (long-context prefill scaling); 1 = off"),
    Knob("tier_demote_low", (None, 0.1, 0.2), None,
         "free-block fraction that TRIGGERS hot->warm KV demotion; "
         "None = watermark-driven demotion off"),
    Knob("tier_demote_high", (None, 0.3, 0.5), None,
         "free-block fraction demotion restores before it stops; dead "
         "(canonicalized to None) when tier_demote_low is None"),
)

#: the kernel tier: the dispatch mode (the per-op schedules are swept
#: apart from this space, autotune/kernel_geometry.py)
KERNEL_KNOBS: Tuple[Knob, ...] = (
    Knob("kernels", ("auto", "pallas", "reference"), "auto",
         "kernel dispatch rung for the compiled serving programs "
         "(ops.set_kernel_mode)"),
)

FLEET_KNOBS: Tuple[Knob, ...] = (
    Knob("fleet_replicas", (1, 2, 4), 1,
         "FleetRouter replica count; 1 = single engine"),
    Knob("prefix_weight", (0.5, 1.0, 2.0), 1.0,
         "routing score weight on matched prefix blocks"),
    Knob("load_weight", (0.5, 1.0, 2.0), 1.0,
         "routing score weight on queue depth + occupancy"),
    Knob("probe_every", (8, 16, 32), 16,
         "router ticks between watchdog deep probes"),
    Knob("degrade_cooldown_s", (0.0, 2.0), 0.0,
         "seconds a degraded replica sits out before re-probe"),
)

ALL_KNOBS: Tuple[Knob, ...] = ENGINE_KNOBS + KERNEL_KNOBS + FLEET_KNOBS


class ConfigSpace:
    """Typed knob space with validity, canonicalization, and seeded
    sampling/mutation.

    ``pins`` freezes knobs to a single value (the engine-tier search
    pins the fleet knobs to their defaults); ``max_len`` bounds
    ``block_size`` choices so one block never exceeds the serving
    horizon; ``devices`` bounds the ``cp`` mesh axis — a cp degree the
    host cannot build a mesh for is invalid, not a runtime crash.
    """

    def __init__(self, knobs: Sequence[Knob] = ALL_KNOBS, *,
                 pins: Optional[Dict[str, Any]] = None,
                 max_len: Optional[int] = None,
                 devices: Optional[int] = None):
        self.devices = devices
        self.knobs: Tuple[Knob, ...] = tuple(knobs)
        names = [k.name for k in self.knobs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate knob names in {names}")
        self._by_name = {k.name: k for k in self.knobs}
        self.pins: Dict[str, Any] = dict(pins or {})
        for name, val in self.pins.items():
            k = self._by_name.get(name)
            if k is None:
                raise ValueError(f"pin for unknown knob {name!r}")
            if val not in k.choices:
                raise ValueError(
                    f"pin {name}={val!r} not in choices {k.choices!r}")
        if max_len is not None:
            bs = self._by_name.get("block_size")
            if bs is not None:
                fit = tuple(c for c in bs.choices if c <= max_len)
                if not fit:
                    raise ValueError(
                        f"no block_size choice fits max_len={max_len}")
                self._by_name["block_size"] = dataclasses.replace(
                    bs, choices=fit, default=fit[-1]
                    if bs.default not in fit else bs.default)
                self.knobs = tuple(self._by_name[k.name]
                                   for k in self.knobs)

    # ------------------------------------------------------------- basics
    def knob(self, name: str) -> Knob:
        return self._by_name[name]

    def default(self) -> Dict[str, Any]:
        cfg = {k.name: k.default for k in self.knobs}
        cfg.update(self.pins)
        return self.canonicalize(cfg)

    def size(self) -> int:
        """Raw cartesian size (pre-constraint, pins collapse to 1)."""
        n = 1
        for k in self.knobs:
            n *= 1 if k.name in self.pins else len(k.choices)
        return n

    # -------------------------------------------------------- constraints
    def errors(self, config: Dict[str, Any]) -> List[str]:
        """Why this config is invalid; empty list = valid. Unknown or
        missing knobs and off-menu values are errors too — a profile
        edited by hand fails loudly, not at serving time."""
        errs: List[str] = []
        for name in config:
            if name not in self._by_name:
                errs.append(f"unknown knob {name!r}")
        for k in self.knobs:
            if k.name not in config:
                errs.append(f"missing knob {k.name!r}")
            elif config[k.name] not in k.choices:
                errs.append(f"{k.name}={config[k.name]!r} not in "
                            f"{k.choices!r}")
        for name, val in self.pins.items():
            if name in config and config[name] != val:
                errs.append(f"{name}={config[name]!r} violates pin "
                            f"{name}={val!r}")
        if errs:
            return errs
        # cross-knob feasibility
        if config.get("pool_frac", 1.0) < 1.0 \
                and config.get("host_pool_mb", None) == 0:
            errs.append(
                "pool_frac < 1.0 starves the KV pool below demand but "
                "host_pool_mb=0 disables swapping — victims would stall "
                "forever; give the overloaded pool a host pool")
        if config.get("draft_k", 0) > 0 and config.get("tick_window", 1) > 8:
            errs.append(
                "draft_k > 0 with tick_window > 8: the fused verify scan "
                "spans tick_window windows of width k+1, so wide windows "
                "explode program size (multi-minute compiles) and surplus "
                "verify work — cap the window at 8 when speculating")
        cp = int(config.get("cp", 1))
        if cp > 1:
            if self.devices is not None and cp > self.devices:
                errs.append(
                    f"cp={cp} needs a {cp}-device mesh but the space was "
                    f"built for {self.devices} device(s)")
            pc = int(config.get("prefill_chunk", 64))
            if pc % cp:
                errs.append(
                    f"cp={cp} must divide prefill_chunk={pc} — the chunk "
                    f"shards evenly over the cp axis by construction")
        lo = config.get("tier_demote_low", None)
        hi = config.get("tier_demote_high", None)
        if lo is not None:
            if hi is None:
                errs.append(
                    "tier_demote_low set without tier_demote_high — the "
                    "watermarks are one ladder, set both or neither")
            elif not (0.0 < lo < hi <= 1.0):
                errs.append(
                    f"tier watermarks must satisfy 0 < low < high <= 1, "
                    f"got low={lo} high={hi}")
        return errs

    def is_valid(self, config: Dict[str, Any]) -> bool:
        return not self.errors(config)

    def canonicalize(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """Collapse dead knobs to their defaults so two configs that
        cannot behave differently share one fingerprint: the spec gate
        without speculation, the host pool without overload, the fleet
        routing knobs without a fleet."""
        cfg = dict(config)
        if cfg.get("draft_k", 0) == 0 and "spec_gate_low" in self._by_name:
            cfg["spec_gate_low"] = self._by_name["spec_gate_low"].default
        if cfg.get("tier_demote_low", None) is None \
                and "tier_demote_high" in self._by_name:
            # the high watermark is dead without the low trigger (cp=1
            # analogously needs no collapse: the cp axis carries no
            # satellite knobs, 1 IS its canonical off value)
            cfg["tier_demote_high"] = \
                self._by_name["tier_demote_high"].default
        if cfg.get("pool_frac", 1.0) >= 1.0 \
                and "host_pool_mb" in self._by_name:
            cfg["host_pool_mb"] = self._by_name["host_pool_mb"].default
        if cfg.get("fleet_replicas", 1) == 1:
            for name in ("prefix_weight", "load_weight", "probe_every",
                         "degrade_cooldown_s"):
                if name in self._by_name:
                    cfg[name] = self._by_name[name].default
        return cfg

    def validate(self, config: Dict[str, Any]) -> Dict[str, Any]:
        """Canonicalize then raise on any remaining invalidity."""
        cfg = self.canonicalize(config)
        errs = self.errors(cfg)
        if errs:
            raise ValueError("invalid serving config: " + "; ".join(errs))
        return cfg

    # ----------------------------------------------------------- sampling
    def sample(self, rng: np.random.RandomState,  # graftlint: noqa[np-random]
               max_tries: int = 64) -> Dict[str, Any]:
        """One valid config, drawn knob-by-knob in declaration order
        (rejection-sampled against the cross-knob constraints). Same rng
        state -> same config, always."""
        for _ in range(max_tries):
            cfg = {}
            for k in self.knobs:
                if k.name in self.pins:
                    cfg[k.name] = self.pins[k.name]
                else:
                    cfg[k.name] = k.choices[int(rng.randint(len(k.choices)))]
            cfg = self.canonicalize(cfg)
            if self.is_valid(cfg):
                return cfg
        raise RuntimeError(
            f"could not sample a valid config in {max_tries} tries — "
            f"the pins/constraints have emptied the space")

    def mutate(self, config: Dict[str, Any], rng: np.random.RandomState,  # graftlint: noqa[np-random]
               mutations: int = 1, max_tries: int = 64) -> Dict[str, Any]:
        """Evolutionary neighbor: flip ``mutations`` unpinned knobs to a
        different choice, keeping the result valid. Deterministic per
        rng state."""
        base = self.validate(config)
        free = [k for k in self.knobs
                if k.name not in self.pins and len(k.choices) > 1]
        if not free:
            return dict(base)
        for _ in range(max_tries):
            cfg = dict(base)
            idx = rng.choice(len(free), size=min(mutations, len(free)),
                             replace=False)
            for i in sorted(int(j) for j in idx):
                k = free[i]
                alts = [c for c in k.choices if c != base[k.name]]
                cfg[k.name] = alts[int(rng.randint(len(alts)))]
            cfg = self.canonicalize(cfg)
            if self.is_valid(cfg) and cfg != base:
                return cfg
        return dict(base)

    # -------------------------------------------------------- fingerprint
    def fingerprint(self, config: Dict[str, Any]) -> str:
        """Stable id of the canonical config — the key trials, profiles
        and dedup all share."""
        cfg = self.validate(config)
        return hashlib.sha256(
            json.dumps(cfg, sort_keys=True, default=str).encode()
        ).hexdigest()[:12]


def engine_space(max_len: Optional[int] = None,
                 pins: Optional[Dict[str, Any]] = None,
                 devices: Optional[int] = None) -> ConfigSpace:
    """The single-engine search space: full knob surface declared, fleet
    tier pinned to its defaults (fleet_replicas=1 collapses the routing
    knobs too). ``devices`` bounds the cp axis to meshes the host can
    build. This is what ``tools/autotune.py`` searches."""
    p = {k.name: k.default for k in FLEET_KNOBS}
    p.update(pins or {})
    return ConfigSpace(ALL_KNOBS, pins=p, max_len=max_len, devices=devices)
