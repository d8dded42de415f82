"""Continuous-batching generation server — the TPU serving engine.

Ref capability: the reference serves models through AnalysisPredictor /
DistModel (inference/api/, fleet_executor/dist_model.cc) with request-level
batching. The TPU-native redesign follows modern LLM serving: a FIXED pool
of ``max_batch`` slots, each with its own KV-cache rows and position; ONE
compiled decode step advances every active slot per tick (static shapes —
compiled exactly once), and finished slots are freed and refilled mid-flight
so throughput is never quantized by batch boundaries (continuous batching).

Two KV-cache backends share the slot machinery (``cache=`` ctor arg):

- ``"dense"`` (the reference oracle): a ``2·L·(max_batch, max_len, KV, D)``
  slab, one cache row span per slot. Prefill runs per request at bucketed
  prompt lengths (one compile per bucket) and scatters into the slot.
- ``"paged"``: a shared pool of fixed-size blocks + per-slot block tables
  (ops/paged_attention.py, inference/paged_cache.py). HBM is proportional
  to ACTIVE tokens instead of ``max_batch · max_len``; prompts stream
  through ONE compiled fixed-chunk prefill program (chunked prefill — no
  per-bucket compile family, no head-of-line blocking: each server step
  advances one chunk per prefilling slot, then runs the decode tick for
  the slots already decoding); full prompt blocks are content-hashed and
  refcount-shared, so a repeated prefix (shared system prompt) prefills
  once (prefix caching). Greedy outputs are token-exact vs the dense
  server. See docs/serving.md.

The decode step uses the model's vector-position path (``pos [B]``): every
slot attends at its own depth. Sampling routes through
``models/generation.py`` (``sample_token_rows`` in the compiled tick,
``next_token`` for the prefill-produced first token) so per-request
``temperature``/``top_k``/``top_p`` match ``model.generate`` semantics.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..jit import functional_call, state_values
from .scheduler import PRIORITY_NORMAL, SchedEntry, Scheduler


def kv_block_bytes(cfg, block_size: int, kv_quant: str = "none") -> int:
    """HBM bytes one KV block costs across ALL layers (K + V pools, plus
    the f32 scale rows for the int8 pool) — the unit `pool_bytes=` sizing
    and the benchmark's ``kv_bytes_per_token`` are derived from."""
    from .cache_spec import dense_decoder_spec

    return dense_decoder_spec(cfg).block_bytes(block_size, kv_quant)


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    generated: List[int] = field(default_factory=list)
    done: bool = False
    draft_k: Optional[int] = None                    # per-request spec budget
    adapter: Optional[str] = None                    # LoRA adapter (None = base)
    sched: Any = None                                # its scheduler.SchedEntry
    # paged-path state
    table: List[int] = field(default_factory=list)   # block ids, in order
    hashes: List[int] = field(default_factory=list)  # chain hash per full blk
    pf_next: int = 0                                 # next prefill position
    # corruption-recovery replay: when a swap payload fails its CRC, the
    # request re-prefills prompt+generated[:-1] (this sequence) through
    # the token-exact chunked-prefill program instead of restoring bits
    replay: Optional[List[int]] = None


@dataclass
class _Trip:
    """A plain decode trip that was dispatched and whose tokens the host
    has not read yet (``GenerationServer._retire_pending`` reads them)."""
    stack: Any                  # (k, B) int32 token stack, on the device
    rows: List[int]             # the slots it advanced
    mask: np.ndarray            # (B,) 0/1 over those slots
    ends: frozenset             # rows whose budget (max_new_tokens/max_len)
    #                             runs out inside it: not in the next trip
    k: int                      # ticks in it = tokens a row has in flight
    tick: int                   # flight seq of the tick that dispatched it
    stats: tuple = ()           # step stats of this call and of the chunk
    #                             calls before it, on the device, unread
    call: int = 0               # its program call's number (_call)


@dataclass
class _Chunk:
    """One prompt chunk whose blocks are reserved and whose tokens are laid
    out, waiting for the program call that carries it
    (``GenerationServer._prepare_chunk``)."""
    slot: int
    req: _Request
    ids: np.ndarray             # (1, C) int32, right-padded
    start: int                  # first position, block-aligned
    end: int                    # one past its last real token
    n: int                      # length of the sequence being prefilled
    call: int = 0               # number of the program call that carried it


class GenerationServer:
    """Continuous-batching decode server for a ``LlamaForCausalLM`` —
    greedy by default, per-request sampling via
    ``submit(..., temperature=, top_k=, top_p=)``.

    Usage::

        srv = GenerationServer(model, max_batch=4, max_len=256)
        rid = srv.submit([1, 5, 9], max_new_tokens=16)
        out = srv.run()          # drain all pending requests
        tokens = out[rid]        # prompt + generated ids
    """

    def __init__(self, model, max_batch: int = 4, max_len: int = 256,
                 prompt_buckets: Sequence[int] = (32, 64, 128),
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 tick_window: int = 1, cache: str = "dense",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 32, spec=None,
                 kv_quant: str = "none",
                 pool_bytes: Optional[int] = None,
                 policy=None,
                 host_pool_bytes: Optional[int] = None,
                 warm_pool_bytes: Optional[int] = None,
                 tier_demote_low: Optional[float] = None,
                 tier_demote_high: Optional[float] = None,
                 lora=None, telemetry=None, faults=None,
                 fault_retries: int = 3, kernels: str = "auto",
                 mesh=None, role: str = "any", profile=None,
                 clock=None):
        """``tick_window``: decode ticks per host round trip. 1 = exact
        per-token semantics. k>1 runs k ticks as ONE compiled lax.scan
        before the host sees the tokens — eos detection and slot refill lag
        by up to k-1 tokens (the surplus is discarded), in exchange for
        amortizing the device→host sync, which bounds the tick rate. The
        serving analogue of generate()'s fully-compiled scan loop. On the
        paged path a plain trip's tokens are read only after the NEXT trip
        has been dispatched (docs/serving.md, "The pending trip"): the next
        trip takes the last row of the k-token stack on the device, a row
        whose budget ends inside the pending window is left out of it, and
        with k>1 a row whose eos falls in mid-window runs one more window
        of k discarded tokens before its slot is released.

        ``cache="paged"``: block-table KV pool. ``block_size`` tokens per
        block; ``num_blocks`` bounds total KV memory (default: dense
        parity, ``max_batch·ceil(max_len/block_size)+1``); prompts prefill
        in fixed ``prefill_chunk``-token chunks (rounded up to a block
        multiple). ``prompt_buckets`` is ignored on the paged path.

        ``spec=SpecConfig(k=4)``: speculative decoding on the paged path —
        a drafter proposes k tokens per tick and ONE compiled verify
        program scores all k+1 window positions with exact accept/reject
        (greedy output token-exact vs the plain server; sampling output
        distribution provably unchanged). Requires ``cache='paged'`` and
        ``tick_window=1``. See inference/speculative.py, docs/serving.md.

        ``kv_quant="int8"`` (paged only): store the KV pool as int8 codes
        + f32 per-block-per-head scales (symmetric absmax) — half the
        bytes of bf16 per block, so ~2× resident blocks at the same pool
        budget and ~2× less KV traffic per decode tick. Dequant is FUSED
        into the compiled attention programs (ops/paged_attention.py
        ``*_q`` twins); the quant mode is fixed at construction so every
        program compiles once at warmup, same as the fp path.

        ``pool_bytes``: size the pool by HBM byte budget instead of block
        count — ``num_blocks = pool_bytes // kv_block_bytes(...)``. The
        int8 pool reports ~2× (bf16) / ~4× (f32) the blocks for the same
        budget. Mutually exclusive with ``num_blocks``.

        ``policy``: request-scheduling hook — None (FIFO, the
        pre-scheduler behavior), a policy name (``"fifo"`` / ``"priority"``
        / ``"wfq"``), or a configured :class:`~.scheduler.Scheduler`
        (for ``max_queue``/TTL/tenant weights). See inference/scheduler.py.

        ``host_pool_bytes`` (paged only): byte cap for the host KV pool
        that swap-preemption parks victim blocks in. None = unbounded
        (host DRAM dwarfs HBM); 0 disables swapping entirely — under
        pressure victims then stall instead of parking.

        ``warm_pool_bytes`` / ``tier_demote_low`` / ``tier_demote_high``
        (paged only): the tiered hot→warm→cold KV ladder
        (docs/serving.md, "Long-context serving"). When both watermarks
        are set (``0 < low < high <= 1``, fractions of usable blocks
        FREE), each paged tick that finds the free fraction below
        ``low`` demotes LRU prefix-cached blocks to the warm tier (a
        hash-keyed, CRC-guarded host store capped at
        ``warm_pool_bytes``; None = unbounded, 0 disables demotion)
        until the free fraction reaches ``high``. Warm blocks promoted
        back on a prefix hit skip their chunked-prefill work; blocks
        that fall off the warm tier re-prefill from replay (cold). Both
        watermarks unset (the default) keeps demotion off — the
        pre-tier behavior.

        ``lora=LoRAConfig(registry, ...)`` (paged only): multi-tenant LoRA
        serving. Each request may name an adapter (``submit(adapter=...)``)
        whose low-rank factors live in a paged device pool
        (inference/lora.py) alongside the KV pool; the compiled
        decode/prefill/verify programs gather each slot's factors by
        adapter index and apply the delta in-program (BGMV), padded to the
        config's static ``max_live_adapters``/``max_rank`` — so adapter
        churn (register/evict/swap) causes zero steady-state recompiles.
        Greedy output with adapter X is token-identical to the dense model
        with X's weights merged in. See docs/serving.md.

        ``telemetry``: observability (inference/telemetry.py). None/False
        (default) keeps span tracing and the tick flight recorder OFF —
        the metrics registry is still live (``sched_metrics()`` and the
        tenant percentiles read through it; counter updates are host dict
        writes) but the traced hot path pays only a truthiness check.
        True enables spans + flight recording; or pass a configured
        :class:`~.telemetry.ServingTelemetry` (injectable clock, ring
        size). See docs/observability.md.

        ``faults``: deterministic fault injection (inference/faults.py).
        None (default) wires the shared disabled injector — every hook
        site is a single attribute check. Pass a
        :class:`~.faults.FaultInjector` built from a scripted
        :class:`~.faults.FaultPlan` to replay pool exhaustion, tick
        faults, drafter failures, and swap corruption deterministically
        (the chaos-soak harness). ``fault_retries``: tick-fault strikes a
        request survives before quarantine to terminal ``failed``.

        ``mesh`` (paged only): multi-chip serving — ``"tp=N"`` (or the
        int N) shards the executor's compiled programs over an N-way
        ``tp`` mesh: attention/kv heads, MLP hidden dim, the KV block
        pool (+ its int8 scale rows), and the LoRA page pool all split on
        the same axis (parallel/serving_mesh.py), while block tables,
        scheduling, snapshots, and swap payloads stay tp-agnostic host
        state. ``"cp=M"`` / ``"tp=NxCp=M"`` adds a context-parallel axis
        that shards ONLY the chunked-prefill sequence dimension (params
        and pools replicate over cp; GSPMD all-gathers the chunk K/V
        before the pool scatter), multiplying prefill tok/s for long
        prompts. Greedy output is token-identical to the single-chip
        engine either way; every tp-sharded dim must divide N and
        ``prefill_chunk`` must divide by M. None/1 = single chip.

        ``role`` (paged only): replica class for disaggregated fleets —
        ``"any"`` (default) serves the full lifecycle; ``"prefill"``
        runs chunked prefill only, parking each request once its first
        token is sampled for ``FleetRouter`` to hand off (see
        :meth:`handoff_ready`/:meth:`evacuate`) and refusing decode-phase
        admits; ``"decode"`` marks the replica as a handoff target
        (routing sends it no fresh prompts, but it can still re-prefill
        salvaged replay work).

        ``kernels``: attention/projection kernel dispatch for the compiled
        serving programs — ``"auto"`` (default) picks the Pallas kernels on
        a TPU backend and the jnp reference elsewhere, ``"pallas"`` forces
        the kernels (interpret mode off-TPU — CPU parity testing), and
        ``"reference"`` pins the jnp compositions. Process-wide
        (``ops.set_kernel_mode``) and read at trace time, so it must agree
        across servers compiling in one process; ``"auto"`` leaves the
        current mode untouched. Recorded in the snapshot fingerprint —
        restore refuses a snapshot taken under a different mode (greedy
        tokens are kernel-identical, but sampling paths need not be
        bit-equal across kernels).

        ``profile``: a tuned profile from the autotuner
        (``paddle_tpu/autotune/``) — a path to the profile JSON, a
        parsed dict, or a :class:`~paddle_tpu.autotune.TunedProfile`.
        Applies the tuned serving knobs (cache geometry, tick window,
        speculation, kv_quant, pool sizing, policy) wherever the caller
        left the ctor argument at its declared default; an explicitly
        passed non-default argument wins over the profile. The loaded
        profile re-verifies its config fingerprint, so a hand-edited
        config fails here, loudly.

        ``clock``: injectable time source (``() -> float``) for request
        wall metrics, the default scheduler, and default-constructed
        telemetry — the autotuner injects a counting clock to make
        measured trials (and therefore tuned profiles) deterministic.
        None = ``time.monotonic``. A ``telemetry=``/``policy=`` instance
        you construct yourself keeps its own clock."""
        self.profile = None
        if profile is not None:
            from ..autotune.profile import resolve_profile

            self.profile = resolve_profile(profile)
            _pkw = self.profile.server_kwargs(
                model.cfg, max_batch=max_batch, max_len=max_len)
            # tuned knobs fill ctor args still at their declared
            # defaults; explicit caller choices always win
            if cache == "dense":
                cache = _pkw["cache"]
            if block_size == 16:
                block_size = _pkw["block_size"]
            if tick_window == 1:
                tick_window = _pkw["tick_window"]
            if prefill_chunk == 32:
                prefill_chunk = _pkw["prefill_chunk"]
            if spec is None:
                spec = _pkw.get("spec")
            if kv_quant == "none":
                kv_quant = _pkw["kv_quant"]
            if policy is None:
                policy = _pkw["policy"]
            if pool_bytes is None and num_blocks is None:
                pool_bytes = _pkw.get("pool_bytes")
            if host_pool_bytes is None:
                host_pool_bytes = _pkw.get("host_pool_bytes")
        cfg = model.cfg
        assert max_len <= cfg.max_position_embeddings
        if cache not in ("dense", "paged"):
            raise ValueError(f"cache must be 'dense' or 'paged', got {cache!r}")
        # what each layer keeps per request (inference/cache_spec.py): the
        # pools, admission, preemption and snapshots below are built from
        # it; a model that declares nothing is a dense decoder
        from .cache_spec import CacheSpecError, dense_decoder_spec

        self.cache_spec = (model.cache_spec() if hasattr(model, "cache_spec")
                           else dense_decoder_spec(cfg))
        # what a spec cannot serve is refused here, by name, not found out
        # mid-request: per-slot window rings and recurrent state would need
        # a state rollback, a quantized ring or a sharded state; for one
        # latent row a position no int8 code pool, no adapter or verify
        # path and no head axis to shard exist
        cannot = ("keeps per-slot state (window rings, recurrent state)"
                  if self.cache_spec.has_slot_state else
                  "keeps a 'latent' cache (one compressed row a position)"
                  if self.cache_spec.latent_layers else None)
        if cannot is not None:
            for what, on in (("cache='dense'", cache != "paged"),
                             ("spec= (speculative decoding)",
                              spec is not None),
                             ("lora=", lora is not None),
                             ("kv_quant='int8'", kv_quant != "none"),
                             ("mesh=", mesh not in (None, 1))):
                if on:
                    raise CacheSpecError(
                        f"{type(model).__name__} {cannot}: {what} is not "
                        f"supported for it")
        if kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8', got {kv_quant!r}")
        if kv_quant != "none" and cache != "paged":
            raise ValueError("kv_quant='int8' requires cache='paged' "
                             "(the dense slab has no block pool to quantize)")
        if pool_bytes is not None:
            if cache != "paged":
                raise ValueError("pool_bytes= requires cache='paged'")
            if num_blocks is not None:
                raise ValueError(
                    "pass either num_blocks= or pool_bytes=, not both")
        if host_pool_bytes is not None and cache != "paged":
            raise ValueError("host_pool_bytes= requires cache='paged' "
                             "(only the block pool can swap to host)")
        if lora is not None and cache != "paged":
            raise ValueError("lora= (multi-adapter serving) requires "
                             "cache='paged' — the adapter pool shares the "
                             "paged slot/eviction machinery")
        if role not in ("any", "prefill", "decode"):
            raise ValueError(
                f"role must be 'any', 'prefill', or 'decode', got {role!r}")
        if role != "any" and cache != "paged":
            raise ValueError("role= (disaggregated replica classes) "
                             "requires cache='paged' — handoff rides the "
                             "paged snapshot/migration path")
        self.role = role
        from ..parallel.serving_mesh import parse_mesh

        tp, cp = parse_mesh(mesh)
        if (tp > 1 or cp > 1) and cache != "paged":
            raise ValueError("mesh= (multi-chip serving) requires "
                             "cache='paged' — only the paged executor "
                             "places its programs on a mesh")
        self._tp = tp
        self._cp = cp
        if (tier_demote_low is None) != (tier_demote_high is None):
            raise ValueError(
                "tier_demote_low/tier_demote_high come as a pair — set "
                "both watermarks (or neither to keep demotion off)")
        if tier_demote_low is not None:
            if cache != "paged":
                raise ValueError("tier_demote_low/high (tiered KV) "
                                 "require cache='paged'")
            low, high = float(tier_demote_low), float(tier_demote_high)
            if not (0.0 < low < high <= 1.0):
                raise ValueError(
                    f"tier watermarks must satisfy 0 < low < high <= 1, "
                    f"got low={tier_demote_low} high={tier_demote_high}")
            tier_demote_low, tier_demote_high = low, high
        if warm_pool_bytes is not None and cache != "paged":
            raise ValueError("warm_pool_bytes= requires cache='paged'")
        self.tier_demote_low = tier_demote_low
        self.tier_demote_high = tier_demote_high
        from ..ops import KERNEL_MODES, set_kernel_mode

        if kernels not in KERNEL_MODES:
            raise ValueError(
                f"kernels must be one of {KERNEL_MODES}, got {kernels!r}")
        if kernels != "auto":
            set_kernel_mode(kernels)
        self.kernels = kernels
        self.kv_quant = kv_quant
        # per-layer kernel geometry (autotune/kernel_geometry.py): a
        # profile carrying a winner cache installs it process-wide
        # BEFORE anything traces — the op seams read it at trace time,
        # same contract as set_kernel_mode above. Without a profile
        # cache, an already-installed swept cache (install_geometry_
        # cache from a sweep artifact) stays in effect. The resolved
        # per-op (geometry, source) map feeds the snapshot fingerprint
        # and the serving_kernel_geometry telemetry gauge.
        from ..autotune.kernel_geometry import (install_geometry_cache,
                                                resolve_server_geometries)
        from ..framework.dtype import convert_dtype as _cvt

        if self.profile is not None \
                and self.profile.kernel_geometry is not None:
            install_geometry_cache(self.profile.geometry_cache(),
                                   source="profile")
        self.kernel_geometry = resolve_server_geometries(
            head_dim=self.cache_spec.kv_geometry()[1],
            hidden=cfg.hidden_size,
            dtype=str(jnp.zeros((), _cvt(cfg.dtype)).dtype),
            kv_quant=kv_quant,
            lora_rank=(int(lora.max_rank) if lora is not None
                       and hasattr(lora, "max_rank") else None))
        self.spec = None
        if spec is not None:
            if cache != "paged":
                raise ValueError(
                    "spec= (speculative decoding) requires cache='paged'")
            spec.validate()
            self.spec = spec
        self.model = model
        self.cfg = cfg
        self.cache_mode = cache
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos = eos_token_id
        if tick_window < 1:
            raise ValueError(f"tick_window must be >= 1, got {tick_window}")
        self.tick_window = int(tick_window)
        self.params = state_values(model)

        from ..framework.dtype import convert_dtype

        kv, d = self.cache_spec.kv_geometry()
        cdtype = convert_dtype(cfg.dtype)
        # per-slot scalars live HOST-side (numpy): slot assignment would
        # otherwise cost one eager device dispatch per field per request
        self.pos = np.zeros((max_batch,), np.int32)
        self.tokens = np.zeros((max_batch,), np.int32)
        self.temps = np.zeros((max_batch,), np.float32)
        self.topks = np.zeros((max_batch,), np.int32)
        self.topps = np.zeros((max_batch,), np.float32)
        self._step_no = 0
        self._base_key = jax.random.PRNGKey(seed)
        self._slots: List[Optional[_Request]] = [None] * max_batch
        if policy is None:
            self._sched = Scheduler() if clock is None \
                else Scheduler(clock=clock)
        elif isinstance(policy, Scheduler):
            self._sched = policy
        elif isinstance(policy, str):
            self._sched = Scheduler(policy=policy) if clock is None \
                else Scheduler(policy=policy, clock=clock)
        else:
            raise ValueError(
                f"policy must be None, a policy name ('fifo'/'priority'/"
                f"'wfq'), or a Scheduler instance, got {policy!r}")
        self._results: Dict[int, List[int]] = {}
        self._dropped: Dict[int, str] = {}   # rid -> cancelled|expired|failed
        # per-rid wall-clock marks (submit/first-token/done) — the
        # benchmark derives TTFT and per-token latency from these
        self._req_metrics: Dict[int, Dict[str, float]] = {}
        self._wall = clock if clock is not None else time.monotonic
        # preemption / overload counters (read via sched_metrics)
        self._preemptions = 0
        self._prefill_aborts = 0
        self._resumes = 0
        self._stalls = 0
        self._stall_streak = 0
        self._idle_streak = 0
        self._next_rid = 0
        self._lora = None

        from .faults import FaultInjector, NULL_INJECTOR

        if faults is None:
            self._faults = NULL_INJECTOR
        elif isinstance(faults, FaultInjector):
            self._faults = faults
        else:
            raise ValueError(
                f"faults must be None or a FaultInjector, got {faults!r}")
        self.faults = self._faults
        if not isinstance(fault_retries, int) or fault_retries < 0:
            raise ValueError(
                f"fault_retries must be an int >= 0, got {fault_retries!r}")
        self.fault_retries = fault_retries
        # degradation-ladder state (all host ints; see _step_paged_inner)
        self._failed: Optional[str] = None      # terminal-failure reason
        self._strikes: Dict[int, int] = {}      # rid -> tick-fault strikes
        self._backoff_ticks = 0                 # ticks left to sit out
        self._degraded_ticks = 0                # pressure-response cooldown
        self._tick_faults = 0
        self._quarantined = 0

        from .telemetry import ServingTelemetry

        # a facade built here shares the clock of the request marks, so
        # request_metrics() lays over the spans by construction
        if telemetry is None or telemetry is False:
            self._tel = ServingTelemetry(enabled=False, clock=self._wall)
        elif telemetry is True:
            self._tel = ServingTelemetry(enabled=True, clock=self._wall)
        elif isinstance(telemetry, ServingTelemetry):
            self._tel = telemetry
        else:
            raise ValueError(
                f"telemetry must be None, a bool, or a ServingTelemetry "
                f"instance, got {telemetry!r}")
        self.telemetry = self._tel
        reg = self._tel.registry
        self._sched.attach_metrics(reg)
        # registry twins of the overload ints above: sched_metrics() reads
        # THESE (single source of truth); the ints stay in lockstep for
        # direct attribute users
        self._c_preempt = reg.counter(
            "serving_preemptions", "decoding slots swapped out to host")
        self._c_aborts = reg.counter(
            "serving_prefill_aborts",
            "prefilling slots aborted under pool pressure (recomputable)")
        self._c_resumes = reg.counter(
            "serving_resumes", "swapped requests restored into a slot")
        self._c_stalls = reg.counter(
            "serving_stalled_reservations",
            "block reservations that found no victim and no headroom")
        self._c_completed = reg.counter(
            "serving_requests_completed", "requests finished with results")
        self._c_dropped = reg.counter(
            "serving_requests_dropped",
            "requests dropped before finishing (reason label)")
        self._h_ttft = reg.histogram(
            "serving_ttft_s",
            "submit -> first token, completed requests (seconds)")
        self._h_tpot = reg.histogram(
            "serving_tpot_ms",
            "per-token latency after the first, completed requests (ms)",
            buckets=(0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
                     250.0, 500.0, 1000.0, 2500.0))
        self._h_e2e = reg.histogram(
            "serving_e2e_s", "submit -> done, completed requests (seconds)")
        # fault-tolerance counters (inference/faults.py ladder)
        self._c_faults = reg.counter(
            "serving_faults_injected",
            "fault-injector firings observed by the server (site label)")
        self._c_retries = reg.counter(
            "serving_tick_retries",
            "decode trips retried after a recoverable tick fault")
        self._c_failed = reg.counter(
            "serving_requests_failed",
            "requests quarantined to terminal failed status (reason label)")
        self._c_corrupt = reg.counter(
            "serving_swap_reprefills",
            "corrupted swap payloads recovered by re-prefill")
        self._c_degrade = reg.counter(
            "serving_degrade_events",
            "watchdog-driven degradation responses (kind label)")
        # work done, counted where it is done (telemetry on or off)
        self._c_tokens = reg.counter(
            "serving_tokens_emitted",
            "output tokens folded into requests, first tokens included")
        self._c_dec_rows = reg.counter(
            "serving_decode_rows",
            "decode row-ticks whose token was folded into a request")
        self._c_dec_ctx = reg.counter(
            "serving_decode_ctx", "positions attended by those row-ticks")
        self._c_pf_tokens = reg.counter(
            "serving_prefill_tokens",
            "prompt tokens run through chunked prefill (no padding)")
        self._c_pf_chunks = reg.counter(
            "serving_prefill_chunks", "prefill chunk programs dispatched")
        self._c_pf_ctx = reg.counter(
            "serving_prefill_ctx",
            "positions attended by those tokens under the causal mask")
        # how often a chunk shares its program call (and so its read of
        # the weights) with the tick's decode rows; the two partition
        # ``serving_prefill_chunks``
        self._c_pf_fused = reg.counter(
            "serving_prefill_chunks_fused",
            "prefill chunks that rode in a decode trip's program call "
            "with at least one decoding row")
        self._c_pf_alone = reg.counter(
            "serving_prefill_chunks_alone",
            "prefill chunks that had a program call to themselves (reason "
            "label: no_decoding_row, second_chunk, slot_state, cp, spec, "
            "tick_window, lora, moe = an expert layer with capacity "
            "buckets, model)")
        # the same, by cache kind, for a spec that has such layers (per
        # ONE layer of the kind: a reader multiplies by the layer count)
        self._c_dec_ctx_win = reg.counter(
            "serving_decode_ctx_window",
            "positions a decode row-tick attended in a window layer "
            "(its context capped at the window)")
        self._c_dec_ctx_shared = reg.counter(
            "serving_decode_ctx_shared",
            "positions a decode row-tick attended in the full layer whose "
            "pool other layers share")
        self._c_state_saves = reg.counter(
            "serving_state_saves",
            "slot states copied to the host (reason label: preempt, "
            "snapshot)")
        # how often the pending trip engages (docs/observability.md):
        # every plain decode trip is retired either after the next one was
        # dispatched (overlapped) or at once, for the reason named
        self._c_overlapped = reg.counter(
            "serving_decode_trips_overlapped",
            "decode trips read only after the next trip was dispatched")
        self._c_early = reg.counter(
            "serving_decode_trips_retired_early",
            "decode trips read before another was dispatched (reason "
            "label: spec, preempt, snapshot, save_slot, cancel, fault, "
            "backoff, idle, demote, metrics, restore)")
        self._c_discarded = reg.counter(
            "serving_decode_rows_discarded",
            "decode row-ticks computed for a request after its eos (the "
            "rest of its window, and the trip dispatched before the host "
            "had read it)")
        # an expert layer that holds a share of the routed experts
        # (incubate/.../moe/held_experts.py) returns its per-layer loads
        # with each program call's outputs; folded where the trip is read
        self._c_moe_pairs = reg.counter(
            "serving_moe_pairs",
            "(token, expert) pairs routed by real rows (held label: 1 = "
            "the expert lives here and the pair was computed, 0 = it lives "
            "on another chip and its part is left out)")
        self._c_moe_active = reg.counter(
            "serving_moe_experts_active",
            "held experts that got at least one row, summed over layers "
            "and program calls")
        self._c_moe_load_max = reg.counter(
            "serving_moe_load_max",
            "rows on the busiest held expert of a layer, summed over "
            "layers and program calls")
        # what the engine knows of the device's queue (docs/serving.md,
        # "The pending trip"): every stretch from a blocking read that
        # left no program call in flight to where the next dispatch begins
        self._c_starved = reg.counter(
            "serving_device_starved_seconds",
            "seconds the device's queue was KNOWN to be empty while the "
            "server had work (a lower bound on idle; after label: the "
            "read that emptied it — first_token_wait, decode_wait, a "
            "between-steps reason — or arrival); telemetry on only")
        self._c_no_work = reg.counter(
            "serving_device_no_work_seconds",
            "seconds the device's queue was known to be empty and the "
            "server had no request; telemetry on only")
        # program calls dispatched / known to have finished: plain ints,
        # kept with telemetry off too. The device runs one stream in
        # order, so a blocking read of call n's outputs proves every call
        # up to n finished
        self._calls_out = 0
        self._calls_seen = 0
        # False where some device program runs outside this count (a
        # drafter with a program of its own): no span, no reading
        self._queue_tracked = True
        self._q_open: Any = None        # the open device-queue span
        self._stats_unread: List[Any] = []
        # decode trips dispatched and not read yet, oldest first: one
        # between steps, two for the moment between a dispatch and the
        # retiring of the trip before
        self._trips: List[_Trip] = []
        self._trip_no = 0               # plain trips dispatched so far
        wins = [l.window for l in self.cache_spec.layers
                if l.kind == "window"]
        self._ctx_window = min(wins) if wins else 0
        self._ctx_shared = bool(self.cache_spec.of_kind("shared"))
        # flight-record seq of the tick in progress (0 with telemetry off,
        # -1 between ticks with it on): what every engine-row phase names
        # as its parent
        self._tick_seq = 0
        # program key of the last paged trip, recorded per tick by the
        # flight recorder; the watchdog keys recompile excusal on it
        self._last_prog = "idle"

        if cache == "dense":
            self.buckets = sorted(b for b in prompt_buckets if b <= max_len)
            if not self.buckets:
                raise ValueError(
                    f"no prompt bucket fits max_len={max_len} "
                    f"(prompt_buckets={tuple(prompt_buckets)})")
            self._caches = [jnp.zeros((max_batch, max_len, kv, d), cdtype)
                            for _ in range(2 * cfg.num_hidden_layers)]
            # donate the KV pool: XLA updates the caches in place instead of
            # copying 2·L·(max_batch, max_len, KV, D) every decoded token
            self._decode = jax.jit(self._decode_fn, donate_argnums=(2,))
            self._prefills: Dict[int, object] = {}  # bucket -> jitted fn
        else:
            from .paged_cache import BlockAllocator

            bs = int(block_size)
            if bs < 1:
                raise ValueError(f"block_size must be >= 1, got {block_size}")
            self.block_size = bs
            chunk = int(prefill_chunk)
            if chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            self.prefill_chunk = -(-chunk // bs) * bs  # round up to blocks
            entries = -(-max_len // bs)  # ceil: real table entries per slot
            self._max_entries = entries
            # slack entries (always 0 = scratch) so the chunk's table
            # dynamic_slice never clamps and window-surplus decode writes
            # past max_len land in scratch instead of a live block; the
            # speculative verify window writes k+1 positions per tick, so
            # its surplus past max_len can be wider than one chunk's
            slack = self.prefill_chunk // bs
            if self.spec is not None:
                # a fused spec trip writes up to tick_window (or turbo)
                # windows of k+1 positions past a row's last live
                # position; a gated plain trip writes gate_ticks positions
                wmax = max(self.tick_window, int(self.spec.turbo_windows))
                slack = max(slack, -(-(wmax * (int(self.spec.k) + 1)) // bs),
                            -(-int(self.spec.gate_ticks) // bs))
            self._table_width = entries + slack
            per_block = self.cache_spec.block_bytes(bs, kv_quant)
            if num_blocks is None:
                if pool_bytes is not None:
                    # byte-budget sizing: this is where the int8 pool's
                    # ~2× capacity win comes from — same budget, half the
                    # bytes per block, twice the resident blocks
                    num_blocks = max(2, int(pool_bytes) // per_block)
                else:
                    num_blocks = max_batch * entries + 1  # dense parity
            self.alloc = BlockAllocator(int(num_blocks), bs,
                                        kv_quant=kv_quant,
                                        bytes_per_block=per_block,
                                        shards=self._tp)
            from .kv_offload import KVOffloadEngine

            self._offload = KVOffloadEngine(
                self.alloc, self._table_width,
                capacity_bytes=host_pool_bytes,
                warm_capacity_bytes=warm_pool_bytes)
            self._offload.telemetry = self._tel
            # cold-tier counter: prefix chains that fell off the warm
            # tier (or arrived with no cached ancestry at all) and paid
            # a fresh chunked prefill — the denominator's third leg in
            # the benchmark's tier_hit_rate
            self._cold_refills = 0
            self._prefill_wall_s = 0.0
            if self._faults is not NULL_INJECTOR:
                # thread the injector through the paged components (even
                # if currently disabled — a chaos harness arms the plan
                # after warmup); the default NULL_INJECTOR is never
                # wired, so the disabled path in each hook stays a plain
                # `is None` check
                self.alloc.faults = self._faults
                self._offload.faults = self._faults
            self._bt = np.zeros((max_batch, self._table_width), np.int32)
            # per-slot adapter page index into the LoRA pool; 0 = the
            # permanently-zero NULL page, so adapterless slots need no
            # branching inside the compiled programs
            self.aidx = np.zeros((max_batch,), np.int32)
            if lora is not None:
                from .lora import AdapterPool

                self._lora = AdapterPool(cfg, lora)
                self._lora.telemetry = self._tel
            # device-side mirror of (temps, topks, topps[, kcaps]): these
            # change only when a slot activates/releases, but were being
            # re-uploaded every trip (~0.1ms eager dispatch each)
            self._samp_dev = None
            # True while the slot is streaming prompt chunks; None once the
            # slot decodes (or is empty)
            self._prefilling: List[Optional[bool]] = [None] * max_batch
            # rids a prefill-class replica has finished prefilling (first
            # token sampled) and parked for the fleet router to hand off
            # to the decode class via evacuate(rids=)/admit_migrated
            self._handoff: set = set()
            if self.spec is not None:
                self.spec_k = int(self.spec.k)
                self.drafter = self.spec.build_drafter(max_len)
                if self._faults is not NULL_INJECTOR \
                        and hasattr(self.drafter, "faults"):
                    self.drafter.faults = self._faults
                # fusible drafters (in-program drafting, e.g. the n-gram
                # matcher) scan tick_window draft→verify→accept windows in
                # ONE program per host trip; host-side drafters need a
                # round trip per window
                self._spec_fused = bool(getattr(self.drafter, "fusible",
                                                False))
                if not self._spec_fused and self.tick_window != 1:
                    raise ValueError(
                        f"tick_window={tick_window} with spec= needs an "
                        f"in-program (fusible) drafter such as 'ngram'; "
                        f"drafter {type(self.drafter).__name__} proposes "
                        f"host-side and supports tick_window=1 only")
                self._spec_windows = self.tick_window if self._spec_fused \
                    else 1
                # per-slot draft budget (host-side, like pos/temps): rows
                # with kcap 0 run a plain decode tick inside the verify
                # program — idle/prefilling slots are masked this way
                self.kcaps = np.zeros((max_batch,), np.int32)
                self._spec_proposed = 0
                self._spec_accepted = 0
                # dynamic speculation gate (see SpecConfig.gate_low):
                # >0 = this many plain-decode trips before the next
                # speculative probe; turbo = long-trip tier while the
                # whole batch accepts near-k drafts per window
                self._spec_gate_off = 0
                self._spec_plain_windows = 0
                self._spec_turbo = False
            # engine/executor split: everything device-side — the KV
            # block pools, the compiled programs, and their (optional)
            # tp-mesh placement — lives in the executor; this engine
            # keeps only host scheduling state and dispatches through
            # the aliases below (inference/executor.py)
            from .executor import PagedExecutor

            self._exec = PagedExecutor(self, num_blocks=int(num_blocks),
                                       tp=self._tp, cp=self._cp)
            self._decode_paged = self._exec.decode_paged
            # a server has ONE of the two chunk programs (executor.py)
            self._chunk_prefill = self._exec.chunk_prefill
            self._decode_chunk = self._exec.decode_chunk
            # the tick's chunk that waits for the decode trip to take it
            # along, and the operands of a trip with every row masked
            self._rider: Optional[_Chunk] = None
            self._masked_rows = None
            if self.spec is not None:
                if self._spec_fused:
                    self._spec_scan = self._exec.spec_scan
                else:
                    self._spec_verify = self._exec.spec_verify
                    # a host-side drafter runs a program of its own that
                    # this engine neither dispatches nor reads
                    self._queue_tracked = False
        if self._tel.enabled:
            # nothing dispatched yet: the queue is known empty from here on
            self._queue_open(self._tel.clock(), "start")
            self._queue_settle("no_work")

    # ------------------------------------------------------------ compiled fns
    @property
    def _pools(self):
        """The executor's flat KV pool list — engine code reads/rotates
        it through this alias so the donation-rotation call sites are
        unchanged by the engine/executor split."""
        return self._exec.pools

    @_pools.setter
    def _pools(self, value):
        self._exec.pools = value

    @property
    def _slot_pools(self):
        """The executor's window rings and state arrays (the spec's slot
        kinds; empty for a dense decoder), rotated like ``_pools``."""
        return self._exec.slot_pools

    @_slot_pools.setter
    def _slot_pools(self, value):
        self._exec.slot_pools = value

    @property
    def _pool_stride(self) -> int:
        return self._exec.pool_stride

    @property
    def _prefill_tokens(self) -> int:
        """Real prompt tokens prefilled so far (``serving_prefill_tokens``
        — the numerator of ``tools/serving_benchmark.py``'s prefill
        throughput)."""
        return int(self._c_pf_tokens.total())

    def _lora_flat(self):
        """Current adapter-pool tensors for a compiled-program call — ()
        when LoRA is off (the programs then skip the gather entirely).
        Host-side: the pool list changes identity on adapter upload but
        never shape, so churn re-runs nothing."""
        return self._lora.device_tensors() if self._lora is not None else ()

    def _head(self, h):
        from ..framework.dispatch import apply_op

        if self.cfg.tie_word_embeddings:
            return apply_op(lambda v, w: jnp.matmul(v, w.T), h,
                            self.model.model.embed_tokens.weight)
        return self.model.lm_head(h)

    def _decode_fn(self, params, tokens, flat_caches, pos, temps, topks,
                   topps, active, key):
        """``tick_window`` ticks as one compiled region: each tick advances
        every slot by one token (per-slot sampling via
        ``generation.sample_token_rows``: temp == 0 → greedy argmax;
        temp > 0 → categorical with that row's top-k/top-p filter).
        ``active`` masks position advance so idle slots don't drift their
        cache write row. Returns the (k, B) token stack + final caches."""
        model = self.model

        def one_tick(carry, k):
            toks, flat_c, p = carry
            caches = [(Tensor(flat_c[2 * i]), Tensor(flat_c[2 * i + 1]))
                      for i in range(self.cfg.num_hidden_layers)]

            def call():
                h, new = model.model.decode_step(Tensor(toks[:, None]),
                                                 caches, p)
                return self._head(h), new

            logits, new = functional_call(model, params, call_fn=call)
            flat = []
            for ck, cv in new:
                flat += [ck.value, cv.value]
            lg = logits.value[:, 0].astype(jnp.float32)   # (B, V)
            from ..models.generation import sample_token_rows

            nxt = sample_token_rows(lg, jax.random.fold_in(key, k), temps,
                                    topks, topps)
            return (nxt, flat, p + active), nxt

        if self.tick_window == 1:
            (_, flat, _), stack = one_tick((tokens, flat_caches, pos), 0)
            return stack[None], flat
        (_, flat, _), stack = jax.lax.scan(
            one_tick, (tokens, flat_caches, pos),
            jnp.arange(self.tick_window))
        return stack, flat

    def _prefill(self, bucket: int):
        """Dense-path prefill + slot scatter as ONE jitted call (donated
        pool): the per-layer eager `.at[slot].set` scatters cost 2·L
        dispatches per request otherwise."""
        if bucket not in self._prefills:
            model = self.model

            def fn(params, prompt, true_len, pool, slot):
                """prompt [1, bucket] right-padded; logits at true_len-1;
                the request's cache rows scatter into pool[slot]."""
                kvs = self.cfg.num_key_value_heads
                d = self.cfg.hidden_size // self.cfg.num_attention_heads
                from ..framework.dtype import convert_dtype

                cdtype = convert_dtype(self.cfg.dtype)
                caches = [(Tensor(jnp.zeros((1, self.max_len, kvs, d), cdtype)),
                           Tensor(jnp.zeros((1, self.max_len, kvs, d), cdtype)))
                          for _ in range(self.cfg.num_hidden_layers)]

                def call():
                    h, new = model.model.prefill(Tensor(prompt), caches)
                    last = jax.lax.dynamic_slice_in_dim(
                        h.value, true_len - 1, 1, 1)
                    return self._head(Tensor(last)), new

                logits, new = functional_call(model, params, call_fn=call)
                flat = []
                for ck, cv in new:
                    flat += [ck.value, cv.value]
                pool = [p.at[slot].set(row[0]) for p, row in zip(pool, flat)]
                return logits.value[:, 0].astype(jnp.float32), pool

            self._prefills[bucket] = jax.jit(fn, donate_argnums=(3,))
        return self._prefills[bucket]

    # --------------------------------------------------------------- requests
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, draft_k: Optional[int] = None,
               priority: int = PRIORITY_NORMAL, tenant: str = "default",
               ttl_s: Optional[float] = None,
               adapter: Optional[str] = None) -> int:
        """Queue one request; returns its rid. ``priority`` (lower = more
        urgent), ``tenant`` (WFQ fairness bucket), and ``ttl_s`` (max
        queue wait before the request expires unstarted) feed the
        scheduler; raises :class:`~.scheduler.AdmissionError` when a
        bounded queue is full (backpressure). ``adapter`` names a
        registered LoRA adapter (requires ``lora=``) — unknown names,
        ranks past the pool's ``max_rank``, and shape-incompatible
        adapters are rejected HERE, not at admission time.

        Raises :class:`~.faults.EngineFailedError` once the server is in
        a terminal failed state — enqueuing would silently strand the
        request behind an engine that will never tick again."""
        if self._failed is not None:
            from .faults import EngineFailedError

            raise EngineFailedError(
                f"server is in a terminal failed state ({self._failed}) — "
                f"restore a snapshot into a fresh server or rebuild")
        prompt = list(prompt)
        if not prompt:
            raise ValueError("prompt must contain at least one token id")
        for t in prompt:
            if isinstance(t, bool) or not isinstance(t, (int, np.integer)):
                raise ValueError(
                    f"prompt must be a sequence of int token ids, got "
                    f"{type(t).__name__}: {t!r}")
        prompt = [int(t) for t in prompt]
        if isinstance(max_new_tokens, bool) or \
                not isinstance(max_new_tokens, (int, np.integer)) or \
                max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be a positive int, got "
                f"{max_new_tokens!r}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len={self.max_len}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if draft_k is not None:
            if self.spec is None:
                raise ValueError(
                    "draft_k= requires a server built with "
                    "spec=SpecConfig(...)")
            if isinstance(draft_k, bool) or \
                    not isinstance(draft_k, (int, np.integer)) or draft_k < 0:
                raise ValueError(
                    f"draft_k must be an int >= 0, got {draft_k!r}")
            if draft_k > self.spec_k:
                raise ValueError(
                    f"draft_k ({draft_k}) exceeds spec.k ({self.spec_k}) — "
                    f"the compiled verify-window width; raise SpecConfig.k")
            draft_k = int(draft_k)
        if not isinstance(tenant, str) or not tenant:
            raise ValueError(
                f"tenant must be a non-empty string, got {tenant!r}")
        if adapter is not None:
            if self._lora is None:
                raise ValueError(
                    "adapter= requires a server built with "
                    "lora=LoRAConfig(...) on the paged path")
            # full ladder: registered? rank <= max_rank? targets/layers/
            # shapes match the pool layout? — fail at the door, not after
            # the request has queued behind a day of traffic
            self._lora.validate(adapter)
        if self.cache_mode == "dense":
            self._bucket_for(len(prompt))  # validate against buckets up front
        else:
            # feasibility gate: a request whose worst-case block need —
            # final position plus the transient decode-window (or
            # speculative-window) reservation — exceeds the pool could
            # never finish; admitting it would wedge the scheduler behind
            # an unsatisfiable reservation, so reject it at the door
            if self.spec is not None:
                wmax = max(self.tick_window, int(self.spec.turbo_windows))
                trans = max(wmax * (self.spec_k + 1),
                            int(self.spec.gate_ticks))
            else:
                trans = self.tick_window
            worst = len(prompt) + max_new_tokens - 1 + trans
            need = min(self._max_entries, -(-worst // self.block_size))
            if need > self.alloc.num_blocks - 1:
                raise ValueError(
                    f"request needs up to {need} KV blocks but the pool "
                    f"has {self.alloc.num_blocks - 1} usable — it could "
                    f"never be scheduled; raise num_blocks/pool_bytes or "
                    f"shorten the request")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, prompt, int(max_new_tokens),
                       temperature=float(temperature),
                       top_k=int(top_k), top_p=float(top_p),
                       draft_k=draft_k, adapter=adapter)
        # cost = estimated total tokens: the WFQ charge a tenant pays
        req.sched = self._sched.submit(
            req, rid, priority=priority, tenant=tenant, ttl_s=ttl_s,
            cost=float(len(prompt) + max_new_tokens), adapter=adapter)
        self._req_metrics[rid] = {"submit_t": self._wall(),
                                  "tenant": tenant}
        if self._tel.enabled:
            tr = self._tel.tracer
            tr.set_meta(rid, tenant=tenant, priority=priority,
                        prompt_len=len(prompt), adapter=adapter or "")
            tr.begin(rid, "queued", priority=priority, tenant=tenant)
            self._work_arrived()
        return rid

    # ----------------------------------------------------------- device queue
    def _call(self, prog: str, fn, *args):
        """Dispatch one program call, ``fn(*args)``, and count it: its
        number is ``_calls_out`` once this returns, which the blocking read
        of its outputs hands to :meth:`_call_read`. An open device-queue
        span ends where the call BEGINS — somewhere inside it the device
        starts to run, and a lower bound on idle claims none of it."""
        if self._q_open is not None:
            self._queue_close(prog=prog)
        out = fn(*args)
        self._calls_out += 1
        return out

    def _call_read(self, call: int, ph, after: Optional[str] = None) -> None:
        """A blocking host read of program call ``call``'s outputs has
        returned (``ph``: the wait phase around it): that call and every
        call dispatched before it have finished — the device runs one
        stream in order. Where none is left in flight the device's queue is
        KNOWN to be empty from the read's end until the next dispatch, and
        with telemetry on a span on the device-queue row opens (named by
        :meth:`_queue_settle`, ended by :meth:`_call`). A lower bound: a
        queue that ran dry before the host looked is not seen until it
        looks."""
        if call > self._calls_seen:
            self._calls_seen = call
        if self._tel.enabled and self._calls_seen == self._calls_out:
            self._queue_open(ph.t1, after or ph.name)

    def _calls_drained(self, after: str) -> None:
        """A blocking read of state that EVERY dispatched call threads
        through (the pools) has returned: all of them have finished."""
        self._calls_seen = self._calls_out
        if self._tel.enabled:
            self._queue_open(self._tel.clock(), after)
            self._queue_settle()

    def _queue_open(self, t0: float, after: str) -> None:
        """Open the device-queue span at ``t0`` (telemetry on), unless one
        is open or this server keeps no count."""
        if self._q_open is None and self._queue_tracked:
            self._q_open = self._tel.queue_span(t0, after, self._tick_seq)

    def _queue_settle(self, name: Optional[str] = None) -> None:
        """Name the open device-queue span, once what the read decided has
        been folded: ``starved`` where the server has work (an occupied
        slot, a queued request), ``no_work`` where it has none."""
        q = self._q_open
        if q is not None and q.name is None:
            q.settle(name or (
                "starved" if len(self._sched) > 0
                or any(sl is not None for sl in self._slots) else "no_work"))

    def _queue_close(self, **args) -> float:
        """End the open device-queue span now and book its seconds;
        returns the reading of the clock it ended at."""
        q, self._q_open = self._q_open, None
        t1 = q.close(**args)
        if q.name == "starved":
            self._c_starved.inc(t1 - q.t0, after=q.args["after"])
        else:
            self._c_no_work.inc(t1 - q.t0)
        return t1

    def _work_arrived(self) -> None:
        """A request entered a server that had none (telemetry on): what
        follows an open ``no_work`` span is the host's time to dispatch it,
        ``starved`` after ``arrival``."""
        q = self._q_open
        if q is not None and q.name == "no_work":
            self._queue_open(self._queue_close(), "arrival")
            self._queue_settle("starved")

    def device_calls_in_flight(self) -> Optional[int]:
        """Program calls dispatched and not yet KNOWN to have finished (an
        upper bound on what the device still has to run; 0 once ``run()``
        has drained); None for a server that cannot keep the count (a
        host-side drafter's program runs outside it)."""
        if not self._queue_tracked:
            return None
        return self._calls_out - self._calls_seen

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket "
                         f"{self.buckets[-1]}")

    def _first_token(self, req: _Request, lg, call: int) -> int:
        """Sample the first generated token from prefill logits (1, V), an
        output of program call ``call`` — same ``next_token`` as
        model.generate, so temperature/top_k/top_p semantics match; one
        host sync per assignment. Greedy requests skip the eager
        sampling-op chain (fold_in + filtering, ~1ms of dispatch per
        admission) for a host argmax — same token."""
        tel, tick = self._tel, self._tick_seq
        if req.temperature == 0.0:
            with tel.phase("first_token_wait", tick, rid=req.rid) as ph:
                # (the whole (1, V) array: ``lg[0]`` would launch an eager
                # slice between the chunk and the decode program)
                row = np.asarray(lg)[0]
            first = int(np.argmax(row))
        else:
            from ..models.generation import next_token

            key = jax.random.fold_in(self._base_key, (req.rid << 20) | 1)
            nxt, _ = next_token(lg, key, req.temperature, req.top_k,
                                req.top_p)
            with tel.phase("first_token_wait", tick, rid=req.rid) as ph:
                first = int(nxt[0])
        self._call_read(call, ph)
        # (the request that just got its token is work)
        self._queue_settle("starved")
        return first

    def _activate_slot(self, slot: int, req: _Request, first: int) -> None:
        """Move a freshly-prefilled request into the decode phase."""
        self.pos[slot] = len(req.prompt)
        self.tokens[slot] = first
        self.temps[slot] = req.temperature
        self.topks[slot] = req.top_k
        self.topps[slot] = req.top_p
        if self.spec is not None:
            self.kcaps[slot] = (self.spec_k if req.draft_k is None
                                else req.draft_k)
        if self.cache_mode == "paged":
            self._samp_dev = None
        req.generated.append(first)
        self._c_tokens.inc()
        t = self._wall()
        m = self._req_metrics.get(req.rid)
        if m is not None:
            m.setdefault("first_token_t", t)
        tel = self._tel
        if tel.enabled:
            tr = tel.tracer
            tr.end(req.rid, "prefill")
            # the mark's own reading where mark and span share a clock
            tr.instant(req.rid, "first_token",
                       at=t if tel.clock is self._wall else None)
            tr.begin(req.rid, "decode")

    def _samp_arrays(self):
        """Device copies of the per-slot sampling params (+ draft caps and
        adapter page indices), re-uploaded only after a slot transition."""
        if self._samp_dev is None:
            kc = (jnp.asarray(self.kcaps) if self.spec is not None
                  else None)
            ai = (jnp.asarray(self.aidx) if self._lora is not None
                  else None)
            self._samp_dev = (jnp.asarray(self.temps),
                              jnp.asarray(self.topks),
                              jnp.asarray(self.topps), kc, ai)
        return self._samp_dev

    def _assign(self, slot: int, req: _Request) -> None:
        n = len(req.prompt)
        bucket = self._bucket_for(n)
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :n] = req.prompt
        if self._tel.enabled:
            self._tel.tracer.end(req.rid, "queued")
            self._tel.tracer.begin(req.rid, "prefill", bucket=bucket,
                                   prompt_len=n)
        # one compiled call: prefill + scatter into the slot's pool rows.
        # Rows beyond the true prompt length hold right-pad garbage, but
        # decode writes sequentially from pos=n, overwriting each such row
        # BEFORE the attention mask (arange <= pos) can reach it.
        lg, self._caches = self._call(
            "prefill_dense", self._prefill(bucket),
            self.params, jnp.asarray(prompt), n, self._caches, slot)
        self._activate_slot(slot, req,
                            self._first_token(req, lg, self._calls_out))
        self._slots[slot] = req

    def _fill_free_slots(self) -> int:
        """Admit waiting requests into free slots in scheduler-policy
        order; returns how many it admitted. Paged admission is gated on
        block headroom, with NO head-of-line bypass: skipping an
        inadmissible head for a smaller, later entry could starve the
        head forever — and strict order is safe because a draining pool
        always reopens headroom."""
        admitted = 0
        for s in range(self.max_batch):
            if self._slots[s] is not None:
                continue
            ent = self._sched.peek()
            if ent is None:
                break
            if self.cache_mode == "paged" and not self._admissible(ent):
                break
            self._sched.pop()
            ent.started = True
            if ent.swap is not None:
                if not self._resume_swapped(s, ent):
                    # headroom moved between the check and the restore
                    # (hash matches changed) — requeue, retry next step
                    self._sched.requeue(ent)
                    break
            elif self.cache_mode == "paged":
                self._admit_paged(s, ent.req)
            else:
                self._assign(s, ent.req)
            admitted += 1
        return admitted

    def _service_queue(self) -> int:
        """Queue maintenance at the top of every step: expire TTL'd
        waiters, fill free slots in policy order, then — paged only — if
        a strictly-more-urgent entry is stuck behind a full batch,
        preempt the least-urgent running request for it (one victim per
        step bounds preemption churn). Returns the admissions made."""
        for ent in self._sched.expire():
            self._drop_entry(ent, "expired")
        if self._lora is not None:
            # replay the queue's adapter demand (pop-priority order)
            # through the pool's LRU: high-share tenants' adapters become
            # most-recently-used and so evict LAST — WFQ shares govern
            # adapter residency, not just slot admission
            self._lora.warm(self._sched.adapter_demand())
        admitted = self._fill_free_slots()
        if self.cache_mode != "paged":
            return admitted
        ent = self._sched.peek()
        if ent is not None and all(sl is not None for sl in self._slots):
            v = self._pick_victim(ent.priority)
            # (a victim that ended in the pending trip, which a
            # preemption reads first, leaves its slot free all the same)
            if v is not None and (self._preempt_slot(v)
                                  or self._slots[v] is None):
                admitted += self._fill_free_slots()
        return admitted

    def _drop_entry(self, ent: SchedEntry, reason: str) -> None:
        """A queued entry leaves without finishing: record why, stamp its
        metrics closed, release any parked host KV."""
        self._dropped[ent.rid] = reason
        self._c_dropped.inc(reason=reason)
        m = self._req_metrics.get(ent.rid)
        if m is not None:
            m["done_t"] = self._wall()
        if ent.swap is not None:
            self._offload.discard(ent.swap)
            ent.swap = None
        self._tel.tracer.close(ent.rid, reason)

    # ---------------------------------------------------------- paged path
    def _admit_paged(self, slot: int, req: _Request) -> None:
        """Claim a slot: reuse cached prefix blocks (prefix caching — the
        matched span skips prefill entirely) and start chunked prefill at
        the first uncached block boundary. A request with an adapter
        acquires its pool page here (upload on miss, warm revival on hit)
        and holds the ref until the slot releases or is preempted."""
        if self._lora is not None:
            self.aidx[slot] = (self._lora.acquire(req.adapter)
                               if req.adapter is not None else 0)
            self._samp_dev = None
        # corruption recovery re-prefills prompt+generated[:-1] (the
        # replay sequence) instead of the bare prompt — same program,
        # same per-block machinery, different token source
        seq = req.replay if req.replay is not None else req.prompt
        # tier-aware prefix match: hot chain blocks ref as before, warm
        # chain blocks swap in through the compile-once promotion
        # scatter (kv_offload.match_prefix_tiered) — either way the
        # matched span skips its chunked prefill
        if self.cache_spec.has_slot_state:
            # no prefix sharing: another request's blocks hold the FULL
            # layers' K/V only — the window rings and the recurrent state
            # at the end of the shared prefix exist nowhere, so a request
            # that skipped those tokens would start from zero state
            req.table, tiers, req.hashes = [], {"hot": 0, "warm": 0}, []
        else:
            req.table, self._pools, tiers = \
                self._offload.match_prefix_tiered(seq, self._pools)
            req.hashes = self.alloc.chain_hashes(seq)
        req.pf_next = len(req.table) * self.block_size
        if req.pf_next < len(seq) and self._offload.warm.demoted_blocks:
            # the chain ran out of cached ancestry while a warm tier is
            # live: the remaining span re-prefills cold (replay rung or
            # plain chunked prefill — either way a cold-tier service)
            self._cold_refills += 1
        self._bt[slot, :] = 0
        self._bt[slot, :len(req.table)] = req.table
        self._prefilling[slot] = True
        self._slots[slot] = req
        if self._tel.enabled:
            tr = self._tel.tracer
            tr.end(req.rid, "queued")
            tr.begin(req.rid, "prefill", cached_blocks=len(req.table),
                     warm_blocks=tiers["warm"],
                     prompt_len=len(seq),
                     replay=req.replay is not None)

    def _ensure_blocks(self, slot: int, entries: int) -> None:
        """Grow the slot's block table to >= ``entries`` real entries
        (capped at ceil(max_len/block_size); writes past that land in
        scratch by construction)."""
        req = self._slots[slot]
        entries = min(entries, self._max_entries)
        while len(req.table) < entries:
            bid = self.alloc.alloc()
            req.table.append(bid)
            self._bt[slot, len(req.table) - 1] = bid

    # ------------------------------------------------- preemption / offload
    def _admissible(self, ent: SchedEntry) -> bool:
        """Block-headroom gate for paged admission: the entry's first
        allocation burst (whole prompt for a fresh request — conservative,
        so a long prompt can't thrash in and straight back out mid-
        prefill; parked block count for a swapped one) PLUS one spare
        block must be reclaimable right now."""
        if self._lora is not None and ent.req.adapter is not None \
                and not self._lora.can_acquire(ent.req.adapter):
            # every adapter page is held by a running slot: admitting
            # would fail the acquire — wait for a slot to release/preempt
            return False
        if ent.swap is not None:
            need = self._offload.restore_cost(ent.swap)
        else:
            seq = (ent.req.replay if ent.req.replay is not None
                   else ent.req.prompt)
            need = min(self._max_entries, -(-len(seq) // self.block_size))
            # hot prefix hits ref existing blocks instead of allocating
            # fresh ones — shrink the burst by them (hot_only: a WARM
            # hit still promotes into a freshly allocated device block,
            # so it must keep counting against headroom)
            need = max(need - self.alloc.probe_prefix(seq, hot_only=True),
                       1)
        ent.kv_need = need          # scheduler's queued-demand aggregate
        usable = self.alloc.num_blocks - 1
        # watchdog-driven admission tightening: while degraded, demand
        # extra spare blocks so admissions stop feeding the pressure that
        # tripped the finding (preemption storm / stall run)
        spare = 3 if self._degraded_ticks > 0 else 1
        headroom = min(need + spare, usable)
        return (self.alloc.blocks_free
                + self.alloc.evictable_cached) >= headroom

    def _maybe_demote(self) -> None:
        """Watermark-driven hot→warm demotion (the tier ladder's
        pressure rung): when the free fraction of usable blocks drops
        below ``tier_demote_low``, move LRU prefix-cached blocks to the
        warm tier until it reaches ``tier_demote_high`` — so long-prompt
        admission finds FREE blocks instead of silently cannibalizing
        the prefix cache (eviction loses the bytes; demotion keeps them
        promotable). Runs before admission each paged tick; a no-op
        without watermarks or without cached blocks to demote."""
        low = self.tier_demote_low
        if low is None:
            return
        a = self.alloc
        usable = a.num_blocks - 1
        if usable <= 0 or a.blocks_free / usable >= low:
            return
        want = int(self.tier_demote_high * usable) - a.blocks_free
        if want <= 0:
            return
        victims = a.coldest_cached(want)
        if victims:
            # the copy to the host reads the pools the pending trip writes
            self._retire_pending("demote")
            self._offload.demote(victims, self._pools)

    def _resume_swapped(self, slot: int, ent: SchedEntry) -> bool:
        """Restore a swapped-out request into ``slot`` exactly where it
        stopped: KV blocks back from host (prefix-hash hits skip the
        upload), position/next-token/sampling scalars from the request.
        Greedy continuation is token-identical to the un-preempted run —
        the round trip is bit-exact and the decode program sees the same
        state it would have seen. Returns False (entry untouched) if
        device headroom vanished."""
        req = ent.req
        # (the upload's writes into the pools are device work too)
        res = self._call("swap_in", self._offload.swap_in, ent.swap,
                         self._pools)
        if res is None:
            return False
        if res == "corrupt":
            # degradation ladder, re-prefill rung: the parked payload
            # failed its CRC and is gone, but the request's TOKENS are
            # host-side state — rebuild its KV by replaying
            # prompt+generated[:-1] through the chunked-prefill program
            # (token-exact vs decode), then continue as if nothing
            # happened. The swap handle's n_tokens is exactly the KV
            # coverage at swap-out time.
            handle, ent.swap = ent.swap, None
            self._c_corrupt.inc()
            req.replay = (req.prompt + req.generated)[:handle.n_tokens]
            if self._tel.enabled:
                tr = self._tel.tracer
                tr.end(req.rid, "preempted", corrupt=True)
                tr.begin(req.rid, "queued", reason="swap_corrupt")
            self._admit_paged(slot, req)
            return True
        if self._lora is not None:
            # re-acquire AFTER the KV restore committed: _admissible
            # already vouched for can_acquire, and acquiring first would
            # leak the adapter ref if swap_in failed
            self.aidx[slot] = (self._lora.acquire(req.adapter)
                               if req.adapter is not None else 0)
        handle, ent.swap = ent.swap, None
        req.table, self._pools = res
        if handle.extra:
            # the window rings and recurrent state the request left with
            tel = self._tel
            _t0 = tel.clock() if tel.enabled else 0.0
            self._call("restore_slot", self._exec.restore_slot, slot,
                       handle.extra)
            if tel.enabled:
                tel.tracer.complete(req.rid, "state_restore", _t0,
                                    tel.clock(), slot=slot)
            handle.extra = None
        self._bt[slot, :] = 0
        self._bt[slot, :len(req.table)] = req.table
        self._prefilling[slot] = None
        self._slots[slot] = req
        self.pos[slot] = handle.n_tokens
        self.tokens[slot] = handle.last_token
        self.temps[slot] = req.temperature
        self.topks[slot] = req.top_k
        self.topps[slot] = req.top_p
        if self.spec is not None:
            self.kcaps[slot] = (self.spec_k if req.draft_k is None
                                else req.draft_k)
        self._samp_dev = None
        self._resumes += 1
        self._c_resumes.inc()
        if self._tel.enabled:
            self._tel.tracer.end(req.rid, "preempted", resumed=True)
            self._tel.tracer.begin(req.rid, "decode", resumed=True)
        return True

    def _save_slot_state(self, s: int, rid: int, reason: str):
        """Host copies of what slot ``s`` owns besides pool blocks (window
        rings, recurrent state) — [] for a spec without slot kinds. They
        ride the swap payload: same CRC, same host-pool accounting, same
        snapshot arrays."""
        if not self.cache_spec.has_slot_state:
            return []
        tel = self._tel
        _t0 = tel.clock() if tel.enabled else 0.0
        arrays = self._exec.save_slot(s)
        self._c_state_saves.inc(reason=reason)
        if tel.enabled:
            tel.tracer.complete(rid, "state_save", _t0, tel.clock(),
                                reason=reason,
                                bytes=sum(a.nbytes for a in arrays))
        return arrays

    def _pick_victim(self, than_priority: int,
                     exclude=()) -> Optional[int]:
        """Least-urgent occupied slot STRICTLY less urgent than
        ``than_priority`` — equal-priority peers never preempt each other
        (that way lies ping-pong). Prefers prefilling victims (aborting
        them loses recomputable work only) and then the largest block
        holder (frees the most pool per preemption)."""
        best, best_key = None, None
        for s in range(self.max_batch):
            if s in exclude:
                continue
            req = self._slots[s]
            if req is None:
                continue
            pr = req.sched.priority
            if pr <= than_priority:
                continue
            key = (pr, 1 if self._prefilling[s] else 0, len(req.table))
            if best_key is None or key > best_key:
                best, best_key = s, key
        return best

    def _preempt_slot(self, s: int) -> bool:
        """Evict the request in slot ``s`` and requeue it. A slot still
        prefilling is ABORTED — its KV is recomputable, nothing is
        generated yet, and registered prompt blocks stay on the LRU so
        the re-run's prefix match skips them anyway. A decoding slot
        SWAPS: its table (truncated of speculative reservations) parks in
        host memory via the offload engine for a bit-exact resume.
        Returns False — slot untouched — when the host pool is full (or
        the request ended in the pending trip, which is read first: a
        victim's position, last token and state have to be the host's)."""
        self._retire_pending("preempt")
        req = self._slots[s]
        if req is None:
            return False
        ent = req.sched
        if self._prefilling[s]:
            for bid in req.table:
                self.alloc.free(bid)
            req.table = []
            req.pf_next = 0
            self._prefill_aborts += 1
            self._c_aborts.inc()
            if self._tel.enabled:
                tr = self._tel.tracer
                tr.end(req.rid, "prefill", aborted=True)
                tr.begin(req.rid, "queued", reason="prefill_abort")
        else:
            n = int(self.pos[s])
            req.table = self.alloc.truncate(req.table, n)
            handle = self._offload.swap_out(
                req.rid, req.table,
                req.hashes[:min(len(req.hashes), len(req.table))],
                self._pools, n_tokens=n, last_token=int(self.tokens[s]),
                extra=self._save_slot_state(s, req.rid, "preempt"))
            if handle is None:
                return False
            req.table = []
            ent.swap = handle
            self._preemptions += 1
            self._c_preempt.inc()
            if self._tel.enabled:
                # spans the time parked on host; swap_out/swap_in spans
                # come from the offload engine itself
                self._tel.tracer.end(req.rid, "decode", preempted=True)
                self._tel.tracer.begin(req.rid, "preempted",
                                       blocks=handle.n_blocks)
        self._slots[s] = None
        self._bt[s, :] = 0
        self._prefilling[s] = None
        self.pos[s] = 0
        self.tokens[s] = 0
        self.temps[s] = 0.0
        self.topks[s] = 0
        self.topps[s] = 0.0
        if self.spec is not None:
            self.kcaps[s] = 0
        if self._lora is not None:
            # drop the victim's adapter ref: the page goes CACHED (LRU),
            # so a quick resume revives it without re-upload while a
            # different adapter under pressure may claim the page
            self._lora.release(int(self.aidx[s]))
            self.aidx[s] = 0
        self._samp_dev = None
        self._sched.requeue(ent)
        return True

    def _reserve_or_preempt(self, s: int, entries: int) -> str:
        """Grow slot ``s``'s table to ``entries``, preempting less-urgent
        slots when the pool is dry. Returns ``"ok"`` (reserved),
        ``"gone"`` (``s`` itself yielded — no victim outranked it, so it
        released its own blocks and requeued; the rest of the batch
        drains and it resumes when pressure clears), or ``"stall"``
        (nothing preemptable and the host pool refused the swap — ``s``
        keeps its state and simply sits out this trip)."""
        tried = {s}
        while True:
            if self._slots[s] is None:
                # ``s`` ended in the pending trip that a preemption below
                # had to read first
                return "gone"
            try:
                self._ensure_blocks(s, entries)
                return "ok"
            except RuntimeError:
                v = self._pick_victim(self._slots[s].sched.priority,
                                      exclude=tried)
                if v is not None:
                    tried.add(v)
                    self._preempt_slot(v)
                    continue
                if self._preempt_slot(s) or self._slots[s] is None:
                    return "gone"
                self._stalls += 1
                self._c_stalls.inc()
                return "stall"

    def _reserve_active(self, active, need_fn) -> List[int]:
        """Reserve each decoding slot's blocks for the coming trip, most
        urgent first — under pool pressure this is where swap-preemption
        fires. Returns the surviving slot list (victims dropped out of
        ``active``; stalled slots skip the trip but keep their state)."""
        out = []
        for s in sorted(active, key=lambda i: (self._slots[i].sched.priority,
                                               i)):
            if self._slots[s] is None:
                continue        # preempted as a victim earlier in the loop
            if self._reserve_or_preempt(s, need_fn(s)) == "ok":
                out.append(s)
        # (a preemption retires the pending trip, which may end a row that
        # was reserved before it)
        out = sorted(s for s in out if self._slots[s] is not None)
        if not out and active:
            self._stall_streak += 1
            if self._stall_streak > 256:
                raise RuntimeError(
                    "paged pool wedged: 256 consecutive trips made no "
                    "progress (every slot stalled on block reservation) — "
                    "raise num_blocks/pool_bytes or host_pool_bytes")
        else:
            self._stall_streak = 0
        return out

    def _prepare_chunk(self, slot: int) -> Optional[_Chunk]:
        """The next prompt chunk of a prefilling slot, its blocks reserved
        and its tokens laid out — or None when the slot stalled or yielded
        (aborted as its own victim): no chunk this tick."""
        req = self._slots[slot]
        seq = req.replay if req.replay is not None else req.prompt
        n = len(seq)
        C = self.prefill_chunk
        start = req.pf_next
        end = min(start + C, n)
        if self._reserve_or_preempt(slot, -(-end // self.block_size)) != "ok":
            return None
        ids = np.zeros((1, C), np.int32)
        ids[0, :end - start] = seq[start:end]
        return _Chunk(slot, req, ids, start, end, n)

    def _chunk_operands(self, ck: _Chunk):
        """(chunk, table, start, last_idx) as the chunk programs take them;
        ``last_idx`` is the chunk's last real token (the rows after it are
        padding): on the final chunk the prompt's last token, whose logits
        are wanted; the logits of an earlier chunk are ignored."""
        last_idx = ck.end - 1 - ck.start
        return (jnp.asarray(ck.ids), jnp.asarray(self._bt[ck.slot]),
                jnp.int32(ck.start), jnp.int32(last_idx))

    def _slot_operand(self, ck: _Chunk):
        """``slot`` as the chunk programs take it: int32 (slot index, real
        tokens in the chunk, 1 on the request's last chunk); None for a
        spec without slot state, whose programs take none."""
        if not self.cache_spec.has_slot_state:
            return None
        return jnp.asarray(np.array([ck.slot, ck.end - ck.start,
                                     ck.end == ck.n], np.int32))

    def _chunk_alone(self, ck: _Chunk, why: str) -> None:
        """Dispatch one prompt chunk in a program call of its own, counted
        under ``why``, and on a final chunk read the first token. A server
        whose chunks can ride in the decode trip (``_decode_chunk``) runs
        that same program with every decode row masked — the way idle rows
        run in every trip — so that it compiles no third program; every
        other server runs the chunk program."""
        tel = self._tel
        _t0 = tel.clock() if tel.enabled else 0.0
        _w0 = self._wall()
        slot, (chunk, table, start, last_idx) = ck.slot, \
            self._chunk_operands(ck)
        if self._decode_chunk is None:
            aidx = (jnp.asarray(self.aidx[slot:slot + 1])
                    if self._lora is not None else None)
            lg, self._pools, self._slot_pools, *stats = self._call(
                "chunk_prefill", self._chunk_prefill,
                self.params, chunk, self._pools, table, start, last_idx,
                aidx, self._lora_flat(), self._slot_pools,
                self._slot_operand(ck))
        else:
            if self._masked_rows is None:
                B = self.max_batch
                self._masked_rows = (
                    jnp.zeros((B, self._table_width), jnp.int32),
                    jnp.zeros((B,), self.pos.dtype),
                    jnp.zeros((B,), jnp.int32))
            bt, posv, active = self._masked_rows
            temps, topks, topps, _, _ = self._samp_arrays()
            # (the stack of an all-masked trip is nobody's tokens)
            _, lg, self._pools, self._slot_pools, *stats = self._call(
                "decode_chunk_masked", self._decode_chunk,
                self.params, jnp.asarray(self.tokens), self._pools, bt, posv,
                temps, topks, topps, active, self._base_key,
                self._exec.prev_stack(None, 1), chunk, table, start,
                last_idx, self._all_greedy(range(self.max_batch)),
                self._slot_pools, self._slot_operand(ck))
        ck.call = self._calls_out
        # (read with the next decode trip's tokens, not now)
        self._stats_unread += stats
        self._c_pf_alone.inc(reason=why)
        self._chunk_dispatched(ck, _t0, _w0)
        self._chunk_ended(ck, lg)

    def _chunk_dispatched(self, ck: _Chunk, _t0: float, _w0: float) -> None:
        """What the host knows of a chunk once its program call returned,
        without waiting for it: the work counted, its span, its blocks
        published, the slot's next chunk."""
        req, start, end = ck.req, ck.start, ck.end
        bs = self.block_size
        # per-chip prefill throughput ledger (tools/serving_benchmark.py
        # divides by tp*cp): real prompt tokens only, not chunk padding
        m = end - start
        self._c_pf_tokens.inc(m)
        self._c_pf_chunks.inc()
        # token p of the chunk attends positions 0..p: start+1 .. end
        self._c_pf_ctx.inc(m * start + m * (m + 1) // 2)
        self._prefill_wall_s += self._wall() - _w0
        tel = self._tel
        if tel.enabled:
            # dispatch is asynchronous: the span ends when the call
            # returns, not when the chunk has run
            tel.tracer.complete(req.rid, "prefill_chunk", _t0, tel.clock(),
                                start=start, tokens=m, tick=self._tick_seq,
                                dispatch_only=True)
        # publish the prompt blocks this chunk completed for prefix reuse
        # (a freshly prefilled hash supersedes any stale warm copy)
        for i in range(start // bs, min(end // bs, len(req.hashes))):
            self.alloc.register(req.table[i], req.hashes[i])
            self._offload.forget_warm(req.hashes[i])
        req.pf_next = start + self.prefill_chunk

    def _chunk_ended(self, ck: _Chunk, lg) -> None:
        """After a prompt's final chunk, sample the first token from its
        logits row ``lg`` (the one host sync of a prefill) and flip the
        slot to decoding; a corruption-recovery replay instead resumes at
        its saved position — nothing new is sampled. Nothing to do after
        an earlier chunk."""
        if ck.end != ck.n:
            return
        slot, req = ck.slot, ck.req
        if req.replay is not None:
            self._activate_replayed(slot, req)
        else:
            self._activate_slot(slot, req,
                                self._first_token(req, lg, ck.call))
        self._prefilling[slot] = None
        if self.role == "prefill" and self._slots[slot] is req:
            # prefill-class replica: the request now holds exactly
            # the KV + first token a decode replica resumes from —
            # park it for the router's evacuate(rids=)/admit_migrated
            # handoff instead of decoding here (replays park too:
            # their decode phase belongs to the decode class)
            self._handoff.add(req.rid)

    def _activate_replayed(self, slot: int, req: _Request) -> None:
        """Flip a corruption-recovery replay straight back to decoding.

        The chunked prefill just rebuilt KV for ``prompt +
        generated[:-1]`` (token-exact vs the decode path — the PR 1
        guarantee), and the next decode input is the last token already
        generated, whose KV is deliberately not written yet (decode
        writes it) — exactly the invariant a swap-in restore lands on.
        Nothing is sampled here; greedy continuation is token-identical
        to the uncorrupted run."""
        n = len(req.replay)
        req.replay = None
        self.pos[slot] = n
        self.tokens[slot] = req.generated[-1]
        self.temps[slot] = req.temperature
        self.topks[slot] = req.top_k
        self.topps[slot] = req.top_p
        if self.spec is not None:
            self.kcaps[slot] = (self.spec_k if req.draft_k is None
                                else req.draft_k)
        self._samp_dev = None
        if self._tel.enabled:
            self._tel.tracer.end(req.rid, "prefill", replayed=True)
            self._tel.tracer.begin(req.rid, "decode", replayed=True)

    def _all_greedy(self, rows) -> bool:
        """True iff every listed slot decodes at temperature 0 — the
        STATIC specialization key for the decode/verify programs (temp 0
        rows ignore top-k/top-p, so temps alone decides). Flipping the
        flag costs one extra compile, then both variants are cached."""
        return all(float(self.temps[s]) == 0.0 for s in rows)

    def _step_paged(self) -> int:
        tel = self._tel
        if not tel.enabled:
            return self._step_paged_inner()
        # flight recording wraps the whole tick: counter/allocator deltas
        # plus the backend-compile delta (recompile_guard's jax.monitoring
        # listener) keyed by the program the tick dispatched
        from ..analysis.recompile_guard import compile_count

        a = self.alloc
        seq = self._tick_seq = tel.flight.total
        with tel.phase("tick", seq) as ph:
            c0 = compile_count()
            w0 = tel.wait_s
            pre = (self._preemptions, self._prefill_aborts, self._resumes,
                   self._stalls, a.fresh_allocs, a.evictions,
                   a.swap_out_blocks, a.swap_in_blocks,
                   a.demoted_blocks, a.promoted_blocks)
            chunks0, tokens0 = (self._c_pf_chunks.total(),
                                self._c_tokens.total())
            sp0, sa0 = ((self._spec_proposed, self._spec_accepted)
                        if self.spec is not None else (0, 0))
            remaining = self._step_paged_inner()
            rec = {
                "prog": self._last_prog,
                "decoding": sum(1 for s in range(self.max_batch)
                                if self._slots[s] is not None
                                and not self._prefilling[s]),
                "prefilling": sum(1 for s in range(self.max_batch)
                                  if self._prefilling[s]),
                "queue_depth": len(self._sched),
                "blocks_in_use": a.blocks_in_use,
                "blocks_allocated": a.fresh_allocs - pre[4],
                "evictions": a.evictions - pre[5],
                "preemptions": self._preemptions - pre[0],
                "prefill_aborts": self._prefill_aborts - pre[1],
                "resumes": self._resumes - pre[2],
                "stalls": self._stalls - pre[3],
                "swap_out_blocks": a.swap_out_blocks - pre[6],
                "swap_in_blocks": a.swap_in_blocks - pre[7],
                "swap_bytes": (a.swap_out_blocks - pre[6]
                               + a.swap_in_blocks - pre[7])
                * a.bytes_per_block,
                "host_bytes": self._offload.host.bytes_in_use,
                "demotions": a.demoted_blocks - pre[8],
                "promotions": a.promoted_blocks - pre[9],
                "warm_bytes": self._offload.warm.bytes_in_use,
                "recompiles": compile_count() - c0,
            }
            ph.note(prog=rec["prog"], decoding=rec["decoding"],
                    prefilling=rec["prefilling"],
                    queue_depth=rec["queue_depth"],
                    chunks=int(self._c_pf_chunks.total() - chunks0),
                    tokens=int(self._c_tokens.total() - tokens0))
        rec["t_wall_s"] = ph.dur
        # the part of the tick the host spent waiting for the device
        # (decode_wait + first_token_wait): a slow tick with a small
        # wait_s is the host's, one with a large wait_s the device's
        rec["wait_s"] = tel.wait_s - w0
        if self.spec is not None:
            rec["spec_proposed"] = self._spec_proposed - sp0
            rec["spec_accepted"] = self._spec_accepted - sa0
        tel.flight.record(**rec)
        # pressure response: every 32 recorded ticks, run the watchdog
        # over the RECENT window; a preemption storm or stall run flips
        # the server degraded for a cooldown — speculation forced off and
        # admission tightened (see _dispatch_trips / _admissible) —
        # instead of letting the pressure feed itself
        if tel.flight.total % 32 == 0:
            from .telemetry import watchdog as _watchdog

            finds = [f for f in _watchdog(tel.flight.dump()[-64:])
                     if f["kind"] in ("preemption_storm",
                                      "pool_pressure_stall")]
            if finds:
                if self._degraded_ticks == 0:
                    for f in finds:
                        self._c_degrade.inc(kind=f["kind"])
                self._degraded_ticks = 64
        self._tick_seq = -1         # between ticks: a phase names none
        return remaining

    def _step_paged_inner(self) -> int:
        tel, tick = self._tel, self._tick_seq
        tel_on = tel.enabled
        if tel_on:
            self._last_prog = "idle"
        self._rider = None
        # demote BEFORE admission: freed blocks feed _service_queue's
        # headroom gate this same tick
        with tel.phase("admit", tick) as ph:
            self._maybe_demote()
            ph.note(admitted=self._service_queue())
        # chunked prefill interleaves with decode: ONE chunk per prefilling
        # slot per step, so a long prompt never blocks slots mid-decode
        # (no head-of-line blocking) and short requests keep streaming out
        # Where a chunk can ride in the decode trip's program call
        # (executor.py, ``chunk_alone_why``) the tick's first one waits for
        # that dispatch, below, so that the tick reads the weights once;
        # every other chunk has a call of its own, here.
        did_prefill = False
        alone_why = self._exec.chunk_alone_why
        with tel.phase("prefill", tick) as ph:
            chunks = 0
            for s in range(self.max_batch):
                if self._slots[s] is not None and self._prefilling[s]:
                    did_prefill = True
                    ck = self._prepare_chunk(s)
                    if ck is None:
                        continue
                    why = alone_why
                    if why is None and self._rider is not None:
                        why = "second_chunk"
                    if why is None and not self._decoding_rows():
                        # nothing to ride with: run now, so that a final
                        # chunk's slot decodes in this very tick
                        why = "no_decoding_row"
                    if why is None:
                        self._rider = ck
                        continue
                    self._chunk_alone(ck, why)
                    chunks += 1
            ph.note(chunks=chunks, riding=int(self._rider is not None))
        # rows for the coming trip: the decoding slots, less those that the
        # pending trip's harvest will end whatever its tokens are — their
        # budget runs out inside it, or their last harvested token was eos
        active = self._decoding_rows()
        if self._degraded_ticks > 0:
            self._degraded_ticks -= 1
        trips0, idle_why = self._trip_no, "idle"
        if active:
            self._step_no += 1
            if self._backoff_ticks > 0:
                # degradation ladder, backoff rung: a recent tick fault
                # left state untouched (faults fire before dispatch), so
                # sitting out a few ticks lets a transient failure domain
                # clear before the identical trip is retried
                self._backoff_ticks -= 1
                idle_why = "backoff"
                if tel_on:
                    self._last_prog = "backoff"
            else:
                rids = [self._slots[s].rid for s in active]
                if self._rider is not None:
                    # (the chunk in the trip's call is a participant too)
                    rids.append(self._rider.req.rid)
                try:
                    self._dispatch_trips(active)
                except Exception as e:
                    from .faults import TickFault

                    if isinstance(e, TickFault):
                        # (strikes and quarantine act on retired state)
                        self._retire_pending("fault")
                        self._on_tick_fault(rids, e)
                    else:
                        # an exception AFTER compiled dispatch may have
                        # consumed donated pool buffers — no further trip
                        # is safe; flag terminal failure (submit() now
                        # refuses) and propagate
                        self._failed = f"{type(e).__name__}: {e}"
                        raise
                else:
                    # a clean trip clears its participants' strikes: the
                    # fault domain that struck them was transient
                    for r in rids:
                        self._strikes.pop(r, None)
        ck = self._take_rider()
        if ck is not None:
            # no trip took the chunk along (no decoding row, a backoff
            # tick, every row stalled, a fault)
            self._chunk_alone(ck, "no_decoding_row")
        if self._trip_no == trips0:
            # no trip went out this tick (no decoding row, a backoff tick,
            # every row stalled): nothing will overlap the pending one, so
            # it is read now — run() drains, step() comes down to 0
            self._retire_pending(idle_why)
        if tel_on and did_prefill:
            # prefill-bearing ticks get their own program-key suffix: the
            # chunk program's (and first-token sampling's) one-time
            # compiles must not read as steady-state recompiles of an
            # already-warm decode program
            self._last_prog += "+pf"
        occupied = sum(sl is not None for sl in self._slots)
        if occupied == 0 and len(self._sched) > 0:
            # every slot empty yet entries wait: admission must succeed
            # against an idle pool, so a persistent streak means state
            # corruption (e.g. leaked pins) — fail loudly, don't spin
            self._idle_streak += 1
            if self._idle_streak > 64:
                self._failed = ("scheduler wedged: 64 steps with empty "
                                "slots and a non-empty queue")
                raise RuntimeError(
                    "scheduler wedged: 64 steps with empty slots and a "
                    "non-empty queue — allocator headroom never recovered")
        else:
            self._idle_streak = 0
        return occupied + len(self._sched)

    def _decoding_rows(self) -> List[int]:
        """Rows for the coming trip: the decoding slots, less those that
        the pending trip's harvest will end whatever its tokens are — their
        budget runs out inside it, or their last harvested token was eos."""
        pend = self._trips[-1] if self._trips else None
        active = []
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is None or self._prefilling[s] \
                    or req.rid in self._handoff:
                continue
            if pend is not None and pend.mask[s] and (
                    s in pend.ends or req.done
                    or (self.eos is not None
                        and req.generated[-1] == self.eos)):
                continue
            active.append(s)
        return active

    def _take_rider(self) -> Optional[_Chunk]:
        """The chunk that waits for this tick's decode trip, if it still
        stands: a block reservation made since may have preempted its slot
        (the request then starts over from the queue; nothing of the chunk
        had been dispatched)."""
        ck, self._rider = self._rider, None
        if ck is not None and self._slots[ck.slot] is not ck.req:
            return None
        return ck

    def _dispatch_trips(self, active) -> None:
        """Dispatch the step's decode work for ``active`` slots — the one
        place a tick fault can fire, and it fires BEFORE any compiled
        call, so the caller may retry the trip verbatim (donated pools
        are still intact). A drafter failure degrades to the always-warm
        plain program and holds the speculation gate off."""
        if self._faults.enabled:
            spec = self._faults.fire("tick")
            if spec is not None:
                self._c_faults.inc(site="tick")
                if spec.kind == "fatal":
                    raise RuntimeError("injected fatal engine fault")
                from .faults import TickFault

                raise TickFault(rid=spec.rid)
        if self.spec is not None:
            # dynamic speculation gate: while recent acceptance is below
            # spec.gate_low, drafts are a net loss (a verify window costs
            # ~(k+1)x a decode tick but advances 1 token when all drafts
            # miss) — run the plain decode program for spec.gate_cooldown
            # trips, then probe again. Both programs compile during
            # warmup; switching is free. A degraded server (watchdog
            # pressure finding) forces the plain program the same way.
            if self._spec_gate_off > 0 or self._degraded_ticks > 0:
                if self._spec_gate_off > 0:
                    self._spec_gate_off -= 1
                self._spec_plain_windows += self.spec.gate_ticks
                self._plain_decode_trip(active, self.spec.gate_ticks)
            else:
                from .speculative import DrafterFault

                try:
                    self._spec_tick(active)
                except DrafterFault:
                    # the drafter is an accelerator, not a correctness
                    # dependency: emit this trip through the plain
                    # program and keep speculation off for a cooldown
                    self._c_faults.inc(site="drafter")
                    self._spec_gate_off = max(
                        int(self.spec.gate_cooldown) or 0, 4)
                    self._spec_turbo = False
                    self._spec_plain_windows += self.spec.gate_ticks
                    self._plain_decode_trip(active, self.spec.gate_ticks)
        else:
            self._plain_decode_trip(active)

    def _on_tick_fault(self, rids, fault) -> None:
        """Degradation ladder, strike rung: attribute the fault (to its
        named rid when the plan says so, else to every participant),
        back off exponentially, and quarantine any request that has
        exhausted its retries — one poison request must never take the
        engine down."""
        self._tick_faults += 1
        self._c_retries.inc()
        targets = rids
        rid = getattr(fault, "rid", None)
        if rid is not None and rid in rids:
            targets = [rid]
        worst = 0
        for r in targets:
            self._strikes[r] = self._strikes.get(r, 0) + 1
            worst = max(worst, self._strikes[r])
        # 1, 2, 4, 8 ticks — capped so a noisy plan can't idle the engine
        self._backoff_ticks = min(1 << max(worst - 1, 0), 8)
        for r in list(targets):
            if self._strikes.get(r, 0) > self.fault_retries:
                self._quarantine_rid(r, "tick_fault_retries_exhausted")

    def _quarantine_rid(self, rid: int, reason: str) -> None:
        """Terminal ``failed`` status for one request: release its slot,
        blocks, and adapter ref; record why. The engine itself keeps
        serving — that is the entire point of the quarantine rung."""
        self._retire_pending("fault")
        self._strikes.pop(rid, None)
        self._quarantined += 1
        self._dropped[rid] = "failed"
        self._c_failed.inc(reason=reason)
        self._c_dropped.inc(reason="failed")
        m = self._req_metrics.get(rid)
        if m is not None:
            m["done_t"] = self._wall()
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is not None and req.rid == rid:
                req.table = self.alloc.truncate(req.table, 0)
                self._tel.tracer.close(rid, "failed")
                self._release_slot(s)
                return
        ent = self._sched.remove(rid)
        if ent is not None:
            if ent.swap is not None:
                self._offload.discard(ent.swap)
                ent.swap = None
            self._tel.tracer.close(rid, "failed")

    def _plain_decode_trip(self, active, ticks=None) -> None:
        """One plain (non-speculative) decode trip: ``ticks`` (default
        ``tick_window``) ticks in one compiled program across the listed
        slots. The trip is DISPATCHED here and stays pending: its tokens
        are read (:meth:`_retire_pending`) only after the next trip has
        been dispatched, so the chip has work queued while the host folds
        tokens, admits and dispatches prefill chunks. What is known
        without the tokens advances now (positions, block reservations);
        what the tokens decide (``generated``, counters, results, slot
        release) advances when the trip is retired. A speculative server
        retires at once: its drafters read the tokens on the host."""
        k = self.tick_window if ticks is None else ticks
        tel = self._tel
        # (under pool pressure a preemption in here retires the pending
        # trip first, and the rows it ended drop out of ``active``)
        active = self._reserve_active(
            active, lambda s: -(-(int(self.pos[s]) + k) // self.block_size))
        if not active:
            if tel.enabled:
                self._last_prog = "stalled"
            return
        # the tick's waiting prompt chunk rides in this trip's program call
        ck = self._take_rider()
        if tel.enabled:
            # program key: tick count + greedy specialization are the
            # static jit-cache axes of the plain decode program
            self._last_prog = (
                f"plain{'' if ck is None else '+chunk'}"
                f":t{'w' if ticks is None else ticks}"
                f":g{int(self._all_greedy(active))}")
        tick, rows = self._tick_seq, len(active)
        prev = self._trips[-1] if self._trips else None
        with tel.phase("decode_dispatch", tick, rows=rows):
            # the greedy-specialized programs never read the key — skip
            # the per-step eager fold_in dispatch (~0.4ms) for it
            key = (self._base_key if self._all_greedy(active)
                   else jax.random.fold_in(self._base_key, self._step_no))
            active_mask = np.zeros((self.max_batch,), np.int32)
            active_mask[active] = 1
            # where a row's input token comes from: 1 = the host's array
            # (a first token, a resume, a replay, a trip read early), 2 =
            # the last row of the pending trip's stack, which never visits
            # the host; the program merges the two
            feed = active_mask if prev is None \
                else active_mask * (1 + prev.mask)
            # idle/prefilling rows run masked: zeroed table + pos 0 routes
            # their (discarded) cache writes to the scratch block
            bt = np.where(active_mask[:, None] > 0, self._bt, 0)
            posv = self.pos * active_mask
            temps, topks, topps, _, aidx = self._samp_arrays()
            if ck is None:
                stack, self._pools, self._slot_pools, *stats = \
                    self._call(
                        "decode_paged", self._decode_paged,
                        self.params, jnp.asarray(self.tokens), self._pools,
                        jnp.asarray(bt), jnp.asarray(posv), temps, topks,
                        topps, jnp.asarray(feed), key, aidx,
                        self._lora_flat(), self._all_greedy(active), ticks,
                        self._slot_pools, self._exec.prev_stack(prev, k))
            else:
                _t0 = tel.clock() if tel.enabled else 0.0
                _w0 = self._wall()
                stack, lg, self._pools, self._slot_pools, *stats = \
                    self._call(
                        "decode_chunk", self._decode_chunk,
                        self.params, jnp.asarray(self.tokens), self._pools,
                        jnp.asarray(bt), jnp.asarray(posv), temps, topks,
                        topps, jnp.asarray(feed), key,
                        self._exec.prev_stack(prev, k),
                        *self._chunk_operands(ck), self._all_greedy(active),
                        self._slot_pools, self._slot_operand(ck))
                ck.call = self._calls_out
                self._c_pf_fused.inc()
                self._chunk_dispatched(ck, _t0, _w0)
            # rows that this trip takes to the end of their budget: the
            # harvest will release them, the next trip leaves them out
            ends = []
            for s in active:
                req = self._slots[s]
                flying = prev.k if feed[s] == 2 else 0
                if k >= min(req.max_new_tokens - len(req.generated) - flying,
                            self.max_len - 1 - int(self.pos[s])):
                    ends.append(s)
            # the step stats of this call, and of the chunk calls made
            # since the trip before, come back with this trip's tokens
            self._trips.append(_Trip(stack, active, active_mask,
                                     frozenset(ends), k, tick,
                                     tuple(self._stats_unread) + tuple(stats),
                                     self._calls_out))
            self._stats_unread = []
            self._trip_no += 1
            self.pos = self.pos + active_mask * k
        self._retire_pending(keep=1)
        if ck is not None:
            # a final chunk's first token: the one sync on THIS call, made
            # after the trip before has been folded (that fold overlaps the
            # call); the slot joins the decode rows from the next tick
            self._chunk_ended(ck, lg)
        if self.spec is not None:
            self._retire_pending("spec")

    def _retire_pending(self, reason: Optional[str] = None,
                        keep: int = 0) -> None:
        """Wait for and harvest the decode trips still unread, oldest
        first, down to the newest ``keep``: the tick's ``decode_wait`` and
        ``harvest`` phases, each naming in ``trip`` the tick that
        dispatched what it reads. ``reason`` None is the overlapped order
        (called after the next dispatch); everything that reads or moves
        per-slot state — a preemption, a snapshot, a cancel, the executor's
        ``save_slot`` — and every tick that dispatches nothing calls it
        with the reason it could not wait, so that ``pos`` / ``tokens`` /
        ``generated`` and the device agree when it returns."""
        tel = self._tel
        if self._failed is not None:
            # the device is not to be trusted, nor waited for: forget the
            # trips; positions fall back to the last harvest, which is
            # what ``generated`` holds
            for trip in self._trips:
                self.pos = self.pos - trip.mask * trip.k
                for s in trip.rows:
                    if self._slots[s] is not None and self._slots[s].done:
                        self._release_slot(s)
            self._trips = []
        while len(self._trips) > keep:
            trip = self._trips[0]
            rows = len(trip.rows)
            # the trip's one host sync, apart from the fold that follows it
            with tel.phase("decode_wait", self._tick_seq, rows=rows,
                           trip=trip.tick) as ph:
                nxt_host = np.asarray(trip.stack)
            # (a read between steps names its reason, not the phase)
            self._call_read(trip.call, ph,
                            reason if self._tick_seq < 0 else None)
            self._fold_step_stats(trip.stats)
            del self._trips[0]
            if reason is None:
                self._c_overlapped.inc()
            else:
                self._c_early.inc(reason=reason)
            self._harvest_phase(rows, trip.tick, self._harvest_window,
                                nxt_host, trip.rows, trip.mask)
            self._queue_settle()
        if keep == 0 and self._stats_unread:
            # chunk calls that no decode trip followed (a server that only
            # prefills; the last chunks before a read of the counters):
            # the newest of them is the newest call of all
            self._fold_step_stats(self._stats_unread)
            self._stats_unread = []
            self._calls_drained(reason or "stats")

    def _fold_step_stats(self, stats) -> None:
        """Add the per-layer expert loads that program calls returned beside
        their outputs (``(ticks, layers, held + 1)`` int32 a call: the rows
        each held expert got, and in the last column the pairs routed to
        experts that live elsewhere) to the ``serving_moe_*`` counters. The
        arrays are outputs of calls whose tokens the host has just read, or
        of calls before those: reading them waits for nothing."""
        for a in stats:
            a = np.asarray(a)           # graftlint: noqa[host-sync]
            held = a[..., :-1]
            self._c_moe_pairs.inc(int(held.sum()), held="1")
            self._c_moe_pairs.inc(int(a[..., -1].sum()), held="0")
            self._c_moe_active.inc(int((held > 0).sum()))
            self._c_moe_load_max.inc(int(held.max(axis=-1).sum()))

    # ----------------------------------------------------------- speculative
    def _spec_tick(self, active) -> None:
        """One speculative server tick: draft k tokens per decoding slot,
        verify all k+1 window positions in one fused program, accept/reject
        exactly — emitting 1..k+1 tokens per slot per window with the same
        compiled shapes every tick regardless of acceptance. Fusible
        drafters scan ``tick_window`` whole windows on device per host
        round trip; host-side drafters run one window per trip."""
        if self._spec_fused and self._faults.enabled \
                and self._faults.fire("drafter") is not None:
            # a fused drafter proposes IN-program, so its host propose()
            # hook never runs — the injector consults the site here,
            # before any reservation or dispatch
            from .speculative import DrafterFault

            raise DrafterFault("injected drafter failure (fused path)")
        k = self.spec_k
        S = self._spec_windows
        if self._spec_turbo and self.spec.turbo_windows > S:
            S = self.spec.turbo_windows
        # reserve blocks for every window of the trip up front (speculative
        # append); rejected-draft tail entries are truncated back in harvest
        tel = self._tel
        active = self._reserve_active(
            active, lambda s: -(-(int(self.pos[s]) + S * (k + 1)) //
                                self.block_size))
        if not active:
            if tel.enabled:
                self._last_prog = "stalled"
            return
        if tel.enabled:
            self._last_prog = (f"spec:w{S}"
                               f":g{int(self._all_greedy(active))}")
            _t0 = tel.clock()
            _rids = [(s, self._slots[s].rid) for s in active]
            _kc = {s: int(self.kcaps[s]) for s in active}
        tick, rows = self._tick_seq, len(active)
        with tel.phase("decode_dispatch", tick, rows=rows):
            key = (self._base_key if self._all_greedy(active)
                   else jax.random.fold_in(self._base_key, self._step_no))
            active_mask = np.zeros((self.max_batch,), np.int32)
            active_mask[active] = 1
            bt = np.where(active_mask[:, None] > 0, self._bt, 0)
            posv = self.pos * active_mask
            # nonzero kcaps exist only on activated, unreleased slots —
            # exactly the active set — so the cached device kcaps already
            # carries the idle/prefilling row masking
            temps, topks, topps, kcaps, aidx = self._samp_arrays()
            if self._spec_fused:
                ctx = np.zeros((self.max_batch, self.max_len), np.int32)
                for s in active:
                    req = self._slots[s]
                    toks = req.prompt + req.generated
                    ctx[s, :len(toks)] = toks
                outs, accs, self._pools = self._call(
                    "spec_scan", self._spec_scan,
                    self.params, jnp.asarray(ctx), self._pools,
                    jnp.asarray(bt), jnp.asarray(posv), temps, topks, topps,
                    kcaps, jnp.asarray(active_mask), key, aidx,
                    self._lora_flat(), self._all_greedy(active), S)
            else:
                contexts: List[Optional[List[int]]] = [None] * self.max_batch
                for s in active:
                    req = self._slots[s]
                    contexts[s] = req.prompt + req.generated
                proposals, qprobs = self.drafter.propose(
                    contexts, k, temps=self.temps,
                    key=jax.random.fold_in(key, 1))
                outs, accs, self._pools = self._call(
                    "spec_verify", self._spec_verify,
                    self.params, jnp.asarray(self.tokens),
                    jnp.asarray(proposals), self._pools, jnp.asarray(bt),
                    jnp.asarray(posv), temps, topks, topps,
                    kcaps, jax.random.fold_in(key, 2),
                    None if qprobs is None else jnp.asarray(qprobs),
                    aidx, self._lora_flat(), self._all_greedy(active))
        call = self._calls_out
        with tel.phase("decode_wait", tick, rows=rows, trip=tick) as ph:
            outs, accs = np.asarray(outs), np.asarray(accs)
            if not self._spec_fused:
                outs, accs = outs[None], accs[None]   # one window a trip
        self._call_read(call, ph)
        self._harvest_phase(rows, tick, self._harvest_spec, outs, accs,
                            active)
        self._queue_settle()
        # (a drafter reads the tokens on the host: never left pending)
        self._c_early.inc(reason="spec")
        if tel.enabled:
            _t1 = tel.clock()
            for s, rid in _rids:
                tel.tracer.complete(
                    rid, "spec_window", _t0, _t1,
                    windows=int(accs.shape[0]),
                    accepted=int(accs[:, s].sum()),
                    proposed=int(accs.shape[0]) * _kc[s])
        if self.spec.gate_cooldown:
            m = float(accs[:, active].mean())
            # below gate_low mean accepted drafts/window, drafting is a
            # net loss — fall back to plain decode, probe again later
            if m < self.spec.gate_low:
                self._spec_gate_off = self.spec.gate_cooldown
                self._spec_turbo = False
            else:
                # near-k acceptance across the batch: switch to long
                # trips (turbo_windows per program) so the host round
                # trip amortizes over many more emitted tokens
                self._spec_turbo = (self._spec_fused
                                    and self.spec.turbo_windows > 0
                                    and m >= self.spec_k - 1)

    def _harvest_spec(self, outs, accs, active) -> None:
        """Fold a trip's verify windows into per-request state. Window w of
        row ``s`` emits ``outs[w, s, :accs[w, s]+1]`` (accepted drafts,
        then one correction/bonus) — appended under the exact same
        eos/max-new/max-len walk as :meth:`_harvest_window`, so an eos
        inside a window truncates the bonus token and later drafts (and
        any later windows) and final results match the plain server token
        for token. Surviving slots advance ``pos`` by the emitted count
        and give back the blocks reserved for rejected drafts
        (``BlockAllocator.truncate`` — refcount-safe rollback; the
        rejected positions' stale K/V is overwritten by the next window
        before any query can attend it)."""
        S = outs.shape[0]
        rows = ctx = 0          # work folded, as in _harvest_window
        for s in active:
            req = self._slots[s]
            kcap = int(self.kcaps[s])
            pos0 = new_pos = int(self.pos[s])
            last_tok = int(self.tokens[s])
            done = False
            if self.eos is None:
                # no-eos fast path: the only stop conditions are budget
                # counters, so each window's emission is a slice — skips
                # the per-token python walk (~1ms/trip at bench shapes)
                gen = req.generated
                for w in range(S):
                    a = int(accs[w, s])
                    self._spec_proposed += kcap
                    self._spec_accepted += a
                    limit = min(req.max_new_tokens - len(gen),
                                self.max_len - 1 - new_pos)
                    take = a + 1
                    if take >= limit:
                        take = limit
                        done = True
                    # outs is host numpy by the time harvest runs — the
                    # one sync already happened in _spec_tick
                    gen.extend(outs[w, s, :take].tolist())  # graftlint: noqa[host-sync]
                    new_pos += take
                    if done:
                        break
                d = new_pos - pos0
                rows += d
                ctx += d * pos0 + d * (d + 1) // 2
                if done:
                    self._emit_result(req)
                    self._release_slot(s)
                else:
                    self.pos[s] = new_pos
                    self.tokens[s] = gen[-1]
                    req.table = self.alloc.truncate(req.table, new_pos)
                    self._bt[s, len(req.table):] = 0
                continue
            n0 = len(req.generated)
            for w in range(S):
                a = int(accs[w, s])
                self._spec_proposed += kcap
                self._spec_accepted += a
                for j in range(a + 1):
                    tok = int(outs[w, s, j])
                    finished_last = (self.eos is not None and
                                     req.generated[-1] == self.eos)
                    if not finished_last:
                        req.generated.append(tok)
                    pos_t = new_pos + j + 1
                    if (finished_last
                            or len(req.generated) >= req.max_new_tokens
                            or pos_t >= self.max_len - 1):
                        done = True
                        break
                if done:
                    break
                new_pos += a + 1
                last_tok = int(outs[w, s, a])
            d = len(req.generated) - n0
            rows += d
            ctx += d * pos0 + d * (d + 1) // 2
            if done:
                self._emit_result(req)
                self._release_slot(s)
            else:
                self.pos[s] = new_pos
                self.tokens[s] = last_tok
                req.table = self.alloc.truncate(req.table, new_pos)
                self._bt[s, len(req.table):] = 0
        self._c_tokens.inc(rows)
        self._c_dec_rows.inc(rows)
        self._c_dec_ctx.inc(ctx)

    def spec_metrics(self) -> Dict[str, float]:
        """Draft/accept counters for the speculative path (empty when
        spec is off). ``acceptance_rate`` = accepted / proposed drafts."""
        if self.spec is None:
            return {}
        prop = self._spec_proposed
        return {"draft_tokens_proposed": prop,
                "draft_tokens_accepted": self._spec_accepted,
                "acceptance_rate":
                    (self._spec_accepted / prop) if prop else 0.0,
                "gated_plain_windows": self._spec_plain_windows}

    def _emit_result(self, req: _Request) -> None:
        """A request finished: publish its tokens, close its metrics —
        TTFT/TPOT are observed HERE (at completion) into the registry
        histograms, making the tenant breakdown and the benchmark's
        percentiles two views of the same samples."""
        self._results[req.rid] = req.prompt + req.generated[
            :req.max_new_tokens]
        m = self._req_metrics.get(req.rid)
        if m is not None:
            m["done_t"] = self._wall()
            m["n_generated"] = min(len(req.generated), req.max_new_tokens)
            tenant = m.get("tenant", "default")
            pr = (req.sched.priority if req.sched is not None
                  else PRIORITY_NORMAL)
            self._c_completed.inc(tenant=tenant)
            if "first_token_t" in m:
                self._h_ttft.observe(m["first_token_t"] - m["submit_t"],
                                     tenant=tenant, priority=pr)
                self._h_e2e.observe(m["done_t"] - m["submit_t"],
                                    tenant=tenant)
                n = int(m["n_generated"])
                if n > 1:
                    self._h_tpot.observe(
                        (m["done_t"] - m["first_token_t"]) / (n - 1) * 1e3,
                        tenant=tenant)
        self._tel.tracer.close(req.rid, "complete")

    # ---------------------------------------------------- request lifecycle
    def cancel(self, rid: int) -> bool:
        """Cooperative cancel, effective immediately at the host level: a
        waiting (or swapped-out) request leaves the queue and any parked
        host KV is discarded; a running request's blocks — including the
        speculative-window tail reservation — roll back through the same
        refcount-safe ``BlockAllocator.truncate`` path that speculative
        rejection uses, returning the allocator to its pre-submit
        occupancy. Returns False for unknown or already-finished rids;
        cancelled requests never appear in results (``status(rid)`` says
        ``"cancelled"``)."""
        ent = self._sched.cancel(rid)
        if ent is not None:
            self._drop_entry(ent, "cancelled")
            return True
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is not None and req.rid == rid:
                # its blocks go back to the pool: the trip that still
                # writes them is read first, and may have finished it
                self._retire_pending("cancel")
                if self._slots[s] is not req:
                    return False
                if self.cache_mode == "paged":
                    req.table = self.alloc.truncate(req.table, 0)
                self._dropped[rid] = "cancelled"
                self._c_dropped.inc(reason="cancelled")
                m = self._req_metrics.get(rid)
                if m is not None:
                    m["done_t"] = self._wall()
                self._tel.tracer.close(rid, "cancelled")
                self._release_slot(s)
                return True
        return False

    def status(self, rid: int) -> str:
        """One of ``done / cancelled / expired / failed / running /
        prefilling / swapped / preempted / queued / unknown``
        (``failed`` = quarantined after exhausting its fault-retry
        budget; terminal, with a telemetry record)."""
        if rid in self._results:
            return "done"
        if rid in self._dropped:
            return self._dropped[rid]
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is not None and req.rid == rid:
                return "prefilling" if (self.cache_mode == "paged"
                                        and self._prefilling[s]) \
                    else "running"
        for ent in self._sched.waiting():
            if ent.rid == rid:
                if ent.swap is not None:
                    return "swapped"
                return "preempted" if ent.preempted else "queued"
        return "unknown"

    def sched_metrics(self) -> Dict[str, Any]:
        """Scheduler + preemption counters (all cache modes; swap fields
        appear on the paged path only; adapter-pool fields and the
        per-tenant TTFT/TPOT breakdown when ``lora=`` is configured)."""
        # thin view over the metrics registry: the counters below ARE the
        # values the registry exposes via to_json()/to_prometheus() — the
        # dict shape is the stable public contract, the registry is the
        # store (attach_metrics seeds scheduler history, so totals always
        # match the legacy int attributes)
        reg = self._tel.registry
        m = {"policy": self._sched.policy,
             "queue_depth": len(self._sched),
             "submitted": int(reg.counter(
                 "sched_requests_submitted").total()),
             "expired": int(reg.counter("sched_requests_expired").total()),
             "cancelled": int(self._c_dropped.total(
                 where={"reason": "cancelled"})),
             "preemptions": int(self._c_preempt.total()),
             "prefill_aborts": int(self._c_aborts.total()),
             "resumes": int(self._c_resumes.total()),
             "stalled_reservations": int(self._c_stalls.total())}
        if self.cache_mode == "paged":
            m["host_bytes_in_use"] = self._offload.host.bytes_in_use
            m["host_bytes_peak"] = self._offload.host.bytes_peak
            m["swapped_waiting"] = sum(
                1 for e in self._sched.waiting() if e.swap is not None)
        m["tenants"] = self._tenant_breakdown()
        if self._lora is not None:
            m.update(self._lora.stats())
        return m

    def _tenant_breakdown(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant latency percentiles over COMPLETED requests: TTFT
        (submit → first token) and TPOT (per-token after the first) p50 /
        p95 — the multi-tenant fairness view the benchmark reports. A
        thin view over the registry's ``serving_ttft_s`` /
        ``serving_tpot_ms`` histograms (observed at completion in
        ``_emit_result``), so these numbers and the exposition formats
        can never drift apart."""
        out: Dict[str, Dict[str, float]] = {}
        for t in self._h_ttft.label_values("tenant"):
            xs = self._h_ttft.samples({"tenant": t})
            row = {"completed": float(len(xs))}
            if xs:
                row["ttft_p50_ms"] = float(np.percentile(xs, 50) * 1e3)
                row["ttft_p95_ms"] = float(np.percentile(xs, 95) * 1e3)
            tp = self._h_tpot.samples({"tenant": t})
            if tp:
                row["tpot_p50_ms"] = float(np.percentile(tp, 50))
                row["tpot_p95_ms"] = float(np.percentile(tp, 95))
            out[t] = row
        return out

    def request_metrics(self) -> Dict[int, Dict[str, float]]:
        """Per-rid wall-clock marks — ``submit_t``, ``first_token_t``,
        ``done_t``, ``n_generated`` (plus the request's ``tenant``) —
        from which TTFT and per-token latency are derived
        (tools/serving_benchmark.py). The marks of the pending trip are
        among them: it is read first, so between steps this is also the
        call after which ``pos`` / ``generated`` and the device agree."""
        self._retire_pending("metrics")
        return self._req_metrics

    def _release_slot(self, slot: int) -> None:
        req = self._slots[slot]
        self._slots[slot] = None
        if self.cache_mode == "paged":
            for bid in req.table:
                self.alloc.free(bid)
            req.table = []
            self._bt[slot, :] = 0
            self._prefilling[slot] = None
            self.pos[slot] = 0
            self.tokens[slot] = 0
            self.temps[slot] = 0.0
            self.topks[slot] = 0
            self.topps[slot] = 0.0
            if self.spec is not None:
                self.kcaps[slot] = 0
            if self._lora is not None:
                self._lora.release(int(self.aidx[slot]))
                self.aidx[slot] = 0
            self._samp_dev = None

    def kv_stats(self) -> Dict[str, int]:
        """Paged-pool occupancy/prefix-cache counters, merged with the
        warm-tier ledger (``warm_*`` keys) and the cold-refill count
        (empty for dense)."""
        if self.cache_mode != "paged":
            return {}
        out = self.alloc.stats()
        out.update(self._offload.tier_stats())
        out["cold_refills"] = self._cold_refills
        out.update(self.cache_bytes())
        return out

    def cache_bytes(self) -> Dict[str, int]:
        """Bytes in use and allotted, by cache kind of the spec: ``full``
        and ``latent`` in blocks of the shared pool (a latent row as the
        device holds it, pad lanes included), ``window`` and ``state`` per
        occupied slot (``serving_cache_bytes{kind=}`` in the registry)."""
        occupied = sum(sl is not None for sl in self._slots)
        per_slot = self.cache_spec.slot_bytes(self.block_size)
        lat = self.cache_spec.latent_block_bytes(self.block_size)
        full = self.alloc.bytes_per_block - lat
        usable = self.alloc.num_blocks - 1
        out = {"cache_bytes_full": self.alloc.blocks_in_use * full,
               "cache_bytes_full_allotted": usable * full,
               "state_slots": occupied if per_slot["state"] else 0}
        if lat:
            out["cache_bytes_latent"] = self.alloc.blocks_in_use * lat
            out["cache_bytes_latent_allotted"] = usable * lat
        g = self._tel.registry.gauge("serving_cache_bytes")
        for kind in ("window", "state"):
            out[f"cache_bytes_{kind}"] = occupied * per_slot[kind]
            out[f"cache_bytes_{kind}_allotted"] = (self.max_batch
                                                   * per_slot[kind])
        for kind in ("full", "latent", "window", "state"):
            if f"cache_bytes_{kind}" in out:
                g.set(float(out[f"cache_bytes_{kind}"]), kind=kind)
        self._tel.registry.gauge("serving_state_slots").set(
            float(out["state_slots"]))
        return out

    # ------------------------------------------------------ fault tolerance
    def assert_conserved(self) -> Dict[str, int]:
        """Pool conservation invariants — raises AssertionError on a leak.

        Checked between steps (the chaos tests call this after EVERY
        tick, so a leak surfaces at the faulting tick, not at teardown):

        - block identity: ``in_use + cached + free == num_blocks - 1``
          (block 0 is scratch) and no block is left pinned;
        - refcount audit: the allocator's live refcounts equal the
          multiset of block-table entries across occupied slots;
        - host-pool audit: parked bytes equal the sum over waiting
          swapped entries, in BOTH byte ledgers (pool and allocator);
        - adapter-pool audit (when ``lora=``): same identity over pages,
          and page refs equal the occupied slots holding each page.

        Returns the audited numbers (handy for test output). Dense-cache
        servers have no pools to audit and return ``{}``."""
        if self.cache_mode != "paged":
            return {}
        from collections import Counter

        a = self.alloc
        errs: List[str] = []
        usable = a.num_blocks - 1
        if a.blocks_in_use + a.blocks_cached + a.blocks_free != usable:
            errs.append(
                f"block identity broken: in_use={a.blocks_in_use} + "
                f"cached={a.blocks_cached} + free={a.blocks_free} != "
                f"usable={usable}")
        if a.pinned_blocks != 0:
            errs.append(f"{a.pinned_blocks} blocks left pinned between "
                        f"steps (pins must be copy-scoped)")
        expect: Counter = Counter()
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is not None:
                expect.update(req.table)
        refs = a.ref_counts()
        if dict(expect) != refs:
            extra = {b: n for b, n in refs.items() if expect.get(b) != n}
            missing = {b: n for b, n in expect.items() if refs.get(b) != n}
            errs.append(f"refcount audit failed: allocator-only={extra} "
                        f"tables-only={missing}")
        swapped = [e for e in self._sched.waiting() if e.swap is not None]
        parked = sum(e.swap.nbytes for e in swapped)
        if self._offload.host.bytes_in_use != parked:
            errs.append(f"host pool ledger {self._offload.host.bytes_in_use}"
                        f" != sum of waiting swap handles {parked}")
        if a.host_bytes_in_use != parked:
            errs.append(f"allocator host ledger {a.host_bytes_in_use} != "
                        f"sum of waiting swap handles {parked}")
        if len(self._offload.host) != len(swapped):
            errs.append(f"host pool parks {len(self._offload.host)} "
                        f"payloads but {len(swapped)} entries are swapped")
        warm = self._offload.warm
        warm_bytes = sum(nb for _, _, nb, _ in warm.entries())
        if warm_bytes != warm.bytes_in_use:
            errs.append(f"warm tier ledger {warm.bytes_in_use} != sum of "
                        f"parked entries {warm_bytes}")
        dual = [h for h, _, _, _ in warm.entries()
                if a.contains_hash(h)]
        if dual:
            # promotion takes the warm copy and demotion unregisters the
            # hot block — a hash resident in BOTH tiers means one of
            # those handoffs half-finished
            errs.append(f"{len(dual)} chain hashes resident in both the "
                        f"hot prefix cache and the warm tier")
        if self._lora is not None:
            la = self._lora.alloc
            lu = la.num_blocks - 1
            if la.blocks_in_use + la.blocks_cached + la.blocks_free != lu:
                errs.append(
                    f"adapter page identity broken: in_use="
                    f"{la.blocks_in_use} + cached={la.blocks_cached} + "
                    f"free={la.blocks_free} != usable={lu}")
            pexp: Counter = Counter()
            for s in range(self.max_batch):
                if self._slots[s] is not None and int(self.aidx[s]) > 0:
                    pexp[int(self.aidx[s])] += 1
            if dict(pexp) != la.ref_counts():
                errs.append(f"adapter page refs {la.ref_counts()} != "
                            f"slot aidx multiset {dict(pexp)}")
        if errs:
            raise AssertionError("; ".join(errs))
        out = {"blocks_in_use": a.blocks_in_use,
               "blocks_cached": a.blocks_cached,
               "blocks_free": a.blocks_free,
               "host_bytes_in_use": parked,
               "warm_blocks": len(warm),
               "warm_bytes_in_use": warm.bytes_in_use,
               "swapped_waiting": len(swapped)}
        # per-shard pool audit (tp executors): donation must rotate the
        # pool buffers without ever resharding them — raises on a lost
        # tp layout, and reports the per-shard accounting alongside
        out.update(self._exec.shard_audit())
        return out

    def _snapshot_fingerprint(self) -> Dict[str, Any]:
        """Shape-critical configuration a snapshot can only restore into:
        these fields decide the compiled programs' shapes and the KV
        payloads' fixed gather width."""
        return {"cache": self.cache_mode,
                "block_size": self.block_size,
                "max_len": self.max_len,
                "max_batch": self.max_batch,
                "kv_quant": self.kv_quant,
                "tick_window": self.tick_window,
                "table_width": self._table_width,
                "num_blocks": self.alloc.num_blocks,
                "spec_k": self.spec_k if self.spec is not None else None,
                "lora": self._lora is not None,
                "kernels": self.kernels,
                # resolved per-layer kernel geometry (non-default ops
                # only; None when everything runs the default schedule,
                # which keeps pre-geometry snapshots restorable)
                "kernel_geometry": ({op: g.asdict()
                                     for op, (g, src)
                                     in self.kernel_geometry.items()
                                     if src != "default"} or None),
                "mesh": self._exec.mesh_fingerprint}

    def _req_state(self, req: _Request) -> Dict[str, Any]:
        return {"rid": req.rid, "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "temperature": req.temperature, "top_k": req.top_k,
                "top_p": req.top_p, "generated": list(req.generated),
                "draft_k": req.draft_k, "adapter": req.adapter,
                "replay": (list(req.replay) if req.replay is not None
                           else None),
                "hashes": list(req.hashes)}

    def _sched_state(self, ent: SchedEntry) -> Dict[str, Any]:
        now = self._sched.now()
        return {"priority": ent.priority, "tenant": ent.tenant,
                "ttl_remaining": (None if ent.deadline is None
                                  else max(ent.deadline - now, 0.0)),
                "seq": ent.seq, "cost": ent.cost, "vtag": ent.vtag,
                "preempted": ent.preempted, "started": ent.started}

    def snapshot(self, *, trust_kv: bool = True) -> Dict[str, Any]:
        """Crash-safe capture of the full in-flight engine state — the
        drain/migrate primitive (ROADMAP 5): every queued, prefilling,
        decoding, and swapped request, with enough state that
        :meth:`restore` on a FRESH server continues each one with
        greedy-token-identical output.

        Decoding slots' KV rides the offload engine's compile-once
        fixed-width gather (non-destructive — the captured server keeps
        serving); already-swapped entries copy their parked host arrays;
        prefilling/queued work is recomputable and restores as queued.
        Per-payload CRC checksums ride along, so a payload corrupted in
        transit degrades to re-prefill on the restoring side instead of
        wrong tokens. Host-only: zero compiled programs on a warm
        server, zero device state mutated. Paged servers only.

        ``trust_kv=False`` captures decoding slots as replay-queued work
        (prompt + generated so far, re-prefilled token-exactly on the
        restoring side) instead of gathering their device KV — the
        salvage mode for an engine whose device state can no longer be
        trusted (a failed replica): host-side request state is always
        consistent at the last completed harvest, the device pools may
        not be. Already-swapped entries keep their KV payloads either
        way — those live in host RAM behind a CRC, not on the device."""
        if self.cache_mode != "paged":
            raise ValueError("snapshot() requires cache='paged' — the "
                             "dense slab has no per-request KV capture")
        if self._failed is not None and trust_kv:
            raise ValueError(
                f"server failed ({self._failed}): device KV is untrusted "
                f"after a post-dispatch failure — capture with "
                f"snapshot(trust_kv=False) to salvage from host state")
        from .kv_offload import payload_checksum

        # what is captured is retired state (a failed engine's unread trips
        # are forgotten instead: the salvage trusts the last harvest only)
        self._retire_pending("snapshot")
        reqs: List[Dict[str, Any]] = []
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is None:
                continue
            d = self._req_state(req)
            d["sched"] = self._sched_state(req.sched)
            if self._prefilling[s]:
                # prefill is recomputable (and must be: its KV covers an
                # unfinished chunk boundary) — restore re-queues it
                d["phase"] = "queued"
            elif not trust_kv:
                # salvage: re-enter through the corruption-recovery replay
                # rung — re-prefill prompt+generated[:-1], resume decode at
                # the saved position with the last generated token as the
                # next input; token-identical by the same argument as the
                # CRC-mismatch fallback
                d["phase"] = "queued"
                d["replay"] = (list(req.prompt)
                               + list(req.generated))[:int(self.pos[s])]
            else:
                extra = self._save_slot_state(s, req.rid, "snapshot")
                arrays = self._offload.gather_payload(req.table,
                                                      self._pools) + extra
                d["phase"] = "kv"
                d["kv"] = {
                    "arrays": arrays,
                    "n_tokens": int(self.pos[s]),
                    "last_token": int(self.tokens[s]),
                    "n_blocks": len(req.table),
                    "n_extra": len(extra),
                    "hashes": list(
                        req.hashes[:min(len(req.hashes), len(req.table))]),
                    "nbytes": (len(req.table) * self.alloc.bytes_per_block
                               + sum(a.nbytes for a in extra)),
                    "checksum": payload_checksum(arrays)}
            reqs.append(d)
        for ent in self._sched.waiting():
            d = self._req_state(ent.req)
            d["sched"] = self._sched_state(ent)
            if ent.swap is not None:
                h = ent.swap
                arrays = [np.array(a)
                          for a in self._offload.host.peek(h.rid)]
                d["phase"] = "kv"
                d["kv"] = {"arrays": arrays, "n_tokens": h.n_tokens,
                           "last_token": h.last_token,
                           "n_blocks": h.n_blocks, "n_extra": h.n_extra,
                           "hashes": list(h.hashes), "nbytes": h.nbytes,
                           "checksum": h.checksum}
            else:
                d["phase"] = "queued"
            reqs.append(d)
        snap: Dict[str, Any] = {
            "format": 1,
            "config": self._snapshot_fingerprint(),
            "rng_key": np.asarray(self._base_key),
            "step_no": self._step_no,
            "next_rid": self._next_rid,
            "sched": {"vnow": self._sched._vnow,
                      "tenant_tag": dict(self._sched._tenant_tag)},
            "requests": reqs,
            "results": {r: list(t) for r, t in self._results.items()},
            "dropped": dict(self._dropped),
            # the warm tier rides along in BOTH modes: its payloads are
            # host RAM behind per-block CRCs (like swapped entries), so
            # an untrusted device never taints them; the restoring side
            # adopts them via adopt_warm (CRC-verified, best-effort)
            "warm_tier": [
                {"hash": h, "arrays": [np.array(x) for x in arrs],
                 "nbytes": nb, "checksum": crc}
                for h, arrs, nb, crc in self._offload.warm.entries()],
        }
        if self.spec is not None:
            snap["spec_state"] = {
                "gate_off": self._spec_gate_off,
                "plain_windows": self._spec_plain_windows,
                "turbo": self._spec_turbo,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted}
        return snap

    def restore(self, snap: Dict[str, Any]) -> int:
        """Rebuild a :meth:`snapshot` into THIS (idle, freshly built)
        server; returns the number of requests restored.

        Every request re-enters through the normal machinery — KV-bearing
        requests become swapped queue entries whose payload is adopted
        into the host pool and restored by the compile-once, CRC-verified
        swap-in path at the next step; queued/prefilling work re-queues.
        Greedy continuation is token-identical to the captured server's
        because resume is the same bit-exact path preemption already
        proves out, and the sampling key + step counter come along."""
        if self.cache_mode != "paged":
            raise ValueError("restore() requires cache='paged'")
        if self._failed is not None:
            raise ValueError(f"cannot restore into a failed server "
                             f"({self._failed}) — build a fresh one")
        self._retire_pending("restore")
        if any(sl is not None for sl in self._slots) or len(self._sched):
            raise ValueError("restore() needs an idle server: slots and "
                             "queue must be empty")
        if snap.get("format") != 1:
            raise ValueError(f"unknown snapshot format "
                             f"{snap.get('format')!r}")
        self._check_snapshot_config(snap["config"])
        # pre-flight the per-request ladder BEFORE mutating anything: a
        # mid-loop rejection (unknown adapter) must leave this server
        # exactly as it was — a partial restore would be corruption, not
        # an error
        for d in snap["requests"]:
            self._validate_snapshot_request(d)
        self._base_key = jnp.asarray(np.asarray(snap["rng_key"]))
        self._step_no = int(snap["step_no"])
        self._next_rid = max(self._next_rid, int(snap["next_rid"]))
        self._sched.restore_state(snap["sched"]["vnow"],
                                  snap["sched"]["tenant_tag"])
        if self.spec is not None and "spec_state" in snap:
            st = snap["spec_state"]
            self._spec_gate_off = int(st["gate_off"])
            self._spec_plain_windows = int(st["plain_windows"])
            self._spec_turbo = bool(st["turbo"])
            self._spec_proposed = int(st["proposed"])
            self._spec_accepted = int(st["accepted"])
        self._results.update(
            {int(r): list(t) for r, t in snap["results"].items()})
        self._dropped.update(snap["dropped"])
        now = self._sched.now()
        restored = 0
        for d in sorted(snap["requests"], key=lambda d: d["sched"]["seq"]):
            self._admit_snapshot_request(d, now)
            restored += 1
        self.adopt_warm(snap.get("warm_tier", ()))
        return restored

    def _check_snapshot_config(self, want: Dict[str, Any]) -> None:
        """Validate a snapshot's config fingerprint against this server's
        (shared by :meth:`restore` and :meth:`admit_migrated`)."""
        have = self._snapshot_fingerprint()
        for k, hv in have.items():
            wv = want.get(k)
            if k == "mesh":
                # provenance stamp, not a gate: snapshot KV payloads are
                # full-width host gathers, so any tp restores into any tp
                # (fleet homogeneity still compares it — replicas must
                # agree — but restore/migration across layouts is legal)
                continue
            if k == "num_blocks":
                if hv < wv:
                    raise ValueError(
                        f"restoring pool has {hv} blocks but the snapshot "
                        f"was taken with {wv} — a smaller pool cannot "
                        f"guarantee the captured requests stay feasible")
            elif hv != wv:
                raise ValueError(
                    f"snapshot/server config mismatch on {k!r}: snapshot "
                    f"has {wv!r}, this server has {hv!r}")

    def _validate_snapshot_request(self, d: Dict[str, Any]) -> None:
        """Reject-at-the-door checks for one snapshot request dict —
        must run before ANY server state mutates."""
        if self.role == "prefill" and (d.get("phase") == "kv"
                                       or d.get("generated")):
            # the prefill class runs chunked prefill ONLY: decode-phase
            # work (a KV payload, or any request that already generated
            # tokens and would resume decoding) belongs to the decode
            # class — admitting it here would wedge it parked forever
            raise ValueError(
                f"prefill-class replica cannot admit decode-phase "
                f"request {d['rid']} (phase={d.get('phase')!r}, "
                f"{len(d.get('generated') or ())} generated tokens) — "
                f"route it to the decode class")
        if d["adapter"] is not None:
            if self._lora is None:
                raise ValueError(
                    f"request {d['rid']} names adapter "
                    f"{d['adapter']!r} but this server has no lora=")
            self._lora.validate(d["adapter"])

    def _admit_snapshot_request(self, d: Dict[str, Any],
                                now: float) -> None:
        """Re-admit one validated snapshot request dict through the
        normal machinery: KV payloads are adopted into the host pool and
        re-enter via the CRC-verified swap-in path; queued/replay work
        re-queues. The per-request half of :meth:`restore`, shared with
        :meth:`admit_migrated`."""
        from .kv_offload import SwapHandle

        req = _Request(int(d["rid"]), list(d["prompt"]),
                       int(d["max_new_tokens"]),
                       temperature=float(d["temperature"]),
                       top_k=int(d["top_k"]), top_p=float(d["top_p"]),
                       draft_k=d["draft_k"], adapter=d["adapter"])
        req.generated = list(d["generated"])
        req.replay = (list(d["replay"]) if d["replay"] is not None
                      else None)
        req.hashes = list(d["hashes"])
        sd = d["sched"]
        ent = SchedEntry(req=req, rid=req.rid,
                         priority=int(sd["priority"]),
                         tenant=sd["tenant"],
                         deadline=(None if sd["ttl_remaining"] is None
                                   else now + sd["ttl_remaining"]),
                         seq=int(sd["seq"]), cost=float(sd["cost"]),
                         vtag=float(sd["vtag"]),
                         preempted=bool(sd["preempted"]),
                         started=bool(sd["started"]),
                         adapter=req.adapter)
        req.sched = ent
        if d["phase"] == "kv":
            kv = d["kv"]
            handle = SwapHandle(
                rid=req.rid, n_tokens=int(kv["n_tokens"]),
                last_token=int(kv["last_token"]),
                n_blocks=int(kv["n_blocks"]),
                hashes=list(kv["hashes"]), nbytes=int(kv["nbytes"]),
                checksum=int(kv["checksum"]),
                n_extra=int(kv.get("n_extra", 0)))
            self._offload.adopt(
                handle, [np.asarray(a) for a in kv["arrays"]])
            ent.swap = handle
        self._sched.restore_entry(ent)
        # fresh wall-clock marks: the captured server's monotonic
        # clock does not transfer across processes, and mixing the
        # two would observe negative latencies
        m: Dict[str, Any] = {"submit_t": self._wall(),
                             "tenant": ent.tenant}
        if req.generated:
            m["first_token_t"] = m["submit_t"]
        self._req_metrics[req.rid] = m
        if self._tel.enabled:
            tr = self._tel.tracer
            tr.set_meta(req.rid, tenant=ent.tenant,
                        priority=ent.priority,
                        prompt_len=len(req.prompt),
                        adapter=req.adapter or "")
            tr.begin(req.rid, "queued", restored=True)
            self._work_arrived()

    def admit_migrated(self, d: Dict[str, Any], *,
                       source_config: Optional[Dict[str, Any]] = None
                       ) -> int:
        """Admit ONE snapshot request dict into this — possibly busy —
        server: the fleet migration primitive. Unlike :meth:`restore`
        (whole-snapshot, idle target only) this re-admits a single
        request through the same validated path while the target keeps
        serving its own traffic; KV payloads adopt into the host pool
        and resume via the compile-once, CRC-verified swap-in program,
        so a payload corrupted in transit degrades to re-prefill.

        ``source_config`` (the snapshot's ``config`` fingerprint) is
        checked when given — fleet replicas are homogeneous, so the
        router passes it once per migration. The caller guarantees rid
        uniqueness across engines (``FleetRouter`` assigns replicas
        disjoint rid spaces). Returns the admitted rid."""
        if self.cache_mode != "paged":
            raise ValueError("admit_migrated() requires cache='paged'")
        if self._failed is not None:
            from .faults import EngineFailedError

            raise EngineFailedError(
                f"cannot migrate into a failed server ({self._failed})")
        if source_config is not None:
            self._check_snapshot_config(source_config)
        self._validate_snapshot_request(d)
        self._admit_snapshot_request(d, self._sched.now())
        return int(d["rid"])

    def adopt_warm(self, entries: Sequence[Dict[str, Any]]) -> int:
        """Adopt a peer's warm-tier entries (a snapshot's ``warm_tier``
        list) into this server's warm tier — the fleet-wide prefix-cache
        half of a migration: a shared prompt prefilled once on the dying
        replica stays promotable on the survivor. Best-effort and
        CRC-verified per entry: a corrupt payload is dropped (a cache
        may always miss), a hash already hot here is skipped (cross-tier
        exclusivity), and the warm pool's own capacity/LRU rules apply.
        Returns the number of entries adopted."""
        if self.cache_mode != "paged":
            raise ValueError("adopt_warm() requires cache='paged'")
        from .kv_offload import payload_checksum

        adopted = 0
        for d in entries:
            h = int(d["hash"])
            if self.alloc.contains_hash(h) or h in self._offload.warm:
                continue
            arrays = [np.asarray(a) for a in d["arrays"]]
            if payload_checksum(arrays) != int(d["checksum"]):
                self._c_corrupt.inc()
                continue
            if self._offload.warm.put(h, arrays, int(d["nbytes"]),
                                      int(d["checksum"])):
                adopted += 1
        return adopted

    def evacuate(self, *, trust_kv: bool = True,
                 rids: Optional[Sequence[int]] = None) -> Dict[str, Any]:
        """Capture a :meth:`snapshot` and then RELEASE every in-flight
        request from this server — the drain half of a fleet migration:
        the caller re-admits the returned snapshot's requests elsewhere,
        and this engine ends empty (slots free, queue empty, host pool
        drained) so :meth:`assert_conserved` holds trivially afterwards.
        Completed results and dropped markers stay readable on this
        server (and ride the snapshot). ``trust_kv=False`` salvages a
        failed engine from host state only.

        ``rids=``: evacuate ONLY the listed requests (the snapshot's
        ``requests`` list is filtered to them and only they release) —
        the disaggregated prefill→decode handoff primitive: a
        prefill-class replica keeps streaming its other prompts while
        its finished ones (:meth:`handoff_ready`) move to the decode
        class over this same CRC-verified snapshot path."""
        snap = self.snapshot(trust_kv=trust_kv)
        if rids is not None:
            keep = set(int(r) for r in rids)
            snap["requests"] = [d for d in snap["requests"]
                                if d["rid"] in keep]
        else:
            keep = None
        for s in range(self.max_batch):
            req = self._slots[s]
            if req is None or (keep is not None and req.rid not in keep):
                continue
            self._handoff.discard(req.rid)
            req.table = self.alloc.truncate(req.table, 0)
            self._tel.tracer.close(req.rid, "migrated")
            self._release_slot(s)
        for ent in list(self._sched.waiting()):
            if keep is not None and ent.rid not in keep:
                continue
            self._handoff.discard(ent.rid)
            self._sched.remove(ent.rid)
            if ent.swap is not None:
                self._offload.discard(ent.swap)
            self._tel.tracer.close(ent.rid, "migrated")
        if keep is None:
            self._handoff.clear()
            # full drain: the warm entries moved with the snapshot (the
            # router offers them to a survivor via adopt_warm) — drop
            # the local copies so this engine truly ends empty
            self._offload.warm.clear()
        return snap

    def handoff_ready(self) -> List[int]:
        """Rids a prefill-class replica has finished prefilling and
        parked for the decode class — the fleet router's per-step
        handoff sweep passes them straight to
        ``evacuate(trust_kv=True, rids=...)``. Pruned lazily against the
        live request set (a parked request can still be cancelled or
        quarantined out from under the set)."""
        live = {r.rid for r in self._slots if r is not None}
        live.update(e.rid for e in self._sched.waiting())
        self._handoff &= live
        return sorted(self._handoff)

    def take_results(self) -> Dict[int, List[int]]:
        """Pop and return every completed result accumulated so far —
        the incremental-harvest form of :meth:`run`'s return value (the
        fleet router collects per step instead of at drain)."""
        out, self._results = self._results, {}
        return out

    @property
    def steps(self) -> int:
        """Completed engine steps — the fleet router's tick-progress
        heartbeat signal (a replica wedged with queued work but no
        active slot holds work without advancing this)."""
        return self._step_no

    def fail(self, reason: str) -> None:
        """Mark this engine terminally failed (idempotent — the first
        reason sticks). ``submit``/``restore``/``admit_migrated`` refuse
        afterwards; the fleet router uses this to poison a replica the
        chaos plan killed so nothing re-enters it behind the salvage."""
        if self._failed is None:
            self._failed = str(reason)

    def load_metrics(self) -> Dict[str, int]:
        """O(1) load signals for routing decisions — the cheap subset of
        :meth:`sched_metrics` (which builds per-tenant percentile tables
        and is priced for end-of-run reporting, not per-submission
        scoring) plus the allocator's admission headroom."""
        m = {"queue_depth": len(self._sched),
             "slots_occupied": sum(sl is not None for sl in self._slots),
             "slots_total": self.max_batch}
        if self.cache_mode == "paged":
            m["blocks_headroom"] = (self.alloc.blocks_free
                                    + self.alloc.evictable_cached)
            m["queued_kv_demand"] = self._sched.kv_demand()
        return m

    def set_rid_base(self, base: int) -> None:
        """Start this server's rid counter at ``base`` — only valid on a
        fresh server (nothing submitted yet). The fleet router assigns
        each replica a disjoint rid space so migrated requests can never
        collide with a peer's own."""
        if (self._next_rid != 0 or len(self._sched)
                or any(sl is not None for sl in self._slots)
                or self._results or self._dropped):
            raise ValueError("set_rid_base() requires a fresh server — "
                             "rids already handed out would collide")
        if not isinstance(base, int) or isinstance(base, bool) or base < 0:
            raise ValueError(f"rid base must be an int >= 0, got {base!r}")
        self._next_rid = base

    # ------------------------------------------------------- router surface
    # Everything the fleet router needs, as methods rather than attribute
    # walks (``srv.alloc...``, ``srv.telemetry.registry...``), so a remote
    # ReplicaHandle can answer the same questions over one RPC each.
    def probe_prefix(self, prompt: Sequence[int]) -> int:
        """Cached-prefix blocks this server could reuse for ``prompt`` —
        the router's routing-affinity signal. Read-only (takes no refs);
        0 on the dense path, which has no content-addressed cache."""
        if self.cache_mode != "paged":
            return 0
        return self.alloc.probe_prefix(list(prompt))

    def watchdog_findings(self) -> List[Dict[str, Any]]:
        """The flight-recorder watchdog's cumulative findings — the
        router's periodic health probe (see
        :meth:`~paddle_tpu.telemetry.ServingTelemetry.watchdog`)."""
        return self._tel.watchdog()

    def slo_observations(self) -> Dict[str, Dict[str, List[float]]]:
        """Per-tenant latency samples for the fleet SLO roll-up:
        ``{"ttft": {tenant: [seconds...]}, "tpot": {tenant: [ms...]}}``
        read from this server's tenant-labeled histograms. The router
        merges these across replicas instead of reaching into each
        replica's registry — the one shape a remote handle can ship."""
        out: Dict[str, Dict[str, List[float]]] = {"ttft": {}, "tpot": {}}
        for hname, key in (("serving_ttft_s", "ttft"),
                           ("serving_tpot_ms", "tpot")):
            h = self._tel.registry.get(hname)
            if h is None:
                continue
            for tenant in h.label_values("tenant"):
                out[key][tenant] = list(h.samples({"tenant": tenant}))
        return out

    # ------------------------------------------------------------ telemetry
    def telemetry_snapshot(self) -> Dict[str, Any]:
        """Sync point-in-time gauges (pool occupancy, adapter pool, spec
        counters, queue depth) into the registry, then return the full
        telemetry blob: registry JSON (histograms carry computed
        p50/p95), watchdog findings over the flight ring, and the serving
        configuration the numbers were produced under."""
        reg = self._tel.registry
        reg.gauge("serving_queue_depth").set(float(len(self._sched)))
        reg.gauge("serving_slots_occupied").set(
            float(sum(sl is not None for sl in self._slots)))
        reg.gauge("serving_slots_total").set(float(self.max_batch))
        if self.cache_mode == "paged":
            self.alloc.publish(reg)
            for k, v in self._offload.host.stats().items():
                reg.gauge(f"serving_host_pool_{k}").set(float(v))
            for k, v in self._offload.tier_stats().items():
                reg.gauge(f"serving_tier_{k}").set(float(v))
            reg.gauge("serving_tier_cold_refills").set(
                float(self._cold_refills))
        if self._lora is not None:
            for k, v in self._lora.stats().items():
                reg.gauge(f"serving_{k}").set(float(v))
        for k, v in self.spec_metrics().items():
            reg.gauge(f"serving_spec_{k}").set(float(v))
        # info gauge: which per-layer kernel schedule actually ran —
        # value 1.0, identity in the labels (op + default/profile/swept)
        for op, (_, src) in self.kernel_geometry.items():
            reg.gauge("serving_kernel_geometry").set(1.0, op=op, source=src)
        snap = self._tel.snapshot()
        snap["config"] = {"cache": self.cache_mode,
                          "max_batch": self.max_batch,
                          "max_len": self.max_len,
                          "tick_window": self.tick_window,
                          "kv_quant": self.kv_quant,
                          "policy": self._sched.policy}
        if self.spec is not None:
            snap["config"]["spec"] = self.spec.describe()
        return snap

    def export_chrome_trace(self, path: str) -> str:
        """Write the span tracer's chrome trace (one timeline row per
        request — queued/prefill/decode/spec/preempt/swap spans). Open in
        chrome://tracing or Perfetto; empty when telemetry is disabled."""
        return self._tel.export_chrome_trace(path)

    # ------------------------------------------------------------- stepping
    def _harvest_phase(self, rows: int, trip: int, harvest, *args) -> None:
        """Run ``harvest(*args)`` — the fold of a trip's host arrays into
        the requests — as the tick's ``harvest`` phase; ``trip`` is the
        tick that dispatched what is folded (this one, or the one before
        for a trip that was left pending)."""
        with self._tel.phase("harvest", self._tick_seq, rows=rows,
                             trip=trip) as ph:
            done0 = len(self._results)
            harvest(*args)
            ph.note(finished=len(self._results) - done0)

    def _harvest_window(self, nxt_host, active, active_mask) -> None:
        """Fold one decode window's (k, B) token stack into the per-request
        state: append tokens, detect eos/max-new/max-len completion (window
        surplus past completion is discarded — tick_window semantics) and
        free finished slots for next window's refill. ``pos`` advanced
        when the window was dispatched, and again for a trip dispatched
        since and still unread (the one left in ``_trips``): a row that
        ends here on eos and is in that newer trip keeps its slot and
        blocks until it is retired, where its tokens are discarded."""
        k = nxt_host.shape[0]
        self.tokens = np.where(active_mask > 0, nxt_host[-1],
                               self.tokens).astype(np.int32)
        newer = self._trips[-1] if self._trips else None
        pos_after = self.pos if newer is None \
            else self.pos - newer.mask * newer.k
        # work folded, for the counters: a row at position p emits its
        # tokens at contexts p+1 .. p+k; what a finished row leaves of the
        # window is taken off again below
        rows = k * len(active)
        ctx = k * int(pos_after[active].sum()) \
            - len(active) * (k * (k - 1) // 2)
        W, ctx_win, discarded = self._ctx_window, 0, 0
        for s in active:
            req = self._slots[s]
            done = False
            if req.done:
                # a whole surplus trip, dispatched before the host had read
                # the eos in the trip before (tick_window > 1 only: with one
                # token a trip the host sees an eos coming): nothing to
                # fold, the slot goes now
                take, done = 0, True
                discarded += k
            elif self.eos is None:
                # no-eos fast path (see _harvest_spec): emission is one
                # slice per window instead of a per-token python walk
                gen = req.generated
                limit = min(req.max_new_tokens - len(gen),
                            self.max_len - 1 - (int(pos_after[s]) - k))
                take = k
                if take >= limit:
                    take = limit
                    done = True
                # nxt_host is host numpy — the window's one sync is done
                gen.extend(nxt_host[:take, s].tolist())  # graftlint: noqa[host-sync]
            else:
                take = 0
                for t in range(k):
                    if req.generated[-1] == self.eos:
                        # ended on the token before: this one and the rest
                        # of the window were computed for nothing
                        done = True
                        discarded += k - t
                        break
                    req.generated.append(int(nxt_host[t, s]))
                    take += 1
                    pos_t = int(pos_after[s]) - k + t + 1
                    if (len(req.generated) >= req.max_new_tokens
                            or pos_t >= self.max_len - 1):
                        done = True
                        break
            if W:
                # the emitted tokens' contexts, each capped at the window
                p0 = int(pos_after[s]) - k
                ctx_win += sum(min(p0 + t, W)
                               for t in range(1, max(take, 0) + 1))
            if done:
                cut = k - max(take, 0)
                if cut:
                    rows -= cut
                    ctx -= cut * int(pos_after[s]) - cut * (cut - 1) // 2
                if not req.done:
                    self._emit_result(req)
                    req.done = True
                if newer is None or not newer.mask[s]:
                    self._release_slot(s)
        self._c_tokens.inc(rows)
        self._c_dec_rows.inc(rows)
        self._c_dec_ctx.inc(ctx)
        if discarded:
            self._c_discarded.inc(discarded)
        if W:
            self._c_dec_ctx_win.inc(ctx_win)
        if self._ctx_shared:
            self._c_dec_ctx_shared.inc(ctx)

    def step(self) -> int:
        """One server step: admit queued requests, advance one prefill
        chunk per prefilling slot (paged), then one decode window
        (``tick_window`` ticks) across decoding slots; returns #remaining
        (occupied slots + queued)."""
        if self.cache_mode == "paged":
            return self._step_paged()
        tel = self._tel
        if not tel.enabled:
            return self._step_dense_inner()
        from ..analysis.recompile_guard import compile_count

        seq = self._tick_seq = tel.flight.total
        with tel.phase("tick", seq) as ph:
            c0 = compile_count()
            w0, tok0 = tel.wait_s, self._c_tokens.total()
            self._last_prog = "idle"
            remaining = self._step_dense_inner()
            rec = {"prog": self._last_prog,
                   "decoding": sum(sl is not None for sl in self._slots),
                   "queue_depth": len(self._sched),
                   "recompiles": compile_count() - c0}
            ph.note(tokens=int(self._c_tokens.total() - tok0), **rec)
        tel.flight.record(t_wall_s=ph.dur, wait_s=tel.wait_s - w0, **rec)
        self._tick_seq = -1
        return remaining

    def _step_dense_inner(self) -> int:
        tel, tick = self._tel, self._tick_seq
        self._service_queue()
        active = [s for s in range(self.max_batch)
                  if self._slots[s] is not None]
        if not active:
            return 0
        self._step_no += 1
        if tel.enabled:
            self._last_prog = "dense"
        with tel.phase("decode_dispatch", tick, rows=len(active)):
            key = jax.random.fold_in(self._base_key, self._step_no)
            active_mask = np.zeros((self.max_batch,), np.int32)
            active_mask[active] = 1
            # only occupied slots advance — idle slots must not drift
            # their write position (their garbage scatters would
            # eventually go OOB)
            stack, self._caches = self._call(
                "decode_dense", self._decode,
                self.params, jnp.asarray(self.tokens), self._caches,
                jnp.asarray(self.pos), jnp.asarray(self.temps),
                jnp.asarray(self.topks), jnp.asarray(self.topps),
                jnp.asarray(active_mask), key)
            call = self._calls_out
        with tel.phase("decode_wait", tick, rows=len(active),
                       trip=tick) as ph:
            nxt_host = np.asarray(stack)
        self._call_read(call, ph)
        self.pos = self.pos + active_mask * nxt_host.shape[0]
        self._harvest_phase(len(active), tick, self._harvest_window,
                            nxt_host, active, active_mask)
        self._queue_settle()
        return sum(sl is not None for sl in self._slots) + len(self._sched)

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue; returns {rid: prompt+generated token ids}."""
        while self.step():
            pass
        out, self._results = self._results, {}
        return out
