"""Paged serving executor — the device half of the engine/executor split.

``GenerationServer`` (serving.py) is the ENGINE: request lifecycle,
scheduling, slot bookkeeping, preemption policy, harvest — all host-side
numpy state. :class:`PagedExecutor` is everything that touches the
accelerator: the KV block pools, the compiled programs (chunked prefill,
decode window, both speculative verify paths), and — new in this layer —
their placement onto a multi-chip ``tp`` mesh.

The split is the roadmap's TP unlock: the engine's host loop is mesh-
oblivious (block tables, positions, sampling params are tiny replicated
arrays), so multi-chip serving is PURELY an executor concern. With
``tp > 1`` the executor places params, KV pools, int8 scale rows, and the
LoRA page pool onto a 1-D ``tp`` mesh (parallel/serving_mesh.py) and jits
the very same program bodies — GSPMD slices the attention heads and MLP
hidden dim and inserts the collectives, keeping each trip ONE compiled
program (the XLA fusion argument from PAPERS.md). Per-shard pools share
the engine's single host-side block table: every shard holds its kv-head
slice of every block, so block ids, prefix hashes, swap payloads, and
snapshots stay tp-agnostic.

Compile discipline is unchanged: programs are keyed on shapes + the two
static args (greedy, trip length); pool donation rotates buffers in
place. The executor additionally guarantees donation never silently
drops the tp layout (:meth:`shard_audit`, wired into
``GenerationServer.assert_conserved``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..jit import functional_call

__all__ = ["PagedExecutor"]


def _has_expert_capacity(model) -> bool:
    """True where some expert layer of ``model`` fills buckets of a fixed
    capacity: what a row is dropped from then depends on the rows it is
    joined with."""
    return any(getattr(l, "capacity_factor", None) is not None
               for l in model.sublayers())


def _step_stats(model) -> tuple:
    """The arrays a model's step left for its caller to return beside the
    step's outputs (``take_step_stats``: per-layer expert loads), as a
    tuple of zero or one arrays — zero for a model that leaves none."""
    take = getattr(model.model, "take_step_stats", None)
    stats = take() if take is not None else None
    return () if stats is None else (stats,)


class PagedExecutor:
    """Owns the paged device state + compiled programs for one engine.

    ``engine`` is the owning :class:`~.serving.GenerationServer`; the
    executor reads its construction-time configuration (model, spec/LoRA
    wiring, tick window) and nothing else — all mutable scheduling state
    stays on the engine side. ``tp=1`` (or None) is the single-chip
    executor, byte-for-byte the pre-split behavior.
    """

    def __init__(self, engine, num_blocks: int, tp: Optional[int] = None,
                 cp: Optional[int] = None):
        from ..framework.dtype import convert_dtype

        self.engine = engine
        cfg = engine.cfg
        spec = self.spec = engine.cache_spec
        cdtype = convert_dtype(cfg.dtype)
        bs = engine.block_size
        kv_quant = engine.kv_quant
        # the shared block pool: K and V per ``full`` layer of the spec,
        # ONE tensor per ``latent`` layer, in layer order
        self.pools: List[Any] = []
        for i in spec.pool_layers:
            block = spec.layers[i].block_shape(bs)
            kv = spec.layers[i].kv_heads
            if spec.layers[i].kind == "latent":
                self.pools.append(jnp.zeros((int(num_blocks),) + block,
                                            cdtype))
                continue
            for _kv in range(2):
                if kv_quant == "int8":
                    # K codes, K scales, V codes, V scales — the scale
                    # rows ride in the flat pool list so donation and
                    # in-place updates cover them too
                    self.pools.append(jnp.zeros(
                        (int(num_blocks),) + block, jnp.int8))
                    self.pools.append(jnp.zeros(
                        (int(num_blocks), kv), jnp.float32))
                else:
                    self.pools.append(jnp.zeros(
                        (int(num_blocks),) + block, cdtype))
        # tensors per ``full`` layer entry in the flat pool list: fp (K, V)
        # = 2; int8 (Kq, Kscale, Vq, Vscale) = 4; a ``latent`` layer has 1
        self.pool_stride = 4 if kv_quant == "int8" else 2
        self._pool_index: Dict[int, range] = {}
        at = 0
        for i in spec.pool_layers:
            n = 1 if spec.layers[i].kind == "latent" else self.pool_stride
            self._pool_index[i] = range(at, at + n)
            at += n
        # what a slot owns for as long as it is occupied: the window
        # layers' rings (slot b = blocks 1 + b*R .. (b+1)*R; block 0 is
        # scratch) and the state layers' slot-indexed arrays
        self.slot_pools: List[Any] = []
        self._slot_index: Dict[int, range] = {}
        for i in spec.slot_layers:
            l, at = spec.layers[i], len(self.slot_pools)
            if l.kind == "window":
                n = 1 + engine.max_batch * spec.ring_blocks(i, bs)
                self.slot_pools += [jnp.zeros((n,) + l.block_shape(bs), cdtype)
                                    for _kv in range(2)]
            else:
                self.slot_pools += [jnp.zeros((engine.max_batch,) + shape, t)
                                    for _, shape, t in l.shapes]
            self._slot_index[i] = range(at, len(self.slot_pools))

        # stand-ins for the token stack of a pending trip, by trip length
        # (:meth:`prev_stack`)
        self._no_prev: Dict[int, Any] = {}

        self.mesh = None
        self.tp = 1
        self.cp = 1
        tp = 1 if tp is None else int(tp)
        cp = 1 if cp is None else int(cp)
        if tp > 1 or cp > 1:
            from ..parallel import serving_mesh as sm

            if tp > 1:
                sm.validate_tp(cfg, tp)
            sm.validate_cp(cp, engine.prefill_chunk)
            self.mesh = sm.build_serving_mesh(tp, cp)
            self.tp = tp
            self.cp = cp
            # construction-time placement is the ONLY transfer the tp
            # path adds: params + pools commit to the mesh once, then
            # every program's outputs inherit the layout via donation
            engine.params = sm.place_params(engine.model, engine.params,
                                            self.mesh)
            self.pools = sm.place_pools(self.pools, self.mesh)
            if engine._lora is not None:
                lp = engine._lora
                lp.place_device_tensors(
                    lambda flat: sm.place_lora_flat(lp.targets, flat,
                                                    self.mesh))

        # per-layer kernel geometry, resolved by the engine ctor from
        # the installed winner cache (autotune/kernel_geometry.py) —
        # recorded here so the executor's compiled programs are
        # attributable to the schedules they traced under
        self.kernel_geometry = dict(getattr(engine, "kernel_geometry",
                                            None) or {})

        # ``greedy`` (the trailing static arg) specializes the program
        # for all-temp-0 ticks: XLA folds the whole sampling pipeline
        # (top-k/top-p filtering = per-row sorts over the vocab) down
        # to one argmax — measured ~2.3ms/window at CPU bench shapes.
        # At most two variants ever compile (greedy / mixed).
        self.decode_paged = self._jit(self._decode_paged_fn,
                                      donate_argnums=(2, 14),
                                      static_argnums=(12, 13))
        # (``prev``, argument 15, is NOT donated: the host still has to
        # read the pending trip's stack)
        # a prompt chunk is the server's second program, and there are two
        # of it: where nothing below stands in the way the chunk rides in
        # the decode program's own call (:meth:`_decode_chunk_fn`), so that
        # a tick reads the weights once; everywhere else it runs alone
        # (:meth:`_chunk_prefill_fn`). A server jits ONE of the two.
        self.chunk_alone_why = self._why_chunks_run_alone()
        self.chunk_prefill = self.decode_chunk = None
        if self.chunk_alone_why is None:
            self.decode_chunk = self._jit(self._decode_chunk_fn,
                                          donate_argnums=(2, 16),
                                          static_argnums=(15,))
        else:
            self.chunk_prefill = self._jit(self._chunk_prefill_fn,
                                           donate_argnums=(2, 8))
        self.spec_scan = None
        self.spec_verify = None
        if engine.spec is not None:
            if engine._spec_fused:
                self.spec_scan = self._jit(self._spec_scan_fn,
                                           donate_argnums=(2,),
                                           static_argnums=(13, 14))
            else:
                self.spec_verify = self._jit(self._spec_verify_fn,
                                             donate_argnums=(3,),
                                             static_argnums=(14,))

    def _why_chunks_run_alone(self) -> Optional[str]:
        """None where a prompt chunk can ride in the decode trip's program
        call, else the reason it cannot — what the engine counts its chunks
        under (``serving_prefill_chunks_alone{reason}``). Decided once,
        from what the server was built with: per-slot state splits the
        joint step where the model's class says how (a method: it is asked
        for, not the class's name), a ``cp`` mesh shapes the chunk program
        itself (the sequence-dim constraint), a speculative server and a
        ``tick_window`` scan have no one-tick plain trip to ride in,
        adapter rows would gather C more copies of the chunk's adapter,
        routed experts that fill fixed-capacity buckets see another capacity
        when rows are joined (a dropless expert layer has none, and rides),
        and a model class may not offer the joint step."""
        engine = self.engine
        joint = hasattr(engine.model.model, "paged_decode_chunk_step")
        if self.spec.has_slot_state and not joint:
            return "slot_state"
        if self.cp > 1:
            return "cp"
        if engine.spec is not None:
            return "spec"
        if engine.tick_window != 1:
            return "tick_window"
        if engine._lora is not None:
            return "lora"
        if _has_expert_capacity(engine.model):
            return "moe"
        return None if joint else "model"

    def _jit(self, body, **jit_kw):
        """jit one program body. Under a tp/cp mesh the body traces inside
        ``mesh_context`` so kernel selection (ops/select.py) sees that
        GSPMD partitions the program — a Mosaic kernel cannot be
        partitioned automatically and must not be selected there."""
        if self.mesh is None:
            return jax.jit(body, **jit_kw)
        from ..parallel.api import mesh_context

        mesh = self.mesh

        @functools.wraps(body)
        def traced(*args, **kwargs):
            with mesh_context(mesh):
                return body(*args, **kwargs)

        return jax.jit(traced, **jit_kw)

    # ----------------------------------------------------------- mesh state
    @property
    def mesh_fingerprint(self) -> str:
        from ..parallel import serving_mesh as sm

        return sm.mesh_fingerprint(self.mesh)

    def shard_audit(self) -> Dict[str, int]:
        """Verify the pools still carry their tp layout (donation must
        rotate buffers, never reshard them) — {} on a single-chip
        executor. Raises AssertionError on a lost sharding."""
        if self.mesh is None:
            return {}
        from ..parallel import serving_mesh as sm

        return sm.audit_pool_shardings(self.pools, self.mesh)

    # ------------------------------------------------------------ pool views
    def _pool_views(self, flat_p, slot_p=()):
        """One view per layer, as the cache spec declares it: a ``full``
        layer's entry of the flat block-pool list — fp → (K, V); int8 →
        (Kq, Kscale, Vq, Vscale); the model's paged methods branch on the
        tuple arity, so the same compiled-fn bodies serve both pool
        formats — a ``latent`` layer's one pool, a ``window`` layer's ring
        pools or a ``state`` layer's
        slot arrays out of ``slot_p``, and ``()`` for a layer that owns
        nothing."""
        views = [()] * len(self.spec.layers)
        for i, idx in self._pool_index.items():
            views[i] = tuple(Tensor(flat_p[j]) for j in idx)
        for i, idx in self._slot_index.items():
            views[i] = tuple(Tensor(slot_p[j]) for j in idx)
        return views

    def _flat_pools(self, new):
        """The model's new views, split back into (block pools, slot
        pools) in the order :meth:`_pool_views` read them."""
        flat = [t.value for i in self.spec.pool_layers for t in new[i]]
        return flat, [t.value for i in self._slot_index for t in new[i]]

    # ----------------------------------------------------------- slot state
    def save_slot(self, slot: int) -> List[Any]:
        """Host copies of everything ``slot`` owns besides blocks of the
        shared pool (window rings, state rows), in ``slot_pools`` order:
        what a preempted or captured request carries along. The state on
        the device is one trip AHEAD of the engine's ``generated`` while a
        decode trip is pending, so that trip is retired first: what comes
        back is the state of exactly the tokens the engine then holds."""
        import numpy as np

        self.engine._retire_pending("save_slot")
        out = []
        for i in self.spec.slot_layers:
            for j in self._slot_index[i]:
                p = self.slot_pools[j]
                if self.spec.layers[i].kind == "window":
                    R = self.spec.ring_blocks(i, self.engine.block_size)
                    p = p[1 + slot * R:1 + (slot + 1) * R]
                else:
                    p = p[slot]
                out.append(np.asarray(p))   # graftlint: noqa[host-sync]
        if out:
            # every program call threads the slot pools through: a read of
            # them waited for the newest call
            self.engine._calls_drained("save_slot")
        return out

    def restore_slot(self, slot: int, arrays) -> None:
        """Write :meth:`save_slot`'s arrays into ``slot`` (any slot: ring
        entries are addressed by position, not by block id)."""
        new, it = list(self.slot_pools), iter(arrays)
        for i in self.spec.slot_layers:
            for j in self._slot_index[i]:
                a = jnp.asarray(next(it)).astype(new[j].dtype)
                if self.spec.layers[i].kind == "window":
                    R = self.spec.ring_blocks(i, self.engine.block_size)
                    new[j] = jax.lax.dynamic_update_slice_in_dim(
                        new[j], a, 1 + slot * R, 0)
                else:
                    new[j] = new[j].at[slot].set(a)
        self.slot_pools = new

    def _gather_lora(self, lora_flat, aidx):
        """Gather each row's adapter factors from the paged LoRA pool —
        one batched take per stacked tensor, inside the compiled program.
        ``lora_flat`` is empty when LoRA is off → None (the model's paged
        methods skip the delta entirely)."""
        if not lora_flat:
            return None
        return self.engine._lora.gather_rows(list(lora_flat), aidx)

    # ------------------------------------------------------------- programs
    def prev_stack(self, trip, k: int):
        """The ``prev`` operand of a ``k``-tick decode trip: the token
        stack of the pending ``trip`` (same length: only a server whose
        every plain trip is ``tick_window`` long leaves one pending), or a
        cached stand-in of that shape which no row reads."""
        if trip is not None:
            return trip.stack
        if k not in self._no_prev:
            z = jnp.zeros((k, self.engine.max_batch), jnp.int32)
            if self.mesh is not None:
                # placed as a trip's own stack comes out (replicated over
                # the mesh): one placement, so ONE compiled decode variant
                from ..parallel.serving_mesh import place_replicated

                z = place_replicated(z, self.mesh)
            self._no_prev[k] = z
        return self._no_prev[k]

    @staticmethod
    def _feed(tokens, prev, active):
        """Each row's input token, merged inside the program: the host's
        ``tokens`` where ``active`` is 1, the last row of the pending
        trip's stack ``prev`` — which never visits the host — where it is
        2. Returns (tokens, active as 0/1)."""
        if prev is not None:
            tokens = jnp.where(active == 2, prev[-1], tokens)
        return tokens, (active > 0).astype(active.dtype)

    def _decode_paged_fn(self, params, tokens, flat_pools, tables, pos,
                         temps, topks, topps, active, key, aidx=None,
                         lora_flat=(), greedy=False, ticks=None,
                         slot_pools=(), prev=None):
        """Paged decode window: K/V reads/writes go through per-slot
        block tables into the shared pool. ``tables``: int32
        (B, table_width) — the engine zeroes rows of idle/prefilling slots
        so their masked ticks write only the scratch block. ``greedy`` is
        STATIC (jit cache key): True promises every active row has temp 0
        and compiles sampling down to argmax. ``ticks`` (STATIC) overrides
        ``tick_window`` — the speculative server's gated plain trips run
        longer windows than its verify trips (SpecConfig.gate_ticks).
        ``aidx``/``lora_flat``: per-slot adapter page indices + the LoRA
        pool's stacked factor tensors — gathered ONCE per trip (rows are
        loop-invariant across ticks) and applied in-program (BGMV).
        ``slot_pools``: the window rings and state arrays of the spec's
        slot kinds (donated like the block pools; empty for a dense
        decoder), and with them the model is told through ``active=``
        which rows may touch them. ``active``: int32 (B,), 0 = idle, 1 =
        decodes from ``tokens[b]``, 2 = decodes from ``prev[-1, b]``, where
        ``prev`` (k, B) is the token stack of the trip before, still
        unread (:meth:`_feed`). Returns the (k, B) token stack, the block
        pools, the slot pools — and, for a model that leaves step stats
        (:func:`_step_stats`), those of every tick, stacked, as a fourth
        output, which the engine reads where it reads the stack."""
        engine = self.engine
        model = engine.model
        lora = self._gather_lora(lora_flat, aidx)
        tokens, active = self._feed(tokens, prev, active)
        slot_kw = {"active": active} if self.spec.has_slot_state else {}

        def one_tick(carry, k):
            toks, (flat_p, slot_p), p = carry
            pools = self._pool_views(flat_p, slot_p)

            def call():
                h, new = model.model.paged_decode_step(Tensor(toks[:, None]),
                                                       pools, tables, p,
                                                       lora=lora, **slot_kw)
                return engine._head(h), new, _step_stats(model)

            logits, new, stats = functional_call(model, params, call_fn=call)
            flat = self._flat_pools(new)
            lg = logits.value[:, 0].astype(jnp.float32)   # (B, V)
            if greedy:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                from ..models.generation import sample_token_rows

                nxt = sample_token_rows(lg, jax.random.fold_in(key, k),
                                        temps, topks, topps)
            return (nxt, flat, p + active), (nxt, stats)

        n = engine.tick_window if ticks is None else ticks
        carry = (tokens, (list(flat_pools), list(slot_pools)), pos)
        if n == 1:
            (_, (flat, slot), _), (stack, stats) = one_tick(carry, 0)
            return (stack[None], flat, slot, *stats)
        (_, (flat, slot), _), (stack, stats) = jax.lax.scan(
            one_tick, carry, jnp.arange(n))
        return (stack, flat, slot, *(st[:, 0] for st in stats))

    def _chunk_prefill_fn(self, params, chunk, flat_pools, table, start,
                          last_idx, aidx=None, lora_flat=(), slot_pools=(),
                          slot=None):
        """ONE compiled program for every prefill chunk of every prompt
        length: chunk (1, C) right-padded; K/V scatter into the slot's
        block table at block-aligned ``start``; returns fp32 logits at
        local index ``last_idx`` (the last real prompt token on the final
        chunk; ignored on earlier chunks) + updated pools. ``aidx`` is the
        prefilling slot's adapter page index, shape (1,) — prompt tokens
        must see the same adapter delta the decode ticks will.
        ``slot_pools`` as in :meth:`_decode_paged_fn`; ``slot``: int32
        ``(slot index, real tokens in this chunk, 1 on the request's last
        chunk)`` — what a model with per-slot state needs to find its rows,
        stop its state at the last real token, and skip stateless layers
        where no logits are wanted.

        Context parallelism is a one-line steer: at ``cp > 1`` the chunk
        is constrained to shard its sequence dim over the ``cp`` axis.
        Params and pools name only ``tp``, so GSPMD partitions the
        per-token work (embedding, projections, rope) across the cp
        group, all-gathers the chunk's K/V where the replicated pool
        scatter needs the full chunk, and leaves every reduction's order
        unchanged — each shard attends over the full prefix, so tokens
        are bit-identical to cp=1. The constraint lives INSIDE the
        traced body: one compile covers every chunk, zero steady-state
        recompiles."""
        if self.cp > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..parallel.serving_mesh import SERVING_CP_AXIS

            chunk = jax.lax.with_sharding_constraint(
                chunk, NamedSharding(self.mesh, P(None, SERVING_CP_AXIS)))
        engine = self.engine
        model = engine.model
        pools = self._pool_views(flat_pools, slot_pools)
        lora = self._gather_lora(lora_flat, aidx)
        slot_kw = {"slot": slot} if self.spec.has_slot_state else {}

        def call():
            h, new = model.model.paged_prefill_chunk(Tensor(chunk), pools,
                                                     table, start,
                                                     lora=lora,
                                                     last_idx=last_idx,
                                                     **slot_kw)
            return engine._head(h), new, _step_stats(model)

        logits, new, stats = functional_call(model, params, call_fn=call)
        return (logits.value[:, 0].astype(jnp.float32),
                *self._flat_pools(new), *stats)

    def _decode_chunk_fn(self, params, tokens, flat_pools, tables, pos,
                         temps, topks, topps, active, key, prev, chunk,
                         table, start, last_idx, greedy=False, slot_pools=(),
                         slot=None):
        """A one-tick decode trip AND one prompt chunk in one program, so
        that the tick reads every weight once (the chunk alone would
        stream them all a second time): the operands of
        :meth:`_decode_paged_fn` (``tokens`` … ``prev``; ``greedy`` STATIC)
        and of :meth:`_chunk_prefill_fn` (``chunk`` (1, C), the slot's
        ``table``, ``start``, ``last_idx``; ``slot_pools``, DONATED, and
        ``slot`` where the spec has slot state — for every other spec both
        are empty and the program is what it is without them), the model's
        joint step over B + C rows, the head on B + 1. It is THE chunk
        program of a server that has it — a chunk that meets no decoding row
        runs it with every row masked (``active`` 0, zeroed ``tables``), as
        idle rows always run — so a server compiles two programs whatever
        its traffic.
        Returns the trip's (1, B) token stack, the chunk's float32 logits
        row (1, V), the block pools, the slot pools (and the model's step
        stats, if it leaves any, as in :meth:`_decode_paged_fn`)."""
        engine = self.engine
        model = engine.model
        tokens, active = self._feed(tokens, prev, active)
        ids = jnp.concatenate([tokens[None, :], chunk], axis=1)
        pools = self._pool_views(flat_pools, slot_pools)
        slot_kw = ({"active": active, "slot": slot}
                   if self.spec.has_slot_state else {})

        def call():
            h, new = model.model.paged_decode_chunk_step(
                Tensor(ids), pools, tables, pos, table, start, last_idx,
                **slot_kw)
            return engine._head(h), new, _step_stats(model)

        logits, new, stats = functional_call(model, params, call_fn=call)
        lg = logits.value[0].astype(jnp.float32)          # (B + 1, V)
        if greedy:
            nxt = jnp.argmax(lg[:-1], axis=-1).astype(jnp.int32)
        else:
            from ..models.generation import sample_token_rows

            nxt = sample_token_rows(lg[:-1], jax.random.fold_in(key, 0),
                                    temps, topks, topps)
        return (nxt[None], lg[-1:], *self._flat_pools(new), *stats)

    def _spec_verify_fn(self, params, tokens, proposals, flat_pools, tables,
                        pos, temps, topks, topps, kcaps, key, qprobs,
                        aidx=None, lora_flat=(), greedy=False):
        """ONE fused speculative tick: target-score the whole window
        [current token, k drafts] through the paged verify path, then run
        exact accept/reject — all on device, so the host sees only the
        (B, W) emitted-token block and the (B,) accepted counts (one sync
        per tick, same as plain decode). ``qprobs`` is None for
        deterministic drafters (one-hot q synthesized inside the program);
        per-row ``kcaps`` force-stop lets requests run mixed draft_k (and
        masks idle slots at kcap 0) without changing compiled shapes."""
        engine = self.engine
        model = engine.model
        pools = self._pool_views(flat_pools)
        lora = self._gather_lora(lora_flat, aidx)
        window = jnp.concatenate([tokens[:, None], proposals], axis=1)

        def call():
            h, new = model.model.paged_verify_step(Tensor(window), pools,
                                                   tables, pos, lora=lora)
            return engine._head(h), new

        logits, new = functional_call(model, params, call_fn=call)
        flat = self._flat_pools(new)[0]
        from .speculative import speculative_accept

        out, acc = speculative_accept(
            logits.value.astype(jnp.float32), proposals, temps, topks,
            topps, kcaps, key, qprobs, greedy=greedy)
        return out, acc, flat

    def _spec_scan_fn(self, params, ctx, flat_pools, tables, pos, temps,
                      topks, topps, kcaps, active, key, aidx=None,
                      lora_flat=(), greedy=False, windows=None):
        """``tick_window`` speculative windows as ONE compiled program —
        the drafter runs IN-PROGRAM (``drafter.propose_device``, e.g. the
        jnp prompt-lookup matcher), so draft → multi-token verify → exact
        accept → context/position update runs on device and the host pays
        one round trip per ``tick_window·(k+1)`` potential tokens.
        ``ctx``: int32 (B, max_len), row b's prompt+generated tokens
        valid through index ``pos[b]`` — accepted tokens are appended to
        it after each window so the next window drafts from them.
        Emitted-token surplus past eos/max-new is discarded by the host
        harvest, exactly like the plain ``tick_window`` decode scan.
        ``windows`` (STATIC) overrides the per-trip window count — the
        turbo tier of the speculation gate (SpecConfig.turbo_windows)
        runs long trips while the whole batch is accepting near-k."""
        engine = self.engine
        model = engine.model
        k = engine.spec_k
        W = k + 1
        B, L = ctx.shape
        S = engine._spec_windows if windows is None else windows
        rows = jnp.arange(B)
        lora = self._gather_lora(lora_flat, aidx)
        from .speculative import speculative_accept

        def one_window(carry, w):
            c, flat_p, p = carry
            pools = self._pool_views(flat_p)
            cur = jnp.take_along_axis(c, p[:, None], axis=1)      # (B, 1)
            proposals = engine.drafter.propose_device(c, p, k)
            window = jnp.concatenate([cur, proposals], axis=1)

            def call():
                h, new = model.model.paged_verify_step(Tensor(window),
                                                       pools, tables, p,
                                                       lora=lora)
                return engine._head(h), new

            logits, new = functional_call(model, params, call_fn=call)
            flat = self._flat_pools(new)[0]
            out, acc = speculative_accept(
                logits.value.astype(jnp.float32), proposals, temps, topks,
                topps, kcaps, jax.random.fold_in(key, w), None,
                greedy=greedy)
            # append the emitted tokens (accepted drafts + correction) to
            # the context so the next window drafts from them; clamped
            # writes past L-1 only touch rows the harvest will release
            widx = jnp.minimum(p[:, None] + 1 + jnp.arange(W)[None, :],
                               L - 1)
            keep = ((jnp.arange(W)[None, :] <= acc[:, None])
                    & (active > 0)[:, None])
            vals = jnp.where(keep, out, jnp.take_along_axis(c, widx, axis=1))
            c = c.at[rows[:, None], widx].set(vals)
            # clamp: only surplus windows past max_len (discarded by the
            # harvest) ever hit L-1 — without it the ``cur`` gather goes
            # out of bounds (fill-mode -> garbage token id -> NaN
            # embedding) and the NaN K/V written to scratch poisons every
            # row whose table padding points there (0 * NaN in p @ V)
            p = jnp.minimum(p + (acc + 1) * active, L - 1)
            return (c, flat, p), (out, acc)

        # UNROLLED, not lax.scan/while_loop: on CPU the loop constructs
        # copy the multi-MB KV pools through the carry every trip (~ms of
        # pure memcpy); straight-line code lets XLA alias the pool
        # buffers through all S windows for free. S is small and static,
        # so program size stays modest and the jit cache sees one shape.
        carry = (ctx, flat_pools, pos)
        outs, accs = [], []
        for w in range(S):
            carry, (out, acc) = one_window(carry, w)
            outs.append(out)
            accs.append(acc)
        _, flat, _ = carry
        return jnp.stack(outs), jnp.stack(accs), flat
