"""Host KV offload — swap-preemption for the paged serving pool.

The mechanism half of overload handling (policy lives in
``inference/scheduler.py``): when the device block pool runs dry, the
server preempts a victim request by copying its KV blocks — fp rows, or
int8 codes + f32 scales, the engine is pool-format agnostic — into a
host-memory pool, freeing the HBM blocks for more urgent work. On resume
the blocks are restored and the request continues exactly where it
stopped: greedy output is token-identical to an un-preempted run because
the round trip is a bit-exact copy of whatever the pool held.

Why swapping beats recompute here: a decoding request's KV past the
prompt was produced by its own sampled continuation — re-prefilling
``prompt + generated`` would rebuild it through a different program
(chunked prefill vs decode steps) with different float rounding, beyond
re-spending the FLOPs. Prefill-only work IS recomputable, which is why
``GenerationServer`` aborts (not swaps) victims still in prefill.

Compile discipline (the zero-steady-state-recompile guarantee must
survive preemption):

- The device↔host copies are EAGER ops, not new jitted programs, and
  they run at ONE fixed shape: every gather/scatter covers the full
  ``table_width`` rows of the slot's block table, padded with the
  scratch block. A swap of 3 blocks and a swap of 30 compile the same
  executables (once, at the first preemption); nothing is keyed on how
  many blocks a victim happens to hold.
- Scatter padding targets block 0 — the reserved scratch block that
  absorbs masked writes everywhere else in the paged path — so the
  fixed-width restore can never touch a live block.

Prefix-cache integration: the victim's chain hashes ride along in the
:class:`SwapHandle`. Swap-out releases the device blocks through the
normal refcount path, so hashed prompt blocks land on the allocator's
LRU — still resident, still shareable. Swap-in first re-matches those
hashes (``BlockAllocator.match_hashes``): every hit is a block restored
WITHOUT an upload (or a byte of HBM traffic), and every uploaded full
prompt block is re-registered under its hash so restored requests keep
participating in prefix sharing.
"""
from __future__ import annotations

import zlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

__all__ = ["SwapHandle", "HostKVPool", "WarmTier", "KVOffloadEngine",
           "payload_checksum"]


def payload_checksum(arrays: Sequence[np.ndarray]) -> int:
    """CRC32 over a parked payload's raw bytes (order-sensitive).

    Cheap enough to run on every swap boundary and strong enough to catch
    the single-bit-flip corruption the chaos plans inject; a mismatch on
    swap-in means the parked copy cannot be trusted and the server falls
    back to re-prefilling the request's tokens.
    """
    c = 0
    for a in arrays:
        c = zlib.crc32(np.ascontiguousarray(a).reshape(-1).view(np.uint8), c)
    return c


@dataclass
class SwapHandle:
    """Resume ticket for one preempted request: where it stopped, which
    chain hashes its prompt blocks carry, and how much host memory the
    parked copy occupies. The block CONTENTS live in the
    :class:`HostKVPool` under ``rid``."""

    rid: int
    n_tokens: int            # KV-valid positions [0, n_tokens)
    last_token: int          # next decode input (its KV is not written yet)
    n_blocks: int            # live table entries parked on host
    hashes: List[int] = field(default_factory=list)  # leading full-prompt-block chain hashes
    nbytes: int = 0          # logical bytes charged to the host pool
    checksum: int = 0        # CRC32 of the parked payload (0 = unverified)
    # trailing arrays of the payload that are NOT pool blocks: what the
    # slot owned besides them (window rings, recurrent state; the cache
    # spec's slot kinds). swap_in hands them back in ``extra``.
    n_extra: int = 0
    extra: Optional[List[np.ndarray]] = None


class HostKVPool:
    """Byte-budgeted host store for swapped block stacks.

    ``capacity_bytes=None`` means unbounded (the default server setting —
    host DRAM dwarfs HBM); a bounded pool makes :meth:`put` refuse once
    full, which the server treats as "this victim cannot be preempted".
    """

    def __init__(self, capacity_bytes: Optional[int] = None):
        if capacity_bytes is not None and capacity_bytes < 0:
            raise ValueError(
                f"capacity_bytes must be >= 0 or None, got {capacity_bytes}")
        self.capacity_bytes = capacity_bytes
        self._store: Dict[int, List[np.ndarray]] = {}
        self.bytes_in_use = 0
        self.bytes_peak = 0
        self.puts = 0
        self.takes = 0
        # refusals are a capacity signal, not a silent drop: the server's
        # telemetry snapshot exports every stats() field as a
        # serving_host_pool_* gauge, so rejects reaching stats() is what
        # makes "the host pool is too small" observable
        self.rejects = 0

    def fits(self, nbytes: int) -> bool:
        return (self.capacity_bytes is None
                or self.bytes_in_use + nbytes <= self.capacity_bytes)

    def put(self, rid: int, arrays: List[np.ndarray], nbytes: int) -> bool:
        if rid in self._store:
            raise KeyError(f"request {rid} already has a parked KV copy")
        if not self.fits(nbytes):
            self.rejects += 1
            return False
        self._store[rid] = arrays
        self.bytes_in_use += nbytes
        self.bytes_peak = max(self.bytes_peak, self.bytes_in_use)
        self.puts += 1
        return True

    def take(self, rid: int, nbytes: int) -> List[np.ndarray]:
        arrays = self._store.pop(rid)
        self.bytes_in_use -= nbytes
        self.takes += 1
        return arrays

    def peek(self, rid: int) -> List[np.ndarray]:
        """Read a parked payload without removing it — snapshot() copies
        already-swapped requests' KV through this."""
        return self._store[rid]

    def discard(self, rid: int, nbytes: int) -> None:
        if self._store.pop(rid, None) is not None:
            self.bytes_in_use -= nbytes

    def stats(self) -> Dict[str, int]:
        return {"bytes_in_use": self.bytes_in_use,
                "bytes_peak": self.bytes_peak,
                "puts": self.puts, "takes": self.takes,
                "rejects": self.rejects,
                "parked": len(self._store)}

    def __len__(self) -> int:
        return len(self._store)


class WarmTier:
    """Hash-keyed warm tier: per-block host copies of DEMOTED prefix
    blocks, addressable by the same chain hash the allocator's hot-tier
    prefix cache uses.

    Where :class:`HostKVPool` parks whole per-request block stacks under
    a rid (swap preemption), the warm tier holds individual shareable
    prompt blocks under their content hash — the second rung of the
    hot (HBM) → warm (host) → cold (re-prefill) ladder. A block demoted
    here left HBM entirely; a later prefix match promotes it back
    through the compile-once fixed-width scatter, CRC-verified, and a
    failed check simply breaks the chain walk (the request re-prefills
    those tokens — the cold rung, never wrong tokens).

    LRU over chain hashes; a bounded tier evicts its coldest entries to
    make room (eviction = the block falls to the cold tier). Bytes are
    ledgered separately from the swap pool so the server's conservation
    audit can hold each ledger to its own invariant.
    """

    def __init__(self, capacity_bytes: Optional[int] = None):
        if capacity_bytes is not None and capacity_bytes < 0:
            raise ValueError(
                f"capacity_bytes must be >= 0 or None, got {capacity_bytes}")
        self.capacity_bytes = capacity_bytes
        # chain_hash -> (per-pool block arrays, nbytes, checksum)
        self._store: "OrderedDict[int, Tuple[List[np.ndarray], int, int]]" \
            = OrderedDict()
        self.bytes_in_use = 0
        self.bytes_peak = 0
        self.demoted_blocks = 0
        self.promoted_blocks = 0
        self.hit_blocks = 0
        self.rejects = 0
        self.evictions = 0
        self.corruptions = 0

    def __contains__(self, chain_hash: int) -> bool:
        return chain_hash in self._store

    def __len__(self) -> int:
        return len(self._store)

    def put(self, chain_hash: int, arrays: List[np.ndarray],
            nbytes: int, checksum: int) -> bool:
        """Admit one demoted block; evicts coldest entries until it
        fits. False (and ``rejects`` ticks) when it can never fit."""
        if chain_hash in self._store:
            return True                   # already warm — nothing to copy
        if self.capacity_bytes is not None:
            if nbytes > self.capacity_bytes:
                self.rejects += 1
                return False
            while self.bytes_in_use + nbytes > self.capacity_bytes:
                _, (_, old_bytes, _) = self._store.popitem(last=False)
                self.bytes_in_use -= old_bytes
                self.evictions += 1
        self._store[chain_hash] = (arrays, int(nbytes), int(checksum))
        self.bytes_in_use += nbytes
        self.bytes_peak = max(self.bytes_peak, self.bytes_in_use)
        self.demoted_blocks += 1
        return True

    def peek(self, chain_hash: int) -> Tuple[List[np.ndarray], int, int]:
        """Read an entry without removing it (refreshes LRU position)."""
        self._store.move_to_end(chain_hash)
        return self._store[chain_hash]

    def take(self, chain_hash: int) -> Tuple[List[np.ndarray], int, int]:
        """Remove an entry — promotion back to HBM (the bytes move
        tiers) or a corruption drop."""
        entry = self._store.pop(chain_hash)
        self.bytes_in_use -= entry[1]
        return entry

    def drop_corrupt(self, chain_hash: int) -> None:
        self.take(chain_hash)
        self.corruptions += 1

    def entries(self) -> List[Tuple[int, List[np.ndarray], int, int]]:
        """(hash, arrays, nbytes, checksum) rows in LRU order — the
        fleet migration capture (``GenerationServer.evacuate``)."""
        return [(h, arrs, nb, crc)
                for h, (arrs, nb, crc) in self._store.items()]

    def clear(self) -> None:
        """Drop every entry (a full evacuate — the snapshot carries the
        copies). Counters keep their history; only occupancy resets."""
        self._store.clear()
        self.bytes_in_use = 0

    def stats(self) -> Dict[str, int]:
        return {"blocks": len(self._store),
                "bytes_in_use": self.bytes_in_use,
                "bytes_peak": self.bytes_peak,
                "demoted_blocks": self.demoted_blocks,
                "promoted_blocks": self.promoted_blocks,
                "hit_blocks": self.hit_blocks,
                "rejects": self.rejects,
                "evictions": self.evictions,
                "corruptions": self.corruptions}


class KVOffloadEngine:
    """Swap-out / swap-in over a server's flat pool list.

    Stateless between calls except for the host pool: the caller passes
    the current (donation-rotated) ``pools`` list each time and takes the
    updated list back from :meth:`swap_in`.
    """

    def __init__(self, alloc, table_width: int,
                 capacity_bytes: Optional[int] = None,
                 warm_capacity_bytes: Optional[int] = None):
        self.alloc = alloc
        self.table_width = int(table_width)
        self.host = HostKVPool(capacity_bytes)
        # hash-addressed warm tier for demoted prefix blocks; the
        # allocator's read-only probe consults it through warm_probe so
        # fleet routing scores warm residency without any side effect
        self.warm = WarmTier(warm_capacity_bytes)
        if hasattr(alloc, "warm_probe"):
            alloc.warm_probe = self.warm.__contains__
        # optional ServingTelemetry (inference/telemetry.py): the owning
        # server sets this so swap copies emit per-request spans + the
        # serving_swap_{out,in}_s histograms. The copies themselves are
        # untouched — timing wraps the whole eager d2h/h2d sequence.
        self.telemetry = None
        # optional FaultInjector (inference/faults.py): host-pool refusal
        # and swap-payload corruption hooks for chaos plans
        self.faults = None

    # ------------------------------------------------------------ KV capture
    def gather_payload(self, table: Sequence[int],
                       pools: List[Any]) -> List[np.ndarray]:
        """Non-destructive fixed-width device→host gather of a table's
        blocks — the same one-compile program :meth:`swap_out` rides, so
        ``GenerationServer.snapshot()`` can capture a warm server's KV
        without compiling anything new. Blocks are pinned for the copy
        and left exactly as they were."""
        import jax.numpy as jnp

        a = self.alloc
        idx = np.zeros((self.table_width,), np.int32)
        idx[:len(table)] = table
        for bid in table:                 # freeze against LRU churn mid-copy
            a.pin(bid)
        try:
            didx = jnp.asarray(idx)
            # the d2h pull IS the point — one sync per pool tensor,
            # outside any trace
            arrays = [np.asarray(p[didx]) for p in pools]  # graftlint: noqa[host-sync]
        finally:
            for bid in table:
                a.unpin(bid)
        return arrays

    # ----------------------------------------------------------- tier ladder
    def demote(self, victims: Sequence[Tuple[int, int]],
               pools: List[Any]) -> int:
        """Move cached (ref==0) prefix blocks HBM → warm tier.

        ``victims`` is ``[(bid, chain_hash), ...]`` straight from
        ``BlockAllocator.coldest_cached``. One fixed-width gather — the
        SAME compiled shape ``gather_payload``/``swap_out`` already use,
        so pressure-driven demotion adds zero steady-state compiles —
        pulls every victim at once; each block is then sliced out,
        CRC-stamped, and admitted to the warm tier individually, and
        only blocks the tier accepted are evicted from HBM. Returns the
        number of blocks demoted."""
        if not victims:
            return 0
        tel = self.telemetry
        _t0 = tel.clock() if tel is not None and tel.enabled else None
        a = self.alloc
        bids = [bid for bid, _ in victims]
        arrays = self.gather_payload(bids, pools)
        moved = 0
        for i, (bid, h) in enumerate(victims):
            block = [np.asarray(p[i]) for p in arrays]
            if not self.warm.put(h, block, a.bytes_per_block,
                                 payload_checksum(block)):
                break                     # tier can never hold it — stay hot
            a.evict_cached(bid)
            moved += 1
        if _t0 is not None and moved:
            _t1 = tel.clock()
            tel.registry.histogram(
                "serving_tier_demote_s",
                "HBM->warm tier demotion wall time (batched)"
            ).observe(_t1 - _t0)
            tel.registry.counter(
                "serving_tier_demoted_bytes",
                "KV bytes demoted to the warm tier"
            ).inc(moved * a.bytes_per_block)
        return moved

    def match_prefix_tiered(self, tokens: Sequence[int], pools: List[Any]
                            ) -> Tuple[List[int], List[Any], Dict[str, int]]:
        """Cross-tier prefix match: the warm-aware twin of
        ``BlockAllocator.match_prefix``.

        Walks the chain hashes of ``tokens`` (last-token rule applies):
        a hot hit re-refs the resident block as before; a warm hit
        allocates a fresh device block, CRC-verifies the parked copy and
        promotes it back through ONE batched fixed-width scatter — the
        same compiled shape ``swap_in`` uses — then re-registers it
        under its hash so the promotion is shareable. The first miss
        (or a failed CRC, or a dry device pool) stops the walk; tokens
        past it re-prefill normally, which IS the cold tier.

        Returns ``(table, pools, {"hot": n, "warm": n})`` — every block
        in ``table`` is ref'd for the caller, ``pools`` reflects the
        promotion scatter (unchanged when nothing was promoted)."""
        import jax.numpy as jnp

        a = self.alloc
        n = len(tokens)
        limit = max((n - 1) // a.block_size, 0)
        hashes = a.chain_hashes(tokens)[:limit]
        table: List[int] = []
        warm_bids: List[int] = []
        warm_hashes: List[int] = []
        warm_blocks: List[List[np.ndarray]] = []
        hot = 0
        for h in hashes:
            bid = a.ref_hash(h)
            if bid is not None:
                table.append(bid)
                hot += 1
                continue
            if h not in self.warm:
                break
            arrs, nbytes, checksum = self.warm.peek(h)
            if self.faults is not None and \
                    self.faults.fire("warm_corrupt") is not None:
                arrs = [np.array(x) for x in arrs]
                self.faults.corrupt(arrs)
            if checksum and payload_checksum(arrs) != checksum:
                # damaged parked block: drop it (cold tier from here on)
                self.warm.drop_corrupt(h)
                tel = self.telemetry
                if tel is not None and tel.enabled:
                    tel.registry.counter(
                        "serving_tier_corruptions",
                        "warm-tier blocks that failed CRC verification"
                    ).inc()
                break
            if a.blocks_free + a.evictable_cached < 1:
                break                     # no headroom to promote into
            try:
                bid = a.alloc()
            except RuntimeError:
                break
            table.append(bid)
            warm_bids.append(bid)
            warm_hashes.append(h)
            warm_blocks.append(arrs)
        a.prefix_lookup_blocks += len(hashes)
        a.prefix_hit_blocks += hot
        if warm_bids:
            tel = self.telemetry
            _t0 = tel.clock() if tel is not None and tel.enabled else None
            # batched fixed-width promotion scatter: rows past the warm
            # hits target the scratch block, exactly like swap_in
            idx = np.zeros((self.table_width,), np.int32)
            idx[:len(warm_bids)] = warm_bids
            didx = jnp.asarray(idx)
            new_pools = []
            for j, p in enumerate(pools):
                stack = np.zeros((self.table_width,)
                                 + warm_blocks[0][j].shape,
                                 dtype=warm_blocks[0][j].dtype)
                for i, blk in enumerate(warm_blocks):
                    stack[i] = blk[j]
                new_pools.append(
                    p.at[didx].set(jnp.asarray(stack).astype(p.dtype)))
            pools = new_pools
            for bid, h in zip(warm_bids, warm_hashes):
                a.register(bid, h)
                self.warm.take(h)         # bytes move tiers with the block
            self.warm.promoted_blocks += len(warm_bids)
            self.warm.hit_blocks += len(warm_bids)
            a.note_promote(len(warm_bids))
            if _t0 is not None:
                _t1 = tel.clock()
                tel.registry.histogram(
                    "serving_tier_promote_s",
                    "warm->HBM tier promotion wall time (batched)"
                ).observe(_t1 - _t0)
                tel.registry.counter(
                    "serving_tier_promoted_bytes",
                    "KV bytes promoted back from the warm tier"
                ).inc(len(warm_bids) * a.bytes_per_block)
        return table, pools, {"hot": hot, "warm": len(warm_bids)}

    def forget_warm(self, chain_hash: int) -> None:
        """A hash just (re)registered in the hot prefix cache supersedes
        any warm copy — same chain hash means bit-identical KV by
        construction, so keeping both only wastes host RAM (and would
        trip the conservation audit's cross-tier exclusivity check).
        Call after every ``BlockAllocator.register`` that can re-create
        a previously demoted block."""
        if chain_hash in self.warm:
            self.warm.take(chain_hash)

    def tier_stats(self) -> Dict[str, int]:
        """Warm-tier occupancy/traffic, ``warm_``-prefixed for merging
        into ``GenerationServer.kv_stats()``."""
        return {f"warm_{k}": v for k, v in self.warm.stats().items()}

    # ------------------------------------------------------------- swap out
    def swap_out(self, rid: int, table: Sequence[int], hashes: Sequence[int],
                 pools: List[Any], n_tokens: int,
                 last_token: int, extra: Sequence[np.ndarray] = ()
                 ) -> Optional[SwapHandle]:
        """Park a request's KV on host and free its device blocks.

        ``table`` must already be truncated to exactly the blocks covering
        ``n_tokens`` (the server drops speculative reservations first).
        ``extra``: host arrays that travel with the request besides its
        blocks (the slot's window rings and recurrent state); they join the
        payload — one CRC, one host-pool charge — and come back from
        :meth:`swap_in` in ``handle.extra``.
        Returns None — and changes nothing — when the host pool is full
        (or an injected ``host_put`` fault says it is).
        """
        tel = self.telemetry
        _t0 = tel.clock() if tel is not None and tel.enabled else None
        a = self.alloc
        n = len(table)
        nbytes = n * a.bytes_per_block + sum(x.nbytes for x in extra)
        if self.faults is not None and self.faults.fire("host_put") is not None:
            return None
        if not self.host.fits(nbytes):
            return None
        arrays = self.gather_payload(table, pools) + list(extra)
        checksum = payload_checksum(arrays)
        if not self.host.put(rid, arrays, nbytes):
            return None
        for bid in table:
            a.free(bid)                   # hashed blocks land on the LRU
        a.note_swap_out(n, nbytes)
        if _t0 is not None:
            _t1 = tel.clock()
            tel.registry.histogram(
                "serving_swap_out_s",
                "device->host KV swap-out wall time").observe(_t1 - _t0)
            tel.registry.counter(
                "serving_swap_out_bytes",
                "KV bytes parked to host").inc(nbytes)
            tel.tracer.complete(rid, "swap_out", _t0, _t1,
                                blocks=n, bytes=nbytes)
        return SwapHandle(rid=rid, n_tokens=int(n_tokens),
                          last_token=int(last_token), n_blocks=n,
                          hashes=list(hashes), nbytes=nbytes,
                          checksum=checksum, n_extra=len(extra))

    # -------------------------------------------------------------- swap in
    def restore_cost(self, handle: SwapHandle) -> int:
        """Upper bound on fresh device blocks a resume needs — the
        server's admission headroom check. Resident-hash-aware: leading
        chain hashes still hot in the allocator restore for free
        (``match_hashes`` will re-ref them), so only the remainder costs
        fresh blocks. Read-only."""
        resident = 0
        for h in handle.hashes:
            if not self.alloc.contains_hash(h):
                break
            resident += 1
        return max(handle.n_blocks - resident, 0)

    def swap_in(self, handle: SwapHandle, pools: List[Any]
                ) -> Union[None, str, Tuple[List[int], List[Any]]]:
        """Restore a parked request: re-match still-resident prefix blocks
        by chain hash (free — no upload), allocate + upload the rest, and
        re-register restored full prompt blocks for prefix sharing.

        Returns ``(table, pools)`` with the updated pool list; None —
        changing nothing — if the device pool lacks headroom (the caller
        keeps the entry queued and tries again later); or the string
        ``"corrupt"`` when the parked payload fails its CRC check — the
        payload is dropped, device and host accounting are rolled back,
        and the caller must re-prefill the request from its tokens.
        """
        import jax.numpy as jnp

        tel = self.telemetry
        _t0 = tel.clock() if tel is not None and tel.enabled else None
        a = self.alloc
        matched = a.match_hashes(handle.hashes)
        need = handle.n_blocks - len(matched)
        if a.blocks_free + a.evictable_cached < need:
            for bid in matched:           # roll back: nothing restored
                a.free(bid)
            return None
        fresh: List[int] = []
        try:
            for _ in range(need):
                fresh.append(a.alloc())
        except RuntimeError:
            # headroom said yes but alloc refused (an injected exhaustion
            # fault, or a pin racing the estimate): roll everything back
            for bid in fresh + matched:
                a.free(bid)
            return None
        table = matched + fresh
        arrays = self.host.take(handle.rid, handle.nbytes)
        if self.faults is not None and \
                self.faults.fire("swap_corrupt") is not None:
            # the parked payload may be a read-only device-array view —
            # rewrap writable before flipping the bit
            arrays = [np.array(x) for x in arrays]
            self.faults.corrupt(arrays)
        if handle.checksum and payload_checksum(arrays) != handle.checksum:
            # the parked copy is damaged: drop it, release the claimed
            # blocks (host.take already uncharged the host pool)
            for bid in table:
                a.free(bid)
            a.note_host_release(handle.nbytes)
            if tel is not None and tel.enabled:
                tel.registry.counter(
                    "serving_swap_corruptions",
                    "parked KV payloads that failed CRC verification"
                ).inc()
            return "corrupt"
        if fresh:
            # fixed-width scatter: matched rows and padding target the
            # scratch block (duplicate writes there are discarded noise)
            idx = np.zeros((self.table_width,), np.int32)
            idx[len(matched):handle.n_blocks] = fresh
            didx = jnp.asarray(idx)
            pools = [p.at[didx].set(jnp.asarray(arr).astype(p.dtype))
                     for p, arr in zip(pools, arrays)]
        handle.extra = list(arrays[len(arrays) - handle.n_extra:]) \
            if handle.n_extra else None
        for i in range(len(matched), min(len(handle.hashes), len(table))):
            a.register(table[i], handle.hashes[i])
            self.forget_warm(handle.hashes[i])
        a.note_swap_in(handle.n_blocks, handle.nbytes)
        if _t0 is not None:
            _t1 = tel.clock()
            tel.registry.histogram(
                "serving_swap_in_s",
                "host->device KV swap-in wall time").observe(_t1 - _t0)
            tel.registry.counter(
                "serving_swap_in_bytes",
                "KV bytes restored from host").inc(handle.nbytes)
            tel.tracer.complete(handle.rid, "swap_in", _t0, _t1,
                                blocks=handle.n_blocks,
                                prefix_hits=len(matched),
                                bytes=handle.nbytes)
        return table, pools

    def discard(self, handle: SwapHandle) -> None:
        """Drop a parked copy without restoring it (cancelled request)."""
        self.host.discard(handle.rid, handle.nbytes)
        self.alloc.note_host_release(handle.nbytes)

    def adopt(self, handle: SwapHandle, arrays: List[np.ndarray]) -> None:
        """Re-park a payload captured by ``GenerationServer.snapshot()``
        into this engine's host pool (restore / migration): the request
        then resumes through the normal checksum-verified :meth:`swap_in`
        path, so a corrupted migration payload degrades to re-prefill
        instead of silently wrong tokens."""
        if not self.host.put(handle.rid, arrays, handle.nbytes):
            raise RuntimeError(
                f"host pool cannot hold restored request {handle.rid} "
                f"({handle.nbytes} bytes) — raise host_pool_bytes on the "
                f"restoring server")
        self.alloc.note_swap_out(handle.n_blocks, handle.nbytes)
