"""Speculative decoding for the paged serving path — drafters + exact
acceptance.

Paged decode (docs/serving.md) still spends one full target-model forward
per emitted token, so decode latency is bound by model depth, not FLOPs.
Speculative decoding amortizes that: a cheap DRAFTER proposes ``k`` tokens
and the target model scores all ``k+1`` window positions in ONE compiled
program (``models/llama.py paged_verify_step`` over the multi-token verify
op in ``ops/paged_attention.py``) — the same "fewer, bigger programs"
economics that operator fusion exploits in XLA.

Exactness contract (the whole point — speculation must be FREE of quality
cost):

- **greedy** (temperature 0): a draft token is accepted iff it equals the
  target argmax at its position, and the first mismatch position's argmax
  is emitted as the correction — the emitted chain is bit-identical to the
  dense server's, token for token.
- **temperature sampling**: standard speculative rejection sampling
  [Leviathan et al.; Chen et al.]. Draft token ``x`` with draft
  probability ``q(x)`` is accepted with probability
  ``min(1, p(x) / q(x))`` against the *filtered* target distribution ``p``
  (the same temperature/top-k/top-p filtering the dense tick samples
  from, ``models/generation.py``); on rejection the emitted token is drawn
  from the normalized residual ``max(p - q, 0)``, and after a fully
  accepted window a bonus token is drawn from ``p`` directly. The output
  DISTRIBUTION provably equals the target model's — acceptance rate only
  moves throughput, never quality.

Both built-in drafters propose deterministically by default, so their
draft distribution is a point mass and ``min(1, p/q)`` reduces to
``p(x)`` (the one-hot ``q`` is synthesized inside the compiled verify
program — nothing extra crosses the host boundary):

- :class:`NgramDrafter` — prompt-lookup decoding: no extra weights, pure
  host-side numpy over the request's own context (prompt + generated), so
  it runs in tier-1 CPU tests and adds zero device programs.
- :class:`DraftModelDrafter` — a small causal LM sharing the target's
  tokenizer, run as ONE fixed-shape compiled program per tick (k full
  forwards over a (B, max_len) buffer via lax.scan — no KV cache, no
  per-context-length recompiles). With ``sample_draft=True`` it samples
  at the request temperature and ships its full softmax as ``q``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

__all__ = ["SpecConfig", "NgramDrafter", "DraftModelDrafter",
           "DrafterFault", "speculative_accept", "ngram_propose_device"]


class DrafterFault(RuntimeError):
    """A drafter failed to produce proposals (injected or real).

    Recoverable by design: the serving loop catches this, runs the trip
    through the always-warm plain decode program instead, and holds the
    speculation gate off for a cooldown — the drafter is an accelerator,
    never a correctness dependency."""


# --------------------------------------------------------------------------- #
# Acceptance — the exact rejection sampler (compiled, fixed shapes)
# --------------------------------------------------------------------------- #


def speculative_accept(logits, proposals, temps, topks, topps, kcaps, key,
                       qprobs=None, greedy=False):
    """Vectorized exact accept/reject over one verify window.

    logits: fp32 (B, W, V) target logits for window positions
    ``pos..pos+k`` (W = k+1); ``logits[:, j]`` is the target distribution
    for the token FOLLOWING window position j. proposals: int32 (B, k)
    draft tokens (window positions ``pos+1..pos+k``). temps/topps fp32
    [B], topks int32 [B]: per-row sampling params (temp 0 → greedy).
    kcaps: int32 [B] per-row draft budget ≤ k — positions ≥ kcap are
    force-stopped: no draft is consumed there, the emitted token comes
    from the FULL target distribution (a kcap of 0 reduces the row to a
    plain decode tick). qprobs: optional fp32 (B, k, V) draft
    distributions; None means deterministic proposals (one-hot q).
    greedy: STATIC python bool — True asserts every row has temp 0, so the
    whole sampling machinery (top-k/top-p filtering, residual resampling)
    is dropped at trace time and acceptance compiles to pure argmax
    comparison. Token-identical to the general path at temp 0 (the
    general path already routes temp-0 rows through ``tgt``); the caller
    promises the precondition and keys the jit cache on the flag.

    Returns ``(out, acc)``: out int32 (B, W) where ``out[b, :acc[b]+1]``
    are the emitted tokens — accepted drafts then one
    correction/bonus — and acc int32 [B] is the accepted-draft count.
    Everything is branch-free jnp so the caller can jit it as part of the
    fused verify program.
    """
    import jax
    import jax.numpy as jnp

    from ..models.generation import filtered_probs_rows

    B, W, V = logits.shape
    k = W - 1
    lg = logits.astype(jnp.float32)

    # greedy target chain: argmax per window position (the dense oracle)
    tgt = jnp.argmax(lg, axis=-1).astype(jnp.int32)              # (B, W)

    if greedy:
        jpos = jnp.arange(k)[None, :]                            # (1, k)
        acc_tok = (proposals == tgt[:, :k]) & (jpos < kcaps[:, None])
        acc = jnp.sum(jnp.cumprod(acc_tok.astype(jnp.int32), axis=1),
                      axis=1).astype(jnp.int32)                  # (B,)
        prop_pad = jnp.concatenate(
            [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1)   # (B, W)
        wpos = jnp.arange(W)[None, :]
        out = jnp.where(wpos < acc[:, None], prop_pad, tgt)
        return out, acc

    # filtered target distribution per position for sampling rows — the
    # SAME temperature/top-k/top-p filter the dense tick samples from
    p = filtered_probs_rows(
        lg.reshape(B * W, V),
        jnp.repeat(temps, W), jnp.repeat(topks, W),
        jnp.repeat(topps, W)).reshape(B, W, V)

    if qprobs is None:
        q = jax.nn.one_hot(proposals, V, dtype=jnp.float32)      # (B, k, V)
        q_at_d = jnp.ones((B, k), jnp.float32)
    else:
        q = qprobs.astype(jnp.float32)
        q_at_d = jnp.take_along_axis(q, proposals[..., None],
                                     axis=-1)[..., 0]
    p_at_d = jnp.take_along_axis(p[:, :k], proposals[..., None],
                                 axis=-1)[..., 0]                # (B, k)

    ukey, rkey, bkey = jax.random.split(key, 3)
    jpos = jnp.arange(k)[None, :]                                # (1, k)
    u = jax.random.uniform(ukey, (B, k))
    acc_sample = u * jnp.maximum(q_at_d, 1e-20) < p_at_d
    acc_greedy = proposals == tgt[:, :k]
    acc_tok = jnp.where((temps > 0)[:, None], acc_sample, acc_greedy)
    acc_tok = acc_tok & (jpos < kcaps[:, None])
    # leading-accept count: first rejection (or kcap) stops the chain
    acc = jnp.sum(jnp.cumprod(acc_tok.astype(jnp.int32), axis=1),
                  axis=1).astype(jnp.int32)                      # (B,)

    # correction tokens, one per window index (only index ``acc`` is used):
    # - true rejection (j < kcap): residual max(p - q, 0), renormalized
    # - forced stop / bonus (j >= kcap, incl. j == k): full target p
    resid = jnp.maximum(p[:, :k] - q, 0.0)
    rs = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rs > 0, resid / jnp.maximum(rs, 1e-20), p[:, :k])
    corr_resid = jax.random.categorical(
        rkey, jnp.log(resid + 1e-30), axis=-1).astype(jnp.int32)  # (B, k)
    corr_full = jax.random.categorical(
        bkey, jnp.log(p + 1e-30), axis=-1).astype(jnp.int32)      # (B, W)
    wpos = jnp.arange(W)[None, :]
    corr_resid = jnp.concatenate([corr_resid, corr_full[:, -1:]], axis=1)
    corr_sample = jnp.where(wpos < kcaps[:, None], corr_resid, corr_full)
    corr = jnp.where((temps > 0)[:, None], corr_sample, tgt)      # (B, W)

    prop_pad = jnp.concatenate(
        [proposals, jnp.zeros((B, 1), jnp.int32)], axis=1)        # (B, W)
    out = jnp.where(wpos < acc[:, None], prop_pad, corr)
    return out, acc


# --------------------------------------------------------------------------- #
# Drafters
# --------------------------------------------------------------------------- #


def ngram_propose_device(ctx, pos, k, max_ngram=3, min_ngram=1):
    """Prompt-lookup drafting as a branch-free jnp op — the in-program twin
    of :meth:`NgramDrafter.propose_one`, so the whole
    draft→verify→accept window can live inside ONE compiled program and
    ``GenerationServer`` can lax.scan several windows per host round trip
    (the spec analogue of ``tick_window``).

    ctx: int32 (B, L) token buffer, row b valid through index ``pos[b]``
    (the current token); pos: int32 (B,). Returns int32 (B, k) proposals:
    the continuation of the most recent longest-n-gram match of each row's
    suffix within its own context, clamped at the context end (which pads
    short continuations by repeating the last token, exactly like the host
    drafter); rows with no match ≥ min_ngram repeat their last token.
    """
    import jax.numpy as jnp

    B, L = ctx.shape
    ar = jnp.arange(L)[None, :]                              # (1, L)
    # cont_start[b]: where the proposed continuation begins; initialized to
    # pos so the fallback (and every clamp) repeats the last token
    cont_start = jnp.broadcast_to(pos[:, None], (B, 1))[:, 0]
    found = jnp.zeros((B,), bool)
    for n in range(max_ngram, min_ngram - 1, -1):
        # suffix token j of the n-gram ending at pos: ctx[pos-n+1+j]
        sidx = jnp.clip(pos[:, None] + jnp.arange(1 - n, 1)[None, :], 0,
                        L - 1)                               # (B, n)
        suffix = jnp.take_along_axis(ctx, sidx, axis=1)      # (B, n)
        match = jnp.ones((B, L), bool)
        for j in range(n):
            # window starting at i matches suffix[j] at i+j (clamped reads
            # past L-1 are masked off by the validity bound below)
            shifted = jnp.take_along_axis(
                ctx, jnp.clip(ar + j, 0, L - 1).repeat(B, 0), axis=1)
            match = match & (shifted == suffix[:, j:j + 1])
        # valid starts: window inside ctx[:pos] — excludes the trivial
        # self-match at pos-n+1 and guarantees a continuation token
        valid = match & (ar <= (pos - n)[:, None])
        last = jnp.max(jnp.where(valid, ar, -1), axis=1)     # (B,)
        hit = (last >= 0) & ~found
        cont_start = jnp.where(hit, last + n, cont_start)
        found = found | hit
    pidx = jnp.minimum(cont_start[:, None] + jnp.arange(k)[None, :],
                       pos[:, None])                         # (B, k)
    return jnp.take_along_axis(ctx, pidx, axis=1).astype(jnp.int32)


class NgramDrafter:
    """Prompt-lookup decoding: propose the continuation of the most recent
    longest n-gram match of the context's own suffix.

    Zero extra weights and zero device work — the draft source is the
    request's context (prompt + generated so far), searched host-side with
    numpy. Strong on repeated-suffix workloads (retrieval answers quoting
    the prompt, code edits, self-repeating generations); on a miss it
    falls back to repeating the last token, whose proposals simply get
    rejected (fixed shapes beat adaptive k on TPU).
    """

    deterministic = True
    fusible = True   # has propose_device: drafting can live in-program

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"min_ngram={min_ngram}, max_ngram={max_ngram}")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        # optional FaultInjector (inference/faults.py), wired by the server
        self.faults = None

    def propose_one(self, ctx: Sequence[int], k: int) -> np.ndarray:
        """k proposed continuation tokens for one context (host numpy)."""
        ctx = np.asarray(ctx, np.int32)
        n_ctx = len(ctx)
        for n in range(min(self.max_ngram, n_ctx - 1), self.min_ngram - 1,
                       -1):
            suffix = ctx[n_ctx - n:]
            # candidate starts i <= n_ctx-1-n: the window view over
            # ctx[:-1] excludes the trivial self-match at the very end and
            # guarantees at least one continuation token exists
            windows = np.lib.stride_tricks.sliding_window_view(
                ctx[:n_ctx - 1], n)
            hits = np.nonzero((windows == suffix).all(axis=1))[0]
            if hits.size:
                i = int(hits[-1])                 # most recent occurrence
                cont = ctx[i + n:i + n + k]
                if len(cont) < k:                 # pad: repeat last token
                    pad = np.full(k - len(cont), cont[-1] if len(cont)
                                  else ctx[-1], np.int32)
                    cont = np.concatenate([cont, pad])
                return cont.astype(np.int32)
        return np.full(k, ctx[-1], np.int32)      # miss: repeat last token

    def propose(self, contexts: List[Optional[Sequence[int]]], k: int,
                temps=None, key=None) -> Tuple[np.ndarray, None]:
        """Batch proposals: (B, k) int32, one row per slot (idle slots pass
        None and get zeros — their rows run masked into scratch)."""
        if self.faults is not None and \
                self.faults.fire("drafter") is not None:
            raise DrafterFault("injected drafter failure (ngram)")
        out = np.zeros((len(contexts), k), np.int32)
        for i, ctx in enumerate(contexts):
            if ctx is not None and len(ctx):
                out[i] = self.propose_one(ctx, k)
        return out, None

    def propose_device(self, ctx, pos, k):
        """In-program drafting (traced): :func:`ngram_propose_device` with
        this drafter's n-gram bounds."""
        return ngram_propose_device(ctx, pos, k, max_ngram=self.max_ngram,
                                    min_ngram=self.min_ngram)


class DraftModelDrafter:
    """Small-LM drafter: a cheap causal model sharing the target's
    tokenizer proposes k tokens autoregressively.

    TPU-shaped: ONE compiled program per tick runs k full forwards over a
    fixed (B, max_len) token buffer via lax.scan — no draft KV cache, no
    per-context-length compile family, zero steady-state recompiles. The
    draft model is depth-cheap by construction, so k extra full forwards
    of it still undercut one target forward per token.

    ``sample_draft=False`` (default): greedy proposals — a point-mass
    draft distribution, acceptance reduces to ``p(x)``. ``True``: rows
    with temperature > 0 sample at the request temperature and the full
    draft softmax ships to the verify program as ``q`` for the
    ``min(1, p/q)`` rule (greedy rows still propose argmax with one-hot
    q), which raises acceptance on hot sampled traffic.
    """

    fusible = False  # drafting needs its own program + host orchestration

    def __init__(self, model, max_len: int, sample_draft: bool = False):
        self.model = model
        self.max_len = int(max_len)
        self.sample_draft = bool(sample_draft)
        self.deterministic = not self.sample_draft
        # optional FaultInjector (inference/faults.py), wired by the server
        self.faults = None
        from ..jit import state_values

        self.params = state_values(model)
        self._jit = {}

    def _build(self, k: int):
        import jax
        import jax.numpy as jnp

        from ..framework.core import Tensor
        from ..jit import functional_call

        model = self.model
        sample = self.sample_draft

        def fn(params, buf, pos, temps, key):
            B, L = buf.shape
            rows = jnp.arange(B)

            def body(carry, j):
                buf, p = carry
                logits = functional_call(model, params, Tensor(buf))
                logits = logits[0] if isinstance(logits, (list, tuple)) \
                    else logits
                lg = jnp.take_along_axis(
                    logits.value, p[:, None, None], axis=1
                )[:, 0].astype(jnp.float32)                     # (B, V)
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                if sample:
                    scaled = lg / jnp.maximum(temps, 1e-6)[:, None]
                    drawn = jax.random.categorical(
                        jax.random.fold_in(key, j), scaled,
                        axis=-1).astype(jnp.int32)
                    nxt = jnp.where(temps > 0, drawn, greedy)
                    q = jnp.where((temps > 0)[:, None],
                                  jax.nn.softmax(scaled, axis=-1),
                                  jax.nn.one_hot(greedy, lg.shape[-1],
                                                 dtype=jnp.float32))
                else:
                    nxt = greedy
                    q = jnp.zeros((B, 0), jnp.float32)  # unused placeholder
                p2 = jnp.minimum(p + 1, L - 1)
                buf = buf.at[rows, p2].set(nxt)
                return (buf, p2), (nxt, q)

            _, (toks, qs) = jax.lax.scan(body, (buf, pos), jnp.arange(k))
            toks = jnp.swapaxes(toks, 0, 1)                     # (B, k)
            qs = jnp.swapaxes(qs, 0, 1) if sample else None     # (B, k, V)
            return toks, qs

        return jax.jit(fn)

    def propose(self, contexts: List[Optional[Sequence[int]]], k: int,
                temps=None, key=None):
        import jax
        import jax.numpy as jnp

        if self.faults is not None and \
                self.faults.fire("drafter") is not None:
            raise DrafterFault("injected drafter failure (draft model)")
        B = len(contexts)
        buf = np.zeros((B, self.max_len), np.int32)
        pos = np.zeros((B,), np.int32)
        for i, ctx in enumerate(contexts):
            if ctx is not None and len(ctx):
                ctx = list(ctx)[-self.max_len:]
                buf[i, :len(ctx)] = ctx
                pos[i] = len(ctx) - 1
        if k not in self._jit:
            self._jit[k] = self._build(k)
        if temps is None:
            temps = np.zeros((B,), np.float32)
        if key is None:
            key = jax.random.PRNGKey(0)
        toks, qs = self._jit[k](self.params, jnp.asarray(buf),
                                jnp.asarray(pos), jnp.asarray(temps), key)
        return toks, (qs if self.sample_draft else None)


# --------------------------------------------------------------------------- #
# Config
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decoding knobs for ``GenerationServer(..., spec=...)``.

    k: draft tokens per verify window (window width = k+1; per-request
    ``submit(..., draft_k=)`` can lower it without changing shapes).
    drafter: ``"ngram"`` (prompt lookup, default), ``"model"`` (requires
    ``draft_model``), or any object with the drafter protocol
    (``deterministic`` attr + ``propose(contexts, k, temps, key)``).

    gate_low / gate_cooldown: the DYNAMIC SPECULATION GATE. A verify
    window costs roughly (k+1)/width more than a plain decode tick but
    advances only 1 token when every draft is rejected — on real streams
    rejection clusters (a request's early tokens, before the drafter has
    context to mine), so paying for drafts there is a pure loss. After
    each speculative trip the server measures mean accepted drafts per
    window per live row; below ``gate_low`` it falls back to the
    already-compiled plain decode program for ``gate_cooldown`` trips,
    then probes speculation again. Both programs exist from warmup, so
    gating switches per trip with zero steady-state compiles.
    ``gate_cooldown=0`` disables the gate (always speculate). The
    break-even acceptance is roughly ``verify_window_cost/tick_cost - 1``
    (~k/2 at small-model shapes) — the default ``gate_low`` is tuned
    for k=4; scale it with k.
    ``gate_ticks`` is the decode-tick count of each gated plain trip —
    independent of the verify ``tick_window``, because the gated-off
    phase is pure sequential decode and wants long trips to amortize the
    host round trip (the probe cadence in tokens is
    ``gate_cooldown * gate_ticks``).

    turbo_windows: the gate's LONG-TRIP tier (fused drafters only,
    default 0 = disabled). When a trip's mean accepted drafts per window
    reaches ``k - 1`` across the batch, streams have locked into
    drafter-predictable runs — the next trips fuse ``turbo_windows``
    windows per program instead of ``tick_window``, amortizing the host
    round trip over up to ``turbo_windows*(k+1)`` tokens. Drops back the
    moment acceptance dips. A third compiled variant, built once. Worth
    enabling when the host<->device round trip dominates; otherwise the
    coarser slot-refill granularity of long trips usually costs more than
    the saved round trips.
    """

    k: int = 4
    drafter: Union[str, Any] = "ngram"
    ngram_max: int = 3
    ngram_min: int = 1
    draft_model: Any = None
    sample_draft: bool = False
    gate_low: float = 2.0
    gate_cooldown: int = 3
    gate_ticks: int = 16
    turbo_windows: int = 0

    def validate(self) -> None:
        if isinstance(self.k, bool) or not isinstance(self.k, int) \
                or self.k < 1:
            raise ValueError(f"spec.k must be an int >= 1, got {self.k!r}")
        if isinstance(self.drafter, str) and \
                self.drafter not in ("ngram", "model"):
            raise ValueError(
                f"spec.drafter must be 'ngram', 'model', or a drafter "
                f"object, got {self.drafter!r}")
        if self.drafter == "model" and self.draft_model is None:
            raise ValueError(
                "spec.drafter='model' requires spec.draft_model (a small "
                "causal LM sharing the target tokenizer)")
        if self.ngram_min < 1 or self.ngram_max < self.ngram_min:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"ngram_min={self.ngram_min}, ngram_max={self.ngram_max}")
        if not isinstance(self.gate_cooldown, int) \
                or isinstance(self.gate_cooldown, bool) \
                or self.gate_cooldown < 0:
            raise ValueError(f"spec.gate_cooldown must be an int >= 0 "
                             f"(0 disables the gate), got "
                             f"{self.gate_cooldown!r}")
        if not self.gate_low >= 0.0:
            raise ValueError(
                f"spec.gate_low must be >= 0, got {self.gate_low!r}")
        if not isinstance(self.gate_ticks, int) \
                or isinstance(self.gate_ticks, bool) or self.gate_ticks < 1:
            raise ValueError(f"spec.gate_ticks must be an int >= 1, got "
                             f"{self.gate_ticks!r}")
        if not isinstance(self.turbo_windows, int) \
                or isinstance(self.turbo_windows, bool) \
                or self.turbo_windows < 0:
            raise ValueError(f"spec.turbo_windows must be an int >= 0 "
                             f"(0 disables the turbo tier), got "
                             f"{self.turbo_windows!r}")

    def describe(self) -> Dict[str, Any]:
        """Flat JSON-safe knob dict for telemetry snapshots — records the
        speculation configuration next to the numbers it produced (an
        acceptance rate is meaningless without k and the gate settings)."""
        return {"k": self.k,
                "drafter": (self.drafter if isinstance(self.drafter, str)
                            else type(self.drafter).__name__),
                "ngram_max": self.ngram_max, "ngram_min": self.ngram_min,
                "sample_draft": self.sample_draft,
                "gate_low": self.gate_low,
                "gate_cooldown": self.gate_cooldown,
                "gate_ticks": self.gate_ticks,
                "turbo_windows": self.turbo_windows}

    def build_drafter(self, max_len: int):
        if not isinstance(self.drafter, str):
            return self.drafter
        if self.drafter == "ngram":
            return NgramDrafter(max_ngram=self.ngram_max,
                                min_ngram=self.ngram_min)
        return DraftModelDrafter(self.draft_model, max_len,
                                 sample_draft=self.sample_draft)
