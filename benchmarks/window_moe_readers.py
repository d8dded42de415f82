"""What the readers of the window + full attention, held-experts cell's
per-layer metrics share, beside ``readers.py`` and ``hybrid_readers.py``:
the positions attended by kind, summed over the layers of the kind, from
records the driver already keeps — a full layer's from the step records'
decode contexts (``serve_paged``) and prompt chunks, a window layer's from
the engine's ``serving_decode_ctx_window`` per step (``run["hybrid_steps"]``,
``drivers/serve_hybrid.py``'s record) and the same chunks, each capped at
the window. A run without the by-kind record has no pairs; every sum is
then zero and the reader returns None."""
from __future__ import annotations

from .hybrid_readers import traced_pairs, window_pairs  # noqa: F401
from .roofline.window_moe_attention import chunk_positions, layers_of


def positions(run, pairs, kind: str) -> int:
    """Positions the decode rows and prompt chunks of ``pairs`` attended,
    summed over the layers of ``kind`` ("window" | "full")."""
    cfg = run["config"]
    dec = "decode_ctx_window" if kind == "window" else "decode_ctx"
    per_layer = sum((h if kind == "window" else s)[dec]
                    + chunk_positions(cfg, kind, s["prefill_chunks"])
                    for s, h in pairs)
    return layers_of(cfg, kind) * per_layer


def attention_roofline(run, kind: str):
    """Share of the roofline of ``kind``'s attention: work from the
    positions attended over the traced steps
    (benchmarks/roofline/window_moe_attention.py), time from the events
    traced under the program's ``<kind>_attend`` scope."""
    from .readers import kernel_roofline
    from .roofline import window_moe_attention as work

    st = traced_pairs(run)
    pos = positions(run, st, kind)
    if not st or not pos:
        return None
    cfg = run["config"]
    chunks = [c for s, _ in st for c in s["prefill_chunks"]]
    rows = sum(s["decode_rows"] for s, _ in st)
    return kernel_roofline(run, [rf"{kind}_attend"], work.flops(cfg, pos),
                           work.nbytes(cfg, kind, pos, rows, chunks))
