"""Cache: peak over the window's steps of the bytes in use by the three
kinds of cache a hybrid model keeps (blocks of the full layer's pool, window
rings and recurrent state of the occupied slots) over the bytes allotted to
them (the program's ``cache_bytes()``, in ``kv_stats()``). In use after each
step: the allocator's blocks in use and the occupied slots, kept per step by
drivers/serve_hybrid.py in the traced run."""
from benchmarks.hybrid_readers import window_pairs

KINDS = ("full", "window", "state")


def read(run):
    st = [h for _, h in window_pairs(run)]
    kv = run.get("kv_stats") or {}
    allotted = sum(kv.get(f"cache_bytes_{k}_allotted", 0) for k in KINDS)
    if not st or not allotted:
        return None
    used = max(sum(h[f"cache_bytes_{k}"] for k in KINDS) for h in st)
    return 100.0 * used / allotted
