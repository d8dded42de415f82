"""Cache: ``kv_stats()`` peak blocks in use over the blocks of the pool."""


def read(run):
    kv = run.get("kv_stats") or {}
    if not kv.get("num_blocks") or "peak_blocks_in_use" not in kv:
        return None
    return 100.0 * kv["peak_blocks_in_use"] / kv["num_blocks"]
