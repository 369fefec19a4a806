"""Peak KV blocks in use over the pool's blocks (PagedKV's kv_blocks_hwm
over kv_blocks_total) in the window."""


def read(run):
    total = run.counters.get("kv_blocks_total")
    if not total:
        return None
    return 100.0 * run.counters["kv_blocks_hwm"] / total
