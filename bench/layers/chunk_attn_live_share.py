"""KV blocks the paged attention kernel attends in the chunk passes over
those of its grid (``chunk_blocks_live / chunk_blocks_grid`` from
``ServeEngine.utilization()`` at drain): the share of the grid at or below
a row's last real query. The kernel skips the rest; an engine that does
not count them reads nothing."""


def read(run):
    grid = run.counters.get("chunk_blocks_grid")
    if not grid:
        return None
    return 100.0 * run.counters["chunk_blocks_live"] / grid
