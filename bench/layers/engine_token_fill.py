"""The engine's own count of the token positions its programs computed
that were useful (``positions_useful / positions_computed`` from
``ServeEngine.utilization()`` at drain): the arithmetic of
``step_token_fill``, kept by the engine at each dispatch."""


def read(run):
    computed = run.counters.get("positions_computed")
    if not computed:
        return None
    return 100.0 * run.counters["positions_useful"] / computed
