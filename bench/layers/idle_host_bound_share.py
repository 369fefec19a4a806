"""Share of the traced window, averaged over the cell's chips, in which the
chip was idle while one of the engine's host spans was open (``engine.*``,
``kv.*``: bench/trace_spans.py): idle time the engine's own host work
holds the chip back. The rest of ``device_idle_share`` fell under no span,
such as the wait for an arrival."""

from bench import trace_spans


def read(run):
    red = trace_spans.extend(run)
    if red is None or not red["host_spans"] or red["window_s"] <= 0:
        return None
    return 100.0 * red["idle_in_span_s"] / red["window_s"]
