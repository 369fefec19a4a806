"""Share of the device's busy time spent in collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, by op
category or name), averaged over the cell's chips (bench/trace_spans.py)."""

from bench import trace_spans


def read(run):
    red = trace_spans.extend(run)
    if red is None or red["busy_s"] <= 0:
        return None
    coll = red["category_s"].get("collective")
    return 100.0 * coll / red["busy_s"] if coll else None
