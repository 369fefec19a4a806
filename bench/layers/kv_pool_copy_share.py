"""Share of the device's busy time spent in ops under the ``layer_scan``
scope and under no scope of the layer body (``attn``, ``mlp``, ``norm``,
``kv_write``): the layer scan slicing each layer's part of the stacked KV
pool out and stacking it back (bench/trace_spans.py)."""

from bench import trace_spans


def read(run):
    red = trace_spans.extend(run)
    if red is None or red["busy_s"] <= 0:
        return None
    scopes = red["scope_s"]
    if not any("layer_scan" in k.split("/") for k in scopes):
        return None
    copies = sum(v for k, v in scopes.items() if trace_spans.is_pool_copy(k))
    return 100.0 * copies / red["busy_s"]
