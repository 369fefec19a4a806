"""Share of the prompt tokens admitted in the window that the radix prefix
index found resident (PagedKV's kv_prefix_shared_tokens over the engine's
prefill_tokens)."""


def read(run):
    admitted = run.counters.get("prefill_tokens", 0)
    if not admitted:
        return None
    return 100.0 * run.counters["kv_prefix_shared_tokens"] / admitted
