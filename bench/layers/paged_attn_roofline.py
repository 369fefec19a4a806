"""The paged attention kernel's share of its roofline: the least time for
the work the rows really need (each real query, and the keys and values of
the row's real length read once: bench/flops.py) over the kernel's device
time in the trace. Decode is the kernel's one-query case, so every call of
the window's programs counts: the chunk pass of each serve step and every
decode microstep. Work is counted the same whatever implements attention."""

from bench.flops import kernel_min_seconds

KERNEL = "paged_prefill_attention"


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(v for k, v in run.trace["ops"].items() if KERNEL in k)
    if kernel_s <= 0:
        return None
    K = run.engine["decode_steps"]
    calls = []
    for p in run.progs:
        if p.chunks:
            calls.append(p.chunks)
        for j in range(K):
            calls.append([(pos + j, 1) for pos, n in p.decode if j < n])
    least = kernel_min_seconds(run.model, calls, run.peaks["bf16_flops"],
                               run.peaks["hbm_bytes_per_s"], run.chips)
    return 100.0 * least / kernel_s if least else None
