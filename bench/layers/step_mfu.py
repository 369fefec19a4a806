"""Model FLOPs of the useful work delivered in the window (prompt positions
absorbed, the head for each first token, decoded tokens kept; attention at
each token's real context, bench/flops.py) over the window's seconds x
chips x the chip's bf16 peak, in percent."""


def read(run):
    m = run.model
    total = 0
    for p in run.progs:
        if p.ready is None or p.ready > run.end:
            continue
        total += sum(m.prefill_flops(s, n, False) for s, n in p.chunks)
        total += len(p.emitted) * 2 * m.head_params
        total += sum(m.decode_flops(pos + j)
                     for pos, n in p.decode for j in range(n))
    peak = run.peaks["bf16_flops"] * run.chips * (run.end - run.t0)
    return 100.0 * total / peak if total else None
