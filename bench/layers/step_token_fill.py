"""Share of the token positions the window's programs computed that were
useful: prompt positions absorbed plus decode microsteps that produced a
kept token, over slots x (chunk width + decode steps) per serve step and
slots x decode steps per plain decode program. Every serve step pads to one
(slots, width) shape and decodes every slot."""


def read(run):
    B, W = run.engine["slots"], run.engine["chunk_width"]
    K = run.engine["decode_steps"]
    useful = computed = 0
    for p in run.progs:
        if p.ready is None or p.ready > run.end:
            continue
        useful += sum(n for _, n in p.chunks) + sum(n for _, n in p.decode)
        computed += B * ((W + K) if p.kind == "serve" else K)
    return 100.0 * useful / computed if computed else None
