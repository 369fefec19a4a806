"""Device time per run of the serve-step program (the chunk pass and its
fused decode microsteps), from the trace. The serve step and the plain
decode program carry the same module name, so the serve step is the module
that ran once per serve step dispatched in the traced window; where two
match, the longer (the serve step holds the decode program's work and the
chunk pass)."""


def read(run):
    if run.trace is None:
        return None
    n = sum(p.kind == "serve" for p in run.progs)
    if not n:
        return None
    hits = [v["seconds"] / v["count"] for v in run.trace["modules"].values()
            if v["count"] == n]
    return 1e3 * max(hits) if hits else None
