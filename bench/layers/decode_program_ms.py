"""Device time per run of the plain decode program (the K fused decode
microsteps a step runs when no slot is mid-prefill), from the trace's
program named ``jit_decode_program``."""

import re

NAME = "jit_decode_program"


def read(run):
    if run.trace is None:
        return None
    runs = [m for name, m in run.trace["modules"].items()
            if re.sub(r"\(\d+\)$", "", name) == NAME]
    count = sum(m["count"] for m in runs)
    if not count:
        return None
    return 1e3 * sum(m["seconds"] for m in runs) / count
