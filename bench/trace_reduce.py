"""From a profiler trace (``.xplane.pb``) to device busy and idle time, time
per program and time per operation.

On a TPU every chip is a plane named ``/device:TPU:<n>`` whose ``XLA Ops``
line holds one event per operation run and whose ``XLA Modules`` line holds
one event per program run. On the CPU (used by the tests) operations run on
host threads and carry their module in the event's stats; a program run is
then the span of the operations that share a ``run_id``.

Each instant on a chip is given to the innermost operation running then, so
the operations' self times add up to the busy time, and busy plus idle is
the window. The window is the host-side ``TraceAnnotation`` named
``WINDOW`` when the trace holds one, else the span of the operations.
Times are averaged over the chips.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench_window"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


def _load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _window(pd) -> Optional[Tuple[int, int]]:
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    return int(e.start_ns), int(e.start_ns + e.duration_ns)
    return None


def _op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``): keep the instruction's name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _devices(pd) -> List[Tuple[List[Interval], List[Interval]]]:
    """Per chip: (operations, program runs)."""
    out = []
    for plane in pd.planes:
        if not _TPU_PLANE.match(plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            dst = ops if line.name == "XLA Ops" else mods
            for e in line.events:
                s = int(e.start_ns)
                dst.append((_op_name(e.name), s, s + int(e.duration_ns)))
        out.append((ops, mods))
    if out:
        return out
    # CPU: operations on host threads, tagged with their module and run
    ops, runs = [], defaultdict(lambda: [None, None, None])
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "hlo_op" not in st or e.name.startswith("end: "):
                    continue
                s = int(e.start_ns)
                end = s + int(e.duration_ns)
                ops.append((e.name, s, end))
                r = runs[(st.get("hlo_module", "?"), st.get("run_id"))]
                r[0] = st.get("hlo_module", "?")
                r[1] = s if r[1] is None else min(r[1], s)
                r[2] = end if r[2] is None else max(r[2], end)
    mods = [tuple(r) for r in runs.values()]
    return [(ops, mods)] if ops else []


def _clip(evs: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if min(e, hi) > max(s, lo)]


def self_times(ops: List[Interval]) -> Tuple[Dict[str, int], List[Tuple[int, int, str]]]:
    """Self time per operation name (each instant to the innermost
    operation), and the busy intervals with the name of the operation that
    ended each."""
    evs = sorted(ops, key=lambda x: (x[1], -x[2]))
    selft: Dict[str, int] = defaultdict(int)
    busy: List[Tuple[int, int, str]] = []
    stack: List[Interval] = []
    t = None
    i = 0
    while i < len(evs) or stack:
        nxt_start = evs[i][1] if i < len(evs) else None
        nxt_end = min(e for _, _, e in stack) if stack else None
        if nxt_end is not None and (nxt_start is None or nxt_end <= nxt_start):
            top = stack[-1]
            selft[top[0]] += nxt_end - t
            t = nxt_end
            ended = [x for x in stack if x[2] == nxt_end]
            stack = [x for x in stack if x[2] != nxt_end]
            if not stack:
                busy[-1] = (busy[-1][0], t, ended[-1][0])
            continue
        name, s, e = evs[i]
        i += 1
        if stack:
            selft[stack[-1][0]] += s - t
        else:
            busy.append((s, e, name))
        t = s
        stack.append((name, s, e))
    return dict(selft), busy


def reduce(path: str, top: int = 10) -> Optional[dict]:
    """The reduced trace, or None when it holds no device operations."""
    pd = _load(path)
    devs = _devices(pd)
    if not devs or not any(ops for ops, _ in devs):
        return None
    win = _window(pd)
    if win is None:
        lo = min(s for ops, _ in devs for _, s, _ in ops)
        hi = max(e for ops, _ in devs for _, _, e in ops)
        win = (lo, hi)
    lo, hi = win
    n = len(devs)
    op_s: Dict[str, float] = defaultdict(float)
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    busy_s = 0.0
    gaps: List[Tuple[str, float]] = []
    for d, (ops, mods) in enumerate(devs):
        selft, busy = self_times(_clip(ops, lo, hi))
        for k, v in selft.items():
            op_s[k] += v / 1e9 / n
        busy_s += sum(e - s for s, e, _ in busy) / 1e9 / n
        for name, s, e in _clip(mods, lo, hi):
            mod_s[name] += (e - s) / 1e9 / n
            if d == 0:
                mod_n[name] += 1
        if d == 0:
            prev_end, prev = lo, "window start"
            for s, e, name in busy:
                if s > prev_end:
                    gaps.append((f"idle after {prev}", (s - prev_end) / 1e9))
                prev_end, prev = e, name
            if hi > prev_end:
                gaps.append((f"idle after {prev}", (hi - prev_end) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "ops": dict(op_s),
        "modules": {k: {"count": mod_n[k], "seconds": v}
                    for k, v in mod_s.items()},
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
    }


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
