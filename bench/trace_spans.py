"""What the host was doing while the chips sat idle, and where device time
went by program, scope and category: the engine's host spans joined with
the device operations of a ``--trace 1`` run's profiler trace.

The engine opens a ``jax.profiler.TraceAnnotation`` for each phase of its
tick (``engine.tick``, ``engine.admit``, ``engine.plan``,
``engine.dispatch``, ``engine.overlap``, ``engine.harvest``,
``engine.finalize``, ``kv.publish``), so they lie on the host planes of the
trace on the profiler's clock, the one the device ops and the
``bench_window`` annotation use. Its programs carry ``jax.named_scope``s
(``chunk_pass``, ``decode_microsteps``, ``layer_scan``, ``attn``, ``mlp``,
``norm``, ``kv_write``, ``head``, ``sample``) in each op's HLO metadata.

``extend(run)`` adds new keys to the reduced trace ``bench/trace_reduce``
made (``run.trace``), from the same trace file, window and busy intervals:

- ``host_spans``: per span name, its count and seconds in the window;
- ``idle_in_span_s`` / ``idle_outside_span_s``: idle time on the chips
  (averaged over them) while some engine span was open / while none was;
  they add up to ``window_s - busy_s``;
- ``modules_by_name``: per program name, runs and device seconds;
- ``scope_s`` / ``category_s``: device self time per scope path (the known
  scopes of the op's ``op_name``, in order, joined by ``/``) and per op
  category, averaged over the chips;
- ``idle_by_activity``: chip 0's idle seconds per host activity;

and names each of ``idle_gaps`` by what the host was doing (lengths and
order unchanged): ``<span> after <op>`` for the innermost engine span open
over most of the gap, ``compile after <op>`` where a JAX compile overlaps
it, ``no engine span after <op>`` where neither does. A program without
spans or scopes reads as having none: the metrics that need them read
nothing.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

#: host annotations the engine opens, by name prefix
SPAN_PREFIXES = ("engine.", "kv.")
#: JAX's compile entry points (``jax._src.compiler``), annotated on the host
COMPILE_PREFIX = "backend_compile"
#: the scopes the serving programs carry; an op_name's other parts are JAX's
SCOPES = ("chunk_pass", "decode_microsteps", "layer_scan", "attn", "mlp",
          "norm", "kv_write", "head", "sample")
#: scopes of the layer body: an op under ``layer_scan`` and none of these
#: is the scan's own slicing and restacking of the per-layer state
BODY_SCOPES = ("attn", "mlp", "norm", "kv_write")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
NO_SPAN = "no engine span"
COMPILE = "compile"

Interval = Tuple[str, int, int]
_MODULE_ID = re.compile(r"\(\d+\)$")


# -- the host side ---------------------------------------------------------

def _host_events(pd, lo: int, hi: int):
    """(engine spans, compiles) on the host planes, clipped to the window.
    A span's ``#k=v,...#`` argument suffix is dropped from its name."""
    spans: List[Interval] = []
    compiles: List[Tuple[int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                s = int(e.start_ns)
                end = s + int(e.duration_ns)
                if min(end, hi) <= max(s, lo):
                    continue
                if name.startswith(SPAN_PREFIXES):
                    spans.append((name.split("#", 1)[0], max(s, lo),
                                  min(end, hi)))
                elif name.startswith(COMPILE_PREFIX):
                    compiles.append((max(s, lo), min(end, hi)))
    return spans, compiles


def innermost(spans: Sequence[Interval]) -> List[Interval]:
    """The timeline of the innermost open span: (name, start, end) segments
    in time order, none where no span is open. Of the spans open at an
    instant the innermost is the one opened last."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: (x[1], -x[2]))
    out: List[Interval] = []
    open_: List[Interval] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        open_ = [x for x in open_ if x[2] > a]
        while i < len(by_start) and by_start[i][1] <= a:
            if by_start[i][2] > a:
                open_.append(by_start[i])
            i += 1
        if open_:
            name = max(open_, key=lambda x: (x[1], -x[2]))[0]
            if out and out[-1][0] == name and out[-1][2] == a:
                out[-1] = (name, out[-1][1], b)
            else:
                out.append((name, a, b))
    return out


def _overlap(segs: Sequence[Interval], starts: Sequence[int], a: int,
             b: int) -> Dict[str, int]:
    """Nanoseconds of [a, b) under each name of the time-ordered segments."""
    got: Dict[str, int] = defaultdict(int)
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(segs) and segs[i][1] < b:
        name, s, e = segs[i]
        d = min(e, b) - max(s, a)
        if d > 0:
            got[name] += d
        i += 1
    return got


def activity(a: int, b: int, segs: Sequence[Interval],
             starts: Sequence[int],
             compiles: Sequence[Tuple[int, int]]) -> str:
    """What the host was doing over [a, b): ``compile`` if a compile
    overlaps it, else whichever of the innermost spans and no span at all
    covers most of it."""
    if any(min(e, b) > max(s, a) for s, e in compiles):
        return COMPILE
    got = _overlap(segs, starts, a, b)
    got[NO_SPAN] = (b - a) - sum(got.values())
    return max(got.items(), key=lambda kv: (kv[1], kv[0]))[0]


def idle_intervals(busy: Sequence[Tuple[int, int, str]], lo: int,
                   hi: int) -> List[Tuple[int, int, str]]:
    """The gaps between busy intervals in [lo, hi), each with the name of
    the operation before it (``window start`` for the first), as
    ``trace_reduce.reduce`` reckons them."""
    out = []
    prev_end, prev = lo, "window start"
    for s, e, name in busy:
        if s > prev_end:
            out.append((prev_end, s, prev))
        prev_end, prev = e, name
    if hi > prev_end:
        out.append((prev_end, hi, prev))
    return out


def split_idle(busy: Sequence[Tuple[int, int, str]], lo: int, hi: int,
               segs: Sequence[Interval]) -> Tuple[int, int]:
    """(idle ns while some span was open, idle ns while none was)."""
    starts = [s for _, s, _ in segs]
    inside = outside = 0
    for a, b, _ in idle_intervals(busy, lo, hi):
        under = sum(_overlap(segs, starts, a, b).values())
        inside += under
        outside += (b - a) - under
    return inside, outside


def named_gaps(busy: Sequence[Tuple[int, int, str]], lo: int, hi: int,
               spans: Sequence[Interval],
               compiles: Sequence[Tuple[int, int]] = ()
               ) -> List[Tuple[str, float, str]]:
    """Every idle gap, longest first (ties in time order), as (``<activity>
    after <op>``, seconds, activity)."""
    segs = innermost(spans)
    starts = [s for _, s, _ in segs]
    gaps = []
    for a, b, prev in idle_intervals(busy, lo, hi):
        what = activity(a, b, segs, starts, compiles)
        gaps.append((f"{what} after {prev}", (b - a) / 1e9, what))
    gaps.sort(key=lambda g: -g[1])
    return gaps


# -- the device side -------------------------------------------------------

def scope_path(op_name: str) -> str:
    """The serving scopes of an op's ``op_name`` metadata, in order."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


def is_pool_copy(scope: str) -> bool:
    """Under ``layer_scan`` and under no scope of the layer body."""
    parts = scope.split("/")
    if "layer_scan" not in parts:
        return False
    rest = parts[parts.index("layer_scan") + 1:]
    return not any(p in BODY_SCOPES for p in rest)


def is_collective(op: str, category: str) -> bool:
    return any(c in category or op.startswith(c) for c in COLLECTIVES)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: varints
    as ints, length-delimited fields as memoryviews, fixed ones skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_metadata(path: str) -> Dict[str, Dict[Tuple[int, str], Tuple]]:
    """Per TPU plane, ``(program id, op event name) -> (op_name, category)``
    from the trace file's event metadata (``XPlane.event_metadata``), which
    ``ProfileData`` does not expose: ``op_name`` is the op's HLO metadata
    (the ``tf_op`` stat, None where the op has none, as the copies XLA
    inserts), ``category`` its ``hlo_category``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:                                   # XSpace.planes
            continue
        name, entries, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:                                  # XPlane.name
                name = bytes(v).decode()
            elif pf == 4:                                # event_metadata
                entries.append(v)
            elif pf == 5:                                # stat_metadata
                for k, sm in _fields(v):
                    if k == 2:
                        sid = sname = None
                        for sf, sv in _fields(sm):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = bytes(sv).decode()
                        stat_names[sid] = sname
        if not trace_reduce._TPU_PLANE.match(name):
            continue
        meta = {}
        for entry in entries:
            for k, em in _fields(entry):
                if k != 2:
                    continue
                ename, stats = "", {}
                for ef, ev in _fields(em):
                    if ef == 2:
                        ename = bytes(ev).decode()
                    elif ef == 5:                        # XStat
                        sid = val = None
                        for xf, xv in _fields(ev):
                            if xf == 1:
                                sid = xv
                            elif xf in (3, 4):
                                val = xv
                            elif xf == 5:
                                val = bytes(xv).decode()
                            elif xf == 7:
                                val = stat_names.get(xv)
                        stats[stat_names.get(sid)] = val
                meta[(stats.get("program_id"), ename)] = (
                    stats.get("tf_op"), str(stats.get("hlo_category") or ""))
        out[name] = meta
    return out


def _tpu_ops(pd, path: str) -> List[List[Tuple[str, str, str, int, int]]]:
    """Per chip: (op, scope path, category, start, end) of every op. An op
    without HLO metadata takes the scope of the innermost op it runs
    inside (a ``while`` of a scan), as its program's own ops do."""
    meta = op_metadata(path)
    out = []
    for plane in pd.planes:
        if plane.name not in meta:
            continue
        pm = meta[plane.name]
        events, mods = [], []
        for line in plane.lines:
            if line.name in ("XLA Ops", "XLA Modules"):
                dst = events if line.name == "XLA Ops" else mods
                for e in line.events:
                    s = int(e.start_ns)
                    dst.append((e.name, s, s + int(e.duration_ns)))
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        events.sort(key=lambda x: (x[1], -x[2]))
        ops, stack = [], []
        for name, s, e in events:
            i = bisect.bisect_right(starts, s) - 1
            pid = None
            if i >= 0 and mods[i][2] >= e:
                m = re.search(r"\((\d+)\)$", mods[i][0])
                pid = int(m.group(1)) if m else None
            op_name, cat = pm.get((pid, name), (None, ""))
            while stack and stack[-1][1] <= s:
                stack.pop()
            scope = (scope_path(op_name) if op_name is not None
                     else stack[-1][0] if stack else "")
            stack.append((scope, e))
            ops.append((trace_reduce._op_name(name), scope, cat, s, e))
        out.append(ops)
    return out


# -- the reader's entry ----------------------------------------------------

def _trace_path(run) -> Optional[str]:
    """The file ``run.trace`` was reduced from: the harness ``Cell`` whose
    ``per_layer`` calls the readers holds it beside the reduced trace."""
    f = sys._getframe(1)
    while f is not None:
        owner = f.f_locals.get("self")
        if (getattr(owner, "reduced", None) is run.trace
                and getattr(owner, "trace_path", None)):
            return owner.trace_path
        f = f.f_back
    return None


def extend(run) -> Optional[dict]:
    """``run.trace`` with the keys above added (once), or None when there
    is no trace to read."""
    red = run.trace
    if red is None:
        return None
    if "host_spans" in red:
        return red
    path = _trace_path(run)
    if path is None:
        return None
    add(red, path)
    return red


def add(red: dict, path: str) -> None:
    """Add the keys to ``red``, the reduction of the trace file ``path``."""
    pd = trace_reduce._load(path)
    devs = trace_reduce._devices(pd)
    win = trace_reduce._window(pd)
    if win is None:
        win = (min(s for ops, _ in devs for _, s, _ in ops),
               max(e for ops, _ in devs for _, _, e in ops))
    lo, hi = win
    n = len(devs)
    spans, compiles = _host_events(pd, lo, hi)
    segs = innermost(spans)
    inside = outside = 0.0
    gaps = []
    for d, (ops, _) in enumerate(devs):
        _, busy = trace_reduce.self_times(trace_reduce._clip(ops, lo, hi))
        i, o = split_idle(busy, lo, hi, segs)
        inside += i / 1e9 / n
        outside += o / 1e9 / n
        if d == 0:
            gaps = named_gaps(busy, lo, hi, spans, compiles)
    host: Dict[str, dict] = {}
    for name, s, e in spans:
        h = host.setdefault(name, {"count": 0, "seconds": 0.0})
        h["count"] += 1
        h["seconds"] += (e - s) / 1e9
    by_activity: Dict[str, float] = defaultdict(float)
    for _, secs, what in gaps:
        by_activity[what] += secs
    modules: Dict[str, dict] = {}
    for name, m in red["modules"].items():
        k = modules.setdefault(_MODULE_ID.sub("", name),
                               {"count": 0, "seconds": 0.0})
        k["count"] += m["count"]
        k["seconds"] += m["seconds"]
    scope_s: Dict[str, float] = defaultdict(float)
    category_s: Dict[str, float] = defaultdict(float)
    for ops in _tpu_ops(pd, path):
        keyed = [(f"{scope}\0{cat}\0{op}", s, e)
                 for op, scope, cat, s, e in ops]
        selft, _ = trace_reduce.self_times(trace_reduce._clip(keyed, lo, hi))
        for key, ns in selft.items():
            scope, cat, op = key.split("\0")
            scope_s[scope] += ns / 1e9 / n
            category_s["collective" if is_collective(op, cat)
                       else cat] += ns / 1e9 / n
    red.update({
        "host_spans": host,
        "idle_in_span_s": inside,
        "idle_outside_span_s": outside,
        "idle_by_activity": dict(by_activity),
        "modules_by_name": modules,
        "scope_s": dict(scope_s),
        "category_s": dict(category_s),
    })
    top = len(red["idle_gaps"])
    if [round(g[1], 9) for g in gaps[:top]] == \
            [round(g[1], 9) for g in red["idle_gaps"]]:
        red["idle_gaps"] = [[name, secs] for name, secs, _ in gaps[:top]]
