"""Print the per-phase step breakdown (and request stats) from a trace file.

Reads either telemetry export — Chrome-trace JSON or raw JSONL (both from
``launch/serve.py --trace`` / ``TraceRecorder``) — validates it against the
event schema and span state machine, and prints:

  * per program kind: steps, total host wall-clock, and the split across
    the pack / dispatch / device / host phases (the table bench_serving's
    step-phase rows are derived from): pack is the host planning before
    the program call (``engine.plan``), dispatch the call itself with its
    table uploads (``engine.dispatch``), device the host's blocking waits
    on results (a non-``ret_async`` commit's sync, ``engine.finalize``),
    host the rest of the step (``engine.overlap``, ``engine.harvest``);
  * the host spans (``engine.tick`` and the phases inside it): count and
    seconds per span name;
  * request lifecycle stats from the spans: completed count, p50/p99 TTFT
    and latency;
  * event-type counts, so a glance shows which subsystems fired (swaps,
    preemptions, verify windows, budget moves);
  * host-tier bandwidth: bytes moved across the device<->host boundary,
    and — when the cache is quantized — the compressed-vs-raw ratio the
    kv_dtype axis saves;
  * fleet traces: per-replica event counts (events stamped with a replica
    id land on distinct Perfetto pid lanes) and the prefill->decode
    handoffs that crossed them, with the chain bytes they carried.

Usage: PYTHONPATH=src python scripts/trace_summary.py TRACE [TRACE...]
"""
from __future__ import annotations

import sys
from collections import Counter

import numpy as np

from repro.serve import (load_trace, phase_breakdown, span_latencies,
                         validate_events, validate_spans)

PHASES = ("pack", "dispatch", "device", "host")


def summarize(path: str) -> None:
    events = load_trace(path)
    validate_events(events)
    paths = validate_spans(events)
    print(f"== {path}: {len(events)} events, schema + spans valid ==")

    pb = phase_breakdown(events)
    if pb:
        kinds = sorted(k for k in pb if k != "all") + ["all"]
        hdr = f"{'kind':<14}{'steps':>7}{'total_s':>10}" + "".join(
            f"{p + '_s':>12}" for p in PHASES)
        print(hdr)
        for kind in kinds:
            cell = pb[kind]
            row = f"{kind:<14}{cell['steps']:>7}{cell['total_s']:>10.4f}"
            for p in PHASES:
                row += f"{cell['phases'][p]:>12.4f}"
            print(row)
        tot = pb["all"]["total_s"]
        if tot > 0:
            shares = "  ".join(
                f"{p}={pb['all']['phases'][p] / tot:.1%}" for p in PHASES)
            print(f"phase shares: {shares}")
    else:
        print("no engine_step events")

    spans = [e for e in events if e["type"] == "host_span"]
    if spans:
        per = {}
        for e in spans:
            n, t = per.get(e["args"]["name"], (0, 0.0))
            per[e["args"]["name"]] = (n + 1, t + e.get("dur", 0.0))
        print(f"{'host span':<18}{'count':>7}{'total_s':>10}")
        for name, (n, t) in sorted(per.items(), key=lambda kv: -kv[1][1]):
            print(f"{name:<18}{n:>7}{t:>10.4f}")

    lat = span_latencies(events)
    done = [d for d in lat.values() if "latency_s" in d]
    ttft = np.array([d["ttft_s"] for d in lat.values() if "ttft_s" in d])
    if ttft.size:
        print(f"requests: {len(lat)} seen, {len(done)} completed; "
              f"ttft p50={np.percentile(ttft, 50):.4f}s "
              f"p99={np.percentile(ttft, 99):.4f}s")
    if done:
        lats = np.array([d["latency_s"] for d in done])
        print(f"latency p50={np.percentile(lats, 50):.4f}s "
              f"p99={np.percentile(lats, 99):.4f}s")

    counts = Counter(e["type"] for e in events)
    print("events: " + "  ".join(f"{t}={n}"
                                 for t, n in sorted(counts.items())))

    # tier bandwidth: quantized caches move compressed bytes and stamp each
    # move with the uncompressed equivalent (``raw_bytes``); the ratio is
    # the host-tier bandwidth the kv_dtype axis saves
    tier = [e for e in events
            if e["type"] in ("swap_out", "swap_in", "demote", "promote")]
    if tier:
        moved = sum(e["args"]["bytes"] for e in tier)
        raw = sum(e["args"].get("raw_bytes", e["args"]["bytes"])
                  for e in tier)
        line = (f"kv tier: {len(tier)} moves, {moved} bytes across the "
                f"device<->host boundary")
        if raw != moved and moved:
            line += (f"; {raw} uncompressed — quantized blocks moved "
                     f"{raw / moved:.2f}x fewer bytes")
        print(line)

    # fleet: replica lanes and the handoffs crossing them
    if any("eng" in e for e in events):
        per_eng = Counter(e.get("eng", 0) for e in events
                          if e["type"] != "span")
        lanes = "  ".join(f"engine/{e}={n}"
                          for e, n in sorted(per_eng.items()))
        print(f"fleet: {len(per_eng)} replica lanes ({lanes})")
        hand = [e for e in events if e["type"] == "handoff"]
        if hand:
            hb = sum(e["args"]["bytes"] for e in hand)
            routes = Counter((e["args"]["src"], e["args"]["dst"])
                             for e in hand)
            path = "  ".join(f"{s}->{d}={n}"
                             for (s, d), n in sorted(routes.items()))
            print(f"handoffs: {len(hand)} chains, {hb} bytes prefill->"
                  f"decode ({path})")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0 if argv else 1
    for path in argv:
        summarize(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
