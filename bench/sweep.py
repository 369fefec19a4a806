#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest arrival rate
the engine sustains. Not part of a benchmark run.

  python3 bench/sweep.py --workload <name> --rates 0.8,1.2,1.6 --seconds S

For each rate, in one process, a run of the cell at that rate: prints
requests due and finished, requests still waiting at the window's end, and
the end-to-end numbers. A rate is sustained while the backlog at the end
stays near what a window at a lower rate leaves and the tail of time to
first token does not grow with the window: ``knee`` takes the highest swept
rate that leaves at most ``WAITING`` requests waiting at the window's end
and a 95th-percentile time to first token within ``TTFT_X`` times the
lowest rate's. The last line printed is the knee and 4/5 of it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
WAITING = 4
TTFT_X = 3.0


def knee(rows) -> float:
    """The highest rate of ``rows`` (sweep results, by rate) sustained."""
    rows = sorted(rows, key=lambda r: r["rate"])
    base = rows[0]["ttft_p95_s"]
    ok = [r["rate"] for r in rows
          if r["waiting_at_end"] <= WAITING and r["ttft_p95_s"] <= TTFT_X * base]
    return max(ok) if ok else 0.0
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=4001)
    args = p.parse_args(argv)

    import jax
    from bench import harness, spec
    from bench.window import p95
    bench = spec.benchmark(ROOT)
    w = spec.workload(bench, args.workload)
    devices = jax.devices()[:w["chips"]]
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.Cell(bench, args.workload, args.seed, args.seconds,
                            root=ROOT)
        cell.mix = dict(cell.mix, rate=rate)
        cell.setup(devices)
        cell.measure()
        e2e = cell.win.metrics()
        sched = cell.eng.sched
        rows.append({"rate": rate, **e2e, "waiting_at_end": sched.n_queued,
                     "active_at_end": len(sched.active),
                     "late_p95_s": p95(cell.win.late),
                     "programs": len(cell.win.progs),
                     "setup_s": cell.setup_s})
        print(json.dumps(rows[-1]), flush=True)
        cell.release()
    k = knee(rows)
    print(json.dumps({"knee": k, "rate": round(0.8 * k, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
