#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a model configuration
(``bench/configs``) and a traffic mix (``bench/traffic``). A run makes the
weights from the seed, warms up the cell's programs, serves the mix for
``--seconds``, and then checks a sample of the served tokens against the
plain reference. ``--trace 1`` records a profiler trace of the window and
reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``: each number compared with its limit. The
same numbers are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--out", default="",
                   help="also write the run's request records and reduced "
                        "trace as JSON into this directory")
    args = p.parse_args(argv)

    from bench import harness, spec
    from bench.window import p95
    bench = spec.benchmark(ROOT)
    w = spec.workload(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"needs a TPU; JAX found {devices[0].platform}")
        return 2
    if len(devices) < w["chips"]:
        harness.log(f"{args.workload} needs {w['chips']} chips; JAX found "
                    f"{len(devices)}")
        return 2
    devices = devices[:w["chips"]]
    peaks = spec.peaks(devices[0].device_kind, ROOT)

    cell = harness.Cell(bench, args.workload, args.seed, args.seconds,
                        trace=bool(args.trace), root=ROOT,
                        process_start=PROCESS_START)
    cell.setup(devices)
    cell.measure()
    e2e = cell.win.metrics()
    late = p95(cell.win.late)
    harness.log(f"window: {e2e}; programs {len(cell.win.progs)}, "
                f"preemptions {cell.win.preemptions}, compiles in window "
                f"{cell.window_compiles}, setup {cell.setup_s:.3f}s"
                + (f", generator late p95 {late:.4f}s" if late else ""))
    if args.trace:
        metrics = cell.per_layer(peaks)
    else:
        metrics = {"setup_s": {"value": cell.setup_s, "unit": "s"}}
        for m in spec.end_to_end_metrics(bench, args.workload):
            if m["name"] in e2e and e2e[m["name"]] is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    red = getattr(cell, "reduced", None)
    if args.out:
        harness.dump(cell, Path(args.out) / f"{args.workload}.{args.seed}."
                     f"{args.trace}.json")
    device = harness.device_record(devices, cell.memory_peak, red)
    if cell.trace_path:
        shutil.rmtree(Path(cell.trace_path).parents[3], ignore_errors=True)
    cell.release()
    t = time.monotonic()
    correct, checks, n_checked = cell.check()
    harness.log(f"reference over {n_checked} served tokens: "
                f"{time.monotonic() - t:.1f}s")
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": 0, "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        harness.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
