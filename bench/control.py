#!/usr/bin/env python3
"""Read the limit's two ends for a cell, on the chip, in one process.

  python3 bench/control.py --workload <name> --seeds 11,12,... --seconds S \
      [--out DIR]

For each seed: a run of the cell as the benchmark makes it (weights from
the seed, warm-up, a window of ``--seconds`` at the cell's own load), the
sample of finished requests a run checks, and then, on the same prompts and
served tokens, the widest gap by which a token lies below the reference's
best for

- ``program``: the tokens the engine served (the lower reading);
- ``bf16``: the token the bfloat16 reference puts first (the control of a
  float32 configuration below ``highest``: the upper reading);
- ``f32-default`` and ``f32-high``: the token float32 matmuls at one and at
  three bf16 passes put first (what the program would read at those
  precisions, and the control of a ``highest`` configuration).

Prints one JSON line per seed. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

CONTROLS = ("bf16", "f32-default", "f32-high")


def readings(cell) -> dict:
    """Widest gaps of the program and of each control on the cell's sample
    (after ``measure``; releases the engine)."""
    import numpy as np
    from bench import reference
    picked = cell.sample()
    prompts = {r.rid: r.prompt for r in cell.reqs.reqs}
    cell.release()
    seqs, rows, toks = reference.served_rows(
        [prompts[r.rid] for r in picked], [r.tokens for r in picked])
    ref = cell.desc.logits(cell.cfg, cell.seed, seqs, rows, "f32")
    best = np.asarray(ref.argmax(axis=1))
    out = {"tokens": int(len(toks)), "requests": len(picked),
           "program": float(reference.gap_of(ref, toks).max()),
           "program_flips": int((best != toks).sum())}
    for mode in CONTROLS:
        ctl = cell.desc.logits(cell.cfg, cell.seed, seqs, rows, mode)
        pick = np.asarray(ctl.argmax(axis=1))
        del ctl
        out[mode] = float(reference.gap_of(ref, pick).max())
        out[mode + "_flips"] = int((best != pick).sum())
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    import jax
    from bench import harness, spec
    bench = spec.benchmark(ROOT)
    w = spec.workload(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        harness.log("needs the cell's TPU chips")
        return 2
    devices = devices[:w["chips"]]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        cell = harness.Cell(bench, args.workload, seed, args.seconds,
                            root=ROOT)
        cell.setup(devices)
        cell.measure()
        e2e = cell.win.metrics()
        r = {"seed": seed, **readings(cell),
             "tokens_per_s": e2e["tokens_per_s"],
             "finished": e2e["finished"],
             "seconds": time.monotonic() - t}
        rows.append(r)
        print(json.dumps(r), flush=True)
    if args.out:
        path = Path(args.out)
        path.mkdir(parents=True, exist_ok=True)
        (path / f"control.{args.workload}.json").write_text(
            json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
