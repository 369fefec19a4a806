"""Benchmark harness: one function per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV.
  Fig. 3  -> bench_dispatch   (simple-syscall latency = per-step dispatch)
  Fig. 4  -> bench_payload    (latency vs payload size)
  Table 2 -> bench_ret        (ret vs iret = async vs sync return)
  Table 3 -> bench_pipeline   (fio = host->device staging)
  Tables 4-6 -> bench_serving (Redis = LM serving across the spectrum)
  Table 7 -> bench_hlo_counters (perf counters = compiled-program counters)
  Table 8 -> bench_load       (Memcached tail latency under load)
  §Roofline -> roofline       (dry-run derived terms, per arch × shape)
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> int:
    """Run every benchmark; one that fails is recorded and the rest still
    run, but the exit status is non-zero if any failed."""
    from benchmarks import (bench_dispatch, bench_hlo_counters, bench_load,
                            bench_payload, bench_pipeline, bench_ret,
                            bench_serving, roofline)
    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_dispatch, bench_payload, bench_ret, bench_pipeline,
                bench_serving, bench_hlo_counters, bench_load, roofline):
        t0 = time.time()
        try:
            mod.run()
        except Exception as e:
            print(f"{mod.__name__}_FAILED,0.0,{e!r}")
            traceback.print_exc()
            failed.append(mod.__name__)
        print(f"# {mod.__name__} took {time.time() - t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
