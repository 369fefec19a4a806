"""Fleet serving launcher — N engine replicas behind the prefix-affinity
router, with optional prefill/decode disaggregation.

UKL's deployment story scaled out: many specialized cells, one
orchestrator, shared resources (MultiK / uTNT in PAPERS.md). Examples:

  python -m repro.launch.fleet --smoke --replicas 2          # colocated
  python -m repro.launch.fleet --smoke --replicas 4 --disaggregate 2
      # 2 prefill cells stream chunked prefill, 2 decode cells receive the
      # finished KV chains over the swap lane and never stall on a long
      # prompt
  python -m repro.launch.fleet --smoke --replicas 2 --shared-prefix-len 16
      # a prefix prefilled by either replica warms both via the shared
      # host-tier prefix store

``--smoke`` serves the config's CPU-sized reduction; without it every
replica runs the arch at its published width.

The report is the fleet-aggregate ``fleet_report``: percentiles over the
pooled completions, counters summed across replicas, handoff and
shared-store totals, and the per-replica breakdown under ``per_replica``.
"""
from __future__ import annotations

import argparse
import json
import sys


def run_fleet_engine(arch: str, preset_name: str, *, replicas: int = 2,
                     disaggregate: int = 0, n_slots: int = 4,
                     prompt_len: int = 32, gen_len: int = 32,
                     requests: int = 8, load: str = "open",
                     rate: float = 25.0, concurrency: int = 0,
                     decode_steps: int = 0, smoke: bool = True,
                     scale: float = 1.0, seed: int = 0, kv: str = "paged",
                     block_size: int = 16, num_blocks: int = 0,
                     admit_cap: int = 0, shared_host_blocks: int = 0,
                     temperature: float = 0.0, top_k: int = 0,
                     shared_prefix_len: int = 0, mesh: str = "",
                     chunked: bool = False, budget: int = 256,
                     preempt: str = "recompute", victim: str = "youngest",
                     kv_dtype: str = "bf16", trace: str = "",
                     metrics: str = ""):
    """Run a request workload through a ``FleetEngine``; returns the
    fleet-aggregate report dict."""
    from repro.core import SamplingConfig
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import _setup
    from repro.serve import (FleetEngine, PreemptionPolicy, Telemetry,
                             fleet_report, synthetic_requests)

    if requests < 1:
        raise ValueError("need --requests >= 1")
    cfg, lk, opts, params = _setup(arch, preset_name, smoke=smoke,
                                   scale=scale, seed=seed, gen_len=gen_len,
                                   decode_steps=decode_steps)
    max_len = prompt_len + gen_len + 8
    sampling = SamplingConfig(temperature=temperature, top_k=top_k,
                              seed=seed)
    tel = None
    if trace or metrics:
        tel = Telemetry(trace=bool(trace),
                        const_labels={"backend": kv, "preset": preset_name,
                                      "replicas": str(replicas)})
    fleet = FleetEngine(
        cfg, params, opts, lk, replicas=replicas,
        prefill_replicas=disaggregate, n_slots=n_slots, max_len=max_len,
        admit_cap=admit_cap or None,
        shared_host_blocks=shared_host_blocks or None,
        telemetry=tel, kv=kv, block_size=block_size,
        num_blocks=num_blocks or None, sampling=sampling,
        mesh=make_serve_mesh(mesh), chunked=chunked, chunk_budget=budget,
        preempt=PreemptionPolicy(mode=preempt, victim=victim),
        kv_dtype=kv_dtype)

    # warmup: one pass compiles every replica's program zoo (prefill cells
    # compile the serve step, decode cells the handoff import + decode)
    warm = synthetic_requests(
        max(2, replicas) if shared_prefix_len else max(1, replicas),
        prompt_len, fleet.engines[0].tokens_per_program + 1,
        cfg.vocab_size, seed=seed + 1, shared_prefix_len=shared_prefix_len)
    fleet.run(warm, load="closed")
    fleet.drop_prefix_cache()     # shed warmup residue (device + shared)
    fleet.reset_counters()

    reqs = synthetic_requests(requests, prompt_len, gen_len, cfg.vocab_size,
                              seed=seed,
                              rate=rate if load == "open" else None,
                              shared_prefix_len=shared_prefix_len)
    completions, wall = fleet.run(reqs, load=load,
                                  concurrency=concurrency or None)
    rep = fleet_report(completions, wall, fleet)
    if tel is not None:
        tel.close()
        if trace:
            n = (tel.trace.export_jsonl(trace) if trace.endswith(".jsonl")
                 else tel.trace.export_chrome(trace))
            rep["trace_path"], rep["trace_events"] = trace, n
        if metrics:
            with open(metrics, "w") as f:
                f.write(tel.metrics.render())
            rep["metrics_path"] = metrics
    rep.update({
        "arch": cfg.name, "preset": preset_name, "load": load,
        "n_slots": n_slots, "prompt_len": prompt_len, "gen_len": gen_len,
        "decode_steps_per_program": fleet.engines[0].tokens_per_program,
    })
    if load == "open":
        rep["offered_rate_req_s"] = rate
    return rep


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="tinyllama-1.1b")
    p.add_argument("--preset", default="nss_shortcut")
    p.add_argument("--replicas", type=int, default=2,
                   help="engine replicas behind the router")
    p.add_argument("--disaggregate", type=int, default=0,
                   help="of the replicas, how many are dedicated prefill "
                        "cells (0 = colocated: every replica prefills and "
                        "decodes its own requests); the rest are decode "
                        "cells receiving KV-chain handoffs")
    p.add_argument("--load", default="open", choices=["open", "closed"])
    p.add_argument("--slots", type=int, default=4,
                   help="cache slots per replica")
    p.add_argument("--kv", default="paged", choices=["slotted", "paged"],
                   help="KV backend per replica (the shared prefix store "
                        "and disaggregation need paged)")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=0,
                   help="paged: per-replica device pool size (0 = auto)")
    p.add_argument("--admit-cap", type=int, default=0,
                   help="router backpressure: max queued requests per "
                        "replica (0 = 2x slots)")
    p.add_argument("--shared-host-blocks", type=int, default=0,
                   help="shared prefix store size in blocks (0 = auto: "
                        "replicas x device pool)")
    p.add_argument("--kv-dtype", default="bf16",
                   choices=["bf16", "int8", "fp8"])
    p.add_argument("--preempt", default="recompute",
                   choices=["recompute", "swap"])
    p.add_argument("--victim", default="youngest",
                   choices=["youngest", "lru"])
    p.add_argument("--chunked", action="store_true",
                   help="chunked prefill on every replica (prefill cells "
                        "are always chunked)")
    p.add_argument("--budget", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="common prompt prefix (exercises the shared "
                        "cross-replica prefix store)")
    p.add_argument("--mesh", default="",
                   help="per-replica serving mesh 'data,model'")
    p.add_argument("--rate", type=float, default=25.0)
    p.add_argument("--concurrency", type=int, default=0,
                   help="closed-loop outstanding requests "
                        "(0 = admitting replicas x slots)")
    p.add_argument("--decode-steps", type=int, default=0)
    p.add_argument("--trace", default="",
                   help="write the fleet's Chrome trace here — replicas "
                        "land on distinct pid lanes (engine/0, engine/1, "
                        "...) with handoff events crossing them")
    p.add_argument("--metrics", default="")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen-len", type=int, default=32)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--smoke", action="store_true",
                   help="serve the reduced same-family config (CPU-sized); "
                        "without it the arch runs at its published width")
    p.add_argument("--scale", type=float, default=1.0,
                   help="width multiplier on the --smoke config")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--report-json", default=None)
    args = p.parse_args(argv)
    if args.scale != 1.0 and not args.smoke:
        p.error("--scale resizes the --smoke config only")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rep = run_fleet_engine(
        args.arch, args.preset, replicas=args.replicas,
        disaggregate=args.disaggregate, n_slots=args.slots,
        prompt_len=args.prompt_len, gen_len=args.gen_len,
        requests=args.requests, load=args.load, rate=args.rate,
        concurrency=args.concurrency, decode_steps=args.decode_steps,
        smoke=args.smoke, scale=args.scale, seed=args.seed, kv=args.kv,
        block_size=args.block_size, num_blocks=args.num_blocks,
        admit_cap=args.admit_cap,
        shared_host_blocks=args.shared_host_blocks,
        temperature=args.temperature, top_k=args.top_k,
        shared_prefix_len=args.shared_prefix_len, mesh=args.mesh,
        chunked=args.chunked, budget=args.budget, preempt=args.preempt,
        victim=args.victim, kv_dtype=args.kv_dtype, trace=args.trace,
        metrics=args.metrics)
    print(json.dumps(rep, indent=1))
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump(rep, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
