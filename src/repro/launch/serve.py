"""Serving launcher — the paper's Redis evaluation for the compiled-decode
boundary.

Two paths share one model/linkage setup:

  engine (default)  continuous-batching ``repro.serve.ServeEngine``: a slot
                    pool served under open-loop (Poisson arrivals) or
                    closed-loop load, reporting tokens/s and p50/p99 latency.
                    ``--kv paged`` swaps the dense slot rows for the paged
                    block-table subsystem (demand allocation, CoW prefix
                    sharing, block watermark reporting).

      python -m repro.launch.serve --smoke --preset nss_shortcut --load open
      python -m repro.launch.serve --smoke --preset ret_byp --load closed \
          --slots 8 --requests 32
      python -m repro.launch.serve --smoke --preset nss_shortcut --kv paged \
          --block-size 16 --shared-prefix-len 16 --bucket-prompts
      python -m repro.launch.serve --smoke --preset nss_shortcut --kv paged \
          --preempt swap --prefix-cache prefix.npz   # two-tier KV:
          # swap-out preemption + restart-persistent prefix cache
      XLA_FLAGS=--xla_force_host_platform_device_count=2 \
          python -m repro.launch.serve --smoke --preset nss_shortcut \
          --kv paged --mesh 1,2   # sharded: TP weights + per-shard KV
      python -m repro.launch.serve --smoke --preset nss_shortcut --kv paged \
          --spec-decode ngram --spec-width 6   # self-speculation: n-gram
          # drafts verified in one chunk-shaped program per step

                    ``--smoke`` serves the config's CPU-sized reduction
                    (``ArchConfig.smoke``); without it the model runs at its
                    published width, which is what a TPU serves:

      python -m repro.launch.serve --kv paged --chunked --slots 8 \
          --prompt-len 512 --gen-len 64 --load closed

  sequential        the original one-request-at-a-time loop (``--load seq``,
                    also ``run_server`` for benchmarks): the baseline the
                    engine's continuous batching is asserted token-identical
                    against in tests/test_serve.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np


def _setup(arch: str, preset_name: str, *, smoke: bool = True,
           scale: float = 1.0, seed: int = 0, gen_len: int = 64,
           decode_steps: int = 0):
    """Shared model/linkage construction for both serving paths."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import L3_NSS, preset
    from repro.models import ModelOptions, init_params

    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
        if scale != 1.0:
            cfg = dataclasses.replace(
                cfg, d_model=int(cfg.d_model * scale),
                d_ff=int(cfg.d_ff * scale),
                d_head=cfg.d_head if cfg.n_heads == 0
                else int(cfg.d_model * scale) // cfg.n_heads)
    lk = preset(preset_name)
    if lk.level == L3_NSS:
        k = decode_steps or min(lk.decode_steps, gen_len)
        lk = dataclasses.replace(lk, decode_steps=k)
    opts = ModelOptions(attn_impl="ref", scan_impl="ref", dtype=jnp.float32)
    if lk.shortcut:
        opts = lk.model_options(opts, on_tpu=jax.default_backend() == "tpu")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, lk, opts, params


def teacher_forced_logits(cfg, opts, params, prompt, forced, *,
                          block_size: int = 16, chunk: int = 256, mesh=None):
    """Logits of one request through the paged, chunked serving passes.

    The prompt goes through the serve-chunk pass in ``chunk``-token pieces,
    then each ``forced`` token is fed as one paged decode step, whatever the
    model would have picked — so two paths (kernels vs reference, one device
    vs a mesh) see identical inputs and their logits compare position by
    position. Returns float32 ``(1 + len(forced), vocab)``: the prompt's
    last-position logits, then each decode step's. With ``mesh`` the weights
    and block pools are placed as the engine places them.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import decode_step_paged, serve_chunk_step_paged
    from repro.serve.paging import init_paged_cache

    nb = -(-(len(prompt) + len(forced)) // block_size)
    max_len = nb * block_size
    cache = init_paged_cache(cfg, nb, block_size, 1, opts.dtype)
    tables = jnp.arange(nb, dtype=jnp.int32)[None]
    if mesh is not None:
        from repro.sharding.rules import ArchSharding, named
        sh = ArchSharding(cfg, mesh)
        params = jax.device_put(params,
                                named(mesh, sh.serve_param_specs(params)))
        cache = jax.device_put(cache,
                               named(mesh, sh.serve_paged_cache_specs(cache)))
        opts = dataclasses.replace(opts, mesh=mesh)
    chunk_fn = jax.jit(lambda p, c, t, s, n: serve_chunk_step_paged(
        p, c, t, tables, s, n, cfg, opts, max_len))
    dec_fn = jax.jit(lambda p, c, t: decode_step_paged(
        p, c, t, tables, cfg, opts, max_len))
    for s in range(0, len(prompt), chunk):
        piece = np.asarray(prompt[s:s + chunk], np.int32)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(piece)] = piece
        logits, cache = chunk_fn(params, cache, toks, np.array([s], np.int32),
                                 np.array([len(piece)], np.int32))
    out = [logits[0]]
    for t in forced:
        logits, cache = dec_fn(params, cache, np.array([t], np.int32))
        out.append(logits[0])
    return np.asarray(jnp.stack(out), np.float32)


def run_engine(arch: str, preset_name: str, *, n_slots: int = 4,
               prompt_len: int = 32, gen_len: int = 32, requests: int = 8,
               load: str = "open", rate: float = 25.0,
               concurrency: int = 0, decode_steps: int = 0,
               smoke: bool = True, scale: float = 1.0, seed: int = 0,
               kv: str = "slotted", block_size: int = 16,
               num_blocks: int = 0, bucket_prompts: bool = False,
               temperature: float = 0.0, top_k: int = 0,
               eos_id: int = -1, shared_prefix_len: int = 0,
               mesh: str = "", chunked: bool = False, budget: int = 256,
               chunk_width: int = 0, preempt: str = "recompute",
               victim: str = "youngest", host_blocks: int = 0,
               async_swap: bool = True, kv_dtype: str = "bf16",
               prefix_cache: str = "", ttft_slo: float = 0.0,
               spec_decode: str = "none", spec_width: int = 0,
               trace: str = "", metrics: str = "",
               log_interval: float = 0.0, profile_dir: str = ""):
    """Continuous-batching serving run; returns the engine report dict.

    Observability (docs/serving.md §Observability): ``trace`` writes the
    run's Chrome-trace JSON (``.jsonl`` suffix: raw JSONL instead),
    ``metrics`` writes the Prometheus text exposition, ``log_interval``
    prints a one-line stats log every S seconds, ``profile_dir`` captures a
    ``jax.profiler`` device trace around the first post-warmup steps. All
    empty/zero by default: the engine then runs with the zero-cost
    NULL_TELEMETRY bundle.
    """
    import os

    from repro.core import MetricWriter, SamplingConfig
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import (PreemptionPolicy, ServeEngine, Telemetry,
                             serve_report, synthetic_requests)

    if requests < 1:
        raise ValueError("need --requests >= 1")
    if prefix_cache and kv != "paged":
        # fail before the (possibly long) run, not at the save afterwards
        raise ValueError("--prefix-cache needs --kv paged (dense slot rows "
                         "have no prompt-keyed blocks to persist)")
    if kv_dtype != "bf16" and kv != "paged":
        raise ValueError("--kv-dtype quantization needs --kv paged (dense "
                         "slot rows have no per-block scale tables)")

    cfg, lk, opts, params = _setup(arch, preset_name, smoke=smoke, scale=scale,
                                   seed=seed, gen_len=gen_len,
                                   decode_steps=decode_steps)
    max_len = prompt_len + gen_len + 8
    sampling = SamplingConfig(temperature=temperature, top_k=top_k, seed=seed)

    tel = None
    if trace or metrics or log_interval > 0 or profile_dir:
        sink = None
        if metrics and log_interval > 0:
            # stream periodic registry snapshots through the MetricWriter
            # co-process (UKL's ordinary process beside the linked one)
            # into <metrics>.jsonl while the run is live, in addition to
            # the final text exposition written to <metrics> itself
            stream_path = metrics + ".jsonl"
            open(stream_path, "w").close()

            def _append(step, m):
                with open(stream_path, "a") as f:
                    f.write(json.dumps({"step": step, **m}) + "\n")

            sink = MetricWriter(_append)
        tel = Telemetry(trace=bool(trace), log_interval=log_interval,
                        sink=sink,
                        const_labels={"backend": kv, "preset": preset_name})
    # --prefix-cache PATH persists the host tier across launcher runs: warm
    # start from the file when it exists, save back after the timed run
    warm_start = prefix_cache if prefix_cache and os.path.exists(
        prefix_cache) else None
    eng = ServeEngine(cfg, params, opts, lk, n_slots=n_slots, max_len=max_len,
                      kv=kv, block_size=block_size,
                      num_blocks=num_blocks or None,
                      sampling=sampling, bucket_prompts=bucket_prompts,
                      mesh=make_serve_mesh(mesh), chunked=chunked,
                      chunk_budget=budget, chunk_width=chunk_width,
                      preempt=PreemptionPolicy(mode=preempt, victim=victim),
                      host_blocks=host_blocks, async_swap=async_swap,
                      kv_dtype=kv_dtype, warm_start=warm_start,
                      ttft_slo_s=ttft_slo / 1e3 if ttft_slo > 0 else None,
                      spec_decode=spec_decode, spec_width=spec_width,
                      telemetry=tel)

    # warmup: compile prefill + decode + admission writers outside the timed
    # region (one decode program suffices — same compiled shapes as the run).
    # With a shared prefix, a second warmup request hits the radix index and
    # compiles the suffix-prefill path at the run's suffix shape too.
    warm = synthetic_requests(2 if shared_prefix_len else 1, prompt_len,
                              eng.tokens_per_program + 1, cfg.vocab_size,
                              seed=seed + 1,
                              shared_prefix_len=shared_prefix_len)
    eng.run(warm, load="closed")
    if hasattr(eng.kv, "drop_prefix_cache"):
        eng.kv.drop_prefix_cache()  # shed warmup residue from the block pool
    eng.reset_counters()          # don't let warmup inflate the report (also
                                  # clears the warmup trace/metrics)
    if tel is not None and profile_dir:
        # arm the profiler only now: capturing the warmup steps would
        # record compilation, not the steady-state programs
        tel.profile_dir = profile_dir

    reqs = synthetic_requests(requests, prompt_len, gen_len, cfg.vocab_size,
                              seed=seed,
                              rate=rate if load == "open" else None,
                              shared_prefix_len=shared_prefix_len,
                              eos_id=eos_id if eos_id >= 0 else None)
    completions, wall = eng.run(reqs, load=load,
                                concurrency=concurrency or None)
    rep = serve_report(completions, wall, utilization=eng.utilization())
    if tel is not None:
        tel.close()               # stop any profiler capture, flush the sink
        if trace:
            n = (tel.trace.export_jsonl(trace) if trace.endswith(".jsonl")
                 else tel.trace.export_chrome(trace))
            rep["trace_path"], rep["trace_events"] = trace, n
        if metrics:
            with open(metrics, "w") as f:
                f.write(tel.metrics.render())
            rep["metrics_path"] = metrics
        if profile_dir:
            rep["profile_dir"] = profile_dir
    rep.update({
        "arch": cfg.name, "preset": preset_name, "load": load,
        "n_slots": n_slots, "prompt_len": prompt_len, "gen_len": gen_len,
        "decode_steps_per_program": eng.tokens_per_program,
    })
    if load == "open":
        rep["offered_rate_req_s"] = rate
    if warm_start:
        rep["prefix_cache_restored"] = eng.kv.restored_entries
    if prefix_cache:
        rep["prefix_cache_saved"] = eng.save_prefix_cache(prefix_cache)
    return rep


def run_server(arch: str, preset_name: str, *, batch: int = 8,
               prompt_len: int = 64, gen_len: int = 64, requests: int = 4,
               smoke: bool = True, scale: float = 1.0, seed: int = 0):
    """Sequential baseline: whole-batch prefill + decode, one request batch
    at a time (no admission between programs)."""
    import jax
    import jax.numpy as jnp
    from repro.core import L3_NSS, build_decode_step
    from repro.models import prefill

    cfg, lk, opts, params = _setup(arch, preset_name, smoke=smoke, scale=scale,
                                   seed=seed, gen_len=gen_len,
                                   decode_steps=gen_len)
    dec = build_decode_step(cfg, opts, lk)
    rng = np.random.default_rng(seed)
    max_len = prompt_len + gen_len + 8

    pf = jax.jit(lambda p, t: prefill(p, t, cfg, opts, max_len=max_len))

    def one_request(toks):
        """prefill + decode gen_len tokens; returns #tokens produced."""
        logits, cache = pf(params, toks)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if lk.level == L3_NSS:
            cache, seq = dec(params, cache, nxt)
            seq.block_until_ready()
            return seq.shape[0] * seq.shape[1]
        n = 0
        for _ in range(gen_len):
            cache, out = dec(params, cache, nxt)
            nxt = out[:, 0]
            n += batch
        nxt.block_until_ready()
        return n

    # warmup: compile prefill + decode outside the timed region
    warm = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    size=(batch, prompt_len), dtype=np.int32))
    one_request(warm)

    lat = []
    tokens_out = 0
    t_all = time.time()
    for r in range(requests):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        size=(batch, prompt_len), dtype=np.int32))
        t0 = time.time()
        tokens_out += one_request(toks)
        lat.append(time.time() - t0)
    wall = time.time() - t_all
    return {
        "arch": cfg.name, "preset": preset_name, "load": "seq",
        "batch": batch, "prompt_len": prompt_len, "gen_len": gen_len,
        "requests": requests, "wall_s": wall,
        "tokens_per_s": tokens_out / wall,
        "mean_latency_s": float(np.mean(lat)),
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="tinyllama-1.1b")
    p.add_argument("--preset", default="nss_shortcut")
    p.add_argument("--load", default="open",
                   choices=["open", "closed", "seq"],
                   help="open: Poisson arrivals at --rate; closed: "
                        "--concurrency outstanding; seq: sequential baseline")
    p.add_argument("--slots", type=int, default=4,
                   help="engine cache slots (continuous-batching batch)")
    p.add_argument("--kv", default="slotted", choices=["slotted", "paged"],
                   help="KV backend: dense slot rows, or the paged "
                        "block-table subsystem (demand allocation + CoW "
                        "prefix sharing)")
    p.add_argument("--block-size", type=int, default=16,
                   help="paged: tokens per physical KV block")
    p.add_argument("--num-blocks", type=int, default=0,
                   help="paged: physical pool size (0 = slots*max_len/bs, "
                        "the slotted-equivalent footprint)")
    p.add_argument("--preempt", default="recompute",
                   choices=["recompute", "swap"],
                   help="paged pool-pressure policy: recompute replays the "
                        "victim from scratch; swap copies its blocks to the "
                        "host tier and resumes without re-prefill")
    p.add_argument("--victim", default="youngest",
                   choices=["youngest", "lru"],
                   help="preemption victim selection (scheduler policy): "
                        "youngest admission, or least-recently-emitting slot")
    p.add_argument("--host-blocks", type=int, default=0,
                   help="paged: host-tier pool size in blocks (0 = auto: "
                        "mirror the device pool when --preempt swap or a "
                        "prefix cache is in play, else disabled)")
    p.add_argument("--kv-dtype", default="bf16",
                   choices=["bf16", "int8", "fp8"],
                   help="paged: block-pool storage dtype — int8/fp8 store "
                        "per-(block, head) symmetric scales beside the pools "
                        "and dequantize inside the attention kernels (2-4x "
                        "resident tokens per HBM byte; bf16 = uncompressed "
                        "control, bit-identical to the unquantized engine)")
    p.add_argument("--sync-swap", action="store_true",
                   help="paged: disable the async swap runtime (batched "
                        "chain transfers behind a double-buffered stream, "
                        "resume-head prefetch, overlapped dispatch) and fall "
                        "back to blocking per-step transfers — escape hatch; "
                        "token streams are bit-identical either way")
    p.add_argument("--prefix-cache", default="",
                   help="paged: persist the prefix cache at this path — "
                        "warm-start from it when it exists, save back after "
                        "the run (prompt-token-keyed, config-fingerprinted)")
    p.add_argument("--spec-decode", default="none",
                   choices=["none", "ngram"],
                   help="speculative decoding: ngram drafts W-1 tokens per "
                        "decode row by prompt-lookup over the slot's own "
                        "history, verified in one chunk-shaped program "
                        "(greedy streams stay bit-identical)")
    p.add_argument("--spec-width", type=int, default=0,
                   help="verify window W per row: 1 next token + up to W-1 "
                        "draft tokens (0 = default 4)")
    p.add_argument("--ttft-slo", type=float, default=0.0,
                   help="chunked: target p50 TTFT in ms — AIMD-adjusts the "
                        "token budget per completion (0 = off)")
    p.add_argument("--chunked", action="store_true",
                   help="chunked prefill: one unified program per engine "
                        "step (decode tokens first, budget-packed prompt "
                        "chunks after) — admission never stalls decode")
    p.add_argument("--budget", type=int, default=256,
                   help="chunked: target tokens per serve step (decode "
                        "always wins; leftover goes to prompt chunks)")
    p.add_argument("--chunk-width", type=int, default=0,
                   help="chunked: compiled per-row chunk width W "
                        "(0 = min(budget, max_len))")
    p.add_argument("--bucket-prompts", action="store_true",
                   help="pad admitted prompts to power-of-two buckets "
                        "(bounds the jit prefill cache under mixed lengths)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature (0 = greedy argmax)")
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k truncation when sampling (0 = full vocab)")
    p.add_argument("--eos-id", type=int, default=-1,
                   help="stop token id (-1 = length-based completion only)")
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="prepend a common prefix of this many tokens to "
                        "every prompt (exercises paged CoW prefix sharing)")
    p.add_argument("--mesh", default="",
                   help="serving mesh as 'data,model' (e.g. 1,2): weights "
                        "tensor-parallel over 'model', KV heads per-shard "
                        "resident, slots over 'data'. On CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N first. "
                        "Empty or 1,1 = single device")
    p.add_argument("--rate", type=float, default=25.0,
                   help="open-loop offered load, requests/s")
    p.add_argument("--concurrency", type=int, default=0,
                   help="closed-loop outstanding requests (0 = slots)")
    p.add_argument("--decode-steps", type=int, default=0,
                   help="L3 tokens per decode program (0 = preset default, "
                        "clipped to gen-len)")
    p.add_argument("--batch", type=int, default=8,
                   help="batch size for --load seq")
    p.add_argument("--trace", default="",
                   help="write the run's trace here: Chrome-trace JSON "
                        "(loads in chrome://tracing / Perfetto; engine "
                        "steps as duration events, requests as async "
                        "spans), or raw JSONL if the path ends in .jsonl")
    p.add_argument("--metrics", default="",
                   help="write the Prometheus text exposition of the run's "
                        "metrics registry here (with --log-interval, also "
                        "streams periodic snapshots to <path>.jsonl via "
                        "the MetricWriter co-process)")
    p.add_argument("--log-interval", type=float, default=0.0,
                   help="print a one-line engine stats log every S seconds "
                        "during the run (0 = off)")
    p.add_argument("--profile-dir", default="",
                   help="capture a jax.profiler device trace around the "
                        "first post-warmup engine steps into this dir")
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
    if args.load == "seq":
        rep = run_server(args.arch, args.preset, batch=args.batch,
                         prompt_len=args.prompt_len, gen_len=args.gen_len,
                         requests=args.requests, smoke=args.smoke,
                         scale=args.scale, seed=args.seed)
    else:
        rep = run_engine(args.arch, args.preset, n_slots=args.slots,
                         prompt_len=args.prompt_len, gen_len=args.gen_len,
                         requests=args.requests, load=args.load,
                         rate=args.rate, concurrency=args.concurrency,
                         decode_steps=args.decode_steps,
                         smoke=args.smoke, scale=args.scale,
                         seed=args.seed, kv=args.kv,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         bucket_prompts=args.bucket_prompts,
                         temperature=args.temperature, top_k=args.top_k,
                         eos_id=args.eos_id,
                         shared_prefix_len=args.shared_prefix_len,
                         mesh=args.mesh, chunked=args.chunked,
                         budget=args.budget, chunk_width=args.chunk_width,
                         preempt=args.preempt, victim=args.victim,
                         host_blocks=args.host_blocks,
                         async_swap=not args.sync_swap,
                         kv_dtype=args.kv_dtype,
                         prefix_cache=args.prefix_cache,
                         ttft_slo=args.ttft_slo,
                         spec_decode=args.spec_decode,
                         spec_width=args.spec_width,
                         trace=args.trace, metrics=args.metrics,
                         log_interval=args.log_interval,
                         profile_dir=args.profile_dir)
    print(json.dumps(rep, indent=1))
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump(rep, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
