#!/usr/bin/env python3
"""Serve TinyLlama-1.1B at its published width on a TPU, and check it.

The quickest proof that the serving system still starts on the chip. It
drives the main path once — ``ServeEngine`` with the paged KV cache, chunked
prefill, the ``nss_shortcut`` linkage preset (so the Pallas kernels run),
greedy sampling and FIFO admission — over random weights made from a seed,
and fails on any phase that fails:

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the same path on a 1x4 (data, model)
                                   # mesh, against the one-chip run

One chip: 8 requests (prompt 512, 64 new tokens, 8 slots, block 16, chunk
budget 256) must each complete with 64 in-vocab tokens; the compiled decode
and serve-step programs must hold Pallas kernels (``tpu_custom_call``); and
one request, teacher-forced through the Pallas path and through the plain
``attn_impl="ref"`` path, must give finite logits that agree within
``TOL_REL``. Four chips: the mesh engine serves the same requests, the mesh
spans four distinct devices with the KV pools split evenly across them, and
its teacher-forced logits agree with the one-chip run's within ``TOL_REL``.

Every phase runs in this one process; it starts no child that needs the
chip. The tokens/s and TTFT it prints are a one-off smoke reading on a cold
process, not a benchmark. The last line of stdout is a JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "tinyllama-1.1b"
PRESET = "nss_shortcut"
SLOTS, PROMPT_LEN, GEN_LEN, REQUESTS = 8, 512, 64, 8
BLOCK, BUDGET = 16, 256
FORCED_STEPS = 8
SEED = 0
# Logits of two paths agree when max|a - b| <= TOL_REL * max|reference|.
# Both run at jax.default_matmul_precision("highest"), so the paths differ
# only in summation order: ~1e-6 relative (CPU, interpret-mode kernels, a
# 6-layer d_model-512 cut of this config). The same cut computed in bf16
# differs by ~2e-2 relative, which this bound rejects.
TOL_REL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included) and the number of persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits = 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lap(self):
        out = (self.seconds, self.hits)
        self.seconds, self.hits = 0.0, 0
        return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")
    log(f"ok: {what}")


def build_engine(cfg, lk, opts, params, mesh=None):
    from repro.core import SamplingConfig
    from repro.serve import ServeEngine
    return ServeEngine(cfg, params, opts, lk, n_slots=SLOTS,
                       max_len=PROMPT_LEN + GEN_LEN + 8, kv="paged",
                       block_size=BLOCK, sampling=SamplingConfig(seed=SEED),
                       mesh=mesh, chunked=True, chunk_budget=BUDGET)


def serve(eng, cfg, clock, tag: str):
    """Warm the engine up (compiles), then serve the smoke requests."""
    from repro.serve import serve_report, synthetic_requests
    t = time.perf_counter()
    eng.run(synthetic_requests(1, PROMPT_LEN, eng.tokens_per_program + 1,
                               cfg.vocab_size, seed=SEED + 1), load="closed")
    eng.kv.drop_prefix_cache()
    eng.reset_counters()
    secs, hits = clock.lap()
    log(f"[{tag}] warmup {time.perf_counter() - t:.1f}s, of which XLA "
        f"compile {secs:.1f}s ({hits} persistent-cache hits)")
    reqs = synthetic_requests(REQUESTS, PROMPT_LEN, GEN_LEN, cfg.vocab_size,
                              seed=SEED)
    comps, wall = eng.run(reqs, load="closed")
    rep = serve_report(comps, wall)
    done = [c for c in comps if len(c.tokens) == GEN_LEN]
    check(len(done) == REQUESTS,
          f"[{tag}] {len(done)}/{REQUESTS} requests completed with "
          f"{GEN_LEN} tokens")
    top = max(int(t) for c in comps for t in c.tokens)
    check(top < cfg.vocab_size,
          f"[{tag}] every token id < vocab {cfg.vocab_size} (max {top})")
    log(f"[{tag}] smoke reading, not a benchmark: "
        f"{rep['tokens_per_s']:.1f} tokens/s, TTFT p50 "
        f"{rep['p50_ttft_s']:.3f}s p99 {rep['p99_ttft_s']:.3f}s, "
        f"wall {wall:.2f}s")
    log(f"[{tag}] XLA compile inside the served window: "
        f"{clock.lap()[0]:.2f}s")
    reqs.sort(key=lambda r: r.rid)
    comps.sort(key=lambda c: c.rid)
    return reqs, comps


def compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def check_kernels_in_programs(eng) -> None:
    """The decode and serve-step programs, compiled for this device, must
    carry Mosaic custom calls — not the blockwise-jnp or interpret-mode
    forms of the shortcut."""
    import jax.numpy as jnp
    kv = eng.kv
    B, W = eng.n_slots, eng.chunk_width
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    no = jnp.zeros((B,), bool)
    tables = jnp.asarray(kv.tables_host)
    dec = compiled_text(kv._dec, kv.params, kv.cache, i32(B), kv.keys,
                        tables)
    check("tpu_custom_call" in dec, "decode program holds tpu_custom_call "
          f"({dec.count('tpu_custom_call')} sites)")
    srv = compiled_text(kv._serve, kv.params, kv.cache, i32(B, W), i32(B),
                        i32(B), no, no, i32(B), no, kv.keys, tables, tables)
    check("tpu_custom_call" in srv, "serve-step program holds "
          f"tpu_custom_call ({srv.count('tpu_custom_call')} sites)")


def compare(a, b, what: str) -> None:
    import numpy as np
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          f"{what}: logits finite, shape {a.shape}")
    diff = float(np.abs(a - b).max())
    scale = float(np.abs(b).max())
    log(f"{what}: max|dlogit| {diff:.3e}, max|reference logit| "
        f"{scale:.3e}, relative {diff / scale:.3e}")
    check(diff <= TOL_REL * scale,
          f"{what}: within {TOL_REL:g} of the reference's logit scale")


def forced_logits(cfg, opts, params, req, comp, mesh=None):
    import jax
    from repro.launch.serve import teacher_forced_logits
    with jax.default_matmul_precision("highest"):
        return teacher_forced_logits(
            cfg, opts, params, req.prompt, comp.tokens[:FORCED_STEPS],
            block_size=BLOCK, chunk=BUDGET, mesh=mesh)


def one_chip(cfg, lk, opts, params, clock) -> None:
    import dataclasses
    eng = build_engine(cfg, lk, opts, params)
    reqs, comps = serve(eng, cfg, clock, "1 chip")
    check_kernels_in_programs(eng)
    del eng
    t = time.perf_counter()
    got = forced_logits(cfg, opts, params, reqs[0], comps[0])
    ref_opts = dataclasses.replace(opts, attn_impl="ref", scan_impl="ref",
                                   fused_norm=False)
    want = forced_logits(cfg, ref_opts, params, reqs[0], comps[0])
    secs, _ = clock.lap()
    log(f"teacher-forced prompt + {FORCED_STEPS} steps, both paths: "
        f"{time.perf_counter() - t:.1f}s ({secs:.1f}s compile)")
    compare(got, want, "Pallas path vs attn_impl='ref'")


def four_chips(cfg, lk, opts, params, clock) -> None:
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh("1,4")
    devs = list(mesh.devices.flat)
    check(len({d.id for d in devs}) == 4
          and all(d.platform == "tpu" for d in devs),
          f"mesh 1,4 spans four distinct TPU devices {[d.id for d in devs]}")

    eng1 = build_engine(cfg, lk, opts, params)
    reqs, comps1 = serve(eng1, cfg, clock, "1 chip")
    del eng1
    eng4 = build_engine(cfg, lk, opts, params, mesh=mesh)
    _, comps4 = serve(eng4, cfg, clock, "mesh 1,4")
    per_dev, total = {d.id: 0 for d in devs}, 0
    for g in eng4.kv.cache:
        for name in ("kp", "vp"):
            total += g[name].nbytes
            for shard in g[name].addressable_shards:
                per_dev[shard.device.id] += shard.data.nbytes
    log(f"KV pool bytes per device: {per_dev} (pools {total})")
    check(all(4 * n == total for n in per_dev.values()),
          "each device holds a quarter of the KV pools")
    same = sum(a.tokens.tolist() == b.tokens.tolist()
               for a, b in zip(comps1, comps4))
    log(f"greedy streams identical to the one-chip run: {same}/{REQUESTS}")
    del eng4

    one = forced_logits(cfg, opts, params, reqs[0], comps1[0])
    four = forced_logits(cfg, opts, params, reqs[0], comps1[0], mesh=mesh)
    compare(four, one, "mesh 1,4 vs one chip")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1,
                   help="4: run the 1x4 mesh phase and the one-chip run it "
                        "is compared with, and nothing else")
    args = p.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devices[0].platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices; "
                         f"JAX found {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import _setup
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()

    t = time.perf_counter()
    cfg, lk, opts, params = _setup(ARCH, PRESET, smoke=False, seed=SEED,
                                   gen_len=GEN_LEN)
    jax.block_until_ready(params)
    secs, hits = clock.lap()
    log(f"device: {devices[0].device_kind} x{len(devices)}; "
        f"{cfg.name}: {cfg.num_blocks} blocks, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; params "
        f"{jax.tree.leaves(params)[0].dtype}, activations and KV "
        f"{jax.numpy.dtype(opts.dtype).name}; attn_impl {opts.attn_impl}, "
        f"fused_norm {opts.fused_norm}; setup {time.perf_counter() - t:.1f}s, "
        f"of which XLA compile {secs:.1f}s ({hits} persistent-cache hits)")
    check(opts.attn_impl == "pallas" and opts.fused_norm,
          "the shortcut preset resolved to the Pallas kernels")

    if args.chips == 4:
        four_chips(cfg, lk, opts, params, clock)
    else:
        one_chip(cfg, lk, opts, params, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
