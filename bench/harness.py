"""One run of one cell: set-up, the measured window, the per-layer readings
and the check against the plain reference.

The system under test is ``repro.serve.ServeEngine`` over ``PagedKV`` with
chunked prefill, built as the configuration file says. The benchmark makes
its weights (``program_params``) and its traffic (``bench.traffic``); the
configuration's model description (``spec.model``) gives its architecture,
its reference and its work counts. From the program it takes the engine, its
counters and the names its programs and kernels carry in the trace.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

from bench import reference, spec, traffic, weights
from bench.window import Window

#: first request id of the warm-up requests, above any the traffic issues
WARM_RID = 1 << 30

EVENTS = ("/jax/core/compile/backend_compile_duration",
          "/jax/compilation_cache/cache_hits")


class CompileCounter:
    """XLA compiles and persistent-cache loads, from JAX's monitoring
    events, with the host time of each."""

    def __init__(self, clock=time.monotonic):
        import jax
        self.times = []

        def on_duration(event, secs, **_):
            if event == EVENTS[0]:
                self.times.append(clock())

        def on_event(event, **_):
            if event == EVENTS[1]:
                self.times.append(clock())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


def arch_config(cfg: dict):
    """The ``repro`` architecture config the file describes."""
    return spec.model(cfg).arch_config(cfg)


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def program_params(arch, seed: int, mesh=None):
    """The program's weight tree, made on the device(s) in one jitted call
    from the seed; on a mesh each chip makes only its own shard."""
    import jax
    from repro.models import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), arch))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [(_path(kp), sd.shape) for kp, sd in flat]

    def make(key):
        leaves = [weights.stacked(key, p, s) if p.startswith("blocks/")
                  else weights.leaf(key, p, s) for p, s in paths]
        return jax.tree_util.tree_unflatten(tree, leaves)

    shardings = None
    if mesh is not None:
        from repro.sharding.rules import ArchSharding, named
        shardings = named(mesh, ArchSharding(arch, mesh).serve_param_specs(
            shapes))
    return jax.jit(make, out_shardings=shardings)(weights.base_key(seed))


class Cell:
    """A cell's run: ``setup()``, ``measure()``, ``check()``."""

    def __init__(self, bench: dict, name: str, seed: int, seconds: float,
                 trace: bool = False, root: Path = spec.ROOT,
                 process_start: Optional[float] = None):
        self.bench, self.root = bench, root
        self.w = spec.workload(bench, name)
        self.cfg = spec.load_config(bench, self.w["config"], root)
        self.mix = spec.load_traffic(self.w["traffic"], root)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.start = process_start or time.monotonic()
        self.desc = spec.model(self.cfg, root)
        self.sizes = self.desc.sizes(self.cfg)
        self.fault = None           # tests: breaks the timed path

    # -- set-up -------------------------------------------------------------
    def setup(self, devices) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import SamplingConfig, preset
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.mesh import make_host_mesh
        from repro.models import ModelOptions
        from repro.serve import ServeEngine
        from repro.serve.scheduler import Request

        enable_compile_cache()
        self.compiles = CompileCounter()
        jax.config.update("jax_default_matmul_precision",
                          self.cfg["matmul_precision"])
        e = self.cfg["engine"]
        self.devices = devices
        self.arch = self.desc.arch_config(self.cfg)
        data, model = e["mesh"]
        self.mesh = (make_host_mesh(data=data, model=model)
                     if data * model > 1 else None)
        lk = dataclasses.replace(preset(e["preset"]),
                                 decode_steps=e["decode_steps"])
        opts = ModelOptions(attn_impl="ref", scan_impl="ref",
                            dtype=jnp.dtype(self.cfg["dtype"]))
        if lk.shortcut:
            opts = lk.model_options(opts,
                                    on_tpu=jax.default_backend() == "tpu")
        params = program_params(self.arch, self.seed, self.mesh)
        self.eng = ServeEngine(
            self.arch, params, opts, lk, n_slots=e["slots"],
            max_len=self.mix["max_len"], kv="paged",
            block_size=e["block_size"], num_blocks=e["num_blocks"],
            sampling=SamplingConfig(), mesh=self.mesh, chunked=True,
            chunk_budget=e["chunk_budget"], chunk_width=e["chunk_width"])
        del params
        if self.fault is not None:
            self.fault(self)
        # warm up what the window runs: the serve step (a chunk that ends
        # each prompt), then plain decode programs. The engine slices each
        # request's last program output to the tokens it keeps, one small
        # program per length, so the warm-up ends requests after each of
        # 1..K kept tokens. Half of them are admitted only as the first
        # finish, beside rows still decoding: on a mesh the sampling keys
        # are sharded by then, and admitting into them is another program
        rng = np.random.default_rng(traffic.seed_words(self.seed) + [1])
        K = self.eng.tokens_per_program
        warm = [Request(rid=WARM_RID + i,
                        prompt=rng.integers(0, self.arch.vocab_size,
                                            min(e["chunk_width"], 16),
                                            dtype=np.int32),
                        max_new_tokens=2 + K + i % K) for i in range(2 * K)]
        self.eng.run(warm, load="closed", concurrency=min(K, e["slots"]))
        self.eng.kv.drop_prefix_cache()
        self.eng.reset_counters()
        self.reqs = traffic.Requests(self.mix, self.arch.vocab_size,
                                     self.seed)

    # -- the window ---------------------------------------------------------
    def measure(self) -> None:
        import jax
        self.win = Window(self.eng, self.reqs, self.mix, self.seconds)
        trace_dir = None
        if self.trace:
            trace_dir = tempfile.mkdtemp(prefix="trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ann = jax.profiler.TraceAnnotation("bench_window")
        self.setup_s = None

        def opened():
            self.setup_s = time.monotonic() - self.start
            if self.trace:
                ann.__enter__()

        self.win.run(on_open=opened)
        self.drained = self.win.drain()
        if self.trace:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.counters = self.eng.utilization()
        self.window_compiles = self.compiles.between(self.win.t0,
                                                     self.drained)
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)
        self.trace_path = None
        if trace_dir is not None:
            from bench import trace_reduce
            self.trace_path = trace_reduce.find(trace_dir)

    # -- per-layer readings --------------------------------------------------
    def per_layer(self, peaks: dict) -> dict:
        from bench import trace_reduce
        red = (trace_reduce.reduce(self.trace_path)
               if self.trace_path else None)
        self.reduced = red
        e = self.cfg["engine"]
        run = SimpleNamespace(
            records=list(self.win.records.values()), progs=self.win.progs,
            t0=self.win.t0, end=self.win.end, drained=self.drained,
            counters=self.counters, trace=red, cfg=self.cfg, engine=e,
            model=self.desc.work(self.cfg),
            chips=self.w["chips"], peaks=peaks,
            window_compiles=self.window_compiles,
            e2e=self.win.metrics())
        out = {}
        for m in spec.per_layer_metrics(self.bench, self.w["name"]):
            value = spec.reader(m["name"], self.root)(run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    # -- correctness ----------------------------------------------------------
    def sample(self):
        """Requests to check, drawn from the seed among those finished: the
        longest, then others until ``check_tokens`` served tokens."""
        c = self.cfg["correct"]
        done = [r for r in self.win.records.values() if r.tokens is not None]
        if not done:
            return []
        done.sort(key=lambda r: r.rid)
        longest = max(done, key=lambda r: (len(r.tokens) + r.prompt_len,
                                           -r.rid))
        rng = np.random.default_rng(traffic.seed_words(self.seed) + [2])
        picked, served = [longest], len(longest.tokens)
        total = longest.prompt_len + len(longest.tokens)
        for i in rng.permutation(len(done)):
            r = done[int(i)]
            if served >= c["check_tokens"] or len(picked) >= c["max_requests"]:
                break
            size = r.prompt_len + len(r.tokens)
            if r is longest or total + size > c["max_packed_tokens"]:
                continue
            picked.append(r)
            served += len(r.tokens)
            total += size
        return picked

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        import jax
        self.eng = None
        self.win.eng = None
        gc.collect()
        jax.clear_caches()

    def check(self):
        """(correct, checks, served tokens compared)."""
        picked = self.sample()
        prompts = {r.rid: r.prompt for r in self.reqs.reqs}
        limit = self.cfg["correct"]["max_logit_gap"]
        vocab = self.sizes["v"]
        if not picked:
            return False, {"finished_requests": {"value": 0, "limit": 1}}, 0
        served = [r.tokens for r in picked]
        in_vocab = all(int(t.min()) >= 0 and int(t.max()) < vocab
                       for t in served)
        if not in_vocab:
            return False, {"token_ids_in_vocab": {"value": 0, "limit": 1}}, 0
        seqs, rows, toks = reference.served_rows(
            [prompts[r.rid] for r in picked], served)
        ref = self.desc.logits(self.cfg, self.seed, seqs, rows, "f32")
        gap = reference.gap_of(ref, toks)
        worst = float(np.max(gap))
        ok = bool(np.isfinite(gap).all()) and worst <= limit
        return ok, {"max_logit_gap": {"value": worst, "limit": limit}}, \
            len(toks)


def device_record(devices, memory_peak: int, red: Optional[dict]) -> dict:
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    if red is not None:
        d["busy_s"] = red["busy_s"]
        d["window_s"] = red["window_s"]
    return d


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def dump(cell: Cell, path: Path) -> None:
    """The run's programs, requests and reduced trace, for a closer look."""
    import json
    path.parent.mkdir(parents=True, exist_ok=True)
    t0 = cell.win.t0
    progs = [{"kind": p.kind, "dispatched": p.dispatched - t0,
              "ready": None if p.ready is None else p.ready - t0,
              "tokens": p.tokens, "chunks": p.chunks, "decode": p.decode}
             for p in cell.win.progs]
    reqs = [{"rid": r.rid, "due": r.due - t0, "prompt": r.prompt_len,
             "max_new": r.max_new,
             "admit": None if r.admit is None else r.admit - t0,
             "first": r.first_prog, "last": r.last_prog}
            for r in cell.win.records.values()]
    with open(path, "w") as f:
        json.dump({"progs": progs, "requests": reqs,
                   "counters": {k: v for k, v in cell.counters.items()
                                if isinstance(v, (int, float, str))},
                   "trace": getattr(cell, "reduced", None)}, f)
