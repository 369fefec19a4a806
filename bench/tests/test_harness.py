"""The harness end to end on the CPU at a tiny size: what a run reports,
that cells, configurations and metrics are added as files alone, that a
configuration cannot quietly shrink, and that ``correct`` catches a broken
timed path."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import harness, reference, spec
from bench.tests import tiny

SEED = 2 ** 31 + 4242
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _run(root, cell, trace=False, fault=None, seconds=2.0, seed=SEED):
    c = harness.Cell(spec.benchmark(root), cell, seed, seconds, trace=trace,
                     root=root)
    c.fault = fault
    c.setup(jax.devices()[:1])
    c.measure()
    layers = c.per_layer(PEAKS) if trace else None
    c.release()
    return c, layers, c.check()


def test_a_cell_added_as_files_runs_and_reports(tmp_path):
    """A new configuration, traffic mix and per-layer metric, each only a
    file plus an entry in BENCHMARK.json, are found by name and run."""
    root = tiny.make_root(tmp_path)
    reader = root / "bench" / "layers" / "finished_requests.py"
    reader.write_text("def read(run):\n"
                      "    return run.e2e['finished'] or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "finished_requests", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": ["tiny.open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell, layers, (ok, checks, n) = _run(root, "tiny.open", trace=True)
    assert ok and n >= 20, checks
    assert checks["max_logit_gap"]["value"] <= 1e-3
    e2e = cell.win.metrics()
    assert e2e["tokens_per_s"] > 0 and e2e["ttft_p95_s"] > 0
    assert e2e["tpot_p95_ms"] > 0 and e2e["attempted"] > 0
    assert cell.window_compiles == 0
    names = {m["name"] for m in spec.per_layer_metrics(bench, "tiny.open")}
    assert "finished_requests" in names and set(layers) <= names
    assert layers["finished_requests"]["value"] == e2e["finished"]
    assert 0 < layers["kv_prefix_hit_share"]["value"] < 100
    assert 0 < layers["step_token_fill"]["value"] <= 100
    assert 0 <= layers["device_idle_share"]["value"] < 100
    assert cell.reduced["busy_s"] <= cell.reduced["window_s"]
    # the closed-loop mix: no shared prefixes, so no hit-share reading
    assert "kv_prefix_hit_share" not in {
        m["name"] for m in spec.per_layer_metrics(bench, "tiny.closed")}


def test_the_benchmark_files_hold_together():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cfg = spec.load_config(bench, w["config"])
        mix = spec.load_traffic(w["traffic"])
        assert mix["max_len"] % cfg["engine"]["block_size"] == 0
        for m in spec.per_layer_metrics(bench, w["name"]):
            assert callable(spec.reader(m["name"]))
        assert {m["name"] for m in spec.end_to_end_metrics(bench, w["name"])} \
            >= {"setup_s", "tokens_per_s"}


@pytest.mark.parametrize("change,ok", [
    ({"hidden_size": 48}, False),                     # a width, unlisted
    ({"num_hidden_layers": 1}, False),                # depth, unlisted
    ({"num_hidden_layers": 1, "reduced": ["num_hidden_layers"]}, True),
    ({"intermediate_size": 64, "reduced": ["intermediate_size"]}, False),
    ({"reduced": ["num_hidden_layers"]}, False),      # listed, not cut
])
def test_a_configuration_cannot_quietly_shrink(change, ok):
    cfg = {**tiny.config(), **change}
    if ok:
        spec.check_config(cfg)
    else:
        with pytest.raises(spec.SpecError):
            spec.check_config(cfg)


def test_an_unknown_device_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")


def _alter_tokens(cell):
    """A token altered where it is produced: every program's output tokens
    come back shifted by one."""
    kv = cell.eng.kv
    vocab = cell.arch.vocab_size
    serve, decode = kv.serve_step, kv.decode

    def bad_serve(*a):
        t0, seq = serve(*a)
        return (t0 + 1) % vocab, (seq + 1) % vocab

    kv.serve_step = bad_serve
    kv.decode = lambda nxt: (decode(nxt) + 1) % vocab


def _stale_decode(cell):
    """Decode that returns the same tokens every step (its state, the fed
    token, never moves on)."""
    kv = cell.eng.kv
    decode = kv.decode
    kv.decode = lambda nxt: decode(nxt) * 0 + nxt[:, None]


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_decode])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    root = tiny.make_root(tmp_path)
    _, _, (ok, checks, _) = _run(root, "tiny.closed", fault=fault)
    assert not ok
    assert checks["max_logit_gap"]["value"] > 1e-3


def test_the_bfloat16_control_fails_the_limit(tmp_path):
    """The control, at a size a test holds: the bfloat16 reference in the
    program's place reads above the limit where the program reads below."""
    root = tiny.make_root(tmp_path)
    cell, _, (ok, checks, _) = _run(root, "tiny.closed", seconds=3.0)
    assert ok
    picked = [r for r in cell.win.records.values()
              if r.tokens is not None][:40]
    prompts = {r.rid: r.prompt for r in cell.reqs.reqs}
    seqs, rows, toks = reference.served_rows(
        [prompts[r.rid] for r in picked], [r.tokens for r in picked])
    ref = reference.logits(cell.cfg, SEED, seqs, rows, "f32")
    ctl = np.asarray(reference.logits(cell.cfg, SEED, seqs, rows, "bf16"))
    gap = reference.gap_of(ref, ctl.argmax(axis=1)).max()
    assert gap > cell.cfg["correct"]["max_logit_gap"]


def test_run_refuses_without_a_tpu(tmp_path):
    """No TPU: a non-zero exit and no result line. And in a directory that
    holds only BENCHMARK.json and bench/, the same."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload",
           "qwen2-7b-stage.longdoc", "--seed", "1", "--seconds", "1"]
    r = subprocess.run(cmd, cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    (tmp_path / "bench").mkdir()
    for f in ("run.py", "spec.py", "harness.py", "window.py", "reference.py",
              "weights.py", "traffic.py", "flops.py", "__init__.py"):
        (tmp_path / "bench" / f).write_text((spec.ROOT / "bench" / f)
                                            .read_text())
    (tmp_path / "BENCHMARK.json").write_text(
        (spec.ROOT / "BENCHMARK.json").read_text())
    env.pop("PYTHONPATH", None)
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
