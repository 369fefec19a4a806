"""The tensor-parallel cell's own fault: the exchange between chips left out.

On four virtual CPU devices, in a child process (the device count is fixed
when JAX starts), the tiny cell on a (data 1, model 4) mesh reads correct
with nothing compiled in its window, and reads not correct once the MLP's
row-parallel down-projection returns each chip's partial sum without the
all-reduce.
"""
import json
import os
import subprocess
import sys
import textwrap

from bench import spec

CHILD = textwrap.dedent("""
    import json, sys, tempfile
    from pathlib import Path
    import jax
    from jax.sharding import PartitionSpec as P
    from bench import harness, spec
    from bench.tests import tiny
    from repro.launch import compile_cache
    compile_cache.enable_compile_cache = lambda: ""   # leave no cache behind

    def no_exchange(cell):
        import repro.models.layers as L
        mesh = cell.mesh

        def mlp(params, x):
            h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
            part = jax.shard_map(lambda h, w: h @ w, mesh=mesh,
                                 in_specs=(P(*([None] * (h.ndim - 1)),
                                             "model"), P("model", None)),
                                 out_specs=P(), check_vma=False)
            return part(h, params["wo"])
        L.mlp = mlp

    out = {}
    for name, fault in (("sound", None), ("no_exchange", no_exchange)):
        cfg = tiny.config("tiny-tp4", mesh=[1, 4])
        root = tiny.make_root(Path(tempfile.mkdtemp()), cfgs=[cfg])
        c = harness.Cell(spec.benchmark(root), "tiny.closed", 2**31 + 77,
                         2.0, root=root)
        c.fault = fault
        c.setup(jax.devices()[:4])
        c.measure()
        c.release()
        ok, checks, n = c.check()
        out[name] = [ok, checks["max_logit_gap"]["value"], n,
                     c.window_compiles]
    print(json.dumps(out))
""")


def test_leaving_out_the_exchange_between_chips_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(spec.ROOT / "src"),
                                           str(spec.ROOT)]))
    r = subprocess.run([sys.executable, "-c", CHILD], cwd=spec.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True and out["sound"][2] > 0
    # the warm-up left nothing for the window to compile on the mesh
    assert out["sound"][3] == 0
    assert out["no_exchange"][0] is False
    assert out["no_exchange"][1] > 1e-3
