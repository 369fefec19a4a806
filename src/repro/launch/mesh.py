"""Production mesh construction (assignment contract).

A function, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the partition rules (repro.sharding.rules) place arrays
    # with NamedShardings and leave the rest to GSPMD propagation.
    # jax.make_mesh's default, Explicit, would type every op's sharding and
    # refuse the gathers whose output sharding it cannot infer.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (tests / examples)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec):
    """``"data,model"`` string (e.g. ``"1,2"``) -> (data, model) ints.
    Returns None for None/empty/"1,1" — the single-device path."""
    if not spec:
        return None
    parts = [p.strip() for p in str(spec).split(",")]
    if len(parts) != 2:
        raise ValueError(f"--mesh wants 'data,model' (got {spec!r})")
    data, model = int(parts[0]), int(parts[1])
    if data < 1 or model < 1:
        raise ValueError(f"--mesh axes must be >= 1 (got {spec!r})")
    if data == model == 1:
        return None
    return data, model


def mesh_device_count(spec) -> int:
    """Devices a ``--mesh data,model`` spec needs (1 for the single-device
    path). Pure string parsing, no device access — safe to call before
    jax's backend initializes, which is where callers need it: XLA locks
    the host device count at first use, so
    ``--xla_force_host_platform_device_count`` must be computed and set
    first (scripts/paged_smoke.py, benchmarks/bench_serving.py)."""
    parsed = parse_mesh_spec(spec)
    if parsed is None:
        return 1
    data, model = parsed
    return data * model


# Re-export: the host→device placement half of the serving two-tier KV
# hierarchy rides next to the mesh constructors for launcher/script use;
# the definition lives with the partition rules (repro.sharding.rules) so
# the serving library never depends on the launch layer.
from repro.sharding.rules import host_to_mesh  # noqa: F401,E402


def make_serve_mesh(spec):
    """Serving mesh from a ``--mesh data,model`` flag. None when the spec is
    single-device. On CPU CI, force virtual devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    parsed = parse_mesh_spec(spec)
    if parsed is None:
        return None
    data, model = parsed
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"--mesh {spec} needs {data * model} devices, have {n}; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{data * model}")
    return make_host_mesh(data=data, model=model)
