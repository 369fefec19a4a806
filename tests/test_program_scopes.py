"""The serving programs' names and scopes: ``jax.named_scope`` changes only
HLO metadata, and the scopes sit where the trace reader expects them (the
layer scan's own slicing and restacking of the KV pool under
``layer_scan`` and no scope of the layer body)."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import SamplingConfig, preset
from repro.core.step import build_paged_decode_step, build_serve_step
from repro.models import ModelOptions, init_params
from repro.serve.paging import init_paged_cache

CFG = get_config("tinyllama-1.1b").smoke()
B, BS, MAX_LEN, W = 4, 16, 64, 16
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_SCOPES = ("chunk_pass", "decode_microsteps", "layer_scan", "attn", "mlp",
           "norm", "kv_write", "head", "sample")


def _scope(op_name: str) -> str:
    """The serving scopes of an op_name, in order."""
    return "/".join(p for p in op_name.split("/") if p in _SCOPES)


def _compiled():
    """Optimized HLO text of the paged decode program and the serve step."""
    lk = dataclasses.replace(preset("nss_shortcut"), decode_steps=4)
    opts = lk.model_options(ModelOptions(dtype=jnp.float32), on_tpu=False)
    nb = MAX_LEN // BS
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    cache = jax.eval_shape(lambda: init_paged_cache(
        CFG, B * nb + 1, BS, B, jnp.float32))
    arg = jax.ShapeDtypeStruct
    i32, no = (lambda *s: arg(s, jnp.int32)), arg((B,), jnp.bool_)
    keys, tables = arg((B, 2), jnp.uint32), i32(B, nb)
    dec = build_paged_decode_step(CFG, opts, lk, MAX_LEN, SamplingConfig())
    srv = build_serve_step(CFG, opts, lk, MAX_LEN, SamplingConfig(),
                           kv_kind="paged")
    return cache, {
        "decode_program": dec.lower(params, cache, i32(B), keys,
                                    tables).compile().as_text(),
        "serve_step": srv.lower(params, cache, i32(B, W), i32(B), i32(B), no,
                                no, i32(B), no, keys, tables,
                                tables).compile().as_text()}


def _stripped(text: str) -> str:
    """The HLO without metadata: no ``metadata={...}``, no stack-frame
    tables, the module and instruction names numbered in order."""
    text = "\n\n".join(b for b in text.split("\n\n")
                       if not b.lstrip("\n").startswith(_TABLES))
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^HloModule \S+", "HloModule M", text, flags=re.M)
    names = {}
    return re.sub(r"%[\w\-.]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.fixture(scope="module")
def programs():
    return _compiled()


def test_the_programs_are_named(programs):
    _, texts = programs
    for name, text in texts.items():
        assert text.startswith(f"HloModule jit_{name}"), name


def test_scopes_change_only_metadata(programs, monkeypatch):
    _, scoped = programs
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, plain = _compiled()
    for name in scoped:
        assert "layer_scan" in scoped[name] and \
            "layer_scan" not in plain[name]
        assert _stripped(scoped[name]) == _stripped(plain[name]), name


@pytest.mark.parametrize("name,parts", [
    ("decode_program", ("decode_microsteps",)),
    ("serve_step", ("chunk_pass", "decode_microsteps")),
])
def test_layer_scan_holds_the_pool_slicing(programs, name, parts):
    """Each layer scan's slicing of the stacked pool out (dynamic-slice) and
    back (dynamic-update-slice) carries ``layer_scan`` and no scope of the
    layer body: what ``kv_pool_copy_share`` reads."""
    cache, texts = programs
    L, *layer = cache[0]["kp"].shape
    dims = lambda *d: "f32[" + ",".join(map(str, d)) + "]"
    found = set()
    for line in texts[name].splitlines():
        m = re.search(r"= (\S+) (dynamic-slice|dynamic-update-slice)\(.*"
                      r'op_name="([^"]*)"', line)
        if not m:
            continue
        shape, opcode, op_name = m.groups()
        scope = _scope(op_name)
        if not scope.endswith("/layer_scan"):
            continue
        if opcode == "dynamic-slice" and shape.startswith(dims(1, *layer)):
            found.add((scope, "slice out"))
        if opcode == "dynamic-update-slice" and \
                shape.startswith(dims(L, *layer)):
            found.add((scope, "stack back"))
    for part in parts:
        assert (f"{part}/layer_scan", "slice out") in found, (part, found)
        assert (f"{part}/layer_scan", "stack back") in found, (part, found)
