"""The benchmark's data, found by name: ``BENCHMARK.json``, one file per
model configuration, one per traffic mix, and one reader per per-layer
metric.

Nothing here imports the serving program. A new cell, configuration or
per-layer metric is a new file plus a new entry in ``BENCHMARK.json``, and a
new architecture is one more file, its model description; no code here names
one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]

#: a published ``config.json`` key -> the ``repro`` ``ArchConfig`` field it
#: sets. ``qkv_bias`` is not a ``config.json`` key: the Qwen2 modeling code
#: always adds biases to q, k and v, so the configuration file states it.
ARCH_KEYS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "num_hidden_layers": "num_blocks",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "attn_bias",
}

#: keys that are widths: a configuration may never cut them
WIDTH_KEYS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_experts_per_tok", "expand", "state_size",
              "d_state", "d_conv"}


def is_width(key: str) -> bool:
    return key in WIDTH_KEYS or key.endswith(("_dim", "_rank"))


class SpecError(ValueError):
    """A benchmark file that breaks its own rules."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                    f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def check_config(cfg: dict, entry: Optional[dict] = None) -> None:
    """Refuse a configuration whose sizes differ from its published source
    where the file does not say so.

    Every key of ``published`` (copied from the source's ``config.json``)
    must hold the same value in the file as it is run, unless the key is
    listed in ``reduced`` or ``assumed``. ``reduced`` never names a width,
    and lists only keys that were cut. The entry in ``BENCHMARK.json``, when
    given, must list the same ``reduced`` keys and the same source. A file
    that names no ``model`` states every key the dense description maps
    (``ARCH_KEYS``); a named description reads its own.
    """
    name = cfg.get("name", "?")
    published = cfg["published"]
    reduced = list(cfg.get("reduced", []))
    assumed = dict(cfg.get("assumed", {}))
    for key in reduced:
        if is_width(key):
            raise SpecError(f"{name}: {key!r} is a width and may not be cut")
        if key not in published:
            raise SpecError(f"{name}: reduced key {key!r} is not in the "
                            "published configuration")
        if cfg.get(key) == published[key]:
            raise SpecError(f"{name}: {key!r} is listed in reduced but "
                            "equals the published value")
    for key, value in published.items():
        if key in reduced or key in assumed:
            continue
        if key not in cfg:
            raise SpecError(f"{name}: published key {key!r} is missing from "
                            "the configuration as run")
        if cfg[key] != value:
            raise SpecError(f"{name}: {key!r} is {cfg[key]!r} but the source "
                            f"publishes {value!r}; list it in reduced or "
                            "assumed")
    for key in (ARCH_KEYS if "model" not in cfg else ()):
        if key not in cfg:
            raise SpecError(f"{name}: the configuration does not state "
                            f"{key!r}")
    if entry is not None:
        if sorted(entry["reduced"]) != sorted(reduced):
            raise SpecError(f"{name}: BENCHMARK.json lists reduced "
                            f"{entry['reduced']} but the file {reduced}")
        if entry["source"] != cfg["source"]:
            raise SpecError(f"{name}: BENCHMARK.json and the file name "
                            "different sources")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = config_entry(bench, name)
    cfg = load_json(root / entry["file"])
    check_config(cfg, entry)
    return cfg


def load_traffic(name: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.exists():
        raise SpecError(f"no traffic mix file {path}")
    return load_json(path)


def per_layer_metrics(bench: dict, workload_name: str) -> List[dict]:
    """The per-layer metrics a cell reports: those that list it, and those
    without a ``workloads`` key whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end_metrics(bench, workload_name)}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None:
            if workload_name in cells:
                out.append(m)
        elif m["moves"] in reported:
            out.append(m)
    return out


def end_to_end_metrics(bench: dict, workload_name: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if m.get("workloads") is None or workload_name in m["workloads"]]


def reader(metric: str, root: Path = ROOT) -> Callable[[Any], Optional[float]]:
    """``read(run)`` of ``bench/layers/<metric>.py``."""
    path = root / "bench" / "layers" / f"{metric}.py"
    if not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {metric!r}")
    return _load(path, f"bench_layer_{metric}").read


#: the description of a configuration file that names no ``model``
DEFAULT_MODEL = "dense"


def model(cfg: dict, root: Path = ROOT) -> ModuleType:
    """The configuration's model description, ``bench/models/<name>.py``
    for the file's ``"model"`` (the dense GQA decoder where it names none).

    A description provides ``arch_config(cfg)``, the program's
    ``ArchConfig``: the layer pattern, expert and window fields, and as
    ``num_blocks`` the repeats of the pattern (``num_hidden_layers`` over
    its length); ``sizes(cfg)``, the sizes its reference reads, the vocabulary
    as ``v``; ``logits(cfg, seed, seqs, rows, mode)``, its plain reference
    (``bench.reference.logits``'s signature and ``MODES``), with weights
    from ``bench.weights.leaf`` by the program's leaf paths; and
    ``work(cfg)``, its counts (``bench.flops.Stack``: ``prefill_flops``,
    ``decode_flops``, ``head_params`` and the ``kinds`` of its layers)."""
    name = cfg.get("model", DEFAULT_MODEL)
    path = root / "bench" / "models" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no model description {path} for {name!r}")
    return _load(path, f"bench_model_{name}")


def _load(path: Path, name: str) -> ModuleType:
    """The module in ``path``, loaded anew (and registered, so that the
    classes it defines find it)."""
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        "bench/peaks.json; add its published peaks")
    return table["devices"][device_kind]
