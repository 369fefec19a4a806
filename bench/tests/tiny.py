"""A benchmark root at a size the CPU runs in seconds: the real harness,
readers and peaks table, with one tiny configuration and two small mixes
written as files, the way a later change adds a cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import spec

TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
}


def config(name: str = "tiny-qwen2", **engine) -> dict:
    cfg = {"name": name, "arch": "qwen2-7b",
           "source": "https://huggingface.co/Qwen/Qwen2-7B/blob/main/config.json",
           "published": dict(TINY), **TINY, "head_dim": 16, "qkv_bias": True,
           "reduced": [], "assumed": {"head_dim": "d / heads",
                                      "qkv_bias": "Qwen2"},
           "deployment": "a CPU test size", "dtype": "float32",
           "matmul_precision": "highest",
           "engine": {"preset": "nss_shortcut", "decode_steps": 4,
                      "slots": 4, "block_size": 16, "chunk_budget": 64,
                      "chunk_width": 32, "num_blocks": 64, "mesh": [1, 1],
                      **engine},
           "correct": {"max_logit_gap": 1e-3, "check_tokens": 24,
                       "max_requests": 3, "max_packed_tokens": 4096}}
    return cfg


MIXES = {
    "tiny-closed": {"loop": "closed", "clients": 4, "block": 4,
                    "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                               "max": 48},
                    "output": {"median": 8, "sigma": 0.3, "min": 4, "max": 12},
                    "shared": {"count": 0, "length": 0}, "max_len": 96},
    "tiny-open": {"loop": "open", "rate": 20.0, "block": 4,
                  "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
                  "output": {"median": 8, "sigma": 0.3, "min": 4, "max": 12},
                  "shared": {"count": 2, "length": 32}, "max_len": 96},
}


def make_root(tmp: Path, cfgs=None, mixes=None, cells=None) -> Path:
    """A root holding the real ``bench`` code and the given data files."""
    root = Path(tmp)
    shutil.copytree(spec.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("configs", "traffic",
                                                  "tests", "__pycache__"))
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "traffic").mkdir()
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    cfgs = cfgs or [config()]
    mixes = mixes or MIXES
    bench["configs"] = []
    for c in cfgs:
        f = f"bench/configs/{c['name']}.json"
        (root / f).write_text(json.dumps(c))
        bench["configs"].append({"name": c["name"], "source": c["source"],
                                 "file": f, "reduced": c["reduced"],
                                 "why": "test"})
    for name, mix in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench["workloads"] = cells or [
        {"name": "tiny.closed", "config": cfgs[0]["name"],
         "traffic": "tiny-closed", "chips": 1, "why": "test"},
        {"name": "tiny.open", "config": cfgs[0]["name"],
         "traffic": "tiny-open", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.open"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
