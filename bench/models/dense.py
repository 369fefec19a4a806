"""The dense GQA decoder: ``num_hidden_layers`` layers alike, each full
causal attention and a SwiGLU MLP, as ``bench/reference.py`` and
``bench/flops.py`` describe it. The description of every configuration file
that names no ``model``."""
import dataclasses

from bench import flops, reference, spec

sizes = reference.sizes
logits = reference.logits


def arch_config(cfg: dict):
    """The ``repro`` architecture config the file describes: its ``arch``
    with the file's sizes laid over it."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    return dataclasses.replace(base, **{spec.ARCH_KEYS[k]: cfg[k]
                                        for k in spec.ARCH_KEYS})


def work(cfg: dict) -> flops.Model:
    return flops.Model.of(reference.sizes(cfg), kv_bytes=4)
