"""The plain reference: a dense GQA decoder in straightforward ``jax.numpy``.

RMSNorm, rotary embedding (the half-split convention of the Llama/Qwen2
modeling code), optional q/k/v biases, causal grouped-query attention,
a SwiGLU MLP and an untied head, as the model's published description has
them. It takes the model's sizes from the configuration file as it is run
and makes its own weights from the seed (``bench.weights``), layer by layer,
so that a whole model never has to fit at once. It imports nothing of the
serving program and takes nothing the program made.

``forward`` runs any layer pattern with this embedding and head; ``layer``
is the dense decoder's layer. A model description that is not the dense
decoder (``bench/models``) passes ``forward`` its own layer functions.

Several sequences are packed into one row: a token attends to the tokens of
its own sequence at positions up to its own. The packed row is padded to a
multiple of ``PAD`` so that few shapes compile.

``mode="f32"`` is the reference: float32 weights and activations, every
matmul at ``Precision.HIGHEST``. ``mode="bf16"`` is the control: weights and
activations in bfloat16, the step a deployment takes below float32.
``f32-high`` and ``f32-default`` keep float32 but take the matmuls at three
bf16 passes and at one: the controls of configurations that state
``highest`` or ``high``, which ``bench/control.py`` reads beside.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

PAD = 2048          # packed rows are padded to a multiple of this
Q_BLOCK = 256       # query rows per attention block

MODES = {"f32": (jnp.float32, jax.lax.Precision.HIGHEST),
         "f32-high": (jnp.float32, jax.lax.Precision.HIGH),
         "f32-default": (jnp.float32, jax.lax.Precision.DEFAULT),
         "bf16": (jnp.bfloat16, jax.lax.Precision.DEFAULT)}


def sizes(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=cfg["num_key_value_heads"],
                dh=cfg.get("head_dim") or d // hq,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"], theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]), bias=bool(cfg["qkv_bias"]),
                tie=bool(cfg["tie_word_embeddings"]))


def layer_shapes(s: dict) -> Dict[str, Tuple[int, ...]]:
    d, q, kv = s["d"], s["hq"] * s["dh"], s["hkv"] * s["dh"]
    out = {"norm1/scale": (d,), "norm2/scale": (d,),
           "mixer/wq": (d, q), "mixer/wk": (d, kv), "mixer/wv": (d, kv),
           "mixer/wo": (q, d), "mlp/wi": (d, s["f"]), "mlp/wg": (d, s["f"]),
           "mlp/wo": (s["f"], d)}
    if s["bias"]:
        out.update({"mixer/bq": (q,), "mixer/bk": (kv,), "mixer/bv": (kv,)})
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype", "block"))
def _layer_weights(key, layer, shapes, dtype, block=0):
    """Layer ``layer`` of the program's stack ``blocks/<block>``."""
    return {name: weights.leaf(key, f"blocks/{block}/{name}", shape,
                               layer=layer).astype(dtype)
            for name, shape in shapes}


@functools.partial(jax.jit, static_argnames=("path", "shape", "dtype"))
def _leaf(key, path, shape, dtype):
    return weights.leaf(key, path, shape).astype(dtype)


def _norm(x, scale, eps, dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs           # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def layer(x, w, pos, seg, s, mode):
    """One dense layer (the weights of ``layer_shapes``) over the packed
    row: ``pos`` and ``seg`` are each token's position and sequence."""
    dtype, prec = MODES[mode]
    s = dict(s)
    dot = functools.partial(jnp.dot, precision=prec)
    T = x.shape[0]
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    h = _norm(x, w["norm1/scale"], s["eps"], dtype)
    q, k, v = dot(h, w["mixer/wq"]), dot(h, w["mixer/wk"]), dot(h, w["mixer/wv"])
    if s["bias"]:
        q, k, v = q + w["mixer/bq"], k + w["mixer/bk"], v + w["mixer/bv"]
    q = _rope(q.reshape(T, hq, dh), pos, s["theta"])
    k = _rope(k.reshape(T, hkv, dh), pos, s["theta"])
    v = v.reshape(T, hkv, dh)
    g = hq // hkv

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK, Q_BLOCK)
        qb = sl(q).reshape(Q_BLOCK, hkv, g, dh)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
        # padding (segment -1, position 0) attends only to padding, so no
        # row is left with nothing to attend to
        live = ((sl(seg)[:, None] == seg[None, :])
                & (pos[None, :] <= sl(pos)[:, None]))
        p = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p.astype(dtype), v, precision=prec)
        return o.reshape(Q_BLOCK, hq * dh)

    att = jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, hq * dh)
    x = x + dot(att.astype(dtype), w["mixer/wo"]).astype(x.dtype)
    h = _norm(x, w["norm2/scale"], s["eps"], dtype)
    up = jax.nn.silu(dot(h, w["mlp/wg"])) * dot(h, w["mlp/wi"])
    return x + dot(up.astype(dtype), w["mlp/wo"]).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def _head(x_rows, norm, head, s, mode):
    dtype, prec = MODES[mode]
    h = _norm(x_rows, norm, dict(s)["eps"], dtype)
    return jnp.dot(h, head, precision=prec,
                   preferred_element_type=jnp.float32)


def pack(seqs: Sequence[np.ndarray]):
    """(tokens, positions, segment ids) of the packed, padded row."""
    T = sum(len(s) for s in seqs)
    T = -(-T // PAD) * PAD
    tok = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    at = 0
    for i, s in enumerate(seqs):
        n = len(s)
        tok[at:at + n], pos[at:at + n], seg[at:at + n] = s, np.arange(n), i
        at += n
    return tok, pos, seg


def forward(s: dict, seed: int, seqs: Sequence[np.ndarray],
            rows: Sequence[Tuple[int, int]], mode: str,
            period: Sequence[Tuple[Callable, Dict[str, Tuple[int, ...]]]],
            blocks: int) -> jax.Array:
    """Logits (len(rows), vocab) float32 at ``rows``: (sequence index,
    position) pairs, of the packed ``seqs``, with weights from ``seed``.

    The model is ``blocks`` repeats of ``period``, the layer pattern: each
    entry a layer function, jitted as ``f(x, w, pos, seg, s, mode)`` with
    ``s`` (the sizes, as sorted items) and ``mode`` static, and the shapes
    of its weights. Layer ``j`` of repeat ``b`` takes its weights from the
    program's leaves ``blocks/<j>/...`` at index ``b``, one layer at a
    time. Embedding, final norm and head are the dense decoder's."""
    dtype = MODES[mode][0]
    key = weights.base_key(seed)
    tok, pos, seg = pack(seqs)
    starts = np.cumsum([0] + [len(q) for q in seqs])
    at = np.array([starts[i] + p for i, p in rows], np.int32)
    embed = _leaf(key, "embed", (s["v"], s["d"]), dtype)
    x = embed[jnp.asarray(tok)].astype(dtype)
    if not s["tie"]:
        del embed
    pos_d, seg_d = jnp.asarray(pos), jnp.asarray(seg)
    frozen = tuple(sorted(s.items()))
    period = [(fn, tuple(sorted(shapes.items()))) for fn, shapes in period]
    for b in range(blocks):
        for j, (fn, shapes) in enumerate(period):
            w = _layer_weights(key, b, shapes, dtype, j)
            x = fn(x, w, pos_d, seg_d, frozen, mode)
            del w
    rows_x = x[jnp.asarray(at)]
    del x
    norm = _leaf(key, "final_norm/scale", (s["d"],), jnp.float32)
    head = (embed.T if s["tie"]
            else _leaf(key, "lm_head", (s["d"], s["v"]), dtype))
    return _head(rows_x, norm, head, frozen, mode)


def logits(cfg: dict, seed: int, seqs: Sequence[np.ndarray],
           rows: Sequence[Tuple[int, int]], mode: str = "f32") -> jax.Array:
    """``forward`` of the dense decoder ``cfg`` describes: one layer kind,
    ``num_hidden_layers`` deep."""
    s = sizes(cfg)
    return forward(s, seed, seqs, rows, mode, [(layer, layer_shapes(s))],
                   s["layers"])


def served_rows(prompts: Sequence[np.ndarray], served: Sequence[np.ndarray]
                ) -> Tuple[List[np.ndarray], List[Tuple[int, int]],
                           np.ndarray]:
    """Teacher-forced sequences for served greedy tokens: each prompt
    followed by its served tokens but the last; the rows whose logits chose
    each served token; and those tokens."""
    seqs, rows, toks = [], [], []
    for i, (p, t) in enumerate(zip(prompts, served)):
        p, t = np.asarray(p, np.int32), np.asarray(t, np.int32)
        seqs.append(np.concatenate([p, t[:-1]]))
        rows += [(i, len(p) - 1 + j) for j in range(len(t))]
        toks.append(t)
    return seqs, rows, np.concatenate(toks)


def gap_of(ref: jax.Array, tokens) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best, per row."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return np.asarray(ref.max(axis=1) - at)
