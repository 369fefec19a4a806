"""Operations and bytes the served work needs, from shapes alone.

A model is its kinds of layers (``Kind``) and its head (``Stack``). Model
FLOPs count two per multiply-add of every weight matrix a token goes
through (for sparse experts, the router and the experts the token is sent
to), plus the attention scores and the weighted sum over the keys the token
really sees: every earlier key of its sequence, or in a windowed layer the
last ``window``. The embedding is a gather and counts nothing. The paged
attention kernel's least work is the query, each key and value the row's
queries see read once, and the output written: not the ``max_len`` blocks
the kernel walks.

``Model`` is the dense GQA decoder: one kind, every layer full attention and
a SwiGLU MLP, with the sizes as the reference reads them.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Kind:
    """``layers`` layers alike: their attention heads, how far back a query
    sees (``window`` keys, its own included; 0: all), and the weight-matrix
    parameters a token goes through in one of them."""
    layers: int
    hq: int
    hkv: int
    dh: int
    matmul_params: int
    window: int = 0
    kv_bytes: int = 4          # bytes per stored key or value element
    act_bytes: int = 4         # bytes per query / output element

    def keys_seen(self, start: int, n: int) -> int:
        """Keys seen, summed over queries at positions [start, start + n)."""
        end = start + n
        if not self.window or end <= self.window:
            return n * start + n * (n + 1) // 2        # sum of (p + 1)
        cap = max(start, self.window)   # queries below cap see p + 1 keys
        return ((cap - start) * (start + 1 + cap) // 2
                + (end - cap) * self.window)

    def attn_flops(self, start: int, n: int) -> int:
        """Scores and weighted sums of those queries, in all these layers."""
        return 4 * self.layers * self.hq * self.dh * self.keys_seen(start, n)

    def kernel_row(self, start: int, n: int) -> Tuple[int, int]:
        """(flops, bytes) of one layer's paged attention for one row whose
        ``n`` queries sit at positions [start, start + n)."""
        if n <= 0:
            return 0, 0
        flops = 4 * self.hq * self.dh * self.keys_seen(start, n)
        first = max(0, start + 1 - self.window) if self.window else 0
        kv = 2 * (start + n - first) * self.hkv * self.dh * self.kv_bytes
        qo = 2 * n * self.hq * self.dh * self.act_bytes
        return flops, kv + qo


@dataclasses.dataclass(frozen=True)
class Stack:
    """A model as its kinds of layers and its output head."""
    kinds: Tuple[Kind, ...]
    head_params: int

    def prefill_flops(self, start: int, n: int, emits: bool) -> int:
        """Prompt positions [start, start + n); ``emits``: the chunk ends the
        prompt, so the head runs once for the first token."""
        if n <= 0:
            return 0
        out = sum(2 * k.layers * k.matmul_params * n + k.attn_flops(start, n)
                  for k in self.kinds)
        return out + (2 * self.head_params if emits else 0)

    def decode_flops(self, pos: int) -> int:
        """One decoded token written at position ``pos``."""
        return 2 * self.head_params + sum(
            2 * k.layers * k.matmul_params + k.attn_flops(pos, 1)
            for k in self.kinds)


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    hq: int
    hkv: int
    dh: int
    f: int
    v: int
    layers: int
    kv_bytes: int = 4
    act_bytes: int = 4

    @classmethod
    def of(cls, sizes: dict, kv_bytes: int = 4) -> "Model":
        return cls(d=sizes["d"], hq=sizes["hq"], hkv=sizes["hkv"],
                   dh=sizes["dh"], f=sizes["f"], v=sizes["v"],
                   layers=sizes["layers"], kv_bytes=kv_bytes,
                   act_bytes=kv_bytes)

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.hq * self.dh, self.hkv * self.dh
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.f

    @property
    def head_params(self) -> int:
        return self.d * self.v

    @property
    def kinds(self) -> Tuple[Kind, ...]:
        return (Kind(self.layers, self.hq, self.hkv, self.dh,
                     self.layer_matmul_params, 0, self.kv_bytes,
                     self.act_bytes),)

    def prefill_flops(self, start: int, n: int, emits: bool) -> int:
        return Stack(self.kinds, self.head_params).prefill_flops(start, n,
                                                                 emits)

    def decode_flops(self, pos: int) -> int:
        return Stack(self.kinds, self.head_params).decode_flops(pos)

    def kernel_row(self, start: int, n: int) -> Tuple[int, int]:
        return self.kinds[0].kernel_row(start, n)


def kernel_min_seconds(m, calls: Iterable[Iterable[Tuple[int, int]]],
                       peak_flops: float, peak_bw: float,
                       chips: int = 1) -> float:
    """Least time for the paged kernel calls of model ``m`` (a ``Stack`` or
    a ``Model``): each call is made by every layer, over rows given as
    (start, n), and each kind of layer reads the keys it sees. The work
    splits evenly over ``chips`` (whole KV heads on each)."""
    kinds = m.kinds
    total = 0.0
    for rows in calls:
        rows = list(rows)
        for k in kinds:
            fl = by = 0
            for start, n in rows:
                a, b = k.kernel_row(start, n)
                fl += a
                by += b
            if fl or by:
                total += k.layers * max(fl / chips / peak_flops,
                                        by / chips / peak_bw)
    return total
