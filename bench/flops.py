"""Operations and bytes the served work needs, from shapes alone.

``Model`` holds a configuration's sizes (as the reference reads them) and
the number of chips the model is split over. Model FLOPs count two per
multiply-add of every weight matrix a token goes through, plus the
attention scores and the weighted sum over the keys the token really sees.
The embedding is a gather and counts nothing. The paged attention kernel's
least work is the query, each key and value of the row's real length read
once, and the output written: not the ``max_len`` blocks the kernel walks.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    hq: int
    hkv: int
    dh: int
    f: int
    v: int
    layers: int
    kv_bytes: int = 4          # bytes per stored key or value element
    act_bytes: int = 4         # bytes per query / output element

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

    def attn_flops(self, ctx: int) -> int:
        """Scores and weighted sum for one query over ``ctx`` keys, in all
        layers."""
        return 4 * self.layers * self.hq * self.dh * ctx

    def prefill_flops(self, start: int, n: int, emits: bool) -> int:
        """Prompt positions [start, start + n); ``emits``: the chunk ends the
        prompt, so the head runs once for the first token."""
        if n <= 0:
            return 0
        ctx_sum = n * start + n * (n + 1) // 2        # sum of (p + 1)
        out = 2 * self.layers * self.layer_matmul_params * n
        out += 4 * self.layers * self.hq * self.dh * ctx_sum
        return out + (2 * self.head_params if emits else 0)

    def decode_flops(self, pos: int) -> int:
        """One decoded token written at position ``pos``."""
        return (2 * (self.layers * self.layer_matmul_params
                     + self.head_params) + self.attn_flops(pos + 1))

    def kernel_row(self, start: int, n: int) -> Tuple[int, int]:
        """(flops, bytes) of one layer's paged attention for one row whose
        ``n`` queries sit at positions [start, start + n)."""
        if n <= 0:
            return 0, 0
        ctx_sum = n * start + n * (n + 1) // 2
        flops = 4 * self.hq * self.dh * ctx_sum
        kv = 2 * (start + n) * self.hkv * self.dh * self.kv_bytes
        qo = 2 * n * self.hq * self.dh * self.act_bytes
        return flops, kv + qo


def kernel_min_seconds(m: Model, calls: Iterable[Iterable[Tuple[int, int]]],
                       peak_flops: float, peak_bw: float,
                       chips: int = 1) -> float:
    """Least time for the paged kernel calls: each call is one layer over
    rows given as (start, n); every layer makes the same call. The work
    splits evenly over ``chips`` (whole KV heads on each)."""
    total = 0.0
    for rows in calls:
        fl = by = 0
        for start, n in rows:
            a, b = m.kernel_row(start, n)
            fl += a
            by += b
        if fl or by:
            total += m.layers * max(fl / chips / peak_flops,
                                    by / chips / peak_bw)
    return total
