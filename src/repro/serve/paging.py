"""Paged KV memory: virtual memory for the serving engine's cache.

UKL's linked application keeps using the kernel's memory-management
subsystem — demand paging, pinned pools, shared mappings — and this module is
that subsystem for the KV cache. The dense ``SlottedKV`` backend reserves a
``max_len`` row per slot, so admission capacity is bounded by *worst-case*
sequence length; here capacity is bounded by tokens actually resident:

  BlockPool     ref-counted allocator over a fixed pool of physical KV
                blocks (``block_size`` token positions each). Row ``P`` of
                the device pool is the reserved *trash block* — the write
                target of empty/finished slots, so their garbage never
                touches a live sequence.
  BlockTable    per-slot chain mapping logical block index -> physical block
                (the slot's "page table"); mirrored on device as one
                (n_slots, nb) int32 array consumed by the decode program.
  PrefixIndex   radix tree over *full* blocks of prompt tokens: identical
                prompt prefixes (system prompts) resolve to the same
                physical blocks, so they are prefilled once and shared
                copy-on-write afterwards. Index-only blocks are evicted LRU
                under pool pressure.
  HostBlockStore  the second tier: a host-memory (numpy) pool with the same
                block geometry, its own free list, refcounts and LRU ticks.
                Cold KV state spills here instead of dying — swapped-out
                victim chains (pinned until swap-in) and demoted prefix
                blocks (evictable LRU) — and it is what
                ``save``/``restore`` persist across engine restarts.
  PagedKV       the ``KVBackend`` implementation tying these to the device
                pool: demand allocation at decode-time block boundaries,
                CoW forks before any write to a shared block, and — under
                pool pressure — either recompute-preemption or swap-out
                preemption (device→host block copy, resume via swap-in
                without re-prefill; the engine's ``PreemptionPolicy``
                chooses).

The two tiers talk through jitted chain-at-once copy programs
(``repro.core.step.build_chain_export_fn`` / ``build_chain_import_fn`` —
one program per swapped sequence, not one per block; the single-block
variants remain for point reads);
under a mesh the copies are per-shard (``ArchSharding.serve_swap_chain_specs``
+ ``repro.sharding.rules.host_to_mesh``), so the host tier mirrors the
physical shard layout. Under async swap (the default) device→host chain
transfers are issued on a ``SwapStream`` double buffer and complete at the
owning engine's step boundaries (``drain_swaps``) — the exported chains
are fresh arrays, so device blocks recycle immediately while the copy is
still in flight; the engine may also ``prefetch_swap_in`` the resume-head
victim so its host→device copy hides under the current device step. Evicted shared prefixes demote device→host and
promote back on a radix hit; ``save(path)``/``restore(path)`` persist the
host tier (plus a lossless export of the device radix index)
prompt-token-keyed and config-fingerprinted.

The subsystem is invisible to the application: token streams are
bit-identical to the slotted backend (and to sequential decode) — the
UKL-style invariant that specialization must not change app-visible
behavior. Sharing is capped at ``prompt_len - 1`` tokens so every request
computes at least its final prompt position (that position's logits seed
generation); a full-prefix hit therefore prefills one token instead of P.
"""
from __future__ import annotations

import dataclasses
import heapq
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import kv_quant
from repro.kernels.kv_quant import KV_DTYPES
from repro.models import prefill_suffix
from repro.sharding.rules import host_to_mesh
from repro.models.transformer import _check_pageable
from repro.serve.cache import make_prefill_fn
from repro.serve.telemetry import NULL_TELEMETRY


# ---------------------------------------------------------------------------
# Host-side allocator / page tables / prefix index
# ---------------------------------------------------------------------------

class BlockPool:
    """Ref-counted allocator over ``num_blocks`` physical KV blocks.

    Deterministic: free blocks are handed out lowest-id-first, so a fixed
    request schedule replays the exact same physical layout. Tracks the
    resident-block high-watermark (the paged analogue of peak RSS).
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1 or block_size < 1:
            raise ValueError("BlockPool needs num_blocks, block_size >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks))
        heapq.heapify(self._free)
        self.refs = np.zeros(num_blocks, np.int32)
        self.hwm = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_resident(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> Optional[int]:
        """Lowest free block with refcount 1, or None when exhausted."""
        if not self._free:
            return None
        blk = heapq.heappop(self._free)
        self.refs[blk] = 1
        self.hwm = max(self.hwm, self.n_resident)
        return blk

    def retain(self, blk: int) -> None:
        if self.refs[blk] <= 0:
            raise ValueError(f"retain of unallocated block {blk}")
        self.refs[blk] += 1

    def free(self, blk: int) -> bool:
        """Drop one reference; returns True when the block went physically
        free. Freeing an unallocated block is a double-free: error."""
        if self.refs[blk] <= 0:
            raise ValueError(f"double free of block {blk}")
        self.refs[blk] -= 1
        if self.refs[blk] == 0:
            heapq.heappush(self._free, blk)
            return True
        return False


class BlockTable:
    """One slot's logical-block -> physical-block chain."""

    def __init__(self, blocks: Optional[List[int]] = None):
        self.blocks: List[int] = list(blocks or [])

    def __len__(self) -> int:
        return len(self.blocks)

    def __getitem__(self, i: int) -> int:
        return self.blocks[i]

    def append(self, blk: int) -> None:
        self.blocks.append(blk)

    def replace(self, i: int, blk: int) -> None:
        self.blocks[i] = blk


class HostBlockStore(BlockPool):
    """The host tier of the two-tier KV hierarchy: the same ref-counted
    free-list allocator as the device ``BlockPool`` (alloc touches, for
    LRU), plus per-block LRU ticks and optional numpy storage. Holds
    swapped-out sequence chains (pinned by their SwapHandles) and demoted
    prefix blocks (one reference from the owner's prefix map — evictable
    least-recently-touched when the tier fills).

    Constructed without ``group_shapes`` it is allocator-only (refcount
    bookkeeping with no storage) — the mode the differential fuzz in
    tests/test_properties.py drives. With shapes — (L, bs, HKV, dh) per
    layer group — it owns the buffers the jitted block export/import
    programs copy through.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 group_shapes: Optional[List[Tuple[int, ...]]] = None,
                 dtype=np.float32, scale_shapes=None):
        super().__init__(num_blocks, block_size)
        self.tick = np.zeros(num_blocks, np.int64)
        self._tick = 0
        self.k = self.v = None
        self.ks = self.vs = None
        #: drain callbacks of every SwapStream writing into this store —
        #: each owning PagedKV registers its own. A store shared across
        #: fleet replicas must complete EVERY writer's in-flight transfers
        #: before a read or a free of a possibly-pending block, not just
        #: the reading replica's (``PagedKV._drain_tier``).
        self.drains: List[Any] = []
        if group_shapes is not None:
            dt = np.dtype(dtype)
            self.k = [np.zeros((s[0], num_blocks) + tuple(s[1:]), dt)
                      for s in group_shapes]
            self.v = [np.zeros((s[0], num_blocks) + tuple(s[1:]), dt)
                      for s in group_shapes]
            # quantized tier: per-block scale tables ride beside the values
            # — (L, num_blocks, HKV) f32 per group
            if scale_shapes is not None:
                self.ks = [np.ones((s[0], num_blocks) + tuple(s[1:]),
                                   np.float32) for s in scale_shapes]
                self.vs = [np.ones((s[0], num_blocks) + tuple(s[1:]),
                                   np.float32) for s in scale_shapes]

    def alloc(self) -> Optional[int]:
        blk = super().alloc()
        if blk is not None:
            self.touch(blk)
        return blk

    def touch(self, blk: int) -> None:
        self._tick += 1
        self.tick[blk] = self._tick

    def write(self, blk: int, kvs) -> None:
        """Store one exported device block (tuple of {"k","v"} — plus
        {"ks","vs"} scales on a quantized tier — per group)."""
        for g, kv in enumerate(kvs):
            self.k[g][:, blk] = np.asarray(kv["k"])
            self.v[g][:, blk] = np.asarray(kv["v"])
            if self.ks is not None:
                self.ks[g][:, blk] = np.asarray(kv["ks"])
                self.vs[g][:, blk] = np.asarray(kv["vs"])

    def read(self, blk: int):
        """The block's K/V as the import program's operand type (copies —
        safe to free the host block as soon as the import is dispatched)."""
        out = []
        for g in range(len(self.k)):
            kv = {"k": self.k[g][:, blk].copy(),
                  "v": self.v[g][:, blk].copy()}
            if self.ks is not None:
                kv["ks"] = self.ks[g][:, blk].copy()
                kv["vs"] = self.vs[g][:, blk].copy()
            out.append(kv)
        return tuple(out)

    def write_chain(self, blks: List[int], kvs) -> None:
        """Store a whole exported chain at once (tuple of {"k","v"} per
        group, leaves (L, n, bs, HKV, dh)) — the host half of
        ``build_chain_export_fn`` and the ``SwapStream`` write callback."""
        idx = np.asarray(blks, np.int64)
        for g, kv in enumerate(kvs):
            self.k[g][:, idx] = np.asarray(kv["k"])
            self.v[g][:, idx] = np.asarray(kv["v"])
            if self.ks is not None:
                self.ks[g][:, idx] = np.asarray(kv["ks"])
                self.vs[g][:, idx] = np.asarray(kv["vs"])

    def read_chain(self, blks: List[int]):
        """A whole chain's K/V as ``build_chain_import_fn``'s operand type
        (fancy indexing copies — safe to free the host blocks as soon as
        the import is dispatched)."""
        idx = np.asarray(blks, np.int64)
        out = []
        for g in range(len(self.k)):
            kv = {"k": self.k[g][:, idx], "v": self.v[g][:, idx]}
            if self.ks is not None:
                kv["ks"] = self.ks[g][:, idx]
                kv["vs"] = self.vs[g][:, idx]
            out.append(kv)
        return tuple(out)


@dataclasses.dataclass
class SwapHandle:
    """A swapped-out sequence: its KV blocks parked in the host tier plus
    the per-slot device state needed to resume without re-prefill."""
    hblks: List[int]                     # host-tier block ids (chain order)
    pos: int                             # sequence position at swap-out
    key: jax.Array                       # (2,) uint32 sampling-chain row
    prompt: Optional[np.ndarray] = None  # chunked: prompt source for the
                                         # remaining (mid-prefill) chunks
    prefetch: Any = None                 # in-flight speculative host→device
                                         # copy of the chain (device tree)
    dropped: bool = False                # drop_swap'd: resuming is an error


class SwapStream:
    """Double-buffered asynchronous device→host transfer queue.

    ``issue`` starts a non-blocking copy of an exported chain
    (``copy_to_host_async`` on every leaf) and parks the (host block ids,
    device arrays) pair; the oldest transfer is completed — ``np.asarray``
    (which merely waits once the async copy landed) then the ``write``
    callback into the host store — whenever more than ``depth`` are in
    flight, and ``drain`` completes everything. The exported chains are
    *fresh* arrays (the gather program copies out of the pool), so the
    device pool blocks may be freed and reused while the transfer is still
    in flight — only the host-tier destination blocks must stay allocated
    until the drain, which is why ``PagedKV`` drains before any host-tier
    read or free of a possibly-pending block.
    """

    def __init__(self, write, depth: int = 2):
        self.write = write               # write(hblks, kvs) callback
        self.depth = depth
        self.pending: List[Tuple[List[int], Any, int]] = []

    def __len__(self) -> int:
        return len(self.pending)

    def issue(self, hblks: List[int], kvs, nbytes: int) -> None:
        """Start the async copy and enqueue its completion."""
        for leaf in jax.tree.leaves(kvs):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self.pending.append((list(hblks), kvs, nbytes))
        while len(self.pending) > self.depth:
            self._complete_one()

    def _complete_one(self) -> Tuple[int, int]:
        hblks, kvs, nbytes = self.pending.pop(0)
        self.write(hblks, jax.tree.map(np.asarray, kvs))
        return len(hblks), nbytes

    def drain(self) -> Tuple[int, int, int]:
        """Complete every in-flight transfer; (transfers, blocks, bytes)."""
        t = b = n = 0
        while self.pending:
            blocks, nbytes = self._complete_one()
            t += 1
            b += blocks
            n += nbytes
        return t, b, n


@dataclasses.dataclass
class SharedHostTier:
    """One host tier shared by every replica of an engine fleet: the
    ``HostBlockStore`` plus the prompt-keyed prefix map all replicas
    demote into and promote from — a system prompt prefilled by any cell
    warms the whole fleet. Also the transfer lane for prefill->decode
    disaggregation handoffs (a ``SwapHandle``'s host blocks are pinned
    here between the source replica's swap-out and the destination's
    swap-in).

    Coherence: single-threaded fleet ticks serialize all map mutations;
    the async hazard is per-replica ``SwapStream`` writes still in flight
    when *another* replica reads or frees a host block — every replica
    registers its drain on ``store.drains`` and drains them all first
    (see ``PagedKV._drain_tier``).
    """
    store: HostBlockStore
    prefix_map: Dict[bytes, int] = dataclasses.field(default_factory=dict)
    prefix_keys: Dict[int, Tuple[bytes, np.ndarray]] = \
        dataclasses.field(default_factory=dict)
    #: prefix key -> replica that wrote it (write-through publish / demote)
    writer: Dict[bytes, Any] = dataclasses.field(default_factory=dict)
    #: promotions of a prefix some *other* replica published — the
    #: cross-engine warm hits the shared store exists for
    cross_hits: int = 0

    @classmethod
    def build(cls, cfg: ArchConfig, opts, block_size: int, host_blocks: int,
              kv_dtype: str = "bf16") -> "SharedHostTier":
        """A store with the same block geometry ``PagedKV`` would build
        for itself — replicas constructed from the same (cfg, opts,
        block_size, kv_dtype) attach to it interchangeably."""
        group_shapes = [(cfg.num_blocks, block_size, cfg.n_kv_heads,
                         cfg.head_dim) for _ in cfg.block_pattern]
        store_dt = kv_quant.storage_dtype(kv_dtype, opts.dtype)
        scale_shapes = None
        if kv_dtype != "bf16":
            scale_shapes = [(cfg.num_blocks, cfg.n_kv_heads)
                            for _ in cfg.block_pattern]
        store = HostBlockStore(host_blocks, block_size,
                               group_shapes=group_shapes, dtype=store_dt,
                               scale_shapes=scale_shapes)
        return cls(store=store)


class _Node:
    __slots__ = ("key", "block", "children", "parent", "tick")

    def __init__(self, key, block, parent):
        self.key = key                  # tuple of block_size token ids
        self.block = block              # physical block id
        self.children: Dict[tuple, "_Node"] = {}
        self.parent = parent
        self.tick = 0


class PrefixIndex:
    """Radix tree over full prompt blocks -> physical blocks.

    Each node covers exactly ``block_size`` tokens, keyed by their values,
    so a lookup is one dict probe per block. The index holds its own
    reference on every block it names; blocks whose only reference is the
    index are evictable (LRU, leaves first — evicting a leaf may expose its
    parent).
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.root = _Node((), -1, None)
        self._by_block: Dict[int, _Node] = {}
        self._tick = 0

    def __len__(self) -> int:
        return len(self._by_block)

    def _touch(self, node: _Node) -> None:
        self._tick += 1
        node.tick = self._tick

    def _keys(self, tokens: np.ndarray):
        bs = self.block_size
        for i in range(len(tokens) // bs):
            yield tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])

    def match(self, tokens: np.ndarray) -> List[int]:
        """Longest chain of full blocks whose token content prefixes
        ``tokens``; touched for LRU."""
        node, out = self.root, []
        for key in self._keys(tokens):
            child = node.children.get(key)
            if child is None:
                break
            self._touch(child)
            out.append(child.block)
            node = child
        return out

    def insert(self, tokens: np.ndarray, blocks: List[int], n_full: int,
               pool: BlockPool) -> List[_Node]:
        """Register the first ``n_full`` full blocks of ``tokens`` (their
        KV already written to ``blocks``). Existing nodes are kept — the
        caller matched them first, so a fresh node always carries a fresh
        block. The index retains each block it adopts. Returns the nodes
        created by THIS insert (the set a shared host tier write-through
        publishes — see ``PagedKV._publish``)."""
        node = self.root
        created: List[_Node] = []
        for i, key in enumerate(self._keys(tokens)):
            if i >= n_full:
                break
            child = node.children.get(key)
            if child is None:
                child = _Node(key, blocks[i], node)
                node.children[key] = child
                self._by_block[blocks[i]] = child
                pool.retain(blocks[i])
                created.append(child)
            self._touch(child)
            node = child
        return created

    def n_evictable(self, pool: BlockPool) -> int:
        """Blocks freeable by cascading leaf eviction: nodes whose whole
        subtree is index-exclusive (refcount 1)."""
        def walk(node: _Node) -> Tuple[int, bool]:
            count, all_ev = 0, True
            for c in node.children.values():
                n, ev = walk(c)
                count += n
                all_ev &= ev
            mine = all_ev and pool.refs[node.block] == 1
            return count + (1 if mine else 0), mine
        return sum(walk(c)[0] for c in self.root.children.values())

    def node_tokens(self, node: _Node) -> np.ndarray:
        """The full token prefix a node covers (root → node key concat) —
        the host-tier / persistence key for its block."""
        parts = []
        while node.parent is not None:
            parts.append(node.key)
            node = node.parent
        return np.array([t for key in reversed(parts) for t in key],
                        np.int32)

    def walk(self):
        """Yield every node, parents before children (deterministic:
        insertion order) — the persistence export order."""
        stack = list(reversed(list(self.root.children.values())))
        while stack:
            node = stack.pop()
            yield node
            stack.extend(reversed(list(node.children.values())))

    def evict(self, pool: BlockPool, need: int, on_evict=None) -> int:
        """Free up to ``need`` blocks, least-recently-touched leaves first
        (evicting a leaf may expose its parent — the candidate heap grows
        inward instead of rescanning the tree per block). Returns how many
        were physically freed.

        ``on_evict(node)``, when given, runs before each block is freed
        (its device content is still intact) — the hook the two-tier
        hierarchy uses to demote evicted prefixes to the host tier instead
        of dropping them."""
        cands = [(n.tick, n.block) for n in self._by_block.values()
                 if not n.children and pool.refs[n.block] == 1]
        heapq.heapify(cands)
        freed = 0
        while freed < need and cands:
            tick, blk = heapq.heappop(cands)
            node = self._by_block.get(blk)
            if (node is None or node.children or node.tick != tick
                    or pool.refs[blk] != 1):
                continue                       # stale heap entry
            if on_evict is not None:
                on_evict(node)
            parent = node.parent
            del parent.children[node.key]
            del self._by_block[blk]
            pool.free(blk)
            freed += 1
            if (parent is not self.root and not parent.children
                    and pool.refs[parent.block] == 1):
                heapq.heappush(cands, (parent.tick, parent.block))
        return freed


# ---------------------------------------------------------------------------
# Device pool + jitted page operations
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int,
                     n_slots: int, dtype=jnp.bfloat16, kv_dtype: str = "bf16"):
    """Physical pools per layer group: {"kp","vp"}: (L, P+1, bs, HKV, dh)
    (row P = trash block), plus per-slot positions (L, B).

    With a quantized ``kv_dtype`` (int8 / fp8) the pools store the compressed
    encoding and each group gains per-block symmetric scale tables
    {"ks","vs"}: (L, P+1, HKV) f32 — one scale per block per KV head (see
    ``repro.kernels.kv_quant``). ``kv_dtype="bf16"`` adds nothing: the cache
    tree is structurally identical to the unquantized engine's."""
    store = kv_quant.storage_dtype(kv_dtype, dtype)
    out = []
    for _ in cfg.block_pattern:
        shape = (cfg.num_blocks, num_blocks + 1, block_size,
                 cfg.n_kv_heads, cfg.head_dim)
        g = {
            "kp": jnp.zeros(shape, store),
            "vp": jnp.zeros(shape, store),
            "pos": jnp.zeros((cfg.num_blocks, n_slots), jnp.int32),
        }
        if kv_dtype != "bf16":
            sshape = (cfg.num_blocks, num_blocks + 1, cfg.n_kv_heads)
            g["ks"] = jnp.ones(sshape, jnp.float32)
            g["vs"] = jnp.ones(sshape, jnp.float32)
        out.append(g)
    return tuple(out)


def _sharding_kwargs(mesh, cache_sharding, n_extra: int, *,
                     out_replicated: bool = False):
    """jit kwargs pinning the physical pools per-shard resident: (cache,
    *extras) -> cache (or a replicated view); every non-cache operand
    replicated."""
    if mesh is None:
        return {}
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())
    return dict(in_shardings=(cache_sharding,) + (repl,) * n_extra,
                out_shardings=repl if out_replicated else cache_sharding)


def _make_scatter(mesh=None, cache_sharding=None):
    """Jitted ``(cache, kvs, blks, offs, slot, new_pos) -> cache``: write a
    prefilled K/V run into physical (block, offset) destinations and set the
    slot's position. Padding rows target the trash block. Donated: the pool
    is updated in place, no reallocation per admission."""

    def scatter(cache, kvs, blks, offs, slot, new_pos):
        out = []
        for g, kv in zip(cache, kvs):
            pos = g["pos"].at[:, slot].set(new_pos)
            if "ks" in g:
                # quantize-on-write: block-level requant around the run
                kp, ks = kv_quant.quant_insert_stacked(
                    g["kp"], g["ks"], blks, offs, kv["k"][:, 0])
                vp, vs = kv_quant.quant_insert_stacked(
                    g["vp"], g["vs"], blks, offs, kv["v"][:, 0])
                out.append(dict(g, kp=kp, vp=vp, ks=ks, vs=vs, pos=pos))
                continue
            kp = g["kp"].at[:, blks, offs].set(
                kv["k"][:, 0].astype(g["kp"].dtype))
            vp = g["vp"].at[:, blks, offs].set(
                kv["v"][:, 0].astype(g["vp"].dtype))
            out.append(dict(g, kp=kp, vp=vp, pos=pos))
        return tuple(out)

    return jax.jit(scatter, donate_argnums=(0,),
                   **_sharding_kwargs(mesh, cache_sharding, 5))


def _make_gather(max_len: int, mesh=None, cache_sharding=None):
    """Jitted ``(cache, table_row (nb,)) -> tuple of {"k","v"}``: assemble
    one slot's logical prefix view (L, 1, max_len, HKV, dh) from the pool —
    the input the shared-prefix suffix prefill attends over. Under a mesh
    the view is returned replicated (the suffix prefill runs per-request,
    batch 1, on replicated activations)."""

    def gather(cache, row):
        out = []
        for g in cache:
            def view(p, s=None):
                v = p[:, row]                        # (L, nb, bs, HKV, dh)
                if s is not None:                    # dequantize the view
                    v = kv_quant.dequantize(v, s[:, row][:, :, None, :, None])
                L_, nb_, bs_ = v.shape[:3]
                v = v.reshape(L_, nb_ * bs_, *v.shape[3:])[:, :max_len]
                return v[:, None]                    # (L, 1, max_len, ...)
            out.append({"k": view(g["kp"], g.get("ks")),
                        "v": view(g["vp"], g.get("vs"))})
        return tuple(out)

    return jax.jit(gather, **_sharding_kwargs(mesh, cache_sharding, 1,
                                              out_replicated=True))


def _make_copy_block(mesh=None, cache_sharding=None):
    """Jitted ``(cache, src, dst) -> cache``: device-side block copy — the
    copy half of copy-on-write. Donated. Under a mesh each shard copies its
    own slice of the block (no cross-shard traffic)."""

    def copy(cache, src, dst):
        out = []
        for g in cache:
            d = dict(g, kp=g["kp"].at[:, dst].set(g["kp"][:, src]),
                     vp=g["vp"].at[:, dst].set(g["vp"][:, src]))
            if "ks" in g:                  # CoW forks copy the block scales
                d["ks"] = g["ks"].at[:, dst].set(g["ks"][:, src])
                d["vs"] = g["vs"].at[:, dst].set(g["vs"][:, src])
            out.append(d)
        return tuple(out)

    return jax.jit(copy, donate_argnums=(0,),
                   **_sharding_kwargs(mesh, cache_sharding, 2))


def _make_zero_block(mesh=None, cache_sharding=None):
    """Jitted ``(cache, blk) -> cache``: clear one physical block's values
    and reset its scales to 1. Run at allocation time on *quantized* pools:
    ``quant_insert`` takes each touched block's amax over all its lanes, so
    a freshly allocated block must not carry a previous tenant's stale
    bytes — they would leak into the scale and make quantized token streams
    depend on pool allocation history (the bf16 control masks stale lanes
    at attention time and needs no zeroing). Donated."""

    def zero(cache, blk):
        out = []
        for g in cache:
            d = dict(g,
                     kp=g["kp"].at[:, blk].set(
                         jnp.zeros((), g["kp"].dtype)),
                     vp=g["vp"].at[:, blk].set(
                         jnp.zeros((), g["vp"].dtype)))
            if "ks" in g:
                d["ks"] = g["ks"].at[:, blk].set(1.0)
                d["vs"] = g["vs"].at[:, blk].set(1.0)
            out.append(d)
        return tuple(out)

    return jax.jit(zero, donate_argnums=(0,),
                   **_sharding_kwargs(mesh, cache_sharding, 1))


def _make_set_pos(mesh=None, cache_sharding=None):
    """Jitted ``(cache, slot, pos) -> cache``: restore one slot's device
    position after a swap-in (the scatter program normally sets it at
    admission; swap-in bypasses admission). Donated."""

    def set_pos(cache, slot, pos):
        return tuple(dict(g, pos=g["pos"].at[:, slot].set(pos))
                     for g in cache)

    return jax.jit(set_pos, donate_argnums=(0,),
                   **_sharding_kwargs(mesh, cache_sharding, 2))


# ---------------------------------------------------------------------------
# The KV backend
# ---------------------------------------------------------------------------

class PagedKV:
    """Block-table KV backend: the engine's ``--kv paged`` subsystem."""

    kind = "paged"
    #: telemetry hooks for tier movement (the owning engine installs its
    #: bundle here; the class default is the zero-cost null singleton)
    tel = NULL_TELEMETRY

    def __init__(self, cfg: ArchConfig, params, opts, linkage, n_slots: int,
                 max_len: int, sampling=None, bucket_fn=None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 mesh=None, chunked: bool = False,
                 host_blocks: Optional[int] = 0,
                 warm_start: Optional[str] = None, spec: bool = False,
                 async_swap: bool = True, kv_dtype: str = "bf16",
                 shared_host: Optional[SharedHostTier] = None):
        from repro.core.linkage import L3_NSS
        from repro.core.step import (build_block_export_fn,
                                     build_block_import_fn,
                                     build_chain_export_fn,
                                     build_chain_import_fn,
                                     build_paged_decode_step,
                                     build_serve_step, build_verify_step,
                                     make_sampler)
        _check_pageable(cfg, "PagedKV")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; known: "
                             f"{KV_DTYPES}")
        self.cfg, self.params, self.opts = cfg, params, opts
        self.kv_dtype = kv_dtype
        self.n_slots, self.max_len = n_slots, max_len
        self.bs = block_size
        self.nb = -(-max_len // block_size)          # logical blocks per slot
        if num_blocks is None:
            # slotted-equivalent footprint, +1 so a lone worst-case request
            # always fits() (a CoW fork transiently holds old + new block)
            num_blocks = n_slots * self.nb + 1
        self.trash = num_blocks                      # reserved pool row
        self.K = linkage.decode_steps if linkage.level == L3_NSS else 1
        self.bucket_fn = bucket_fn
        self.mesh = mesh

        self.pool = BlockPool(num_blocks, block_size)
        self.index = PrefixIndex(block_size)
        self.chains: Dict[int, BlockTable] = {}
        self.tables_host = np.full((n_slots, self.nb), self.trash, np.int32)
        self.pos_host = np.zeros(n_slots, np.int64)
        self.keys = jnp.zeros((n_slots, 2), jnp.uint32)
        self.cache = init_paged_cache(cfg, num_blocks, block_size, n_slots,
                                      opts.dtype, kv_dtype=kv_dtype)
        self.cow_forks = 0
        self.prefix_shared_tokens = 0
        self.swap_out_blocks = 0
        self.swap_in_blocks = 0
        self.bytes_moved = 0          # every block crossing the tier boundary
        self.prefix_demotions = 0
        self.prefix_promotions = 0
        self.restored_entries = 0
        self.swap_fails = 0           # tier moves that fell back to recompute
        self.stream_transfers = 0     # async transfers completed at drains
        self.prefetch_issued = 0
        self.prefetch_hits = 0
        self.prefetch_cancels = 0
        self.prefix_publishes = 0     # write-through copies to a shared tier
        self._pending_publish: List[Any] = []  # chunked: nodes whose blocks
                                               # the next serve_step writes
        #: fleet replica id (the fleet runtime stamps it); feeds the shared
        #: tier's writer map so cross-replica warm hits are countable
        self.owner: Any = None

        # -- the host tier ---------------------------------------------------
        # host_blocks: 0 disables it; None sizes it like the device pool (the
        # swap-preemption default); warm_start grows it to fit the file.
        # A SharedHostTier overrides all of that: the store and prefix maps
        # are the fleet's, sized and built once by the fleet runtime.
        self.shared = shared_host
        group_shapes = [(cfg.num_blocks, block_size, cfg.n_kv_heads,
                         cfg.head_dim) for _ in cfg.block_pattern]
        store_dt = kv_quant.storage_dtype(kv_dtype, opts.dtype)
        scale_shapes = None
        if kv_dtype != "bf16":
            scale_shapes = [(cfg.num_blocks, cfg.n_kv_heads)
                            for _ in cfg.block_pattern]
        if shared_host is not None:
            st = shared_host.store
            if st.block_size != block_size or st.k is None or \
                    tuple(st.k[0].shape[2:]) != tuple(group_shapes[0][1:]) \
                    or np.dtype(st.k[0].dtype) != np.dtype(store_dt):
                raise ValueError(
                    "shared host tier geometry does not match this replica "
                    "(build it via SharedHostTier.build from the same cfg/"
                    "opts/block_size/kv_dtype)")
            self.host: Optional[HostBlockStore] = st
            self.host_map = shared_host.prefix_map
            self.host_keys = shared_host.prefix_keys
        else:
            if host_blocks is None:
                host_blocks = num_blocks
            n_persisted = 0
            if warm_start:
                with np.load(warm_start) as data:
                    n_persisted = int(data["n"])
                host_blocks = max(host_blocks, n_persisted)
            self.host = None
            if host_blocks > 0:
                self.host = HostBlockStore(host_blocks, block_size,
                                           group_shapes=group_shapes,
                                           dtype=store_dt,
                                           scale_shapes=scale_shapes)
            self.host_map: Dict[bytes, int] = {}  # token-prefix key -> hblk
            self.host_keys: Dict[int, Tuple[bytes, np.ndarray]] = {}
        # per-block tier-transfer bytes: quantized values + scale tables.
        # _raw_block_bytes is the uncompressed equivalent — the ratio is the
        # bandwidth saving the report's *_raw counter makes visible.
        self._block_bytes = sum(
            2 * int(np.prod(s)) * np.dtype(store_dt).itemsize
            for s in group_shapes)
        if scale_shapes is not None:
            self._block_bytes += sum(2 * int(np.prod(s)) * 4
                                     for s in scale_shapes)
        self._raw_block_bytes = sum(
            2 * int(np.prod(s)) * np.dtype(opts.dtype).itemsize
            for s in group_shapes)

        param_sh = cache_sh = blk_sh = chain_sh = None
        if mesh is not None:
            from repro.sharding.rules import ArchSharding, named
            sh = ArchSharding(cfg, mesh)
            param_sh = named(mesh, sh.serve_param_specs(params))
            cache_sh = named(mesh, sh.serve_paged_cache_specs(self.cache))
            blk_sh = named(mesh, sh.serve_swap_block_specs(self.cache))
            chain_sh = named(mesh, sh.serve_swap_chain_specs(self.cache))
            self.params = params = jax.device_put(params, param_sh)
            self.cache = jax.device_put(self.cache, cache_sh)
        self._blk_sh = blk_sh
        self._chain_sh = chain_sh

        self.chunked = chunked
        self._copy = _make_copy_block(mesh, cache_sh)
        self._zero = (_make_zero_block(mesh, cache_sh)
                      if self.kv_dtype != "bf16" else None)
        self._export = build_block_export_fn(mesh, cache_sh, blk_sh)
        self._import = build_block_import_fn(mesh, cache_sh, blk_sh)
        self._export_chain = build_chain_export_fn(mesh, cache_sh, chain_sh)
        self._import_chain = build_chain_import_fn(mesh, cache_sh, chain_sh)
        self._setpos = _make_set_pos(mesh, cache_sh)
        # the async swap stream: device→host chain transfers issued at
        # swap-out/demote time, completed at the owning engine's step
        # boundaries (``drain_swaps``); None = fully synchronous tier moves
        self.async_swap = bool(async_swap)
        self.stream: Optional[SwapStream] = None
        if self.async_swap and self.host is not None:
            self.stream = SwapStream(self.host.write_chain)
            # every writer registers on the store: a shared tier must be
            # able to complete ALL replicas' in-flight writes before any
            # replica reads or frees a possibly-pending host block
            self.host.drains.append(self.drain_swaps)
        # the decode program is shared by both step disciplines: two-phase
        # decode, and the chunked engine's pure-decode fast path
        self._dec = build_paged_decode_step(cfg, opts, linkage, max_len,
                                            sampling, mesh=mesh,
                                            param_sharding=param_sh,
                                            cache_sharding=cache_sh)
        if chunked:
            # the unified serve step replaces the blocking admission prefill
            # (full-prompt AND shared-prefix suffix paths) plus the mixed
            # prefill+decode program: per-bucket prefill shapes vanish
            self.prompts: Dict[int, np.ndarray] = {}
            self._serve = build_serve_step(cfg, opts, linkage, max_len,
                                           sampling, kv_kind="paged",
                                           mesh=mesh, param_sharding=param_sh,
                                           cache_sharding=cache_sh)
        else:
            self._sample = jax.jit(make_sampler(sampling))
            self._scatter = _make_scatter(mesh, cache_sh)
            self._gather = _make_gather(max_len, mesh, cache_sh)
            # full-prompt prefill (the no-sharing path) — the same program as
            # the slotted backend's, so non-shared admissions are trivially
            # bit-identical across backends
            self._prefill = make_prefill_fn(cfg, opts, max_len, bucket_fn,
                                            mesh, param_sh)
            suffix_kwargs = {}
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                repl = NamedSharding(mesh, P())
                suffix_kwargs = dict(in_shardings=(param_sh,) + (repl,) * 4,
                                     out_shardings=repl)
            self._suffix = jax.jit(
                lambda p, t, pre, plen, n: prefill_suffix(p, t, pre, plen,
                                                          cfg, opts,
                                                          true_len=n),
                **suffix_kwargs)
        if spec:
            self._verify = build_verify_step(cfg, opts, linkage, max_len,
                                             sampling, kv_kind="paged",
                                             mesh=mesh, param_sharding=param_sh,
                                             cache_sharding=cache_sh)

        if warm_start:
            self.restored_entries = self.restore(warm_start)

    # -- allocation ---------------------------------------------------------

    def _alloc(self) -> Optional[int]:
        blk = self.pool.alloc()
        if blk is None and self.index.evict(self.pool, 1,
                                            on_evict=self._demote):
            blk = self.pool.alloc()
        if blk is not None and self._zero is not None:
            # quantized pools: scrub the previous tenant's bytes so block
            # scales stay a pure function of the sequence's own content
            self.cache = self._zero(self.cache, jnp.asarray(blk, jnp.int32))
        return blk

    def _cow(self, slot: int, chain: BlockTable, bi: int) -> bool:
        """Fork chain[bi] if shared: allocate, device-copy, swap, decref."""
        old = chain[bi]
        if self.pool.refs[old] <= 1:
            return True
        new = self._alloc()
        if new is None:
            return False
        self.cache = self._copy(self.cache, jnp.asarray(old, jnp.int32),
                                jnp.asarray(new, jnp.int32))
        self.pool.free(old)
        chain.replace(bi, new)
        self.tables_host[slot, bi] = new
        self.cow_forks += 1
        return True

    # -- the host tier: demotion / promotion / swap -------------------------

    def _host_alloc(self) -> Optional[int]:
        """A free host block, evicting least-recently-touched *prefix map*
        entries to make room (swapped chains are pinned by their handles)."""
        if self.host is None:
            return None
        h = self.host.alloc()
        while h is None and self._host_evict_lru():
            h = self.host.alloc()
        return h

    def _drain_tier(self) -> None:
        """Complete every in-flight write into this host tier — ours AND,
        on a fleet-shared tier, every other replica's (their streams all
        registered on ``host.drains``). The guard before any host-tier
        read or any free of a possibly-pending host block; equivalent to
        ``drain_swaps`` for a private tier."""
        if self.host is None:
            return
        for drain in self.host.drains:
            drain()

    def _host_evict_lru(self) -> bool:
        # drain first: an entry picked here may still have its demote write
        # in flight — freeing (and reallocating) it before the deferred
        # write lands would corrupt the new owner's data
        self._drain_tier()
        cands = [(self.host.tick[h], h) for h in self.host_map.values()
                 if self.host.refs[h] == 1]
        if not cands:
            return False
        _, h = min(cands)
        key, _ = self.host_keys.pop(h)
        del self.host_map[key]
        if self.shared is not None:
            self.shared.writer.pop(key, None)
        self.host.free(h)
        return True

    def _raw_bytes_of(self, blocks: int):
        """``raw_bytes`` telemetry arg for a tier move of ``blocks`` blocks:
        None for the bf16 control (wire bytes == logical bytes, and its
        trace events stay identical to the pre-quantization schema), else
        what the compressed blocks decode to."""
        if self.kv_dtype == "bf16":
            return None
        return blocks * self._raw_block_bytes

    def drain_swaps(self) -> int:
        """Complete every in-flight async device→host transfer (no-op when
        the stream is empty or the backend is synchronous). The engine
        calls this at step boundaries; internally it guards every host-tier
        read and every free of a possibly-pending host block. Returns the
        number of transfers completed."""
        if self.stream is None or not len(self.stream):
            return 0
        t, b, n = self.stream.drain()
        self.stream_transfers += t
        self.tel.swap_stream(t, b, n, self._raw_bytes_of(b))
        return t

    def _demote(self, node) -> None:
        """Device index eviction hook: copy the block's K/V into the host
        tier (keyed by its full token prefix) before the device block is
        freed — evicted shared prefixes spill instead of dying. The export
        is a fresh device array, so under async swap the device→host copy
        is issued on the stream and completes at the next drain; the device
        block may be reused immediately."""
        if self.host is None:
            return
        h = self._host_alloc()
        if h is None:
            return                    # host tier pinned full: drop as before
        kvs = self._export_chain(self.cache,
                                 jnp.asarray([node.block], jnp.int32))
        if self.stream is not None:
            self.stream.issue([h], kvs, self._block_bytes)
        else:
            self.host.write_chain([h], jax.device_get(kvs))
        tokens = self.index.node_tokens(node)
        key = tokens.tobytes()
        old = self.host_map.pop(key, None)
        if old is not None:           # stale duplicate: keep the fresh copy
            del self.host_keys[old]
            self.host.free(old)
        self.host_map[key] = h
        self.host_keys[h] = (key, tokens)
        self.host.touch(h)
        if self.shared is not None:
            self.shared.writer[key] = self.owner
        self.prefix_demotions += 1
        self.bytes_moved += self._block_bytes
        self.tel.demote(self._block_bytes, self._raw_bytes_of(1))

    def _publish(self, nodes: List[Any]) -> None:
        """Write-through to a fleet-shared tier: copy freshly indexed
        prompt blocks host-side immediately (not only at eviction time),
        so a prefix prefilled by THIS replica warms every other replica's
        next admission. A prompt block's content is final once the index
        adopts it (decode writes land past the prompt; CoW forks shared
        blocks before any write), so the copy never goes stale. One chain
        export program for all new blocks; async via the stream. No-op on
        a private tier — single-engine behavior is untouched."""
        if self.shared is None or self.host is None or not nodes:
            return
        hblks: List[int] = []
        todo: List[Tuple[bytes, np.ndarray, Any]] = []
        for node in nodes:
            tokens = self.index.node_tokens(node)
            key = tokens.tobytes()
            if key in self.host_map:  # another replica already published it
                continue
            h = self._host_alloc()
            if h is None:
                break                 # tier pinned full: publish what fits
            hblks.append(h)
            todo.append((key, tokens, node))
        if not hblks:
            return
        kvs = self._export_chain(
            self.cache,
            jnp.asarray([n.block for _, _, n in todo], jnp.int32))
        nbytes = len(hblks) * self._block_bytes
        if self.stream is not None:
            self.stream.issue(hblks, kvs, nbytes)
        else:
            self.host.write_chain(hblks, jax.device_get(kvs))
        for h, (key, tokens, _) in zip(hblks, todo):
            self.host_map[key] = h
            self.host_keys[h] = (key, tokens)
            self.host.touch(h)
            self.shared.writer[key] = self.owner
        self.prefix_publishes += len(hblks)
        self.bytes_moved += nbytes
        for _ in hblks:
            self.tel.demote(self._block_bytes, self._raw_bytes_of(1))

    def _promote(self, prompt: np.ndarray, matched: List[int]) -> List[int]:
        """Extend a device radix match with host-tier hits: pop each
        matching host entry, copy it back into a fresh device block, and
        adopt the promoted chain into the device index (so later admissions
        share on-device). Returns the promoted blocks — index-owned, like
        ``PrefixIndex.match`` results.

        On a private tier the hits MOVE (host entry consumed); on a
        fleet-shared tier they COPY — the entry stays in the shared map so
        every other replica can still warm-hit it (it is pinned for the
        duration against LRU eviction by a concurrent ``_host_alloc``)."""
        if self.host is None or not self.host_map:
            return []
        for b in matched:             # pin against demote-eviction below
            self.pool.retain(b)
        P = int(prompt.shape[0])
        move = self.shared is None
        # pop (or pin) every consecutive host hit first, then allocate
        # device blocks in the same order the per-block path did (identical
        # block ids), then move the whole chain in ONE import program
        hits: List[Tuple[bytes, int]] = []     # (key, hblk), chain order
        i = len(matched)
        while (i + 1) * self.bs <= P:
            key = prompt[:(i + 1) * self.bs].tobytes()
            h = self.host_map.get(key)
            if h is None:
                break
            if move:
                del self.host_map[key]
                del self.host_keys[h]
            else:
                self.host.retain(h)   # pin: refs 2 blocks LRU eviction
            hits.append((key, h))
            i += 1
        out: List[int] = []
        for j, (key, h) in enumerate(hits):
            b = self._alloc()
            if b is None:             # device dry: put unplaced entries back
                for key2, h2 in hits[j:]:
                    if move:
                        ntok = len(key2) // prompt.itemsize
                        self.host_map[key2] = h2
                        self.host_keys[h2] = (key2, prompt[:ntok].copy())
                    else:
                        self.host.free(h2)       # just drop the pin
                del hits[j:]
                break
            out.append(b)
        if out:
            self._drain_tier()        # pending demote/publish writes may
                                      # target hits — any replica's stream
            hblks = [h for _, h in hits]
            kvs = host_to_mesh(self.host.read_chain(hblks), self._chain_sh)
            self.cache = self._import_chain(self.cache, kvs,
                                            jnp.asarray(out, jnp.int32))
            for key, h in hits:
                self.host.free(h)     # move: releases; copy: drops the pin
                if not move:
                    self.host.touch(h)
                    if self.shared.writer.get(key, self.owner) != self.owner:
                        self.shared.cross_hits += 1
            self.prefix_promotions += len(out)
            self.bytes_moved += len(out) * self._block_bytes
            for _ in out:
                self.tel.promote(self._block_bytes, self._raw_bytes_of(1))
            self.index.insert(prompt, matched + out,
                              len(matched) + len(out), self.pool)
            for b in out:             # hand ownership to the index
                self.pool.free(b)
        for b in matched:             # drop the pins
            self.pool.free(b)
        return out

    def _match_resident(self, prompt: np.ndarray) -> List[int]:
        """The full resident prefix chain for a prompt: device radix match
        extended by host-tier promotion."""
        matched = self.index.match(prompt)
        return matched + self._promote(prompt, matched)

    def swap_out(self, slot: int) -> Optional[SwapHandle]:
        """Copy the slot's chain into the host tier and release its device
        memory; the returned handle resumes it via ``swap_in`` without
        re-prefill. None when no host tier exists or it is pinned full —
        the engine falls back to recompute-preemption (``swap_fail``).

        The whole chain moves as ONE export program; under async swap the
        device→host copy is issued on the stream (the export is a fresh
        array, so the device blocks are released immediately below) and
        completes at the next drain — host blocks are allocated here
        either way, so refcounts are identical to the synchronous path."""
        if self.host is None:
            return None
        chain = self.chains.get(slot)
        if chain is None:
            return None
        hblks: List[int] = []
        for _ in chain.blocks:
            h = self._host_alloc()
            if h is None:
                for hb in hblks:
                    self.host.free(hb)
                self.swap_fails += 1
                self.tel.swap_fail(slot, len(chain.blocks), "swap_out")
                return None
            hblks.append(h)
        if hblks:
            kvs = self._export_chain(self.cache,
                                     jnp.asarray(chain.blocks, jnp.int32))
            nbytes = len(hblks) * self._block_bytes
            if self.stream is not None:
                self.stream.issue(hblks, kvs, nbytes)
            else:
                self.host.write_chain(hblks, jax.device_get(kvs))
        handle = SwapHandle(
            hblks=hblks, pos=int(self.pos_host[slot]), key=self.keys[slot],
            prompt=self.prompts.get(slot) if self.chunked else None)
        self.swap_out_blocks += len(hblks)
        self.bytes_moved += len(hblks) * self._block_bytes
        self.tel.swap_out(slot, len(hblks), len(hblks) * self._block_bytes,
                          self._raw_bytes_of(len(hblks)))
        self.release(slot)
        return handle

    def drop_swap(self, handle: SwapHandle) -> None:
        """Abandon a swapped-out sequence (its request will recompute):
        release the handle's host-tier blocks so they cannot leak, cancel
        any speculative swap-in copy, and mark the handle unresumable —
        a later ``swap_in`` on it is a caller bug and raises."""
        # drain first: the chain's own swap-out transfer may still be in
        # flight — freeing (and reallocating) its target blocks before the
        # deferred write lands would corrupt the new owner's data
        self._drain_tier()
        if handle.prefetch is not None:
            handle.prefetch = None
            self.prefetch_cancels += 1
            self.tel.prefetch(len(handle.hblks), "cancel")
        for h in handle.hblks:
            self.host.free(h)
        handle.hblks = []
        handle.dropped = True

    def can_swap_in(self, handle: SwapHandle) -> bool:
        """Is there device memory to resume this chain now? (Mirrors
        ``has_room``: +1 headroom for the next demand block, free blocks
        plus what LRU index eviction can reclaim.)"""
        need = min(len(handle.hblks) + 1, self.pool.num_blocks)
        if self.pool.n_free >= need:
            return True
        return need <= self.pool.n_free + self.index.n_evictable(self.pool)

    def prefetch_swap_in(self, handle: SwapHandle) -> bool:
        """Speculatively start the host→device copy for a swapped chain
        (the engine calls this for the resume-head victim while the device
        still executes the current step). The device tree parks on the
        handle; ``swap_in`` consumes it, ``drop_swap`` cancels it. The
        handle keeps its host blocks until then, so nothing here changes
        refcounts — pure data staging, a no-op on the synchronous path."""
        if (self.stream is None or handle.dropped or not handle.hblks
                or handle.prefetch is not None):
            return False
        self._drain_tier()            # its swap-out may be in flight — on a
                                      # shared tier, on ANOTHER replica's
                                      # stream (a disaggregation handoff)
        handle.prefetch = host_to_mesh(self.host.read_chain(handle.hblks),
                                       self._chain_sh)
        self.prefetch_issued += 1
        self.tel.prefetch(len(handle.hblks), "issued")
        return True

    def swap_in(self, slot: int, handle: SwapHandle) -> bool:
        """Restore a swapped-out chain into ``slot``: one host→device
        chain copy into fresh blocks (or the handle's prefetched device
        tree, if the speculative copy was issued), then the slot's table /
        position / sampling-chain row. False = device pool dry (caller
        gates with ``can_swap_in``; emits ``swap_fail``). Raises on a
        handle that ``drop_swap`` already released."""
        if handle.dropped:
            raise RuntimeError(
                "swap_in on a dropped SwapHandle: drop_swap already "
                "released its host blocks (the request must recompute)")
        dblks: List[int] = []
        for _ in handle.hblks:
            b = self._alloc()
            if b is None:
                for db in dblks:
                    self.pool.free(db)
                self.swap_fails += 1
                self.tel.swap_fail(slot, len(handle.hblks), "swap_in")
                return False
            dblks.append(b)
        if dblks:
            kvs = handle.prefetch
            if kvs is not None:
                handle.prefetch = None
                self.prefetch_hits += 1
                self.tel.prefetch(len(dblks), "hit")
            else:
                self._drain_tier()    # its swap-out may be in flight — on a
                                      # shared tier, on the SOURCE replica's
                                      # stream (a disaggregation handoff)
                kvs = host_to_mesh(self.host.read_chain(handle.hblks),
                                   self._chain_sh)
            self.cache = self._import_chain(self.cache, kvs,
                                            jnp.asarray(dblks, jnp.int32))
        for h in handle.hblks:
            self.host.free(h)
        handle.hblks = []
        handle.dropped = True         # consumed: a second resume is a bug
        self.chains[slot] = BlockTable(dblks)
        self.tables_host[slot, :] = self.trash
        self.tables_host[slot, :len(dblks)] = dblks
        self.pos_host[slot] = handle.pos
        self.cache = self._setpos(self.cache, jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(handle.pos, jnp.int32))
        self.keys = self.keys.at[slot].set(handle.key)
        if self.chunked and handle.prompt is not None:
            self.prompts[slot] = handle.prompt
        self.swap_in_blocks += len(dblks)
        self.bytes_moved += len(dblks) * self._block_bytes
        self.tel.swap_in(slot, len(dblks), len(dblks) * self._block_bytes,
                         self._raw_bytes_of(len(dblks)))
        return True

    # -- persistence --------------------------------------------------------

    def _fingerprint(self) -> str:
        """The cache-compatibility key: KV geometry only. NOT covered:
        parameter values — pair a cache file with the checkpoint it was
        built from (docs/serving.md §KV memory hierarchy)."""
        return json.dumps({
            "arch": self.cfg.name, "layers": self.cfg.num_blocks,
            "groups": len(self.cfg.block_pattern),
            "n_kv_heads": self.cfg.n_kv_heads,
            "head_dim": self.cfg.head_dim, "block_size": self.bs,
            "dtype": np.dtype(self.opts.dtype).name,
            "kv_dtype": self.kv_dtype}, sort_keys=True)

    def save(self, path: str) -> int:
        """Persist every prefix block the hierarchy knows — host-tier
        entries plus a lossless export of the device radix index — keyed by
        prompt tokens, fingerprinted by config. Unquantized pools store
        float32 (lossless for f32 and bf16); quantized pools persist the
        compressed bytes plus their f32 scale tables (fp8 rides as a uint8
        bitcast — numpy has no float8 dtype in npz). Returns the number of
        entries written."""
        self._drain_tier()             # pending demote writes must land
        entries = []                   # (tokens, kvs) in LRU-ish order
        seen = set()
        for key, h in self.host_map.items():
            entries.append((self.host_keys[h][1], self.host.read(h)))
            seen.add(key)
        for node in self.index.walk():
            tokens = self.index.node_tokens(node)
            if tokens.tobytes() in seen:
                continue
            kvs = jax.device_get(
                self._export(self.cache, jnp.asarray(node.block, jnp.int32)))
            entries.append((tokens, kvs))
        payload: Dict[str, Any] = {
            "fingerprint": np.array(self._fingerprint()),
            "n": np.int64(len(entries)),
        }
        for i, (tokens, kvs) in enumerate(entries):
            payload[f"tok_{i}"] = tokens
            for g, kv in enumerate(kvs):
                if self.kv_dtype == "bf16":
                    payload[f"k_{i}_{g}"] = np.asarray(kv["k"], np.float32)
                    payload[f"v_{i}_{g}"] = np.asarray(kv["v"], np.float32)
                    continue
                k, v = np.asarray(kv["k"]), np.asarray(kv["v"])
                if self.kv_dtype == "fp8":
                    k, v = k.view(np.uint8), v.view(np.uint8)
                payload[f"k_{i}_{g}"] = k
                payload[f"v_{i}_{g}"] = v
                payload[f"ks_{i}_{g}"] = np.asarray(kv["ks"], np.float32)
                payload[f"vs_{i}_{g}"] = np.asarray(kv["vs"], np.float32)
        with open(path, "wb") as f:
            np.savez(f, **payload)
        return len(entries)

    def restore(self, path: str) -> int:
        """Load persisted prefix blocks into the host tier (they promote to
        device on the first radix hit — no re-prefill). Raises on a config
        fingerprint mismatch; keeps what fits when the tier is smaller than
        the file. Returns the number of entries restored."""
        if self.kv_dtype == "bf16":
            dt = np.dtype(self.opts.dtype)
        else:
            dt = np.dtype(kv_quant.storage_dtype(self.kv_dtype,
                                                 self.opts.dtype))
        with np.load(path) as data:
            fp = str(data["fingerprint"])
            if fp != self._fingerprint():
                raise ValueError(
                    f"prefix cache at {path!r} was saved under a different "
                    f"config: {fp} != {self._fingerprint()}")
            restored = 0
            for i in range(int(data["n"])):
                tokens = data[f"tok_{i}"].astype(np.int32)
                key = tokens.tobytes()
                if key in self.host_map:
                    continue
                # plain alloc, not _host_alloc: evicting earlier-restored
                # entries to admit later ones would churn forever and lie
                # about the count — a full tier genuinely keeps what fits
                h = self.host.alloc()
                if h is None:
                    break              # host tier full: keep what fits
                kvs = []
                for g in range(len(self.cfg.block_pattern)):
                    k, v = data[f"k_{i}_{g}"], data[f"v_{i}_{g}"]
                    if self.kv_dtype == "fp8":
                        k, v = k.view(dt), v.view(dt)
                    kv = {"k": k.astype(dt), "v": v.astype(dt)}
                    if self.kv_dtype != "bf16":
                        kv["ks"] = data[f"ks_{i}_{g}"].astype(np.float32)
                        kv["vs"] = data[f"vs_{i}_{g}"].astype(np.float32)
                    kvs.append(kv)
                kvs = tuple(kvs)
                self.host.write(h, kvs)
                self.host_map[key] = h
                self.host_keys[h] = (key, tokens)
                self.host.touch(h)
                restored += 1
        return restored

    # -- KVBackend ----------------------------------------------------------

    def admit(self, slot: int, prompt: np.ndarray, key: jax.Array):
        P = int(prompt.shape[0])
        n_prompt_blocks = -(-P // self.bs)
        matched = self._match_resident(prompt)
        shared = min(len(matched) * self.bs, P - 1)
        use = -(-shared // self.bs)
        chain = BlockTable()
        for b in matched[:use]:
            self.pool.retain(b)
            chain.append(b)
        for _ in range(use, n_prompt_blocks):
            b = self._alloc()
            if b is None:
                raise RuntimeError("paged admit ran out of KV blocks "
                                   "(has_room gate should prevent this)")
            chain.append(b)
        self.tables_host[slot, :] = self.trash
        self.tables_host[slot, :len(chain)] = chain.blocks
        if shared % self.bs:
            # a full-prefix hit was clipped to P-1: the final prompt token
            # lands inside the last shared block — fork it first
            if not self._cow(slot, chain, shared // self.bs):
                raise RuntimeError("paged admit ran out of KV blocks on "
                                   "CoW fork")

        if shared == 0:
            Sb = P if self.bucket_fn is None else self.bucket_fn(P)
            logits, c1 = self._prefill(self.params, prompt)
            kvs = tuple({"k": g["k"][:, :, :Sb], "v": g["v"][:, :, :Sb]}
                        for g in c1)
            logical = np.arange(Sb)
        else:
            suf = prompt[shared:]
            Ls = P - shared
            Sb = Ls if self.bucket_fn is None else min(self.bucket_fn(Ls),
                                                       self.max_len - shared)
            padded = np.zeros((Sb,), np.int32)
            padded[:Ls] = suf
            pre = self._gather(self.cache,
                               jnp.asarray(self.tables_host[slot]))
            logits, kvs = self._suffix(self.params, jnp.asarray(padded)[None],
                                       pre, jnp.asarray(shared, jnp.int32),
                                       jnp.asarray(Ls, jnp.int32))
            logical = shared + np.arange(Sb)
            self.prefix_shared_tokens += shared

        blks = np.where(logical < P,
                        np.array([chain[p // self.bs] if p < P else 0
                                  for p in logical], np.int32),
                        self.trash).astype(np.int32)
        offs = (logical % self.bs).astype(np.int32)
        self.cache = self._scatter(self.cache, kvs, jnp.asarray(blks),
                                   jnp.asarray(offs),
                                   jnp.asarray(slot, jnp.int32),
                                   jnp.asarray(P, jnp.int32))
        self._publish(self.index.insert(prompt, chain.blocks, P // self.bs,
                                        self.pool))
        self.chains[slot] = chain
        self.pos_host[slot] = P
        first, krow = self._sample(logits, key[None])
        self.keys = self.keys.at[slot].set(krow[0])
        return first

    def decode(self, next_tokens: jax.Array) -> jax.Array:
        tables = jnp.asarray(self.tables_host)
        self.cache, toks, self.keys = self._dec(self.params, self.cache,
                                                next_tokens, self.keys,
                                                tables)
        self.pos_host += self.K
        return toks

    def reserve(self, slot: int, k: int) -> bool:
        """Demand-allocate (and CoW-fork) the blocks the next ``k`` decode
        writes will touch. False = pool dry: the engine preempts a slot."""
        chain = self.chains[slot]
        pos = int(self.pos_host[slot])
        last = min(pos + k - 1, self.nb * self.bs - 1)
        b0, b1 = pos // self.bs, last // self.bs
        while len(chain) <= b1:
            b = self._alloc()
            if b is None:
                return False
            chain.append(b)
            self.tables_host[slot, len(chain) - 1] = b
        for bi in range(b0, min(b1, len(chain) - 1) + 1):
            if not self._cow(slot, chain, bi):
                return False
        return True

    # -- chunked prefill ----------------------------------------------------

    def admit_chunked(self, slot: int, prompt: np.ndarray, key: jax.Array
                      ) -> int:
        """Begin a chunked admission: radix-match the prompt, retain the
        shared prefix blocks (they are resident — an identical system prompt
        prefills once), and seed the sampling chain. Blocks for the rest of
        the prompt are demand-allocated chunk by chunk (``append_chunk``),
        not up front — admission holds only what is actually resident."""
        P = int(prompt.shape[0])
        matched = self._match_resident(prompt)
        shared = min(len(matched) * self.bs, P - 1)
        use = -(-shared // self.bs)
        chain = BlockTable()
        for b in matched[:use]:
            self.pool.retain(b)
            chain.append(b)
        self.tables_host[slot, :] = self.trash
        self.tables_host[slot, :len(chain)] = chain.blocks
        self.chains[slot] = chain
        self.prompts[slot] = np.asarray(prompt, np.int32)
        self.pos_host[slot] = shared
        self.prefix_shared_tokens += shared
        self.keys = self.keys.at[slot].set(key)
        return shared

    def append_chunk(self, slot: int, start: int, tokens: np.ndarray) -> bool:
        """Demand-allocate (and CoW-fork) the blocks the chunk [start,
        start+len) will write, then register every prompt block the chunk
        *completes* in the prefix index — progressively, so an identical
        prompt admitted while this one is still mid-prefill shares the
        blocks already landed. (Admissions in the same step still can't
        share: non-blocking admission has nothing resident yet — the one
        sharing case blocking two-phase admission got for free.)
        False = pool dry: the engine preempts a slot and replans (safe to
        retry — allocation and insertion are idempotent for an unchanged
        chain)."""
        n = int(np.asarray(tokens).shape[0])
        if n == 0:
            return True
        chain = self.chains[slot]
        b0, b1 = start // self.bs, (start + n - 1) // self.bs
        while len(chain) <= b1:
            b = self._alloc()
            if b is None:
                return False
            chain.append(b)
            self.tables_host[slot, len(chain) - 1] = b
        for bi in range(b0, b1 + 1):
            if not self._cow(slot, chain, bi):
                return False
        prompt = self.prompts[slot]
        n_full = min(start + n, int(prompt.shape[0])) // self.bs
        if n_full:
            # publish deferred to the end of serve_step: the chunk that
            # completes these blocks has not been written yet — the insert
            # here runs at plan time, before the program dispatches
            self._pending_publish.extend(
                self.index.insert(prompt, chain.blocks, n_full, self.pool))
        return True

    def serve_step(self, chunk_tokens, clen, start, reset, emit0, dec_mask,
                   dec_tok):
        tables = jnp.asarray(self.tables_host)
        # rows not in decode phase ride the scan against the trash block
        # only: their garbage microsteps can never touch a live block (in
        # particular not a CoW-shared prefix block)
        scan_tables = jnp.asarray(
            np.where(np.asarray(dec_mask)[:, None], self.tables_host,
                     self.trash).astype(np.int32))
        self.cache, t0, seq, self.keys = self._serve(
            self.params, self.cache, jnp.asarray(chunk_tokens),
            jnp.asarray(clen), jnp.asarray(start), jnp.asarray(reset),
            jnp.asarray(emit0), dec_tok, jnp.asarray(dec_mask), self.keys,
            tables, scan_tables)
        self.pos_host[:] = (np.asarray(start, np.int64)
                            + np.asarray(clen, np.int64)
                            + self.K * np.asarray(dec_mask, np.int64))
        if self._pending_publish:
            # the updated cache now carries this step's chunk writes; skip
            # nodes a preemption/eviction replan removed in the meantime
            with self.tel.span("kv.publish"):
                nodes, self._pending_publish = self._pending_publish, []
                self._publish([n for n in nodes
                               if self.index._by_block.get(n.block) is n])
        return t0, seq

    # -- speculative decode -------------------------------------------------

    def verify_step(self, tokens, clen, start, vmask):
        """One draft-widened verify program over the block pools. Host
        positions are NOT advanced here: the engine commits each row via
        ``rollback(slot, start + n_emit)`` once it has the accept counts —
        commit and rejection-truncation are the same host transition."""
        tables = jnp.asarray(self.tables_host)
        self.cache, out, n_emit, self.keys = self._verify(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(clen),
            jnp.asarray(start), jnp.asarray(vmask), self.keys, tables)
        return out, n_emit

    def rollback(self, slot: int, new_len: int) -> None:
        """Truncate ``slot``'s residency to exactly ``new_len`` tokens: free
        whole blocks past the accepted length and rewind the position.

        Every freed block lies wholly beyond ``new_len`` > prompt_len, so it
        can be neither a radix-registered prompt block (the index covers
        full *prompt* blocks only) nor CoW-shared (``reserve`` forked the
        whole draft write span to refcount 1) — ``pool.free`` physically
        returns it. The device side needs no fixup: the verify program
        rewound per-row ``pos`` in-graph, and stale K/V beyond it is
        overwritten before it can ever be attended (position ``new_len``
        is rewritten by the next program; beyond is causally masked)."""
        chain = self.chains[slot]
        keep = -(-new_len // self.bs)
        for b in chain.blocks[keep:]:
            self.pool.free(b)
        if len(chain) > keep:
            self.tables_host[slot, keep:len(chain)] = self.trash
            del chain.blocks[keep:]
        self.pos_host[slot] = new_len

    def release(self, slot: int) -> None:
        for b in self.chains.pop(slot, BlockTable()).blocks:
            self.pool.free(b)
        if self.chunked:
            self.prompts.pop(slot, None)
        self.tables_host[slot, :] = self.trash
        self.pos_host[slot] = 0

    def fits(self, prompt_len: int, max_new: int) -> bool:
        worst_pos = prompt_len + -(-max_new // self.K) * self.K
        need = min(self.nb, -(-worst_pos // self.bs)) + 1
        return need <= self.pool.num_blocks

    def has_room(self, prompt_len: int) -> bool:
        # prompt + CoW fork + first decode block, capped at the pool size so
        # a request that fits() can always be admitted on an idle pool
        need = min(-(-prompt_len // self.bs) + 2, self.pool.num_blocks)
        if self.pool.n_free >= need:
            return True                       # skip the index walk
        return need <= self.pool.n_free + self.index.n_evictable(self.pool)

    def utilization(self) -> dict:
        u = {
            "kv_blocks_total": self.pool.num_blocks,
            "kv_block_size": self.bs,
            "kv_blocks_resident": self.pool.n_resident,
            "kv_blocks_hwm": self.pool.hwm,
            "kv_cow_forks": self.cow_forks,
            "kv_prefix_shared_tokens": self.prefix_shared_tokens,
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_block": self._block_bytes,
        }
        if self.host is not None:
            u.update({
                "kv_host_blocks_total": self.host.num_blocks,
                "kv_host_blocks_resident": self.host.n_resident,
                "kv_host_blocks_hwm": self.host.hwm,
                "kv_swap_out_blocks": self.swap_out_blocks,
                "kv_swap_in_blocks": self.swap_in_blocks,
                "kv_host_bytes_moved": self.bytes_moved,
                # uncompressed-equivalent traffic: every increment is a
                # whole-block multiple, so the ratio recovers the block count
                "kv_host_bytes_moved_raw": (
                    (self.bytes_moved // self._block_bytes)
                    * self._raw_block_bytes if self._block_bytes else 0),
                "kv_prefix_demotions": self.prefix_demotions,
                "kv_prefix_promotions": self.prefix_promotions,
                "kv_prefix_publishes": self.prefix_publishes,
                "kv_host_shared": int(self.shared is not None),
                "kv_swap_fails": self.swap_fails,
                "kv_async_swap": int(self.stream is not None),
                "kv_stream_transfers": self.stream_transfers,
                "kv_prefetch_issued": self.prefetch_issued,
                "kv_prefetch_hits": self.prefetch_hits,
                "kv_prefetch_cancels": self.prefetch_cancels,
            })
        return u

    def reset_counters(self) -> None:
        self.cow_forks = 0
        self.prefix_shared_tokens = 0
        self.pool.hwm = self.pool.n_resident
        self.swap_out_blocks = 0
        self.swap_in_blocks = 0
        self.bytes_moved = 0
        self.prefix_demotions = 0
        self.prefix_promotions = 0
        self.prefix_publishes = 0
        self.swap_fails = 0
        self.stream_transfers = 0
        self.prefetch_issued = 0
        self.prefetch_hits = 0
        self.prefetch_cancels = 0
        if self.host is not None:
            self.host.hwm = self.host.n_resident

    def drop_prefix_cache(self) -> int:
        """Evict every index-only block (e.g. to shed warmup residue before
        a timed run). Returns how many blocks were freed."""
        freed = self.index.evict(self.pool, self.pool.num_blocks)
        self.pool.hwm = self.pool.n_resident
        return freed
