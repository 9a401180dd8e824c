"""Paged KV cache: block allocator, prefix reuse, paged attention.

Fleet-scale serving memory management (ROADMAP item 1).  The reference's
serving stack sizes one contiguous KV region per batch slot
(fused_multi_transformer's cache_kv tensors) — at `max_len` granularity
every admitted request pays for its worst case, and two requests sharing
a 2-kilotoken system prompt each prefill and store it twice.  This module
rebuilds the memory path vLLM-style around fixed-size **token blocks**:

* :class:`BlockAllocator` — host-side refcounted free list over a pool of
  physical blocks.  Allocation/free is O(1); refcounts make a physical
  block shareable by many sequences (prefix reuse, fork).
* :class:`SequenceBlocks` — one sequence's logical→physical block list.
  ``fork()`` is O(blocks) refcount bumps (no data movement);
  ``ensure_writable()`` implements **copy-on-write**: the first divergent
  write to a shared block allocates a private copy, so a fork never
  observes its sibling's later writes.
* :class:`PrefixCache` — a trie over *full* blocks keyed by the token ids
  they hold (chain-keyed: a node's identity is its whole prefix, so equal
  system prompts map to equal nodes).  A matched prefix hands the new
  request refcounted references to the already-filled physical blocks —
  repeated prefixes prefill **once**.  The cache holds its own reference
  on every registered block and evicts LRU leaves when the allocator runs
  dry.
* :class:`PagedKVPool` — the device-side pools, one
  ``[num_blocks, block_size, kv_heads, head_dim]`` pair (k, v) per layer.
  Physical block ids are shared across the layers of a group: one
  logical allocation covers a token's KV in every layer of it.  A model
  whose layers all see the whole context has one group; one with
  sliding-window layers has two (below).
* :func:`paged_cache_attention` — the decode/prefill attention path over
  the pools: writes land through the block table
  (``pool[bt[pos//bs], pos%bs] = kv``), reads gather the table back into
  logical order.  Routes to the Pallas paged-decode kernel when eligible
  (``ops/pallas/paged_attention.py``), ``jnp.take``-style gather
  fallback elsewhere.  Numerics match ``static_cache_attention`` exactly:
  the gather preserves values bitwise and the extra masked positions
  contribute exact zeros, so greedy decode is token-for-token identical
  to ``generation.generate`` over a ``StaticCache``.

This is the serving engine's only KV cache (``inference/serving.py``).

A **latent** pool (``PagedKVPool(..., latent=True)``) is the same pool
for a layer whose attention caches one compressed row a token instead of
per-head keys and values (latent attention, ``models/latent_attention.py``):
one ``[num_blocks, block_size, width]`` array a layer — the row
``[c | k_r]`` padded to whole lanes — and no V pool.  Block ids, the
allocator, copy-on-write, export / import and the prefix cache work on
blocks and do not know the difference; :func:`latent_cache_attention` is
its attention path.  A latent is not quantised to int8 (refused).

**Two block groups.**  A layer whose attention sees only the last
``window`` positions needs ``window`` of them cached, not the request:
under one table a 32k-token request would hold 32k positions in every
layer, 45 of 60 of them for nothing.  For a model with such layers the
engine builds a *full* group (the layers that see everything:
``num_kv_blocks`` ids, ``ceil(total / block)`` of them a request) and a
*window* group (its own ids, allocator and per-slot table): a request
reserves a **ring** of ``R = min(ceil(total / block), ceil((window +
longest write of one dispatch) / block) + 1)`` window blocks at
admission, and its window table names them over and over (``table[lb] =
ring[lb % R]``), so writes go through the table as everywhere
(``_write_targets``) and a block is overwritten only once every key in
it is older than any later query's window — which is why the readers
(the decode kernel, :func:`paged_cache_attention`'s walk and its gather)
take the ``window`` and mask below it.  Nothing is paged per layer
inside a group.  What moves blocks by id (prefix sharing, copy-on-write,
park / resume, handoff, the tier, speculative verify's roll-back) knows
one group and is refused for such a model.

A second kind of state lives beside it (:class:`SlotStatePool`): a layer
with recurrent state (a Mamba-2 mixer's convolution tail and SSM state)
keeps a fixed-size state **per slot**, not per token.  The engine builds
block pools for the layers that attend and slot state for the layers
that recur; a layer's cache is a :class:`PagedCache` or a
:class:`SlotState`, and :class:`StepInfo` tells every layer which rows
of a dispatch are real.  Slot state is not paged, shared, exported or
tiered: prefix sharing, speculative verify, park / resume and handoff
carry KV blocks only, and the engine refuses them for such a model.
"""

from __future__ import annotations

import os
from collections import OrderedDict, deque
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, \
    Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["BlockAllocator", "SequenceBlocks", "PrefixCache",
           "PagedKVPool", "PagedCache", "paged_cache_attention",
           "latent_cache_attention", "query_positions",
           "SlotStatePool", "SlotState", "StepInfo",
           "quant_kv_mode", "serialize_handoff",
           "deserialize_handoff"]


def quant_kv_mode(explicit: Optional[str] = None) -> Optional[str]:
    """The ``PADDLE_TPU_QUANT_KV`` knob (explicit ctor value wins):
    ``"int8"`` stores the paged K/V pools as int8 with per-block fp32
    scale arrays — at fixed pool HBM bytes that is 2x the blocks of a
    bf16 pool (4x vs fp32), directly raising ``kv_blocks_total`` and
    concurrent sessions.  None/unset/0 keeps the fp pools exactly as
    before."""
    raw = explicit if explicit is not None \
        else os.environ.get("PADDLE_TPU_QUANT_KV")
    if raw is None:
        return None
    raw = str(raw).strip().lower()
    if raw in ("", "0", "off", "none", "false"):
        return None
    if raw != "int8":
        raise ValueError(
            f"PADDLE_TPU_QUANT_KV={raw!r}: only int8 is supported "
            "(or unset/0 for fp pools)")
    return raw


def _quantize_kv(x):
    """Symmetric int8 quantization of a K/V tensor along head_dim: one
    fp32 scale per (token, kv-head) row.  The scales live in
    block-shaped ``[num_blocks, block_size, kv_heads]`` arrays so they
    scatter/gather/export by the SAME physical block ids as the data —
    'per-block scales' that ride every handoff."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127,
                 127).astype(jnp.int8)
    return q, scale


# -- host-side block bookkeeping ---------------------------------------------

class BlockAllocator:
    """Refcounted free list over ``num_blocks`` physical blocks.

    Block 0 is reserved as the **scratch block**: inactive batch rows and
    out-of-range padded writes are routed there by construction, so it is
    never handed out.  ``free()`` is a decref — the block returns to the
    free list only when the last holder lets go; freeing an unreferenced
    block raises (the double-free invariant the chaos tests drill).
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        if num_blocks <= reserved:
            raise ValueError(f"num_blocks {num_blocks} must exceed the "
                             f"{reserved} reserved scratch block(s)")
        self.num_blocks = num_blocks
        self.reserved = reserved
        self._free: deque = deque(range(reserved, num_blocks))
        self._ref = np.zeros((num_blocks,), np.int64)

    def alloc(self) -> Optional[int]:
        """One block with refcount 1, or None when exhausted (callers
        shed load / evict; exhaustion is a normal serving condition,
        not an error)."""
        if not self._free:
            return None
        bid = self._free.popleft()
        self._ref[bid] = 1
        return bid

    def ref(self, bid: int):
        if self._ref[bid] <= 0:
            raise RuntimeError(f"ref of unallocated block {bid}")
        self._ref[bid] += 1

    def refcount(self, bid: int) -> int:
        return int(self._ref[bid])

    def free(self, bid: int) -> bool:
        """Decref; True when the block actually returned to the free
        list.  Freeing a block with refcount 0 is a double free."""
        if bid < self.reserved:
            raise RuntimeError(f"free of reserved scratch block {bid}")
        if self._ref[bid] <= 0:
            raise RuntimeError(f"double free of block {bid}")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)
            return True
        return False

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - self.reserved - len(self._free)


class SequenceBlocks:
    """One sequence's logical block list over a shared allocator.

    Blocks arrive either fresh (``ensure_capacity``) or shared
    (``adopt_shared`` from the prefix cache, ``fork`` from a sibling).
    Writes must go through :meth:`ensure_writable` first: a shared block
    is copied to a private one (COW) before the caller may touch it.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self._alloc = allocator
        self.block_size = block_size
        self.bids: List[int] = []

    @property
    def capacity(self) -> int:
        return len(self.bids) * self.block_size

    def adopt_shared(self, bids: Sequence[int]):
        """Append already-allocated blocks, taking a reference on each
        (prefix-cache hit: the physical blocks stay owned by the cache
        too)."""
        for b in bids:
            self._alloc.ref(b)
            self.bids.append(b)

    def ensure_capacity(self, tokens: int) -> bool:
        """Grow to >= `tokens` capacity.  All-or-nothing: on exhaustion
        nothing is allocated and False returns (the caller sheds load)."""
        need = -(-tokens // self.block_size) - len(self.bids)
        if need <= 0:
            return True
        if self._alloc.free_blocks < need:
            return False
        for _ in range(need):
            self.bids.append(self._alloc.alloc())
        return True

    def fork(self) -> "SequenceBlocks":
        """Share every block with a child (refcount bump, zero copies).
        Either side's next write triggers COW via ensure_writable."""
        child = SequenceBlocks(self._alloc, self.block_size)
        child.adopt_shared(self.bids)
        return child

    def ensure_writable(self, idx: int,
                        copier: Optional[Callable[[int, int], None]]
                        = None) -> Optional[Tuple[int, int]]:
        """Copy-on-write: if logical block `idx` is shared, allocate a
        private block, run `copier(src, dst)` (device block copy) and
        swap it in.  Returns (src, dst) when a copy happened, None when
        the block was already private.  Exhaustion here raises rather
        than shedding — the caller has already committed writes to this
        sequence, so sizing must reserve COW headroom (the engine
        allocates private decode blocks up front; its steady state
        never COWs)."""
        bid = self.bids[idx]
        if self._alloc.refcount(bid) == 1:
            return None
        new = self._alloc.alloc()
        if new is None:
            raise RuntimeError(
                "allocator exhausted during copy-on-write — size the pool "
                "with COW headroom or evict before writing")
        if copier is not None:
            copier(bid, new)
        self.bids[idx] = new
        self._alloc.free(bid)
        return (bid, new)

    def release(self):
        """Drop every reference (retirement).  Shared blocks survive in
        their other holders (prefix cache, forks)."""
        for b in self.bids:
            self._alloc.free(b)
        self.bids.clear()


class _TrieNode:
    __slots__ = ("key", "bid", "children", "parent")

    def __init__(self, key, bid, parent):
        self.key = key          # tuple of this block's token ids
        self.bid = bid
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.parent: Optional["_TrieNode"] = parent


class PrefixCache:
    """Trie over full blocks of token ids → physical block ids.

    A node's position in the trie encodes its whole prefix, so the
    lookup key is effectively a chain hash of token-id blocks: two
    requests share a physical block iff their prompts agree on every
    token up to and including that block.  The cache owns one reference
    per registered block; :meth:`evict` releases LRU leaves whose only
    remaining holder is the cache (refcount 1), freeing real memory
    without touching blocks any live sequence still reads.
    """

    def __init__(self, block_size: int, allocator: BlockAllocator,
                 on_evict=None):
        self.block_size = block_size
        self._alloc = allocator
        self._root = _TrieNode((), -1, None)
        # LRU over nodes: key id(node) → node, most-recently-used last
        self._lru: "OrderedDict[int, _TrieNode]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # demote-before-free: called with the victim node (its token
        # chain is reachable by walking .parent) right before the block
        # is freed, so a KV tier manager can spill it to host RAM
        self.on_evict = on_evict

    def __len__(self):
        return len(self._lru)

    def _touch(self, node: _TrieNode):
        self._lru.move_to_end(id(node))

    @staticmethod
    def node_tokens(node: _TrieNode) -> List[int]:
        """Full token chain (root → node) for a trie node — the lookup
        key a demoted block must be refiled under in a lower tier."""
        chunks = []
        while node is not None and node.key:
            chunks.append(node.key)
            node = node.parent
        out: List[int] = []
        for key in reversed(chunks):
            out.extend(int(t) for t in key)
        return out

    def match(self, tokens: np.ndarray) -> List[int]:
        """Physical block ids covering the longest cached full-block
        prefix of `tokens` (possibly empty).  Counts one hit (>=1 block)
        or miss per lookup and refreshes LRU recency along the path."""
        bs = self.block_size
        node, bids = self._root, []
        for i in range(len(tokens) // bs):
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                break
            bids.append(child.bid)
            self._touch(child)
            node = child
        if bids:
            self.hits += 1
        else:
            self.misses += 1
        return bids

    def register(self, tokens: np.ndarray, bids: Sequence[int],
                 limit_tokens: Optional[int] = None) -> int:
        """Insert every full block of `tokens` (bounded by
        `limit_tokens`, e.g. the prompt length — generated tokens are
        per-request and would pollute the shared trie).  The cache takes
        its own reference on newly inserted blocks; blocks whose content
        is already cached are left to their current physical id (dedupe
        — the caller keeps its possibly-different copy).  Returns the
        number of newly registered blocks."""
        bs = self.block_size
        n = len(tokens) if limit_tokens is None else min(limit_tokens,
                                                        len(tokens))
        node, new = self._root, 0
        for i in range(n // bs):
            if i >= len(bids):
                break
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(key, int(bids[i]), node)
                self._alloc.ref(child.bid)
                node.children[key] = child
                self._lru[id(child)] = child
                new += 1
            self._touch(child)
            node = child
        return new

    def evict(self, n_blocks: int = 1) -> int:
        """Release up to `n_blocks` LRU **leaf** blocks whose refcount is
        1 (cache-only — nothing live reads them).  Returns blocks
        actually freed."""
        freed = 0
        # repeated sweeps: freeing a leaf may expose its parent
        while freed < n_blocks:
            victim = None
            for node in self._lru.values():           # oldest first
                if not node.children and \
                        self._alloc.refcount(node.bid) == 1:
                    victim = node
                    break
            if victim is None:
                break
            if self.on_evict is not None:
                try:
                    self.on_evict(victim)
                except Exception:  # noqa: BLE001 — demotion is
                    # best-effort; eviction must free memory regardless
                    pass
            self._alloc.free(victim.bid)
            victim.parent.children.pop(victim.key, None)
            del self._lru[id(victim)]
            self.evictions += 1
            freed += 1
        return freed

    def clear(self):
        """Drop every cached block (engine error-recovery path)."""
        for node in list(self._lru.values()):
            if self._alloc.refcount(node.bid) > 0:
                self._alloc.free(node.bid)
        self._lru.clear()
        self._root = _TrieNode((), -1, None)


# -- device-side pools -------------------------------------------------------

class PagedKVPool:
    """Per-layer ``[num_blocks, block_size, kv_heads, head_dim]`` k/v
    pools.  One physical block id addresses the same slice in every
    layer of its group, so host bookkeeping is per-token-block, not
    per-layer.

    There is one group unless ``window_layers`` names layers (indices
    among this pool's) of a second, the **window group**: layers whose
    attention sees a sliding window keep ``window_blocks`` blocks of
    their own, handed out by an allocator of their own, because a
    request holds a ring of them and not a block a 16 tokens of its
    length.  The arrays stay one list in layer order (the programs take
    and return them as they always have); what copies, exports or
    imports a block by one id — copy-on-write, handoff, the tier —
    addresses one group and is refused for a pool with two.

    ``quant="int8"`` stores the pools as int8 plus per-layer
    ``[num_blocks, block_size, kv_heads]`` fp32 scale arrays (one scale
    per token row per kv head, block-shaped so scales follow the same
    block ids through COW copies, exports and handoffs).  Quantization
    is fused into the block scatter and dequantization into the
    attention read (``paged_cache_attention`` / the Pallas paged-decode
    kernel's block loads) — the fp K/V never exist pool-shaped."""

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 kv_heads: int, head_dim: int, dtype,
                 quant: Optional[str] = None, latent: bool = False,
                 window_layers: Sequence[int] = (), window_blocks: int = 0):
        if quant not in (None, "int8"):
            raise ValueError(f"PagedKVPool quant={quant!r}: only int8")
        if latent and quant:
            raise ValueError(
                "PagedKVPool latent=True with quant='int8': a latent row "
                "is expanded into every head's keys and values, so one "
                "scale a token would carry its rounding into all of them; "
                "no per-head scale exists to quantise against")
        if latent and kv_heads != 1:
            raise ValueError(f"a latent pool holds one row a token, not "
                             f"{kv_heads} kv heads")
        self.window_layers = tuple(sorted(window_layers))
        if self.window_layers and (latent or quant or window_blocks < 2):
            raise ValueError(
                "a window group holds keys and values as computed "
                "(neither latent nor int8) in window_blocks >= 2 blocks "
                "of its own")
        self.num_blocks = num_blocks
        self.window_blocks = int(window_blocks)
        self.block_size = block_size
        self.quant = quant
        self.latent = bool(latent)
        self.compute_dtype = dtype
        store = jnp.int8 if quant == "int8" else dtype
        self._store = store
        if latent:
            # one array a layer, no V pool; a row ``[c | k_r]`` of
            # ``head_dim`` values is stored padded to whole lanes: the
            # TPU's tiled HBM layout holds a 576-wide row as 640 whatever
            # the array's shape says (Mosaic's memref of a
            # [blocks, 16, 576] bf16 pool reads 16384x16x640, and a DMA
            # of 576 of them is refused), so the pool says 640 and
            # ``nbytes`` counts what is held
            self.row_width = int(head_dim)
            shape = (num_blocks, block_size, -(-head_dim // 128) * 128)
        else:
            shape = (num_blocks, block_size, kv_heads, head_dim)
        # a layer's pool shape: its group's blocks of the common block
        self._shapes = [
            ((self.window_blocks,) + shape[1:])
            if i in self.window_layers else shape
            for i in range(num_layers)]
        self.vpools = [] if latent else \
            [jnp.zeros(sh, store) for sh in self._shapes]
        self.kpools = [jnp.zeros(sh, store) for sh in self._shapes]
        if quant:
            sshape = (num_blocks, block_size, kv_heads)
            self.kscales = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
            self.vscales = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
        else:
            self.kscales, self.vscales = [], []
        self._copy = jax.jit(
            lambda pool, src, dst: pool.at[dst].set(pool[src]),
            donate_argnums=(0,))
        # block export/import (cross-replica KV handoff): one compiled
        # gather / scatter covers every layer's k AND v pool (and the
        # scale arrays, when quantized), so a prefill->decode transfer
        # costs two device dispatches, not 4 * num_layers
        self._gather = jax.jit(lambda pools, idx: [p[idx] for p in pools])
        self._scatter = jax.jit(
            lambda pools, idx, vals: [p.at[idx].set(v.astype(p.dtype))
                                      for p, v in zip(pools, vals)],
            donate_argnums=(0,))
        self.cow_copies = 0

    @property
    def nbytes(self) -> int:
        """Device bytes held by the pools: K/V payload + scale arrays
        (the ``paddle_tpu_serving_kv_pool_bytes`` gauge)."""
        return sum(int(p.nbytes) for p in
                   self.kpools + self.vpools + self.kscales + self.vscales)

    def _all_pools(self):
        return self.kpools + self.vpools + self.kscales + self.vscales

    def _one_group(self, what: str):
        if self.window_layers:
            raise RuntimeError(
                f"{what}: this pool has a window group beside the full "
                f"one, and a block id names a block of one group only")

    def copy_block(self, src: int, dst: int):
        """Device-side COW body: duplicate physical block `src` into
        `dst` across every layer's k and v pool (scales included when
        quantized — a copied block keeps its dequant factors)."""
        self._one_group("copy_block")
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        self.kpools = [self._copy(p, s, d) for p in self.kpools]
        self.vpools = [self._copy(p, s, d) for p in self.vpools]
        if self.quant:
            self.kscales = [self._copy(p, s, d) for p in self.kscales]
            self.vscales = [self._copy(p, s, d) for p in self.vscales]
        self.cow_copies += 1

    def reset(self):
        n = len(self.kpools)
        self.kpools = [jnp.zeros(sh, self._store) for sh in self._shapes]
        self.vpools = [jnp.zeros(sh, self._store)
                       for sh in self._shapes[:len(self.vpools)]]
        if self.quant:
            sshape = self.kscales[0].shape
            self.kscales = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(n)]
            self.vscales = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(n)]

    # -- cross-replica block transfer (prefill/decode disaggregation) --------
    @staticmethod
    def _bucket(n: int) -> int:
        """Transfer shapes are padded to powers of two so the gather/
        scatter executables see a handful of shapes, not one per prompt
        length (a shape-fresh transfer would pay an XLA compile INSIDE
        the handoff)."""
        return 1 << max(0, n - 1).bit_length()

    def export_blocks(self, bids: Sequence[int]) -> dict:
        """Read physical blocks `bids` out of every layer's k/v pool as
        host arrays — the payload side of a prefill→decode KV handoff.
        Layout: ``{"block_size", "dtype", "k": [L x [n, bs, kvh, hd]],
        "v": [...]}`` plus ``"k_scale"/"v_scale"`` (``[n, bs, kvh]``
        fp32 per layer) when the pool is quantized — a quantized
        handoff ships the int8 payload + scales on the wire (half the
        bf16 bytes).  Blocks are ordered as `bids` (logical order for a
        sequence's prompt).  Pure read: the pools are untouched.  The
        device gather runs at the padded bucket size (pad ids = scratch
        block 0), but the returned arrays are trimmed to the real count
        so the wire payload carries no padding."""
        self._one_group("export_blocks")
        bids = list(bids)
        n = len(bids)
        idx = jnp.asarray(bids + [0] * (self._bucket(n) - n), jnp.int32)
        outs = self._gather(self._all_pools(), idx)
        L = len(self.kpools)
        # a latent pool has no V pool: ``"v"`` is the empty list, on the
        # wire too (``serialize_handoff`` writes the arrays there are)
        payload = {"block_size": int(self.block_size),
                   "dtype": str(jnp.dtype(self.kpools[0].dtype)),
                   "k": [np.asarray(o)[:n] for o in outs[:L]],
                   "v": [np.asarray(o)[:n]
                         for o in outs[L:L + len(self.vpools)]]}
        if self.quant:
            payload["k_scale"] = [np.asarray(o)[:n]
                                  for o in outs[2 * L:3 * L]]
            payload["v_scale"] = [np.asarray(o)[:n]
                                  for o in outs[3 * L:]]
        return payload

    def import_blocks(self, payload: dict, dst_bids: Sequence[int],
                      src_start: int = 0):
        """Scatter exported blocks into this pool at physical ids
        `dst_bids` (the receiving replica's own allocation), starting at
        logical block `src_start` of the payload — a receiver whose
        prefix cache already holds the leading blocks imports only the
        tail.  Pad writes land in the scratch block (never observable).
        Raises on geometry mismatch (block size / kv heads / head dim /
        layer count must agree across the fleet).

        Mixed-precision fleets convert at the boundary: an fp payload
        into a quantized pool is quantized on import (same rowwise
        scheme as the write path), a quantized payload into an fp pool
        is dequantized via its shipped scales.  A quantized payload
        WITHOUT scales is rejected loudly — a wire format that lost its
        scales can only produce garbage KV."""
        self._one_group("import_blocks")
        dst_bids = list(dst_bids)
        if not dst_bids:
            return
        L = len(self.kpools)
        if len(payload["k"]) != L or len(payload["v"]) != len(self.vpools):
            raise ValueError(
                f"handoff payload has {len(payload['k'])}/"
                f"{len(payload['v'])} k/v layers, pool has {L}/"
                f"{len(self.vpools)}")
        want = self.kpools[0].shape[1:]
        got = tuple(payload["k"][0].shape[1:])
        if got != want:
            raise ValueError(
                f"handoff block geometry {got} != pool {want} "
                "(block_size / kv_heads / head_dim must match)")
        if src_start + len(dst_bids) > payload["k"][0].shape[0]:
            raise ValueError(
                f"import of {len(dst_bids)} blocks from offset "
                f"{src_start} exceeds payload of "
                f"{payload['k'][0].shape[0]} blocks")
        src_quant = payload["k"][0].dtype == np.int8
        if src_quant and ("k_scale" not in payload
                          or "v_scale" not in payload):
            raise ValueError(
                "quantized handoff payload carries no k_scale/v_scale "
                "— refusing to import scaleless int8 KV")
        n = len(dst_bids)
        pad = self._bucket(n) - n
        sel = slice(src_start, src_start + n)
        idx = jnp.asarray(dst_bids + [0] * pad, jnp.int32)

        def prep(a):
            a = np.ascontiguousarray(a[sel])
            if pad:
                a = np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            return jnp.asarray(a)

        ks = [payload[f] for f in ("k", "v")]
        kdata, vdata = ks
        kscale = payload.get("k_scale")
        vscale = payload.get("v_scale")
        if src_quant and not self.quant:
            # dequantize at the boundary: fp pool receives fp values
            kdata = [np.asarray(d, np.float32)
                     * np.asarray(s, np.float32)[..., None]
                     for d, s in zip(kdata, kscale)]
            vdata = [np.asarray(d, np.float32)
                     * np.asarray(s, np.float32)[..., None]
                     for d, s in zip(vdata, vscale)]
            kscale = vscale = None
        elif not src_quant and self.quant:
            # quantize at the boundary: same rowwise scheme as the
            # fused write-path quantization
            def q(arrs):
                outs, scales = [], []
                for a in arrs:
                    qa, sa = _quantize_kv(jnp.asarray(
                        np.asarray(a, np.float32)))
                    outs.append(np.asarray(qa))
                    scales.append(np.asarray(sa))
                return outs, scales
            kdata, kscale = q(kdata)
            vdata, vscale = q(vdata)
        vals = [prep(a) for a in list(kdata) + list(vdata)]
        pools = list(self.kpools) + list(self.vpools)
        if self.quant:
            sw = self.kscales[0].shape[1:]
            sg = tuple(np.asarray(kscale[0]).shape[1:])
            if sg != sw:
                raise ValueError(
                    f"handoff scale geometry {sg} != pool {sw}")
            vals += [prep(a) for a in list(kscale) + list(vscale)]
            pools += list(self.kscales) + list(self.vscales)
        out = self._scatter(pools, idx, vals)
        self.kpools, self.vpools = out[:L], out[L:L + len(self.vpools)]
        if self.quant:
            self.kscales = out[2 * L:3 * L]
            self.vscales = out[3 * L:]

    def warm_transfer(self, max_blocks: int):
        """Compile the export/import executables for every pow-2 bucket
        up to `max_blocks` (pad target = scratch block, so the dummy
        import is invisible) — keeps XLA compiles out of the first real
        handoff's latency.  Nothing to warm with two groups: no block
        is handed off."""
        if self.window_layers:
            return
        b = 1
        while b <= max(1, max_blocks):
            payload = self.export_blocks([0] * b)
            self.import_blocks(payload, [0] * b)
            b *= 2


# -- handoff wire format -----------------------------------------------------

def _dtype_of(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bfloat16 & friends (jax's extended dtypes)
        return np.dtype(getattr(ml_dtypes, name))


def serialize_handoff(payload: dict) -> bytes:
    """Flatten a handoff payload (scalars + numpy arrays + the nested
    ``kv`` block export) into one length-prefixed bytes blob that rides
    any byte transport — the TCPStore for a multi-process fleet, shared
    memory in-process.  Arrays are raw little-endian buffers with dtype
    recorded by name (bfloat16 survives; no pickle anywhere).

    Wire format v2: quantized KV exports additionally carry per-layer
    ``kv.ks<i>/kv.vs<i>`` scale arrays and a ``kv_dtype`` scalar, so a
    fleet prefill→decode handoff stays int8 on the wire (half the bf16
    payload bytes).  v1 readers never see these keys on fp payloads;
    this reader accepts both."""
    import json as _json
    meta: dict = {"version": 2, "scalars": {}, "arrays": []}
    chunks: List[bytes] = []

    def add_array(name, a):
        a = np.ascontiguousarray(a)
        meta["arrays"].append({"name": name, "dtype": str(a.dtype),
                               "shape": list(a.shape)})
        chunks.append(a.tobytes())

    for key, val in payload.items():
        if key == "kv":
            meta["scalars"]["kv_block_size"] = int(val["block_size"])
            meta["kv_layers"] = len(val["k"])
            if "dtype" in val:
                meta["scalars"]["kv_dtype"] = str(val["dtype"])
            for i, a in enumerate(val["k"]):
                add_array(f"kv.k{i}", a)
            for i, a in enumerate(val["v"]):
                add_array(f"kv.v{i}", a)
            for i, a in enumerate(val.get("k_scale") or ()):
                add_array(f"kv.ks{i}", a)
            for i, a in enumerate(val.get("v_scale") or ()):
                add_array(f"kv.vs{i}", a)
        elif isinstance(val, np.ndarray):
            add_array(key, val)
        else:
            meta["scalars"][key] = val
    head = _json.dumps(meta).encode()
    return len(head).to_bytes(8, "big") + head + b"".join(chunks)


def deserialize_handoff(data) -> dict:
    """Inverse of :func:`serialize_handoff` (v1 and v2 payloads).
    Accepts any bytes-like (bytes, bytearray, memoryview): arrays are
    zero-copy views into the buffer — a bulk consumer (peer-snapshot
    restore) decodes tens of MB without re-copying it."""
    import json as _json
    mv = memoryview(data)
    hlen = int.from_bytes(mv[:8], "big")
    meta = _json.loads(bytes(mv[8:8 + hlen]).decode())
    off = 8 + hlen
    arrays: Dict[str, np.ndarray] = {}
    for ent in meta["arrays"]:
        dt = _dtype_of(ent["dtype"])
        n = int(np.prod(ent["shape"], dtype=np.int64)) * dt.itemsize
        arrays[ent["name"]] = np.frombuffer(
            mv[off:off + n], dtype=dt).reshape(ent["shape"])
        off += n
    out: dict = {k: v for k, v in meta["scalars"].items()
                 if k not in ("kv_block_size", "kv_dtype")}
    for name, a in arrays.items():
        if not name.startswith("kv."):
            out[name] = a
    L = meta.get("kv_layers", 0)
    if L:
        out["kv"] = {
            "block_size": int(meta["scalars"]["kv_block_size"]),
            "k": [arrays[f"kv.k{i}"] for i in range(L)],
            # a latent pool's export has no V arrays
            "v": [arrays[f"kv.v{i}"] for i in range(L)
                  if f"kv.v{i}" in arrays],
        }
        if "kv_dtype" in meta["scalars"]:
            out["kv"]["dtype"] = meta["scalars"]["kv_dtype"]
        if f"kv.ks{0}" in arrays:
            out["kv"]["k_scale"] = [arrays[f"kv.ks{i}"]
                                    for i in range(L)]
            out["kv"]["v_scale"] = [arrays[f"kv.vs{i}"]
                                    for i in range(L)]
    return out


def publish_handoff(store, key: str, payload: dict):
    """Ship a serialized handoff through a TCPStore-contract store —
    the multi-process fleet transport (the router's in-process path
    hands the payload over directly)."""
    store.set(key, serialize_handoff(payload))


def fetch_handoff(store, key: str) -> Optional[dict]:
    """Read a handoff published by :func:`publish_handoff`; None when
    the key is absent."""
    if not store.check(key):
        return None
    return deserialize_handoff(store.get(key, wait=False))


# -- the paged attention path ------------------------------------------------

class SlotState(NamedTuple):
    """One recurrent layer's state for every slot: ``conv``
    ``[slots, d_conv - 1, conv_dim]`` (the convolution's tail, in the
    model's type) and ``ssm`` ``[slots, heads, head_dim, d_state]``
    (float32).  Which rows a dispatch touches is in :class:`StepInfo`."""
    conv: object
    ssm: object


class StepInfo(NamedTuple):
    """What a dispatch tells every layer, passed as one more entry after
    the layers' caches: ``valid`` ``[B]`` int32, how many of a row's
    positions are real (a decode row: 1 or 0; a prefill chunk: the
    tokens before its padded tail) — a recurrent layer leaves the state
    of the rest untouched and an expert layer routes them nowhere;
    ``slot``, the one slot a ``B == 1`` prefill chunk belongs to (None:
    row ``b`` is slot ``b``); ``moe_counts`` int32 ``[3]``, summed over
    the expert layers as the forward passes them: held experts with at
    least one row, picks that landed on a held expert, picks made."""
    valid: object
    slot: object = None
    moe_counts: object = None


class SlotStatePool:
    """Per-slot recurrent state, one :class:`SlotState` a recurrent
    layer, beside the block pools.  ``shapes`` is the model's
    ``slot_state_shapes()``: ``[(conv_shape, ssm_shape)]`` a layer,
    without the slot axis.  The engine donates ``layers`` through its
    programs as it does the block pools, zeroes a slot at admission
    (:meth:`reset_slot`: one dispatch for every layer) and rebuilds
    everything in ``_recover`` (:meth:`reset`)."""

    def __init__(self, slots: int, shapes, dtype):
        self.slots = int(slots)
        self._shapes = [(tuple(c), tuple(s)) for c, s in shapes]
        self._dtype = dtype
        self.reset()
        self._zero = jax.jit(
            lambda layers, slot: jax.tree.map(
                lambda a: a.at[slot].set(0), layers),
            donate_argnums=(0,))

    def reset(self):
        self.layers = [
            SlotState(jnp.zeros((self.slots,) + c, self._dtype),
                      jnp.zeros((self.slots,) + s, jnp.float32))
            for c, s in self._shapes]

    def zeros_like(self):
        """Zero-filled copies to compile against (``aot_warmup``)."""
        return jax.tree.map(jnp.zeros_like, self.layers)

    def reset_slot(self, slot: int):
        self.layers = self._zero(self.layers, jnp.asarray(slot, jnp.int32))

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for st in self.layers for a in st)


class PagedCache(NamedTuple):
    """One layer's paged KV view: the physical pools plus this batch's
    block table ``[B, max_blocks]`` (logical block index → physical
    block id; unallocated entries point at scratch block 0).  Quantized
    pools (int8) additionally carry the per-block scale arrays; fp
    pools leave them None (the default keeps every existing
    3-argument constructor working).  A latent pool's view has ``v``
    None and ``k`` ``[num_blocks, block_size, width]``."""
    k: object                   # [num_blocks, block_size, kv_heads, hd]
    v: object
    block_table: object         # [B, max_blocks] int32
    k_scale: object = None      # [num_blocks, block_size, kv_heads] f32
    v_scale: object = None


def query_positions(position_offset, B: int, S: int):
    """``[B, S]`` positions of a dispatch's queries: ``position_offset``
    a scalar, or a per-row ``[B]`` vector (continuous batching, chunked
    prefill: each row sits at its own offset)."""
    if getattr(position_offset, "ndim", 0) == 1:
        return position_offset[:, None] + jnp.arange(S)[None]
    return jnp.broadcast_to(position_offset + jnp.arange(S)[None], (B, S))


def _write_targets(bt, qpos, bs: int):
    """Logical position -> (physical block, slot in it), ``[B, S]`` each.
    Positions past the table (padded chunk tails near max_len) are routed
    to the scratch block EXPLICITLY — clamping them into the row's last
    real block would let a pad row overwrite live prompt KV when a
    sequence has every block allocated.  Within the table, unallocated
    entries are 0 (scratch) by construction."""
    mb = bt.shape[1]
    lb = qpos // bs
    bids = jnp.take_along_axis(bt, jnp.minimum(lb, mb - 1), axis=1)
    return jnp.where(lb < mb, bids, 0), qpos % bs


def paged_cache_attention(q, k, v, cache: PagedCache, position_offset,
                          attn_mask=None, window=None):
    """Paged analog of ``static_cache_attention``: write the step's k/v
    through the block table, gather the table back into logical order,
    attend under the causal bound.

    q/k/v: ``[b, s, heads, head_dim]`` current-step projections.
    ``position_offset``: scalar, or per-row ``[B]`` vector (continuous
    batching / chunked prefill — each row sits at its own offset; unlike
    the static path, s > 1 composes with per-row offsets, which is what
    lets speculative drafts verify in ONE batched forward).

    Returns ``(out, new_cache)``.  Decode (s == 1) routes to the Pallas
    paged-attention kernel when eligible; the ``jnp.take`` gather
    fallback runs elsewhere and is numerically identical (the gathered
    values are bitwise the static buffer's, the extra masked tail
    contributes exact zeros).

    ``window`` (a static count of positions; None: none): a query at
    position t sees key j iff ``0 <= t - j < window``, on every path;
    the kernel and the walk read no table entry older than that, so the
    table may be a ring (logical block ``lb`` under physical block
    ``ring[lb % R]``, ``R`` blocks covering the window and the longest
    write of one dispatch).  What the kernel does not take walks the
    tiles of context the queries can see with an online softmax
    (``paged_chunk_attention``) instead of gathering the table where the
    layer has a window, or the span is more than one query and the table
    longer than one tile of the walk; an int8 pool and a caller's mask
    gather, as does a table of one tile."""
    from paddle_tpu.core.dispatch import unwrap, wrap_like
    from paddle_tpu.generation import reject_scalar_mask
    from paddle_tpu.nn.functional.attention import \
        scaled_dot_product_attention

    B, S = q.shape[0], q.shape[1]
    kp, vp = unwrap(cache.k), unwrap(cache.v)
    bt = unwrap(cache.block_table)
    bs = kp.shape[1]
    mb = bt.shape[1]
    qpos = query_positions(position_offset, B, S)
    bids, slot = _write_targets(bt, qpos, bs)
    quant = cache.k_scale is not None
    if quant:
        # quantization fused into the block scatter: the step's fp K/V
        # become int8 rows + per-(token, kv-head) scales in one shot;
        # the fp values never exist pool-shaped
        kq, ks_new = _quantize_kv(unwrap(k))
        vq, vs_new = _quantize_kv(unwrap(v))
        ksc = unwrap(cache.k_scale).at[bids, slot].set(ks_new)
        vsc = unwrap(cache.v_scale).at[bids, slot].set(vs_new)
        kp = kp.at[bids, slot].set(kq)
        vp = vp.at[bids, slot].set(vq)
        new_cache = PagedCache(wrap_like(kp), wrap_like(vp),
                               cache.block_table, wrap_like(ksc),
                               wrap_like(vsc))
    else:
        kp = kp.at[bids, slot].set(unwrap(k).astype(kp.dtype))
        vp = vp.at[bids, slot].set(unwrap(v).astype(vp.dtype))
        new_cache = PagedCache(wrap_like(kp), wrap_like(vp),
                               cache.block_table)

    from paddle_tpu.ops.pallas import paged_attention as PA
    uq = unwrap(q)
    if attn_mask is None and S == 1 and \
            PA.paged_decode_eligible(kp.shape[-1], bs, uq.dtype, pool=kp):
        PA.record_path("pallas")
        lengths = qpos[:, 0] + 1
        if quant:
            out = PA.paged_decode_attention(uq[:, 0], kp, vp, bt,
                                            lengths, k_scale=ksc,
                                            v_scale=vsc, window=window)
        else:
            out = PA.paged_decode_attention(uq[:, 0], kp, vp, bt,
                                            lengths, window=window)
        return wrap_like(out[:, None]), new_cache
    # The gather below holds ``[B, heads, S, max_len]`` float32 scores at
    # once and costs the whole table whatever the queries can see (32
    # heads, a 256-query chunk, 2,576 positions: 84 MB a layer where the
    # median prompt fills one tile in five).  So a span of more than one
    # query — a prefill chunk, a speculative verify — walks the tiles it
    # can see wherever the table is longer than one tile of the walk, as a
    # layer with a window always does; a table of one tile has nothing to
    # skip.  The rule reads shapes only.
    if attn_mask is None and not quant and \
            (window is not None
             or (S > 1 and mb * bs > PA._WALK_TILE_TOKENS)):
        PA.record_path("walk")
        return wrap_like(PA.paged_chunk_attention(
            uq, kp, vp, bt, qpos, window=window)), new_cache
    PA.record_path("fallback")

    # gather the block table back into logical order: [B, mb*bs, kvh, hd]
    if quant:
        # dequantization fused into the gather read: int8 blocks widen
        # through their scales straight into the compute dtype
        kb = (kp[bt].astype(jnp.float32)
              * ksc[bt][..., None]).astype(uq.dtype)
        vb = (vp[bt].astype(jnp.float32)
              * vsc[bt][..., None]).astype(uq.dtype)
    else:
        kb, vb = kp[bt], vp[bt]
    kb = jnp.reshape(kb, (B, mb * bs) + kp.shape[2:])
    vb = jnp.reshape(vb, (B, mb * bs) + vp.shape[2:])
    kpos = jnp.arange(mb * bs)
    mask = kpos[None, None, None, :] <= qpos[:, None, :, None]  # [B,1,S,T]
    if window is not None:
        mask &= kpos[None, None, None, :] > qpos[:, None, :, None] - window
    if attn_mask is not None:
        am = reject_scalar_mask(attn_mask)
        if am.dtype == jnp.bool_:
            mask = mask & am
        else:
            mask = jnp.where(mask, am.astype(jnp.float32), -1e30)
    out = scaled_dot_product_attention(q, wrap_like(kb), wrap_like(vb),
                                       attn_mask=mask, is_causal=False)
    return out, new_cache


def latent_cache_attention(q, row, cache: PagedCache, position_offset,
                           w_kvb, *, rank: int, nope: int, scale: float):
    """Latent attention over a latent pool: write the step's rows
    through the block table, then attend what the queries can see.

    q ``[b, s, heads, nope + rope]`` (rotary part rotated, unscaled);
    row ``[b, s, rank + rope]``, a token's ``[c | k_r]``; ``cache.k``
    ``[num_blocks, block_size, width]`` with ``width`` the row padded to
    whole lanes; ``w_kvb`` ``[rank, heads * (nope + v)]``.  Decode
    (s == 1) on the TPU is the absorbed form as one Pallas call
    (``ops/pallas/latent_attention.py``: ``[q_n W_uk | q_r]`` against the
    cached rows, ``P c`` back, then ``W_uv``); anything else — a prefill
    chunk, a speculative verify, the CPU — walks the tiles of context
    the queries can see.  Returns ``(out [b, s, heads, v], new_cache)``."""
    from paddle_tpu.core.dispatch import unwrap, wrap_like
    from paddle_tpu.ops.pallas import latent_attention as LA
    q, row, w_kvb = unwrap(q), unwrap(row), unwrap(w_kvb)
    B, S, h, _ = q.shape
    pool, bt = unwrap(cache.k), unwrap(cache.block_table)
    bs, width = pool.shape[1], pool.shape[2]
    qpos = query_positions(position_offset, B, S)
    bids, slot = _write_targets(bt, qpos, bs)
    stored = jnp.pad(row, ((0, 0), (0, 0), (0, width - row.shape[-1])))
    pool = pool.at[bids, slot].set(stored.astype(pool.dtype))
    new_cache = PagedCache(wrap_like(pool), None, cache.block_table)
    if S == 1 and LA.latent_decode_eligible(rank, bs, q.dtype):
        LA.record_path("decode_kernel")
        wb = w_kvb.reshape(rank, h, -1)
        q1 = q[:, 0]
        qa = jnp.concatenate(
            [jnp.einsum("bhn,chn->bhc", q1[..., :nope], wb[..., :nope],
                        preferred_element_type=jnp.float32),
             q1[..., nope:].astype(jnp.float32),
             jnp.zeros((B, h, width - row.shape[-1]), jnp.float32)],
            axis=-1) * scale
        ol = LA.latent_decode_attention(qa.astype(q.dtype), pool, bt,
                                        qpos[:, 0] + 1, rank)
        out = jnp.einsum("bhc,chv->bhv", ol, wb[..., nope:],
                         preferred_element_type=jnp.float32)
        return wrap_like(out.astype(q.dtype)[:, None]), new_cache
    out = LA.latent_chunk_attention(q, pool, bt, qpos, w_kvb, rank=rank,
                                    nope=nope, scale=scale)
    return wrap_like(out), new_cache
