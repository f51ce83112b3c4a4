"""Paged KV cache: fixed-size blocks + per-slot block tables.

Instead of statically reserving a dense ``[slots, max_seq]`` cache per
layer, global-attention layers share a pool of ``num_blocks`` fixed-size
blocks; each slot holds a *block table* mapping its logical cache
positions to physical blocks.  Mixed-length traffic then only pays for
the positions it actually fills, and the pool (not per-slot reservation)
caps concurrency.

Block allocation is delegated to the open memory interface
(``core/memory/manager.py``): the managers the paper studies on recorded
traces here drive a *live* serving workload — allocator policies
(caching vs bump, and their fragmentation stats) become swappable
serving experiments.

Static-shape discipline (TPU/jit): the pool has a fixed block count, the
table a fixed ``[slots, max_blocks]`` shape, and physical block 0 is a
reserved *trash* block — unmapped table entries point at it, so idle
slots' decode writes land harmlessly without dynamic shapes or masking
inside the jitted step.  Ring-buffer (sliding-window) layer caches are
already small and fixed per slot, so they stay dense.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.memory import telemetry
from repro.core.memory.manager import (BumpMemoryManager,
                                       CachingMemoryManager,
                                       MemoryManagerAdapter, OutOfMemory)

from .prefix import PrefixIndex, PrefixNode

__all__ = ["BlockTable", "PagedKVCache", "OutOfMemory", "paged_block_bytes"]


@jax.tree_util.register_pytree_node_class
@dataclass
class BlockTable:
    """Device-side view of the per-slot block tables.

    ``table``: int32 ``[slots, max_blocks]`` physical block ids (0 = the
    reserved trash block).  ``block_size`` is static (pytree aux data),
    so it is a Python int inside jitted code.
    """

    table: Any
    block_size: int

    def tree_flatten(self):
        return (self.table,), self.block_size

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


def paged_block_bytes(cfg, block_size: int) -> int:
    """Bytes one block occupies across every paged (global-attention)
    layer — the allocation unit handed to the memory manager."""
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    n_global = sum(1 for i in range(cfg.n_layers)
                   if cfg.layer_kind(i) == "A"
                   and cfg.window_for_layer(i) == 0)
    item = jnp.dtype(cfg.resolved_cache_dtype).itemsize
    per_pos = 2 * kv * hd * item                    # k + v
    if cfg.cache_dtype == "fp8":
        per_pos += 2 * kv * 4                       # float32 scales
    return max(1, n_global * per_pos * block_size)


class PagedKVCache:
    """Host-side block-table + pool manager for one ``ServeEngine``.

    The device pools live in ``self.pools`` (the model's paged cache
    pytree — per-layer ``[num_blocks * block_size, ...]`` arrays, shared
    across slots).  This object owns the host block tables and talks to
    the allocator; the jitted decode/prefill steps only ever see the
    pools plus a :class:`BlockTable` snapshot.
    """

    def __init__(self, model, *, slots: int, max_seq: int, block_size: int,
                 num_blocks: int | None = None,
                 manager: MemoryManagerAdapter | str | None = None,
                 prefix=None):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.model = model
        self.slots = slots
        self.max_seq = max_seq
        self.block_size = block_size
        self.max_blocks = math.ceil(max_seq / block_size)
        if num_blocks is None:
            # roomy default: every slot can reach max_seq (+ trash block)
            num_blocks = slots * self.max_blocks + 1
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_bytes = paged_block_bytes(model.cfg, block_size)
        if manager is None or isinstance(manager, str):
            make = {None: CachingMemoryManager, "caching": CachingMemoryManager,
                    "bump": BumpMemoryManager}[manager]
            kw = {} if make is BumpMemoryManager else \
                {"round_to": self.block_bytes}
            manager = make(capacity=num_blocks * self.block_bytes, **kw)
        self.manager = manager
        self.pools = model.init_paged_cache(slots, max_seq,
                                            num_blocks=num_blocks,
                                            block_size=block_size)
        self.table = np.zeros((slots, self.max_blocks), np.int32)
        self._blocks: dict[int, list[tuple[int, int]]] = {}  # slot -> [(id, ptr)]
        # -- prefix sharing (optional) ---------------------------------------
        # refcount[bid] = slot mappings + (1 if the radix tree holds it);
        # blocks return to the allocator only when the last sharer lets go.
        self.prefix = prefix                      # PrefixPolicy | None
        self.prefix_index = (PrefixIndex(block_size)
                             if prefix is not None else None)
        self.refcount: dict[int, int] = {}
        self._shared_len: dict[int, int] = {}     # slot -> matched positions
        # slot -> (lo, hi) of the most recent prepared write range; the
        # audit re-checks exactly this range against the refcounts (a
        # *past* write into a block that became shared afterwards — the
        # registrant's own prefill — is fine)
        self._prepared: dict[int, tuple[int, int]] = {}
        self._pending: dict[int, list[PrefixNode]] = {}  # pre-ready nodes
        # -- speculative decoding / forking ----------------------------------
        # committed[slot] = positions whose KV content is final; anything a
        # slot holds past blocks_for(committed-1) that is not covered by a
        # declared write intent (_prepared) is rollback debris the audit
        # flags (kv.rollback-dangling).  Only speculative engines maintain
        # this — plain decode never rolls back, so the map stays empty.
        self._committed: dict[int, int] = {}
        self._forks: dict[int, int] = {}          # child slot -> parent slot
        self.rollback_blocks_freed = 0
        self.forks = 0
        self._leaf_axes_cache: list[int | None] | None = None
        self.cow_copies = 0
        # ambient tracer at construction (the engine builds its cache
        # inside the serving session); None = observability off
        self._obs = obs.get_tracer()
        # reserve physical block 0 as the trash block, never freed
        ptr0 = self.manager.alloc(self.block_bytes)
        if ptr0 // self.block_bytes != 0:
            raise ValueError(
                "paged KV cache needs a fresh block-aligned arena (the "
                "offset->block-id mapping requires every allocation to be "
                f"a block_bytes={self.block_bytes} multiple); got first "
                f"offset {ptr0}")

    # -- capacity ------------------------------------------------------------
    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1          # minus the trash block

    @property
    def blocks_in_use(self) -> int:
        return sum(len(v) for v in self._blocks.values())

    def blocks_for(self, pos: int) -> int:
        """Blocks a slot needs so position ``pos`` is writable."""
        return pos // self.block_size + 1

    # -- refcounted allocation ----------------------------------------------
    def _alloc_block(self) -> tuple[int, int]:
        """One fresh block (refcount 1); under pool pressure, reclaim
        LRU tree-only prefix blocks before giving up with OutOfMemory."""
        while True:
            try:
                ptr = self.manager.alloc(self.block_bytes)
                break
            except OutOfMemory:
                if not self._evict_prefix(1):
                    raise
        bid = ptr // self.block_bytes
        self.refcount[bid] = 1
        # bridge into the allocation-telemetry stream (negative uid
        # namespace: KV block ids must not collide with LazyTensor uids
        # in a recording that spans both sources)
        telemetry.record_alloc(-(bid + 1), self.block_bytes, tag="kv.block")
        return bid, ptr

    def _decref(self, bid: int) -> None:
        c = self.refcount.get(bid, 0) - 1
        if c > 0:
            self.refcount[bid] = c
        else:
            self.refcount.pop(bid, None)
            self.manager.unlock(bid * self.block_bytes)
            telemetry.record_free(-(bid + 1))

    def _evict_prefix(self, n: int) -> bool:
        """Drop up to ``n`` LRU radix leaves nobody maps (refcount 1 =
        tree-only) and return their blocks to the allocator."""
        if self.prefix_index is None:
            return False
        freed = self.prefix_index.evict(
            lambda b: self.refcount.get(b, 0) == 1, limit=n)
        for bid in freed:
            self._decref(bid)
        return bool(freed)

    # -- slot lifecycle ------------------------------------------------------
    def ensure(self, slot: int, pos: int) -> None:
        """Map enough blocks that ``pos`` is writable for ``slot``.

        Raises :class:`OutOfMemory` when the allocator cannot satisfy the
        growth — the engine's preemption trigger.
        """
        need = self.blocks_for(pos)
        if need > self.max_blocks:
            raise OutOfMemory(
                f"position {pos} exceeds max_seq={self.max_seq} "
                f"({self.max_blocks} blocks/slot)")
        held = self._blocks.setdefault(slot, [])
        if len(held) >= need:
            return
        with obs.span("kv.grow", "memory", self._obs, slot=slot,
                      blocks=need - len(held)):
            while len(held) < need:
                bid, ptr = self._alloc_block()
                self.table[slot, len(held)] = bid
                held.append((bid, ptr))

    def release(self, slot: int) -> None:
        """Drop every reference a slot holds (finished or evicted).

        Shared blocks only decref — the block stays live for its other
        sharers (tree included) and reaches the allocator when the last
        one lets go.  Registrations that never became ready (the owner
        was evicted before its prefill round completed) are unlinked so
        no future admission can match garbage content.
        """
        for node in reversed(self._pending.pop(slot, [])):
            if node.parent is not None and not node.children:
                self.prefix_index.remove(node)
                self._decref(node.block)
        for bid, _ptr in self._blocks.pop(slot, []):
            self._decref(bid)
        self.table[slot] = 0
        self._shared_len.pop(slot, None)
        self._prepared.pop(slot, None)
        self._committed.pop(slot, None)
        self._forks.pop(slot, None)
        # a released parent orphans its children: they own their blocks
        # (refcounted) and stop being audited as forks of a dead slot
        for child, parent in list(self._forks.items()):
            if parent == slot:
                del self._forks[child]
        if self.prefix_index is not None and not self.prefix.retain:
            for bid in self.prefix_index.sweep(
                    lambda b: self.refcount.get(b, 0) == 1):
                self._decref(bid)

    # -- speculative rollback / forking --------------------------------------
    def set_committed(self, slot: int, n: int) -> None:
        """Record that positions ``[0, n)`` hold final KV content for
        ``slot`` (speculative engines call this at admission and after
        every verify round; the rollback audit keys off it)."""
        self._committed[slot] = n

    def begin_write(self, slot: int, lo: int, hi: int) -> None:
        """Declare an upcoming write to positions ``[lo, hi]`` *before*
        growing the mapping — so an audit triggered mid-growth (a
        preemption freeing room for this very span) sees the extra
        blocks as intended, not as rollback debris."""
        self._prepared[slot] = (lo, hi)

    def rollback(self, slot: int, new_len: int) -> int:
        """Truncate ``slot`` to ``new_len`` committed positions.

        The speculative-decoding rejection path: verify wrote K/V for
        the whole proposed span, acceptance kept a prefix, and the
        surplus *blocks* return to the memory manager (refcount-aware —
        a block other sharers or the radix tree still reference only
        decrefs).  Positions inside the last kept block need no cleanup:
        the decode validity mask hides them and future writes overwrite
        them.  Returns the number of block references dropped.
        """
        held = self._blocks.get(slot, [])
        keep = 0 if new_len <= 0 else (new_len - 1) // self.block_size + 1
        freed = 0
        while len(held) > keep:
            bid, _ptr = held.pop()
            self.table[slot, len(held)] = 0
            self._decref(bid)
            freed += 1
        self._committed[slot] = new_len
        if slot in self._prepared:
            lo, hi = self._prepared[slot]
            if lo >= new_len:
                del self._prepared[slot]
            elif hi >= new_len:
                self._prepared[slot] = (lo, new_len - 1)
        self.rollback_blocks_freed += freed
        return freed

    def fork(self, src: int, dst: int) -> None:
        """Clone ``src``'s block table into pristine slot ``dst``.

        Every mapped block gains a reference; nothing is copied — the
        first divergent write through :meth:`prepare_write` triggers
        copy-on-write for whichever sequence writes first.  This is the
        beam-search primitive: a fork costs O(blocks) refcount bumps.
        """
        if self._blocks.get(dst):
            raise ValueError(f"fork() into non-empty slot {dst}")
        held = self._blocks.get(src, [])
        self._blocks[dst] = list(held)
        for bid, _ptr in held:
            self.refcount[bid] = self.refcount.get(bid, 0) + 1
        self.table[dst] = 0
        self.table[dst, :len(held)] = self.table[src, :len(held)]
        # the child inherits the parent's shared-prefix semantics: any
        # write past it into a still-shared block must COW
        self._shared_len[dst] = self._shared_len.get(src, 0)
        if src in self._committed:
            self._committed[dst] = self._committed[src]
        self._forks[dst] = src
        self.forks += 1

    # -- prefix sharing ------------------------------------------------------
    def admit(self, slot: int, tokens: list[int]) -> int:
        """Map the longest cached prefix of ``tokens`` into ``slot``.

        Walks the radix tree, increfs every matched block, and installs
        it in the slot's table; returns the number of leading positions
        already cached (the engine skips their prefill).  Call before
        :meth:`ensure` — the private tail extends past the shared head.
        """
        if self.prefix_index is None:
            return 0
        held = self._blocks.setdefault(slot, [])
        if held:
            raise ValueError(f"admit() into non-empty slot {slot}")
        nodes, matched = self.prefix_index.match(
            tokens, partial=self.prefix.partial)
        for j, node in enumerate(nodes):
            self.refcount[node.block] += 1
            self.table[slot, j] = node.block
            held.append((node.block, node.block * self.block_bytes))
        self._shared_len[slot] = matched
        return matched

    def register(self, slot: int, tokens: list[int]) -> None:
        """Publish the slot's full blocks of ``tokens`` (the prefill
        extent) into the radix tree so later admissions can share them.

        First registrant of a span wins; spans already in the tree are
        skipped (this slot's block for them is either the shared block
        itself or a private duplicate that stays private).  New nodes
        start non-ready — call :meth:`mark_ready` once the prefill round
        has actually materialized their content on device.
        """
        if self.prefix_index is None:
            return
        held = self._blocks.get(slot, ())
        nfull = min(len(tokens) // self.block_size, len(held))
        if not nfull:
            return
        created = self.prefix_index.insert(
            tokens, [held[j][0] for j in range(nfull)])
        for node in created:
            self.refcount[node.block] += 1
        if created:
            self._pending.setdefault(slot, []).extend(created)

    def mark_ready(self, slot: int) -> None:
        """Flip the slot's pending registrations to ready (their prefill
        round ran; partial-match COW may now copy out of them)."""
        for node in self._pending.pop(slot, []):
            if node.parent is not None:
                node.ready = True

    def prepare_write(self, slot: int, lo: int, hi: int, pools):
        """Copy-on-write barrier for writes to positions ``[lo, hi]``.

        Positions below the slot's shared prefix length are idempotent
        rewrites of identical values (KV at position p is a function of
        the matched token prefix) and stay shared; a *divergent* write
        (pos >= shared_len) into a block with other sharers gets a
        private copy first.  Takes and returns the live device pools —
        the engine's ``self.cache``, not the construction-time
        ``self.pools`` — so the copy reads current data.
        """
        self._prepared[slot] = (lo, hi)
        held = self._blocks.get(slot)
        shared = self._shared_len.get(slot, 0)
        if held and hi >= shared:
            todo = [j for j in range(max(lo, shared) // self.block_size,
                                     min(hi // self.block_size,
                                         len(held) - 1) + 1)
                    if self.refcount.get(held[j][0], 0) > 1]
            if todo:
                with obs.span("kv.cow_copy", "memory", self._obs,
                              blocks=len(todo)):
                    for j in todo:
                        bid = held[j][0]
                        nbid, nptr = self._alloc_block()
                        pools = self._copy_block(pools, src=bid, dst=nbid)
                        self.cow_copies += 1
                        held[j] = (nbid, nptr)
                        self.table[slot, j] = nbid
                        self._decref(bid)
        self.pools = pools
        return pools

    def clear_prefix(self) -> int:
        """Drop every tree-only cached prefix block; returns the count
        of blocks returned to the allocator."""
        if self.prefix_index is None:
            return 0
        freed = self.prefix_index.sweep(
            lambda b: self.refcount.get(b, 0) == 1)
        for bid in freed:
            self._decref(bid)
        return len(freed)

    def shared_len(self, slot: int) -> int:
        return self._shared_len.get(slot, 0)

    # -- copy-on-write device copy -------------------------------------------
    def _leaf_axes(self) -> list:
        """Per-pool-leaf block-pool axis (None = dense ring/window leaf).

        Derived structurally: a paged leaf's shape is the dense leaf's
        shape with the (batch, seq) pair replaced by the pool dimension
        ``num_blocks * block_size`` at axis 0 (unstacked layer) or axis
        1 (scan-stacked layers prepend a layer axis).
        """
        if self._leaf_axes_cache is not None:
            return self._leaf_axes_cache
        dense = jax.tree_util.tree_leaves(
            self.model.cache_spec(self.slots, self.max_seq))
        paged = jax.tree_util.tree_leaves(
            self.model.paged_cache_spec(self.slots, self.max_seq,
                                        num_blocks=self.num_blocks,
                                        block_size=self.block_size))
        p = self.num_blocks * self.block_size
        axes: list[int | None] = []
        for dm, pm in zip(dense, paged):
            ds, ps = tuple(dm.shape), tuple(pm.shape)
            if ds == ps:
                axes.append(None)
                continue
            hits = [k for k in (0, 1)
                    if len(ds) >= k + 2
                    and ps == ds[:k] + (p,) + ds[k + 2:]]
            if len(hits) != 1:
                raise ValueError(
                    f"cannot identify pool axis for paged leaf {ps} vs "
                    f"dense {ds} (pool={p}); candidates: {hits}")
            axes.append(hits[0])
        self._leaf_axes_cache = axes
        return axes

    def _copy_block(self, pools, *, src: int, dst: int):
        """Device-copy one physical block's rows across every paged
        pool leaf (the COW body)."""
        leaves, treedef = jax.tree_util.tree_flatten(pools)
        bs = self.block_size
        out = []
        for leaf, ax in zip(leaves, self._leaf_axes()):
            if ax is None:
                out.append(leaf)
                continue
            row = jax.lax.dynamic_slice_in_dim(leaf, src * bs, bs, axis=ax)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                leaf, row, dst * bs, axis=ax))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- static audit --------------------------------------------------------
    def snapshot(self):
        """Immutable :class:`repro.analysis.CacheSnapshot` of the block
        table, held-block map, and allocator live set — the input the
        static serving checker reasons over."""
        from repro.analysis.serving import snapshot_cache

        return snapshot_cache(self)

    def audit(self):
        """Run :func:`repro.analysis.check_paged_cache` over the current
        state; returns the :class:`~repro.analysis.DiagnosticReport`
        (leaks, double-frees, double-maps, trash-block violations,
        table/held divergence)."""
        from repro.analysis.serving import check_paged_cache

        return check_paged_cache(self.snapshot(), where="PagedKVCache")

    # -- device views --------------------------------------------------------
    def device_table(self) -> BlockTable:
        return BlockTable(jnp.asarray(self.table), self.block_size)

    def describe(self) -> dict:
        s = self.manager.stats
        d = {"block_size": self.block_size,
             "num_blocks": self.num_blocks,
             "max_blocks_per_slot": self.max_blocks,
             "block_bytes": self.block_bytes,
             "blocks_in_use": self.blocks_in_use,
             "manager": type(self.manager).__name__,
             "device_allocs": s.n_device_allocs,
             "internal_fragmentation": s.internal_fragmentation,
             "rollback_blocks_freed": self.rollback_blocks_freed,
             "forks": self.forks}
        if self.prefix_index is not None:
            d["prefix"] = {**self.prefix_index.describe(),
                           "cow_copies": self.cow_copies,
                           "shared_blocks": sum(
                               1 for c in self.refcount.values() if c > 1)}
        return d
