"""Paged KV-cache block pool: host-side free-list allocator + page tables.

The paged cache layout (``kv_layout='paged'``) replaces the contiguous
per-lane ``(B, max_len, ...)`` KV regions with one global **block pool**
per layer — head-major ``(num_blocks, n_kv_heads, block_size, hd)`` — plus a
per-lane **page table** ``(B, max_blocks)`` mapping each lane's logical
position range ``[j*block_size, (j+1)*block_size)`` to a physical block.
Cache HBM is then ``num_blocks * block_size`` rows, decoupled from
``batch * max_len``: a pool sized for the *expected* footprint serves
traffic whose per-request ``max_len`` would otherwise reserve the
worst case for every lane.

This module is the host side of that design, mirroring the slot
scheduler's philosophy: pure bookkeeping, no device state. The pool
owns the free list, the per-block refcounts and the page table (an
int32 numpy array the engine ships to the device whenever ``version``
changes — exactly how the engine's position vector is the single source
of truth for cache write indices). Blocks are appended on demand as a
lane's position crosses a block boundary (``ensure``/``grow`` before
every launch) and dereferenced the step the lane finishes or is
preempted (``release``).

Prefix sharing (serving/prefix_cache.py) turns single ownership into
**refcounted, copy-on-write sharing**: a block may be mapped by several
lanes at once (identical prompt prefixes) and pinned by the prefix /
session caches after its writer finished. The safety argument is
write-discipline, not hardware protection:

  * a block enters sharing only through ``share``/``incref`` *after*
    its writer finished — every row it will ever expose is already
    written;
  * a lane only ever writes rows at its own ``pos``, and ``pos`` for a
    lane that attached a shared prefix of ``m`` tokens starts at ``m``
    — so writes land exclusively in blocks allocated fresh for that
    lane (``grow``/``fork``), never in a shared block;
  * a divergence *inside* a block (``m % block_size != 0``) is handled
    by ``fork``: allocate a fresh block, remap the lane's page-table
    entry, and let the engine device-copy the rows — classic COW.

Invariants (property-tested in tests/test_kv_pool.py):

  * ``refcount[b] == (#page-table references to b) + external pins``
    where external pins are the prefix-cache / session holdings;
  * ``refcount[b] == 0``  ⇔  ``b`` is on the free list;
  * ``free_blocks + used_blocks == num_blocks`` always (conservation);
  * ``release`` unmaps every block the lane mapped, same call, and a
    block is recycled the moment its last reference drops;
  * page-table rows list a lane's blocks in logical order, ``-1`` padded.

The device side never sees the allocator: the jitted step receives the
page table as a plain array, computes physical write indices
``(table[lane, pos // bs], pos % bs)`` and gathers K/V through the
table (models/layers.py). Unmapped entries are ``-1``: writes through
them are pushed out of range so the ``mode='drop'`` scatter discards
them, gathers clamp and are masked by the existing per-lane validity
masks — stale block contents can never become valid, because a lane
writes position ``p`` in the same step ``p`` first enters its valid
range.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["KVBlockPool"]


class KVBlockPool:
    """Refcounted free-list allocator over ``num_blocks`` physical KV blocks.

    ``max_blocks_per_lane`` is the page-table width (ceil(max_len /
    block_size)): a lane can never map more logical positions than the
    engine's cache cap.
    """

    def __init__(self, num_blocks: int, block_size: int, n_lanes: int,
                 max_blocks_per_lane: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        if max_blocks_per_lane < 1:
            raise ValueError(
                f"max_blocks_per_lane must be >= 1, got {max_blocks_per_lane}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.n_lanes = n_lanes
        self.max_blocks_per_lane = max_blocks_per_lane
        # LIFO free list: recycled blocks are reused first (hot in cache)
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = np.zeros(num_blocks, np.int32)
        self._owned: List[List[int]] = [[] for _ in range(n_lanes)]
        self.table = np.full((n_lanes, max_blocks_per_lane), -1, np.int32)
        # bumped on every table mutation: the engine re-ships the table
        # to the device only when this changed since the last launch
        self.version = 0

    # -- accounting -----------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def lane_blocks(self, lane: int) -> int:
        return len(self._owned[lane])

    def lane_chain(self, lane: int) -> List[int]:
        """The lane's mapped blocks in logical order (a copy)."""
        return list(self._owned[lane])

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def shared_blocks(self) -> int:
        """Blocks referenced more than once (mapped by several lanes
        and/or pinned by the prefix / session caches)."""
        return int((self._ref > 1).sum())

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to back ``n_tokens`` logical positions."""
        return -(-max(0, n_tokens) // self.block_size)

    # -- refcounting ----------------------------------------------------
    def incref(self, block: int) -> None:
        """Add an external pin (prefix-cache / session holding). The
        block must already be live — pinning a free block would resurrect
        garbage."""
        if not (0 <= block < self.num_blocks):
            raise ValueError(f"bad block id {block}")
        if self._ref[block] <= 0:
            raise ValueError(f"incref on free block {block}")
        self._ref[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; recycle the block when the last drops.
        Returns True iff the block was freed by this call."""
        if not (0 <= block < self.num_blocks):
            raise ValueError(f"bad block id {block}")
        if self._ref[block] <= 0:
            raise ValueError(f"decref on free block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)
            return True
        return False

    # -- allocation -----------------------------------------------------
    def grow(self, lane: int, n_tokens: int) -> int:
        """Append fresh blocks until ``lane`` backs ``n_tokens`` positions
        (or the pool / page table runs out). Returns the number of
        positions actually backed — callers clip their chunk to it; a
        return below ``n_tokens`` means the pool is exhausted (preempt
        or retry)."""
        want = min(self.blocks_for(n_tokens), self.max_blocks_per_lane)
        owned = self._owned[lane]
        while len(owned) < want and self._free:
            blk = self._free.pop()
            self._ref[blk] = 1
            self.table[lane, len(owned)] = blk
            owned.append(blk)
            self.version += 1
        return min(len(owned) * self.block_size,
                   self.max_blocks_per_lane * self.block_size)

    def ensure(self, lane: int, n_tokens: int) -> bool:
        """True iff ``lane`` backs ``n_tokens`` positions after growing."""
        return self.grow(lane, n_tokens) >= min(
            n_tokens, self.max_blocks_per_lane * self.block_size)

    def share(self, lane: int, blocks: Sequence[int]) -> None:
        """Map an already-live prefix chain into an *empty* lane
        (prefix-cache hit at admission). Each block gains a reference;
        none is ever written by this lane — its ``pos`` starts past
        them."""
        owned = self._owned[lane]
        if owned:
            raise ValueError(f"share into non-empty lane {lane}")
        if len(blocks) > self.max_blocks_per_lane:
            raise ValueError("shared chain longer than page table")
        for j, blk in enumerate(blocks):
            if self._ref[blk] <= 0:
                raise ValueError(f"share of free block {blk}")
            self._ref[blk] += 1
            self.table[lane, j] = blk
            owned.append(blk)
        if blocks:
            self.version += 1

    def pop_last(self, lane: int) -> int:
        """Unmap the lane's last mapped block (dropping one reference).
        Degrade path for a COW fork that found the pool dry: the
        partially-matched tail block leaves the lane again. Returns the
        block id unmapped."""
        owned = self._owned[lane]
        if not owned:
            raise ValueError(f"pop_last on empty lane {lane}")
        blk = owned.pop()
        self.table[lane, len(owned)] = -1
        self.version += 1
        self.decref(blk)
        return blk

    def trim(self, lane: int, new_len: int) -> int:
        """Rollback primitive for speculative decoding: unmap the lane's
        tail blocks so it backs only ``new_len`` logical positions,
        dropping one reference per unmapped block. COW-aware by
        construction — a shared or cache-pinned block merely loses this
        lane's mapping (it is recycled only when its last reference
        drops) and its contents are never touched; stale rows past
        ``new_len`` in blocks the lane keeps are harmless under the
        write-discipline invariant (a lane writes position ``p`` the
        step ``p`` re-enters its valid range). Returns how many blocks
        were unmapped."""
        if new_len < 0:
            raise ValueError(f"trim to negative length {new_len}")
        keep = self.blocks_for(new_len)
        owned = self._owned[lane]
        n = 0
        while len(owned) > keep:
            blk = owned.pop()
            self.table[lane, len(owned)] = -1
            self.decref(blk)
            n += 1
        if n:
            self.version += 1
        return n

    def fork(self, lane: int, index: int) -> Optional[int]:
        """Copy-on-write fork of the lane's ``index``-th mapped block:
        allocate a fresh block, remap the page-table entry to it, drop
        the lane's reference to the shared original. Returns the new
        physical block id (the engine device-copies the rows), or None
        if the pool is dry — the caller degrades to re-prefilling the
        partial block."""
        owned = self._owned[lane]
        if not (0 <= index < len(owned)):
            raise ValueError(f"fork index {index} out of range")
        if not self._free:
            return None
        src = owned[index]
        dst = self._free.pop()
        self._ref[dst] = 1
        self.table[lane, index] = dst
        owned[index] = dst
        self.version += 1
        self.decref(src)
        return dst

    def release(self, lane: int) -> int:
        """Unmap every block the lane references (EOS / recycle /
        preempt) and drop one reference per mapping — a block is only
        recycled when no other lane and no cache pin still holds it.
        Returns how many blocks were unmapped from the lane."""
        owned = self._owned[lane]
        n = len(owned)
        if n:
            # LIFO: blocks freed here sit on top of the free list
            for blk in reversed(owned):
                self.decref(blk)
            self.table[lane, :n] = -1
            owned.clear()
            self.version += 1
        return n

    def check_invariants(
            self, external: Optional[Dict[int, int]] = None) -> None:
        """Raise AssertionError on any broken allocator invariant
        (test/debug hook — the engine never calls this on the hot path).

        ``external`` maps block id -> number of pins held outside the
        page tables (prefix-cache entries + session chains). With the
        default None, refcounts must be fully explained by the page
        tables alone."""
        ext = external or {}
        want_ref = np.zeros(self.num_blocks, np.int64)
        for lane, owned in enumerate(self._owned):
            row = self.table[lane]
            assert list(row[: len(owned)]) == owned, (
                f"lane {lane}: table row disagrees with owned list")
            assert (row[len(owned):] == -1).all(), (
                f"lane {lane}: table row not -1 beyond owned blocks")
            for b in owned:
                assert 0 <= b < self.num_blocks, f"bad block id {b}"
                want_ref[b] += 1
        for b, n in ext.items():
            assert 0 <= b < self.num_blocks, f"bad external block id {b}"
            assert n >= 0, f"negative external pin count on block {b}"
            want_ref[b] += n
        bad = [(b, int(self._ref[b]), int(want_ref[b]))
               for b in range(self.num_blocks) if self._ref[b] != want_ref[b]]
        assert not bad, (
            "refcounts disagree with page tables + external pins "
            f"(block, have, want): {bad}")
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate block on free list"
        live = {b for b in range(self.num_blocks) if self._ref[b] > 0}
        assert not (live & free), "block both referenced and free"
        assert len(live) + len(free) == self.num_blocks, (
            "free-list conservation violated")
