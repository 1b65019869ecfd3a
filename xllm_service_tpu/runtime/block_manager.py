"""Paged KV-cache block manager with content-addressed prefix caching.

Engine-tier counterpart of the service's global cache index: allocates
fixed-size token blocks, commits full blocks under their chained murmur3
hash (common/hashing.py — the cross-tier invariant), serves intra-instance
prefix-cache hits, evicts LRU, and accumulates the stored/removed deltas
that the heartbeat reports as a KvCacheEvent
(reference contract: proto/xllm_rpc_service.proto:44-48;
global_kvcache_mgr.cpp:177-225 consumes these on the service side).

Block 0 is reserved as the garbage slot for masked scatter writes
(models/llama.py) and is never allocated.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from xllm_service_tpu.common.hashing import prefix_block_hashes
from xllm_service_tpu.common.types import KvCacheEvent


class OutOfBlocksError(RuntimeError):
    pass


class StateFamilyUnsupported(NotImplementedError):
    """A feature that is not built for a family whose sequence state is a
    recurrent state slot (power retention, models/brumby.py). Raised by
    name at engine build or at the request, never served wrongly."""


@dataclass
class _BlockInfo:
    ref_count: int = 0
    hash: Optional[bytes] = None  # set once the block is full + committed


class BlockManager:
    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        seed: int = 1024,
    ):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.seed = seed
        self._blocks: Dict[int, _BlockInfo] = {
            i: _BlockInfo() for i in range(1, num_blocks)
        }
        self._free: List[int] = list(range(1, num_blocks))
        # hash -> block_id for committed blocks (both live and evictable).
        self._hash_to_block: Dict[bytes, int] = {}
        # Evictable committed blocks in LRU order: block_id -> None.
        self._evictable: OrderedDict[int, None] = OrderedDict()
        # Heartbeat deltas. Guarded by _ev_mu: the heartbeat thread drains
        # them (take_cache_event) while the engine thread mutates.
        self._ev_mu = threading.Lock()
        self._stored: Set[bytes] = set()
        self._removed: Set[bytes] = set()
        self._offloaded: Dict[bytes, str] = {}
        # Optional host-offload hook: called as on_evict([(block_id, hash),
        # ...]) with ALL of an allocation's committed victims BEFORE their
        # device blocks are reused (ONE batched device->host copy, not one
        # sync per block); returns the iterable of hashes actually saved —
        # those become offload_cache['dram'] deltas instead of
        # removed_cache (reference proto:47).
        self.on_evict = None
        # Lifetime eviction count (engine-thread only, like the rest of
        # the class) — exported as xllm_engine_block_evictions_total.
        self.evictions_total = 0

    # ------------------------------------------------------------------ util

    @property
    def num_free_blocks(self) -> int:
        return len(self._free) + len(self._evictable)

    @property
    def num_referenced_blocks(self) -> int:
        """Blocks with live references — 0 when the engine is drained
        (stress-harness invariant; mirrors NativeBlockManager). Like the
        rest of this class, call from the engine thread or after stop()."""
        return sum(1 for b in self._blocks.values() if b.ref_count > 0)

    @property
    def usage(self) -> float:
        total = self.num_blocks - 1
        return (total - self.num_free_blocks) / max(total, 1)

    def can_allocate(self, n: int) -> bool:
        return self.num_free_blocks >= n

    # ------------------------------------------------------------- allocate

    def _evict_batch(self, victims: List[int]) -> None:
        """Un-commit a batch of LRU victims, offering their content to the
        host tier in ONE hook call (one bulk device->host copy)."""
        self.evictions_total += len(victims)
        hashed = [
            (b, self._blocks[b].hash)
            for b in victims
            if self._blocks[b].hash is not None
        ]
        for _, h in hashed:
            del self._hash_to_block[h]
        saved: Set[bytes] = set()
        if self.on_evict is not None and hashed:
            try:
                saved = set(self.on_evict(hashed))
            except Exception:
                saved = set()
        with self._ev_mu:
            for b, h in hashed:
                if h in saved:
                    self._offloaded[h] = "dram"
                    # A transient removal recorded earlier in this batch
                    # (host-pool LRU churn) must not ride the same beat as
                    # the offload — the master applies removed last.
                    self._removed.discard(h)
                else:
                    self._removed.add(h)
                self._stored.discard(h)
                self._blocks[b].hash = None

    def allocate(self, n: int) -> List[int]:
        if not self.can_allocate(n):
            raise OutOfBlocksError(
                f"need {n} blocks, only {self.num_free_blocks} free"
            )
        out = []
        while len(out) < n and self._free:
            out.append(self._free.pop())
        victims = []
        while len(out) + len(victims) < n:
            victim, _ = self._evictable.popitem(last=False)  # LRU
            victims.append(victim)
        if victims:
            self._evict_batch(victims)
            out.extend(victims)
        for b in out:
            self._blocks[b].ref_count = 1
        return out

    def acquire_cached(self, block_id: int) -> None:
        """Take a reference on a committed block found via match_prefix."""
        info = self._blocks[block_id]
        if info.ref_count == 0:
            self._evictable.pop(block_id, None)
        info.ref_count += 1

    def free(self, block_ids: Sequence[int]) -> None:
        for b in block_ids:
            info = self._blocks[b]
            info.ref_count -= 1
            assert info.ref_count >= 0, f"double free of block {b}"
            if info.ref_count == 0:
                if info.hash is not None:
                    self._evictable[b] = None  # keep cached, evictable
                else:
                    self._free.append(b)

    # --------------------------------------------------------- prefix cache

    def commit_block(self, block_id: int, block_hash: bytes) -> None:
        """Register a now-full block under its chained hash. If the hash is
        already cached by another block, the new block stays uncommitted
        (duplicate content; dedup happens on the next match)."""
        if block_hash in self._hash_to_block:
            return
        info = self._blocks[block_id]
        if info.hash is not None:
            return
        info.hash = block_hash
        self._hash_to_block[block_hash] = block_id
        with self._ev_mu:
            self._stored.add(block_hash)
            self._removed.discard(block_hash)
            # Re-promotion: an offloaded block recommitted to HBM (host
            # re-import or recompute) moves the index entry back to the hot
            # tier.
            self._offloaded.pop(block_hash, None)

    def lookup_hash(self, block_hash: bytes) -> Optional[int]:
        """Block id currently committed under this hash, if any."""
        return self._hash_to_block.get(block_hash)

    def committed_hashes(self) -> List[bytes]:
        """Every committed hash (reconcile manifests / cache resync).
        Racy off-thread read by design — callers tolerate one-beat drift;
        the retry only guards resize-during-iteration."""
        for _ in range(3):
            try:
                return list(self._hash_to_block)
            except RuntimeError:
                continue
        return []

    def match_prefix(
        self,
        token_ids: Sequence[int],
        hashes: Optional[List[bytes]] = None,
    ) -> Tuple[int, List[int]]:
        """Longest cached prefix: returns (num_cached_tokens, block_ids) and
        takes a reference on each matched block (same walk as the service's
        GlobalKVCacheMgr.match — global_kvcache_mgr.cpp:73-131). Pass
        `hashes` when the caller already computed the chain (the engine's
        host-tier continuation reuses it)."""
        if hashes is None:
            hashes = prefix_block_hashes(token_ids, self.block_size, self.seed)
        matched: List[int] = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            matched.append(b)
        for b in matched:
            self.acquire_cached(b)
        return len(matched) * self.block_size, matched

    # ------------------------------------------------------------ heartbeat

    def record_tier_offload(self, block_hash: bytes, tier: str) -> None:
        """A colder tier (dram->ssd demotion) now holds this hash. No-op if
        HBM still holds it — the hot location stays authoritative."""
        with self._ev_mu:
            if block_hash in self._hash_to_block:
                return
            self._offloaded[block_hash] = tier
            self._removed.discard(block_hash)
            self._stored.discard(block_hash)

    def record_host_removed(self, block_hash: bytes) -> None:
        """The host tier dropped this hash. Only emit a removal if NO tier
        still holds it (an HBM re-promotion must not be un-indexed)."""
        with self._ev_mu:
            self._offloaded.pop(block_hash, None)
            if block_hash not in self._hash_to_block:
                self._removed.add(block_hash)
                self._stored.discard(block_hash)

    def take_cache_event(self) -> KvCacheEvent:
        """Drain accumulated deltas for the next heartbeat (called from the
        heartbeat thread — atomic swap under the event lock)."""
        with self._ev_mu:
            ev = KvCacheEvent(
                stored_cache=self._stored,
                removed_cache=self._removed,
                offload_cache=self._offloaded,
            )
            self._stored = set()
            self._removed = set()
            self._offloaded = {}
        return ev


class _NoPrefixReuse:
    """The content-addressed half of the block manager, inert: for a
    family whose sequences carry a recurrent state. A state is not
    addressable by block hash, and a prefix hit on the K/V blocks of such
    a family would skip tokens whose state was never computed, so nothing
    is committed, matched or told to the fabric (the engine's build
    refuses the prefix cache's tiers by name; reusing a prefix needs state
    snapshots at chunk boundaries, which are not built). Inert and not
    refused, because the engine thread's drains commit full blocks on the
    way to finishing a sequence."""

    def commit_block(self, block_id: int, block_hash: bytes) -> None:
        pass

    def match_prefix(self, token_ids, hashes=None):
        return 0, []

    def lookup_hash(self, block_hash: bytes):
        return None


class HybridBlockManager(_NoPrefixReuse, BlockManager):
    """Blocks for a family with BOTH kinds of sequence memory
    (models/granite.py): K/V blocks of its attention layers that grow with
    the context, allocated and freed here as for any family, beside one
    state slot for the sequence's life, which is the sequence's row of the
    engine's running rows and so is owned and freed with it
    (`InferenceEngine._free_slots`): admission needs a free row AND blocks,
    and finish, cancel and preemption return both. A preempted sequence
    resumes by recomputing its tokens from position 0 (nothing of it is
    matched), which rewrites its blocks and makes its new slot clean."""


class StateSlotManager(_NoPrefixReuse, BlockManager):
    """Slot ownership for a family whose sequence state is ONE fixed slot
    of the executor's state pool (ops/retention.py), whatever the
    context's length. The engine gives such a family blocks as long as
    `max_seq_len`, so its block arithmetic asks for exactly one block a
    sequence: that block is the slot (slot = block id - 1; id 0 stays
    the dead row's). A sequence owns it from admission to finish, cancel
    or preemption; admission therefore counts slots, not tokens, and a
    preempted sequence resumes by recomputing its tokens from position 0,
    which also makes a freed slot clean (a chunk at position 0 ignores
    what the slot held).

    The content-addressed half is inert (`_NoPrefixReuse`): the one block
    is FULL at exactly `max_seq_len` tokens and the engine thread's drains
    would commit it on the way to finishing the sequence with LENGTH."""

    def __init__(self, slots: int, block_size: int, seed: int = 1024):
        super().__init__(slots + 1, block_size, seed=seed)

    @property
    def num_slots(self) -> int:
        return self.num_blocks - 1

    @property
    def slots_in_use(self) -> int:
        return self.num_slots - len(self._free)

    def allocate(self, n: int) -> List[int]:
        if n != 1:
            raise StateFamilyUnsupported(
                f"state pool: a sequence owns exactly one slot, {n} asked "
                f"(a block is as long as max_seq_len={self.block_size})"
            )
        return super().allocate(1)


class WindowFamilyUnsupported(NotImplementedError):
    """A feature that is not built for a family with window layers, whose
    sequences own blocks of TWO paged pools (models/granite.py). Raised by
    name at engine build or at the request, never served wrongly."""


class SparseFamilyUnsupported(NotImplementedError):
    """A feature that is not built for a family whose attention layers
    SELECT the pages a query reads (ops/sparse_attention.py): its sequences
    own rows of a compressed-key pool beside their K/V blocks, and a state
    slot. Raised by name at engine build or at the request, never served
    wrongly."""


class WindowBlockManager(_NoPrefixReuse, BlockManager):
    """Blocks for a family whose attention layers are of two kinds
    (models/granite.py): the FULL layers' K/V in this manager's pool,
    allocated and freed here as for any family, and the WINDOW layers' K/V
    in a second pool (`self.window`, a BlockManager of its own ids) whose
    blocks a sequence holds only while some position in them is within
    `sliding_window` of its next one.

    A sequence's blocks are the engine's `seq.block_ids`, full-pool ids
    indexed by position // block_size. The window block of logical block
    j, while it lives, is PAIRED with the full block at j (`_beside`):
    `slide` makes the pairs of a range live and frees the ones behind it,
    and `free` returns the window block of every full block it is given,
    so finish, abort, preemption and recompute return both pools through
    the calls the engine already makes. Every block has one owner (the
    content-addressed half is inert: a hit on the full pool's blocks would
    skip tokens whose window rows were never written; matching the window
    blocks too is not built), which is what makes the pairing sound."""

    def __init__(self, num_blocks: int, window_blocks: int, block_size: int,
                 seed: int = 1024):
        super().__init__(num_blocks, block_size, seed=seed)
        self.window = BlockManager(window_blocks, block_size, seed=seed)
        self._beside: Dict[int, int] = {}  # full block id -> its window block
        self.window_blocks_freed = 0  # behind a sequence (not at its end)

    @property
    def window_blocks_live(self) -> int:
        return len(self._beside)

    def slide(self, block_ids: Sequence[int], was_lo: int, lo: int, hi: int,
              row) -> int:
        """Move a sequence's window to logical blocks [lo, hi): free the
        window blocks of [was_lo, lo) (the caller's last `lo`), make those
        of [lo, hi) live, and write the window table into `row` (window
        block ids at [lo, hi), 0 at [was_lo, lo): a freed entry is the
        garbage block's, and no walk of a kernel reaches it). Returns
        max(was_lo, lo), the caller's next `was_lo`."""
        behind = [
            w for w in (self._beside.pop(b, 0) for b in block_ids[was_lo:lo]) if w
        ]
        if behind:
            self.window.free(behind)
            self.window_blocks_freed += len(behind)
        row[was_lo:lo] = 0
        for j in range(lo, min(hi, len(block_ids))):
            w = self._beside.get(block_ids[j])
            if w is None:
                w = self._beside[block_ids[j]] = self.window.allocate(1)[0]
            row[j] = w
        return max(was_lo, lo)

    def free(self, block_ids: Sequence[int]) -> None:
        beside = [w for w in (self._beside.pop(b, 0) for b in block_ids) if w]
        if beside:
            self.window.free(beside)
        super().free(block_ids)
