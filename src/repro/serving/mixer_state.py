"""MixerState: pluggable per-request cache layouts for the engine.

The serving engine schedules heterogeneous mixer stacks through ONE
protocol.  Every layout answers the same request-lifecycle calls
(``MixerState`` below); the engine and scheduler never branch on the
architecture family.  Three concrete layouts exist:

  * **paged KV / latent blocks** (``block_cache.BlockKVCache`` with
    ``ring_blocks=0``) — full-attention GQA stacks page per-head K/V
    token blocks; MLA stacks page compressed (c_kv, k_rope) latent
    blocks.  Refcounts, prefix cache, copy-on-write and swap-to-host
    all operate on physical block ids.

  * **ring-buffer block tables** (``BlockKVCache`` with
    ``ring_blocks=N``) — sliding-window attention (and windowed MLA)
    wraps the logical block index modulo a window-sized table, so the
    trailing block is recycled to the front as the window advances and
    a request's block list never exceeds the window.  Prefix-index
    depth is capped at the ring (blocks past the window get
    overwritten, so only the head of the prompt is ever shareable).

  * **per-slot recurrent snapshots** (``RecurrentSlotState``) — SSM
    (mamba2 SSD) layers keep O(1) state per request: one slot in a
    fixed pool holding (hidden state, conv tail).  There is no block
    table and nothing pages; swap/preempt snapshots the whole slot to
    host and back.

Recurrent state is O(1) in sequence length, so unlike KV blocks a
shared prompt head cannot be adopted by aliasing storage — but its
STATE can be replayed: ``SlotSnapshotIndex`` keeps a fixed device pool
of whole-state snapshots captured at block-aligned prefill boundaries,
keyed by the same sha256 hash chain the block-family ``PrefixIndex``
uses.  An incoming prompt restores the deepest matching snapshot into
its slot and starts prefill past it, which is what lets mamba2/jamba
traffic skip shared prompt heads at all.

``layer_layouts`` assigns one layout per layer from the arch config, so
hybrid stacks (jamba: SSD + periodic attention) compose layouts — the
composite cache in ``block_cache.MixerStateCache`` owns one
block-family state and/or one slot-family state and fans the calls out.
"""
from __future__ import annotations

import abc
import functools
import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers import mamba2
from repro.models.transformer import layer_plan
from repro.serving.tracing import Tracer

LAYOUT_PAGED = "paged"     # unbounded block table (full attention)
LAYOUT_RING = "ring"       # window-sized circular block table
LAYOUT_SLOT = "slot"       # per-request recurrent state slot


def chunk_key(parent: str, tokens: np.ndarray) -> str:
    """Content hash of one full token block, chained on the parent
    block's key so equal windows at different prefix depths differ.
    Shared by the block-family ``PrefixIndex`` and the slot-family
    ``SlotSnapshotIndex`` — one prompt walks ONE chain."""
    h = hashlib.sha256()
    h.update(parent.encode())
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.hexdigest()


def layer_layouts(cfg) -> list[str]:
    """One mixer-state layout per layer (plan order)."""
    out = []
    for mix, _f in layer_plan(cfg):
        if mix == "ssm":
            out.append(LAYOUT_SLOT)
        elif cfg.sliding_window:
            out.append(LAYOUT_RING)
        else:
            out.append(LAYOUT_PAGED)
    return out


def ring_block_count(window: int, block_size: int,
                     prefill_chunk: int) -> int:
    """Blocks a sliding-window ring table needs.

    The ring must still hold every key a query can attend AFTER a full
    prefill chunk lands: the first chunk query at position L needs keys
    back to L - window + 1 while the newest write sits at
    L + chunk - 1, so capacity >= window + chunk - 1 tokens.
    """
    return -(-(window + max(prefill_chunk, 1) - 1) // block_size)


class MixerState(abc.ABC):
    """Request-lifecycle protocol every mixer-state layout implements.

    A layout owns the device pools for ITS layers plus whatever
    bookkeeping maps a request onto them (block lists, slot ids).  The
    scheduler/engine drive requests exclusively through these calls;
    "no capacity" is always reported by returning False so the caller
    can preempt, never by raising.
    """

    @abc.abstractmethod
    def alloc_prompt(self, req) -> bool:
        """Admission-time allocation for req's prompt (all-or-nothing)."""

    @abc.abstractmethod
    def ensure_capacity(self, req, n_tokens: int) -> bool:
        """Grow req's state to cover n_tokens; False under pressure."""

    @abc.abstractmethod
    def release(self, req):
        """Drop req's references; state becomes reclaimable."""

    @abc.abstractmethod
    def swap_out(self, req):
        """Park req's state on host; device references drop."""

    @abc.abstractmethod
    def swap_in(self, req) -> bool | None:
        """Restore req's state.  True = resumed; False = retry later
        (pool short); None = content lost, caller must recompute."""

    def make_writable(self, req, idx: int) -> bool:
        """Copy-on-write hook (block layouts); slots are never shared."""
        return True

    def writable_indices(self, pos: int, n: int) -> range:
        """Logical indices a write of n tokens at pos touches."""
        return range(0)


# Slot-pool device updates follow the same donation discipline as the
# engine steps: the old pool buffer is donated so XLA updates one slot
# in place instead of double-buffering the whole pool.

@functools.partial(jax.jit, donate_argnums=(0,))
def _slot_zero(pool, slot):
    with jax.named_scope("ssm_state"):
        return {k: v.at[slot].set(0.0) for k, v in pool.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _slot_restore(pool, slot, host):
    with jax.named_scope("ssm_state"):
        return {k: v.at[slot].set(host[k]) for k, v in pool.items()}


# store AND restore are the same device-to-device row copy with the
# destination pool donated: store writes a live slot into the snapshot
# pool, restore writes a snapshot row into the live pool
_snap_copy = functools.partial(jax.jit, donate_argnums=(0,))(
    mamba2.copy_slot)


class SlotSnapshotIndex:
    """content-hash -> snapshot row over a fixed device pool of
    recurrent-state captures, LRU-ordered for eviction.

    Each row holds one layer-stack's worth of (SSD hidden state, conv
    tail) exactly as it stood after some block-aligned prompt prefix —
    the recurrent analogue of a prefix-cached KV block chain.  Entries
    are STANDALONE (a snapshot captures the whole state at its depth),
    so unlike ``PrefixIndex`` there is no parent chaining, nothing can
    be orphaned, and eviction is plain LRU row recycling."""

    def __init__(self, cfg, n_layers: int, capacity: int,
                 dtype=np.float32):
        if capacity < 1:
            raise ValueError("need at least one snapshot slot")
        self.capacity = capacity
        self.pools = [mamba2.init_paged_state(cfg, capacity, dtype)
                      for _ in range(n_layers)]
        self._map: OrderedDict[str, int] = OrderedDict()  # key -> row
        self._free = list(range(capacity))
        self.stores = 0
        self.evictions = 0
        self.peak_used = 0

    def __len__(self) -> int:
        return len(self._map)

    def __contains__(self, key: str) -> bool:
        return key in self._map

    def lookup(self, key: str) -> int | None:
        row = self._map.get(key)
        if row is not None:
            self._map.move_to_end(key)
        return row

    def store(self, key: str, live_pools: list, slot: int) -> bool:
        """Capture ``slot``'s state from every layer's live pool under
        ``key``; recycles the LRU row when the pool is full.  A
        duplicate key keeps the existing snapshot (the state under one
        content hash is deterministic, so it is the same bits)."""
        if key in self._map:
            self._map.move_to_end(key)
            return False
        if not self._free:
            _, row = self._map.popitem(last=False)       # LRU entry
            self._free.append(row)
            self.evictions += 1
        row = self._free.pop()
        src, dst = jnp.int32(slot), jnp.int32(row)
        for li in range(len(self.pools)):
            self.pools[li] = _snap_copy(self.pools[li], dst,
                                        live_pools[li], src)
        self._map[key] = row
        self.stores += 1
        self.peak_used = max(self.peak_used, len(self._map))
        return True

    def flush(self):
        """Drop every entry (rows return to the free list)."""
        self._free.extend(self._map.values())
        self._map.clear()

    def reset_stats(self):
        self.stores = self.evictions = 0
        self.peak_used = len(self._map)


class RecurrentSlotState(MixerState):
    """Per-slot recurrent snapshots: the SSM mixer-state layout.

    Pool shape per layer: (num_slots, ...) SSD hidden state + conv
    tail.  Slot 0 is reserved scratch (padded batch rows write there).
    A request owns exactly one slot for its whole life, regardless of
    sequence length; slots are zeroed on allocation (the previous
    owner's state is arbitrary) and snapshotted whole on swap.

    With ``snapshot_slots > 0`` the layout additionally runs a
    ``SlotSnapshotIndex``: block-aligned prefill states are published
    under the prompt's content-hash chain, an incoming prompt restores
    the deepest matching snapshot and starts prefill past it
    (``match_prefix`` / ``alloc_prompt``), and a request parked by
    swap exactly AT a registered snapshot skips the host round-trip —
    swap-in re-adopts the snapshot by hash, with the ``swap_lost``
    recompute fallback when the entry was evicted while parked.
    """

    def __init__(self, cfg, layer_ids: list[int], num_slots: int,
                 dtype=np.float32, *, block_size: int = 0,
                 snapshot_slots: int = 0, prefill_chunk: int = 0,
                 tracer: Tracer | None = None):
        # BlockAllocator gives the same reserved-id-0 free-list +
        # invariant checking a slot pool needs (slots are just blocks
        # that are never shared)
        from repro.serving.block_cache import BlockAllocator
        self.cfg = cfg
        # snapshot copy timings go through the tracer span API (shared
        # with the engine; standalone instances get a disabled one)
        self.tracer = tracer if tracer is not None else Tracer()
        self.layer_ids = list(layer_ids)
        self.num_slots = num_slots
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.allocator = BlockAllocator(num_slots)
        self.pools = [mamba2.init_paged_state(cfg, num_slots, dtype)
                      for _ in self.layer_ids]
        self.snapshots = (
            SlotSnapshotIndex(cfg, len(self.layer_ids), snapshot_slots,
                              dtype)
            if snapshot_slots > 0 and block_size > 0 else None)
        self.peak_used = 0
        self.swapped_slots = 0
        # snapshot-index counters (engine.stats surfaces these)
        self.snap_queries = 0            # full prompt blocks walked
        self.snap_hits = 0               # blocks-worth of state adopted
        self.skipped_prefill_tokens = 0  # prompt tokens never re-prefilled
        self.readopted_snapshots = 0     # swap-ins served from the index

    def reset_stats(self, *, flush_snapshots: bool = False):
        self.peak_used = 0
        self.tracer.reset_spans("snapshot_out", "snapshot_in")
        self.swapped_slots = 0
        self.snap_queries = self.snap_hits = 0
        self.skipped_prefill_tokens = self.readopted_snapshots = 0
        if self.snapshots is not None:
            if flush_snapshots:
                self.snapshots.flush()
            self.snapshots.reset_stats()

    # ---------------------------------------------------- prefix match

    def match_prefix(self, prompt: np.ndarray, limit: int | None = None
                     ) -> tuple[int, str, int]:
        """(adoptable tokens, snapshot key, full blocks walked).

        Snapshots are standalone whole-state captures, so unlike the
        attn hash chain a missing depth does not block a deeper hit —
        the deepest present entry wins.  Depth is capped at
        prompt_len - 1: at least one prompt token must still prefill to
        produce first-token logits, and re-running it from a
        full-prompt snapshot would fold it into the recurrent state
        TWICE (the block layouts' re-prefill-the-last-token trick is
        idempotent only for positional KV writes).  ``limit`` (hybrid
        stacks) additionally caps the depth at the attn chain's matched
        depth — every layer must resume from the same position."""
        if self.snapshots is None:
            return 0, "", 0
        bs = self.block_size
        n_full = (len(prompt) - 1) // bs
        if limit is not None:
            n_full = min(n_full, limit // bs)
        if not len(self.snapshots):
            return 0, "", n_full       # nothing to hash against
        best_tok, best_key, parent = 0, "", ""
        for j in range(n_full):
            key = chunk_key(parent, prompt[j * bs:(j + 1) * bs])
            if key in self.snapshots:
                best_tok, best_key = (j + 1) * bs, key
            parent = key
        if best_key:
            self.snapshots.lookup(best_key)      # LRU-touch the winner
        return best_tok, best_key, n_full

    # ------------------------------------------------------- lifecycle

    def alloc_prompt(self, req, match: tuple[int, str, int] = (0, "", 0),
                     count: bool = True) -> bool:
        """Admission-time allocation: give req a slot and, when
        ``match`` names a snapshot (from ``match_prefix``), restore it
        and start the request past the matched tokens (prefill skip).
        ``count=False`` defers the stat counting to the caller — the
        composite cache counts only once the WHOLE admission succeeded
        (the attn side may still come up short after this)."""
        n_tok, key, walked = match
        if not self._alloc_slot(req, zero=not n_tok):
            return False
        if n_tok:
            row = self.snapshots.lookup(key)
            # nothing between match and here evicts snapshot entries
            assert row is not None, "matched snapshot vanished"
            slot = jnp.int32(req.slot)
            for li in range(len(self.pools)):
                self.pools[li] = _snap_copy(self.pools[li], slot,
                                            self.snapshots.pools[li],
                                            jnp.int32(row))
            req.pos = n_tok
            req.skipped_prefill = n_tok
            req.snap_registered = n_tok // self.block_size
            req.snap_key = key
        if count:
            self.count_match(match)
        return True

    def count_match(self, match: tuple[int, str, int]):
        """Fold one admission's match into the hit counters — called
        only for ADMITTED requests, mirroring the block index (a
        deferred request re-matches every retry and would otherwise
        distort the hit rate)."""
        if self.snapshots is None:
            return
        n_tok, _key, walked = match
        hits = n_tok // self.block_size if n_tok else 0
        self.snap_queries += min(hits + 1, walked)
        self.snap_hits += hits
        self.skipped_prefill_tokens += n_tok

    def register_snapshot(self, req):
        """Publish req's CURRENT recurrent state into the snapshot
        index when it sits at a chunk-grid-aligned block boundary.

        Two alignment constraints, not one: boundaries crossed
        mid-chunk have no materialized state (the hash chain still
        walks through their blocks), and a position that is a chunk
        END without being a chunk MULTIPLE (the partial final chunk of
        a prompt can end block-aligned) must not be captured either —
        a consumer resuming there would run its remaining prefill on a
        SHIFTED chunk grid, and the SSD dual form's fp association
        differs across groupings, breaking the snapshots-on/off
        token-identity contract."""
        if self.snapshots is None:
            return
        bs = self.block_size
        pos = req.pos
        if pos == 0 or pos > req.prompt_len or pos % bs:
            return
        if self.prefill_chunk > 1 and pos % self.prefill_chunk:
            return
        depth = pos // bs
        if depth <= req.snap_registered:
            return
        key = req.snap_key
        for j in range(req.snap_registered, depth):
            key = chunk_key(key, req.prompt[j * bs:(j + 1) * bs])
        self.snapshots.store(key, self.pools, req.slot)
        req.snap_registered = depth
        req.snap_key = key

    def ensure_capacity(self, req, n_tokens: int) -> bool:
        return self._alloc_slot(req, zero=True)

    def _alloc_slot(self, req, *, zero: bool) -> bool:
        """Give req a slot if it lacks one.  ``zero`` wipes the previous
        owner's state; a swap_in skips it (the restore overwrites the
        whole slot anyway)."""
        if req.slot is not None:
            return True
        got = self.allocator.alloc(1)
        if got is None:
            return False
        req.slot = got[0]
        if zero:
            slot = jnp.int32(req.slot)
            for li in range(len(self.pools)):
                self.pools[li] = _slot_zero(self.pools[li], slot)
        self.peak_used = max(self.peak_used, self.allocator.num_used)
        return True

    def release(self, req):
        if req.slot is not None:
            self.allocator.free([req.slot])
            req.slot = None

    def swap_out(self, req, peer: "RecurrentSlotState | None" = None):
        """Park req's slot state on the host — or, with ``peer``, decide
        re-adoption against the PEER's snapshot index (swap-to-peer): if
        the destination already holds the snapshot for the parked depth
        by content hash, no state crosses shards at all."""
        with self.tracer.span("snapshot_out", rid=req.rid) as sp:
            bs = self.block_size
            index = self.snapshots if peer is None else peer.snapshots
            if (index is not None and req.pos
                    and req.pos <= req.prompt_len and req.pos % bs == 0
                    and req.snap_registered == req.pos // bs
                    and req.snap_key in index):
                # the parked state IS a snapshot still RESIDENT in the
                # index: skip the D2H trip — swap_in re-adopts it by
                # content hash.  (The membership check matters: for an
                # already-recycled entry the host copy is far cheaper
                # than the swap_lost full recompute.  Eviction between
                # here and swap_in still falls back to recompute.)
                req.snap_readopt = True
                sp.extra["bytes"] = 0        # content resident on peer
            else:
                s = req.slot
                req.host_state = [
                    {k: np.ascontiguousarray(jax.device_get(v[s]))
                     for k, v in pool.items()}
                    for pool in self.pools]
                self.swapped_slots += 1
                sp.extra["bytes"] = sum(int(a.nbytes)
                                        for layer in req.host_state
                                        for a in layer.values())
            self.release(req)

    def swap_in(self, req) -> bool | None:
        if req.snap_readopt:
            # req.snap_key is the chain key at the parked depth (the
            # swap_out condition pinned snap_registered == pos//bs)
            row = (self.snapshots.lookup(req.snap_key)
                   if self.snapshots is not None else None)
            if row is None:
                return None              # evicted while parked: recompute
            if not self._alloc_slot(req, zero=False):
                return False
            with self.tracer.span("snapshot_in", rid=req.rid,
                                  readopt=True):
                slot = jnp.int32(req.slot)
                for li in range(len(self.pools)):
                    self.pools[li] = _snap_copy(self.pools[li], slot,
                                                self.snapshots.pools[li],
                                                jnp.int32(row))
            req.snap_readopt = False
            self.readopted_snapshots += 1
            return True
        if not self._alloc_slot(req, zero=False):
            return False
        with self.tracer.span("snapshot_in", rid=req.rid):
            slot = jnp.int32(req.slot)
            for li, host in enumerate(req.host_state):
                self.pools[li] = _slot_restore(self.pools[li], slot, host)
            jax.block_until_ready([p["h"] for p in self.pools])
            req.host_state = None
        return True

    # ------------------------------------------------------------ step

    def slot_rows(self, reqs, batch: int) -> np.ndarray:
        """(batch,) slot ids; padded rows point at scratch slot 0."""
        slots = np.zeros(batch, np.int32)
        for i, r in enumerate(reqs):
            slots[i] = 0 if r.slot is None else r.slot
        return slots

    def stats(self) -> dict:
        cap = self.allocator.capacity
        out = {
            "layout": LAYOUT_SLOT,
            "layers": len(self.layer_ids),
            "num_slots": cap,
            "used_slots": self.allocator.num_used,
            "peak_used_slots": self.peak_used,
            "occupancy": self.peak_used / cap if cap else 0.0,
            "swapped_slots": self.swapped_slots,
        }
        s = self.snapshots
        out["snapshot_slots"] = s.capacity if s else 0
        out["cached_snapshots"] = len(s) if s else 0
        out["snapshot_occupancy"] = (s.peak_used / s.capacity
                                     if s else 0.0)
        return out
