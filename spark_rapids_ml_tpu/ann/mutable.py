#
# Live IVF-Flat index mutation (srml-stream, the ann/ half).
#
# The reference's FAISS/cuML ANN tier rebuilds an index to change it; this
# module mutates a SERVING IVF-Flat index in place:
#
#   add_items:    new rows are assigned to their nearest coarse list by the
#                 SAME fused distance+argmin kernel that built the index
#                 (assign_nearest — cached executable, zero new compiles at
#                 a seen row bucket) and appended into the free slots of
#                 the existing (nlist_pad, L_pad, D) pow2 geometry.
#   delete_items: per-list TOMBSTONE bitmap; a tombstoned slot's stored
#                 ||x||^2 flips to +inf, so its expanded-form distance is
#                 +inf and it can never win a probe slot — the probe
#                 kernel is UNCHANGED (no new compile, no mask argument),
#                 and the host id map already turns inf-distance rows into
#                 the -1 sentinel.  Slots are reclaimed at repack.
#   repack:       when a list outgrows L_pad (or tombstones accumulate),
#                 the live rows re-lay into the NEXT pow2 slot bucket; the
#                 new geometry's probe kernels are warmed ON THE
#                 PRECOMPILE POOL before the atomic index swap, so probes
#                 never block on the repack (searches keep hitting the old
#                 staged index until the swap instant) and the next search
#                 dispatches a ready executable.
#
# Concurrency model: mutators serialize on one lock; readers take an
# ATOMIC SNAPSHOT of the staged index reference and search it lock-free —
# a search overlapping a mutation sees either the whole old index or the
# whole new one, never a half-written state.  The coarse quantizer is
# FIXED for the index lifetime (the FAISS semantics): adds assign to the
# existing centroids, so heavy drift degrades list balance, not
# correctness — rebuild when the distribution moves (docs/ann_engine.md
# §incremental-mutation).
#

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import profiling, sanitize
from ..ops.precompile import shape_bucket
from .ivfflat import (
    IVFFlatIndex,
    PackedIVF,
    TieredIVFFlatIndex,
    _MIN_LIST_SLOTS,
    assign_nearest,
    item_norms,
    ivfflat_search_prepared,
    padded_host_layout,
    stage_padded_layout,
    tiered_stage_padded_layout,
    warm_probe_kernels,
)


class MutableIVFIndex:
    """A PackedIVF staged for one mesh with live add/delete/repack.

    Host mirrors (padded data/norms/ids/counts + the tombstone bitmap +
    an id->position map) are the source of truth; every mutation updates
    the mirrors and restages the touched device buffers (a device_put,
    never a compile), then swaps the staged IVFFlatIndex reference
    atomically.  `index` is the snapshot readers search."""

    def __init__(
        self,
        packed: PackedIVF,
        mesh: Any,
        hot_fraction: float = 1.0,
        pool_slots: Optional[int] = None,
    ):
        self._mesh = mesh
        # hot_fraction < 1 opts into TIERED staging (ann/tier.py): the tier's
        # host planes are views of this holder's mirrors, so in-place
        # mutations are visible to every later page-in; deletes additionally
        # refresh() the touched lists' RESIDENT copies so tombstones are
        # honored device-side immediately (the tombstone-interaction gate)
        self._hot_fraction = float(hot_fraction)
        self._pool_slots = pool_slots
        self._lock = sanitize.lockdep_lock(
            "ann.mutable.mutator", factory=threading.RLock
        )
        (
            self._data, self._norms, self._ids, self._counts,
            self._cpad, self._c_norm, self._nlist_pad, self._l_pad,
        ) = padded_host_layout(packed, mesh)
        self._n_lists = packed.n_lists
        self._live = int(packed.n_items)
        # per-list tombstone bitmap: bit set => slot holds a deleted item
        # awaiting reclamation (np.packbits over the slot axis)
        self._tombstones = np.zeros(
            (self._nlist_pad, self._l_pad), dtype=bool
        )
        self._dead = 0
        live = self._ids >= 0
        self._pos_of_id: Dict[int, int] = {
            int(i): int(p) for p, i in zip(np.flatnonzero(live), self._ids[live])
        }
        # probe geometries to re-warm before a repack swap: {(k, nprobe,
        # query_block)} noted by search()/the serving warm hook.  Guarded
        # by its OWN lock: noting a spec is on the READ path, and taking
        # the mutator lock there would stall searches behind a repack's
        # staging + compile wait — the blocking the snapshot design avoids
        self._spec_lock = sanitize.lockdep_lock("ann.mutable.warmspec")
        self._warm_specs: set = set()
        self._repacks = 0
        self._index = self._stage()

    # -- read side ---------------------------------------------------------
    @property
    def index(self) -> IVFFlatIndex:
        """Atomic snapshot of the staged index (searches hold the returned
        object; a concurrent mutation swaps the reference, never the
        buffers a running search reads).  Deliberately LOCK-FREE: the
        reference read is atomic, and taking the mutator lock here would
        stall every probe behind a repack's layout+warm work — exactly the
        blocking the snapshot design exists to avoid."""
        return self._index

    @property
    def n_items(self) -> int:
        with self._lock:
            return self._live

    def tombstone_bitmap(self) -> np.ndarray:
        """(nlist_pad, ceil(L_pad/8)) uint8 — the packed per-list tombstone
        bitmap (introspection/persistence surface; the mutation hot path
        keeps the unpacked bool mirror)."""
        with self._lock:
            return np.packbits(self._tombstones, axis=1)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "n_items": self._live,
                "tombstoned": self._dead,
                "n_lists": self._n_lists,
                "l_pad": self._l_pad,
                "repacks": self._repacks,
                "device_bytes": self._index.device_bytes(),
            }

    def search(
        self, queries: np.ndarray, k: int, nprobe: int, **kw: Any
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Probed search against the current snapshot (lock-free after the
        snapshot read).  Notes the (k, nprobe, block) geometry so a later
        repack can warm the successor's kernels before the swap."""
        idx = self.index
        self._note_spec(k, nprobe, queries.shape[0] if hasattr(queries, "shape") else None)
        return ivfflat_search_prepared(idx, queries, k, nprobe, self._mesh, **kw)

    def register_warm(self, k: int, nprobe: int, n_queries: int) -> None:
        """Record a probe geometry the serving plane dispatches (the
        serve.ann warm hook calls this) so repack re-warms it."""
        self._note_spec(k, nprobe, n_queries)

    def _note_spec(self, k: int, nprobe: int, n_queries: Optional[int]) -> None:
        from ..ops.knn import _query_block_bucket

        block = _query_block_bucket(n_queries or 8192, 8192)
        with self._spec_lock:
            self._warm_specs.add((int(k), int(nprobe), int(block)))

    # -- mutation ----------------------------------------------------------
    def add_items(self, items: np.ndarray, ids: np.ndarray) -> None:
        """Append rows into their nearest lists' free slots.  Lists that
        would overflow L_pad trigger a repack to the pow2 bucket that fits
        (reclaiming tombstones first — the common case needs no growth).
        Duplicate ids fail loudly before any state changes."""
        items = np.ascontiguousarray(np.asarray(items), dtype=np.float32)
        ids = np.asarray(ids, dtype=np.int64)
        if items.ndim != 2 or items.shape[1] != self._data.shape[1]:
            raise ValueError(
                f"items must be (n, {self._data.shape[1]}); got {items.shape}"
            )
        if items.shape[0] != ids.shape[0]:
            raise ValueError(
                f"{items.shape[0]} items vs {ids.shape[0]} ids"
            )
        if items.shape[0] == 0:
            return
        if len(np.unique(ids)) != len(ids):
            raise ValueError("duplicate ids within the added batch")
        # nearest-list assignment OUTSIDE the lock (device work; the fixed
        # centroids it reads never mutate)
        assign = assign_nearest(
            items, self._cpad[: self._n_lists],
            phase="ann.mutate.assign", counter="ann.mutate.assign_blocks",
        )
        with self._lock:
            dup = [int(i) for i in ids if int(i) in self._pos_of_id]
            if dup:
                raise ValueError(
                    f"ids already present in the index: {dup[:8]}"
                    f"{'...' if len(dup) > 8 else ''}"
                )
            demand = np.bincount(assign, minlength=self._nlist_pad)
            need = self._counts + demand
            if int(need.max()) > self._l_pad:
                # reclaim tombstones and grow to the pow2 bucket that fits
                live_need = (
                    self._counts
                    - self._tombstones.sum(axis=1).astype(np.int64)
                    + demand
                )
                self._repack_locked(
                    shape_bucket(int(live_need.max()), lo=_MIN_LIST_SLOTS)
                )
            norms = item_norms(items)
            order = np.argsort(assign, kind="stable")
            sorted_assign = assign[order]
            # slot offset of each row within its list for THIS batch:
            # arange minus the first index of the row's group
            starts = np.searchsorted(sorted_assign, sorted_assign, side="left")
            within = np.arange(len(order), dtype=np.int64) - starts
            pos = (
                sorted_assign * self._l_pad
                + self._counts[sorted_assign]
                + within
            )
            grew = self._l_pad != self._index.l_pad
            self._data[pos] = items[order]
            self._norms[pos] = norms[order]
            self._ids[pos] = ids[order]
            self._counts += demand
            for i, p in zip(ids[order], pos):
                self._pos_of_id[int(i)] = int(p)
            self._live += items.shape[0]
            staged = self._stage()
            if grew:
                # a repack changed the probe geometry: warm its kernels
                # from the FINAL staged buffers before the swap, so the
                # first post-swap search dispatches a ready executable
                # (probes keep serving the old snapshot meanwhile)
                # graftlint: disable=R11 (compile wait holds only the mutator lock, by design: probes are lock-free on the snapshot, and releasing mid-mutation would tear the staged swap)
                self._warm_for(staged)
            self._index = staged
            profiling.incr_counter("ann.mutate.adds", items.shape[0])

    def delete_items(self, ids: np.ndarray) -> int:
        """Tombstone rows by user id: the slot's stored norm flips to +inf
        (its probe distance becomes +inf — the unchanged kernel can never
        select it ahead of a live candidate) and its id leaves the map.
        Returns the number of rows actually deleted; unknown ids are
        ignored (idempotent deletes).  Only the small (nlist_pad, L_pad)
        norm plane restages — the data buffer is untouched."""
        removed = 0
        touched: List[int] = []
        with self._lock:
            for i in np.asarray(ids, dtype=np.int64):
                pos = self._pos_of_id.pop(int(i), None)
                if pos is None:
                    continue
                lst, slot = divmod(pos, self._l_pad)
                self._tombstones[lst, slot] = True
                self._norms[pos] = np.inf
                self._ids[pos] = -1
                touched.append(int(lst))
                removed += 1
            if removed:
                self._live -= removed
                self._dead += removed
                self._index = self._swap_norms(np.unique(touched))
                profiling.incr_counter("ann.mutate.deletes", removed)
        return removed

    def repack(self, l_pad: Optional[int] = None) -> None:
        """Reclaim tombstoned slots (and optionally re-bucket): live rows
        re-lay contiguously, L_pad re-derives from the longest LIVE list
        (or is forced), the successor geometry's probe kernels warm on the
        precompile pool, and the staged index swaps atomically — probes in
        flight finish on the old geometry, the next search dispatches the
        warmed successor executable."""
        with self._lock:
            self._repack_locked(l_pad)
            staged = self._stage()
            if staged.l_pad != self._index.l_pad:
                # graftlint: disable=R11 (compile wait holds only the mutator lock, by design: probes are lock-free on the snapshot, and releasing mid-repack would tear the staged swap)
                self._warm_for(staged)
            self._index = staged

    def to_packed(self) -> PackedIVF:
        """Compacted mesh-independent payload of the LIVE rows — what a
        model persists after a mutation session (ApproximateNearestNeighborsModel
        .freeze_mutations)."""
        with self._lock:
            return self._to_packed_locked()

    # -- internals (lock held) ---------------------------------------------
    def _repack_locked(self, l_pad: Optional[int]) -> None:
        packed = self._to_packed_locked()
        new_l = l_pad or shape_bucket(
            int(max(packed.counts.max(), 1)), lo=_MIN_LIST_SLOTS
        )
        (
            self._data, self._norms, self._ids, self._counts,
            self._cpad, self._c_norm, self._nlist_pad, self._l_pad,
        ) = padded_host_layout(packed, self._mesh, l_pad=new_l)
        self._tombstones = np.zeros((self._nlist_pad, self._l_pad), bool)
        self._dead = 0
        live = self._ids >= 0
        self._pos_of_id = {
            int(i): int(p) for p, i in zip(np.flatnonzero(live), self._ids[live])
        }
        self._repacks += 1
        profiling.incr_counter("ann.mutate.repacks")

    def _warm_for(self, staged: IVFFlatIndex) -> None:
        """Warm every noted probe geometry against a freshly staged index
        and WAIT for the compiles, so the first search after the caller's
        swap dispatches a ready executable (the zero-steady-compile gate
        across repacks).  Probes keep serving the old snapshot meanwhile —
        the swap happens only after this returns."""
        with self._spec_lock:
            specs = sorted(self._warm_specs)
        keys: List = []
        for k, nprobe, block in specs:
            keys.extend(
                warm_probe_kernels(
                    staged, k, nprobe, self._mesh, n_queries=block
                )
            )
        if keys:
            from ..ops.precompile import global_precompiler

            global_precompiler().wait(keys)

    def _to_packed_locked(self) -> PackedIVF:
        live_counts = (
            self._counts - self._tombstones.sum(axis=1).astype(np.int64)
        )
        items, ids = [], []
        for lst in range(self._nlist_pad):
            base = lst * self._l_pad
            sl = slice(base, base + int(self._counts[lst]))
            keep = self._ids[sl] >= 0
            items.append(self._data[sl][keep])
            ids.append(self._ids[sl][keep])
        return PackedIVF(
            np.concatenate(items) if items else self._data[:0],
            np.concatenate(ids) if ids else self._ids[:0],
            live_counts,
            self._cpad[: self._n_lists].copy(),
            self._n_lists,
            self._live,
        )

    def _stage(self) -> IVFFlatIndex:
        # ids are COPIED into the snapshot: the staged index host-maps
        # positions through index.ids, and handing it the live mirror
        # would let a later in-place add/delete mutate an older snapshot
        # a concurrent search still holds (device buffers are immutable
        # uploads, so they need no copy)
        if self._hot_fraction < 1.0:
            # tiered restage: a NEW slot pool over the (possibly regrown)
            # mirrors — device_puts plus cached slot writes, never a compile
            idx = tiered_stage_padded_layout(
                self._data, self._norms, self._ids.copy(), self._counts,
                self._cpad, self._c_norm, self._nlist_pad, self._l_pad,
                self._live, self._n_lists, self._mesh,
                self._hot_fraction, self._pool_slots,
            )
            profiling.incr_counter(
                "ann.mutate.bytes", int(idx.tier.device_bytes())
            )
            return idx
        idx = stage_padded_layout(
            self._data, self._norms, self._ids.copy(), self._counts,
            self._cpad, self._c_norm, self._nlist_pad, self._l_pad,
            self._live, self._n_lists, self._mesh,
        )
        profiling.incr_counter(
            "ann.mutate.bytes", int(self._data.nbytes + self._norms.nbytes)
        )
        return idx

    def _swap_norms(self, touched_lists: np.ndarray) -> IVFFlatIndex:
        """Delete-path restage: only the (nlist_pad, L_pad) norm plane
        re-uploads; the data/counts/centroid device buffers carry over.
        Tiered: the mirror edit is already visible to future page-ins
        (views), so only the touched lists' RESIDENT slot copies re-page —
        paged-in cold lists honor the tombstone bitmap either way."""
        import jax

        from ..parallel.mesh import axis_sharding

        old = self._index
        if isinstance(old, TieredIVFFlatIndex):
            old.tier.refresh(touched_lists)
            return TieredIVFFlatIndex(
                tier=old.tier,
                counts=old.counts,
                centroids=old.centroids,
                c_norm=old.c_norm,
                ids=self._ids.copy(),  # snapshot isolation (see _stage)
                n_items=self._live,
                n_lists=self._n_lists,
                nlist_pad=self._nlist_pad,
                l_pad=self._l_pad,
                dim=old.dim,
                hot_fraction=self._hot_fraction,
            )
        norms_dev = jax.device_put(
            self._norms.reshape(self._nlist_pad, self._l_pad),
            axis_sharding(self._mesh, 0, 2),
        )
        profiling.incr_counter("ann.mutate.bytes", int(self._norms.nbytes))
        return IVFFlatIndex(
            list_data=old.list_data,
            list_norm=norms_dev,
            counts=old.counts,
            centroids=old.centroids,
            c_norm=old.c_norm,
            ids=self._ids.copy(),  # snapshot isolation (see _stage)
            n_items=self._live,
            n_lists=self._n_lists,
            nlist_pad=self._nlist_pad,
            l_pad=self._l_pad,
            dim=old.dim,
        )
