"""Fixed-capacity KV/state slot pool with donated in-place inserts.

The engine allocates ONE pooled cache tree up front —
``model.init_cache(capacity, max_seq)`` — and serves every request out
of a *slot*: one index along each leaf's batch axis.  Requests borrow a
slot at admission and hand it back at retirement; the arrays themselves
are never reallocated, which is exactly the paper's ``noupdate``
residency applied to serving state: the cache buffers are uploaded
(well, allocated) once and stay device-resident for the engine's
lifetime, while per-request traffic is row-sized.

Inserting a freshly prefilled request writes its row into every pooled
leaf with one jitted ``dynamic_update_index_in_dim`` scatter that
**donates** the pooled buffers (``donate_argnums``) — on donating
backends the pool is updated in place, so slot recycling reuses the
same device memory request after request (the leak test asserts both
the slot-index reuse and, where the platform supports donation, the
buffer handoff).

The batch axis of each leaf is *inferred*, not assumed: the pool
eval-shapes ``init_cache`` at two batch sizes and takes the unique axis
whose extent differs.  That keeps the pool agnostic to cache layout —
full KV ``(layers, B, T, K, D)``, Griffin's ``(periods, 2, B, ...)``
recurrent stacks, RWKV's constant-size ``(layers, B, ...)`` state — and
to future cache kinds, as long as decode is row-independent.
"""
from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

__all__ = ["KVSlotPool", "infer_batch_axes", "cache_bytes_per_slot"]


def _diff_axis(sa, sb) -> int:
    """The unique axis where two shapes differ (the batch axis)."""
    if len(sa) != len(sb):
        raise ValueError(f"cache leaf rank changed with batch: {sa} vs {sb}")
    diff = [i for i, (a, b) in enumerate(zip(sa, sb)) if a != b]
    if len(diff) != 1:
        raise ValueError(
            f"cannot infer batch axis from shapes {sa} vs {sb}: "
            f"{len(diff)} axes differ")
    return diff[0]


def infer_batch_axes(model, max_seq: int) -> List[int]:
    """Per-leaf batch-axis index of ``model.init_cache``'s tree, in leaf
    order, found by diffing the abstract shapes at two batch sizes."""
    import jax
    s2 = jax.eval_shape(lambda: model.init_cache(2, max_seq))
    s3 = jax.eval_shape(lambda: model.init_cache(3, max_seq))
    l2, t2 = jax.tree.flatten(s2)
    l3, t3 = jax.tree.flatten(s3)
    if t2 != t3:
        raise ValueError("init_cache tree structure depends on batch size")
    return [_diff_axis(a.shape, b.shape) for a, b in zip(l2, l3)]


def cache_bytes_per_slot(model, max_seq: int) -> int:
    """Device bytes one request's slot owns (all leaves, batch=1) — the
    per-sequence unit of the engine's device-bytes budget."""
    import jax
    shapes = jax.eval_shape(lambda: model.init_cache(1, max_seq))
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(shapes))


def _slot_bytes(cache, capacity: int) -> dict:
    """Bytes of one slot under each top-level key of the pooled tree
    (e.g. a recurrent state, a conv window, a KV cache)."""
    import jax
    return {k: sum(x.nbytes for x in jax.tree.leaves(v)) // capacity
            for k, v in cache.items()}


class KVSlotPool:
    """Slot allocator + owner of the pooled cache tree.

    Free slots are recycled LIFO so a just-retired slot is the next one
    handed out — the access pattern donation rewards (the freed row's
    buffers are hottest).  ``alloc`` returns ``None`` when exhausted
    (the admission queue waits; nothing OOMs), ``free`` asserts against
    double-free, and ``assert_no_leaks`` is the engine-shutdown check
    that every borrowed slot came back.
    """

    def __init__(self, model, capacity: int, max_seq: int):
        if capacity < 1:
            raise ValueError("pool capacity must be >= 1")
        self.capacity = int(capacity)
        self.max_seq = int(max_seq)
        self.batch_axes = infer_batch_axes(model, max_seq)
        self.cache = model.init_cache(capacity, max_seq)
        self.slot_bytes = _slot_bytes(self.cache, capacity)
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._in_use: set = set()
        self.allocs = 0
        self.frees = 0
        self.peak_in_use = 0
        self.reused_slots = 0          # allocs that recycled a freed slot
        self._ever_used: set = set()

    # -- slot bookkeeping ----------------------------------------------------
    @property
    def in_use(self) -> int:
        return len(self._in_use)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        self.allocs += 1
        if slot in self._ever_used:
            self.reused_slots += 1
        self._ever_used.add(slot)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._in_use:
            raise RuntimeError(f"double free / foreign slot {slot}")
        self._in_use.remove(slot)
        self._free.append(slot)       # LIFO: next alloc reuses it
        self.frees += 1

    def assert_no_leaks(self) -> None:
        if self._in_use:
            raise RuntimeError(
                f"KV slot leak: {sorted(self._in_use)} still allocated "
                f"({self.allocs} allocs / {self.frees} frees)")
        assert self.free_count == self.capacity, (
            self.free_count, self.capacity)

    def stats(self) -> dict:
        return {
            "capacity": self.capacity,
            "in_use": self.in_use,
            "peak_in_use": self.peak_in_use,
            "allocs": self.allocs,
            "frees": self.frees,
            "reused_slots": self.reused_slots,
            "slot_bytes": dict(self.slot_bytes),
        }

    # -- pooled-cache insert -------------------------------------------------
    def insert(self, new_cache: Any, src_idx: int, slot: int) -> None:
        """Scatter row ``src_idx`` of ``new_cache`` (a prefill-produced
        cache tree, any batch size) into pooled row ``slot``, donating
        the pooled buffers.  One jitted dispatch for the whole tree."""
        import jax
        if slot not in self._in_use:
            raise RuntimeError(f"insert into unallocated slot {slot}")
        pool_leaves, treedef = jax.tree.flatten(self.cache)
        new_leaves, new_def = jax.tree.flatten(new_cache)
        if new_def != treedef:
            raise ValueError(
                f"prefill cache tree {new_def} != pool tree {treedef}")
        out = _insert_fn(tuple(self.batch_axes))(
            tuple(pool_leaves), tuple(new_leaves), src_idx, slot)
        self.cache = jax.tree.unflatten(treedef, out)


def _insert_fn(axes: tuple):
    """Jitted per-leaf row scatter, shared by every pool with the same
    batch-axis layout — a fresh pool (new engine, new benchmark mode)
    must not recompile it."""
    fn = _INSERT_FNS.get(axes)
    if fn is None:
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def insert(pool_leaves, new_leaves, src_idx, slot):
            out = []
            for pl, nl, ax in zip(pool_leaves, new_leaves, axes):
                row = jax.lax.dynamic_index_in_dim(nl, src_idx, ax,
                                                   keepdims=False)
                out.append(jax.lax.dynamic_update_index_in_dim(
                    pl, row.astype(pl.dtype), slot, ax))
            return tuple(out)

        fn = _INSERT_FNS.setdefault(axes, insert)
    return fn


_INSERT_FNS: dict = {}
