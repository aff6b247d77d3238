"""Paged, sharded KV cache: block-table allocation over one physical pool.

The serving tier's memory model (vLLM-style paging, adapted to the
scan-over-layers cache layout of :meth:`repro.models.model.Model`):

* every attention layer owns a **physical page pool** ``(n_rep, n_pages,
  page_size, ...)`` (:meth:`Model.init_paged_state`); sequences of
  different lengths share it through a host-side **block table**
  ``(n_slots, max_pages)`` of physical page ids, one row per decode slot;
* Mamba layers need no paging — SSD state is O(1) per sequence, so their
  caches stay slot-dense and the slot index is the "page";
* **page 0 is the trash page**: never allocated, it absorbs the reads and
  writes of inactive decode slots (all-zero table rows, pos 0) so the
  compiled decode step is total — admission and eviction are pure
  host-side data edits, the program never changes;
* stale pool contents after eviction are *unreachable*, not just
  unlikely: the decode mask scores positions past ``pos`` at ``-2^20``
  and fp32 softmax underflows them to exactly ``0.0`` (property-tested in
  ``tests/test_serve.py`` by dirtying the whole pool).

Shardings come from :func:`repro.dist.sharding.paged_cache_specs`: the
page/slot axis shards over the DP axes exactly like the decode batch
would — the block table itself is host memory and never enters the
compiled program.

:class:`BlockAllocator` is deliberately a tiny deterministic LIFO
free-list: given the same alloc/free call sequence it hands out the same
pages (tested), so a failure-requeued request reproduces its healthy-run
output bit for bit (page *identity* never affects gathered values).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import ssm as ssm_mod
from repro.models.model import Model

__all__ = ["BlockAllocator", "pages_needed", "pool_pages_for",
           "make_cache_writer"]

TRASH_PAGE = 0


def pages_needed(total_len: int, page_size: int) -> int:
    """Pages covering ``total_len`` cache rows."""
    return max(1, math.ceil(total_len / page_size))


def pool_pages_for(n_slots: int, max_len: int, page_size: int) -> int:
    """Pool size (pages) so ``n_slots`` worst-case sequences always fit,
    plus the reserved trash page."""
    return n_slots * pages_needed(max_len, page_size) + 1


class BlockAllocator:
    """Deterministic page allocator over one physical pool.

    LIFO free list seeded with pages ``1 .. n_pages-1`` (page 0 is the
    trash page and is never handed out). Allocation is all-or-nothing:
    a request that doesn't fit stays in the queue rather than holding a
    partial reservation.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.n_pages = n_pages
        self.page_size = page_size
        # LIFO with low pages on top: pop() returns 1, 2, 3, ...
        self._free = list(range(n_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_alloc(self, total_len: int) -> bool:
        return pages_needed(total_len, self.page_size) <= len(self._free)

    def alloc(self, total_len: int) -> list[int]:
        """Allocate pages for a sequence of ``total_len`` rows."""
        n = pages_needed(total_len, self.page_size)
        if n > len(self._free):
            raise MemoryError(
                f"need {n} pages, {len(self._free)} free")
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: list[int]) -> None:
        for pg in pages:
            if pg == TRASH_PAGE:
                raise ValueError("page 0 (trash) is not allocatable")
            if pg in self._free:
                raise ValueError(f"double free of page {pg}")
            self._free.append(pg)


def make_cache_writer(model: Model):
    """Build the pure prefill→pool scatter for ``model``.

    Returns ``write(paged_state, dense_state, pages, slot) ->
    paged_state`` where ``dense_state`` is a batch-1
    :meth:`Model.prefill` state of prompt length L, ``pages`` is the
    ``(n_alloc,)`` int32 page list for the sequence (``n_alloc * PS >=
    L``; the tail of the last page is zero-filled — masked, never read),
    and ``slot`` is the scalar decode-slot index for the Mamba leaves.
    Jit per prompt-length bucket (L and n_alloc are shape-static).
    """

    def serve_cache_write(paged, dense, pages, slot):
        new_state = []
        for seg_pool, seg_dense in zip(paged, dense):
            per_pos = []
            for pool_c, dense_c in zip(seg_pool, seg_dense):
                if isinstance(pool_c, ssm_mod.MambaCache):
                    # slot-dense: drop the batch-1 axis, land in the slot
                    per_pos.append(jax.tree.map(
                        lambda pl, dn: pl.at[:, slot].set(
                            dn[:, 0].astype(pl.dtype)),
                        pool_c, dense_c))
                else:
                    def scatter(pl, dn):
                        # pl (n_rep, NP, PS, *t); dn (n_rep, 1, L, *t)
                        n_rep, _, ps = pl.shape[:3]
                        length = dn.shape[2]
                        n_alloc = pages.shape[0]
                        pad = n_alloc * ps - length
                        d = jnp.pad(dn[:, 0],
                                    ((0, 0), (0, pad)) +
                                    ((0, 0),) * (dn.ndim - 3))
                        d = d.reshape(n_rep, n_alloc, ps, *pl.shape[3:])
                        return pl.at[:, pages].set(d.astype(pl.dtype))
                    per_pos.append(jax.tree.map(scatter, pool_c, dense_c))
            new_state.append(tuple(per_pos))
        return new_state

    return serve_cache_write
