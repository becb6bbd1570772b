"""Host-side page arena for the paged resident state (ISSUE 20).

The continuous engine's resident HBM used to be provisioned per SLOT at
the worst-case article shape — PR 11's length masks cut compute, not
memory.  This module is the HOST half of the fix: a free-list allocator
over a fixed pool of ``decode_enc_block``-row pages.  The device half
(decode/beam_search.py's slot kernels) holds the pooled
encoder-axis leaves; the engine (decode/decoder.SlotDecodeEngine) calls
``alloc`` at pack time with the admitted article's true page count and
``free`` at harvest/release, and mirrors the allocation into the
per-slot page-table rows it passes to the kernels as DATA (never shape
— the compile-once discipline of PRs 6/11).

Deliberately jax-free: allocation runs on the serving dispatch thread
between chunks (a tslint TS002 hot path) — pure numpy, no device sync.

``ArenaExhaustedError`` is the typed backpressure signal: the batcher
catches it and REQUEUES the admission (never a wrong decode, never a
dropped request) until a harvest frees pages.
"""

from __future__ import annotations

from typing import Iterable, List

import numpy as np

# the typed backpressure signal LIVES in resilience/errors.py (the
# repo's failure vocabulary, import-light) so the jax-free serve
# scheduler can catch it without importing the jax-heavy decode
# package; re-exported here because the arena is what raises it
from textsummarization_on_flink_tpu.resilience.errors import (  # noqa: F401
    ArenaExhaustedError,
)

__all__ = ["ArenaExhaustedError", "PageArena"]


class PageArena:
    """LIFO free-list over page ids ``0..pages-1``.

    LIFO on purpose: a just-freed page is the page most likely still
    warm in cache, and reuse churn is exactly what the allocation-
    pattern compile pin exercises.  The SCRATCH page (id ``pages`` by
    the kernels' convention) is NOT managed here — it is never
    allocated, never freed, and every unused page-table entry points at
    it."""

    def __init__(self, pages: int):
        if pages < 1:
            raise ValueError(f"arena needs at least one page, got {pages}")
        self._capacity = int(pages)
        self._free: List[int] = list(range(pages - 1, -1, -1))
        self._owned = np.zeros(pages, dtype=bool)

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self._capacity - len(self._free)

    @property
    def fill(self) -> float:
        """In-use fraction in [0, 1] — the serve/arena_fill observable."""
        return self.pages_in_use / self._capacity

    def alloc(self, n: int) -> np.ndarray:
        """Allocate ``n`` pages; returns their ids as int32 [n].  Raises
        typed ``ArenaExhaustedError`` (allocating NOTHING — admission is
        all-or-nothing, so a failed pack leaks no pages)."""
        n = int(n)
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise ArenaExhaustedError(
                f"arena exhausted: need {n} pages, {len(self._free)} free "
                f"of {self._capacity}", needed=n, free=len(self._free))
        ids = [self._free.pop() for _ in range(n)]
        self._owned[ids] = True
        return np.asarray(ids, dtype=np.int32)

    def free(self, ids: Iterable[int]) -> None:
        """Return pages to the free list.  Double-free and out-of-range
        ids raise — an accounting bug must fail loudly, not silently
        hand one page to two residents."""
        for pid in np.asarray(list(ids), dtype=np.int64).tolist():  # tslint: disable=TS002 — host numpy id normalization, no device value
            if not 0 <= pid < self._capacity:
                raise ValueError(
                    f"page id {pid} outside arena of {self._capacity}")
            if not self._owned[pid]:
                raise ValueError(f"double free of page {pid}")
            self._owned[pid] = False
            self._free.append(int(pid))  # tslint: disable=TS002 — plain python int from .tolist(), no device value
