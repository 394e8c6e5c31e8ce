"""Pages of a revision's key and oid columns — and, for the hash-keyed
count's guard, of its path column — kept on the device between calls
(docs/DEVICE.md §5).

A sidecar is one file per *feature tree oid*, content-addressed: a tree oid
never changes meaning (diff/sidecar.py). So rows ``[p * R, (p + 1) * R)`` of
one column of one tree are the same bytes in every command that reads them,
whatever revision they are joined with, and the device classify
(:func:`kart_tpu.ops.diff_kernel.classify_blocks_streamed`) computes from
such pages: it asks :data:`PAGES` for each page first and puts only those
the device does not hold. In a chain of pushes the old side of each diff is
the new side of the one before, so half of a call's bytes are here from real
traffic though no diff is ever repeated; a second look at the same pair
ships nothing. What is kept is the input, never an answer: the join runs
over all rows of both revisions in every call.

The key is the tree oid, the column, the page number and the page's rows
(:func:`page_key`), so nothing here can go stale and no ref move has
anything to drop; the byte budget alone reclaims memory. A block without a
tree oid (the json-lines filtered route's compacted survivors, a test's
arrays) is never kept: its pages live as long as the call that put them.
"""

import threading
from collections import OrderedDict

from kart_tpu import telemetry as tm

#: The store's byte budget as a share of the device's own
#: ``memory_stats()["bytes_limit"]``: half. The other half is left to what
#: runs — a chunk's programs and their temporaries (the windowed join's
#: word planes are 2 x 29 MB a 1,048,576-row chunk, the sort-join of an
#: overflowed chunk some hundreds of MB), three chunks of pages that are not
#: kept, the merge kernels and the envelope columns of ``ops/bbox.py``. On a
#: v5e (15.75 GiB) half holds twenty-eight revisions of 10M rows (280 MB
#: each), or both sides of one 100M-row diff (5.6 GB): the north star's
#: layer fits, a second pair of it evicts the first.
BUDGET_SHARE = 0.5
#: a backend that reports no limit (XLA-CPU: the test suite forced onto the
#: device route) gets a fixed one
BUDGET_WITHOUT_LIMIT = 1 << 28


def page_key(tree_oid, column, page, rows):
    """What a resident page is known by: the feature tree's oid (the
    sidecar's own content address), the column (``keys`` | ``oids`` |
    ``paths``), the
    page number and the rows of a page (the geometry it was cut at: a page
    of another size is another page)."""
    return (tree_oid, column, page, rows)


class PageStore:
    """``page_key`` -> device array, least recently used first, within a
    byte budget. A page is pinned while a call reads it (a count: two calls
    may read one page) and only unpinned pages are evicted; a page that
    finds no room is simply not kept. One lock around the map — the HTTP
    server and the CDC worker are threads; no device call is made under it.
    The arrays are immutable and are never donated to a program, so a page
    evicted while a program reads it lives until that program ends."""

    def __init__(self, budget_bytes=None):
        self._lock = threading.Lock()
        self._pages = OrderedDict()  # key -> [array, pins]
        self._pads = {}  # (column, rows) -> the shared all-padding page
        self._strides = {}  # tree oid -> its path rows' one width, or 0
        self._bytes = 0
        self._budget = budget_bytes

    def _budget_for(self, array):
        """The budget, read once from the device the first kept page is on."""
        if self._budget is None:
            stats = next(iter(array.devices())).memory_stats() or {}
            limit = stats.get("bytes_limit")
            self._budget = (
                int(limit * BUDGET_SHARE) if limit else BUDGET_WITHOUT_LIMIT
            )
        return self._budget

    def pin(self, key):
        """The page ``key`` if it is here, pinned (:meth:`unpin` it when
        the call ends) and now the most recently used; else None."""
        with self._lock:
            entry = self._pages.get(key)
            if entry is None:
                return None
            entry[1] += 1
            self._pages.move_to_end(key)
            return entry[0]

    def keep(self, key, array):
        """Offer a page just put -> (the array to read, whether it is kept
        and pinned under ``key``). Another thread may have kept the same
        page meanwhile: that one is handed back and ``array`` is let go.
        Least recently used unpinned pages make room; where they cannot,
        the page is not kept and the caller's reference is all it has."""
        budget = self._budget_for(array)
        evicted = 0
        with self._lock:
            entry = self._pages.get(key)
            if entry is not None:
                entry[1] += 1
                self._pages.move_to_end(key)
                return entry[0], True
            for old in list(self._pages):
                if self._bytes + array.nbytes <= budget:
                    break
                if self._pages[old][1] == 0:
                    self._bytes -= self._pages.pop(old)[0].nbytes
                    evicted += 1
            kept = self._bytes + array.nbytes <= budget
            if kept:
                self._pages[key] = [array, 1]
                self._bytes += array.nbytes
            total = self._bytes
        if evicted:
            tm.incr("diff.device.resident_evictions", evicted, why="budget")
        tm.gauge_set("diff.device.resident_bytes", total)
        return array, kept

    def unpin(self, keys):
        with self._lock:
            for key in keys:
                entry = self._pages.get(key)
                if entry is not None and entry[1] > 0:
                    entry[1] -= 1

    def discard(self, keys):
        """Forget pages whatever their pins: those a failed call put, whose
        copies may never have landed."""
        with self._lock:
            for key in keys:
                entry = self._pages.pop(key, None)
                if entry is not None:
                    self._bytes -= entry[0].nbytes
            total = self._bytes
        tm.gauge_set("diff.device.resident_bytes", total)

    def drop_all(self, why):
        """Empty the store (the device refused an allocation: ``why`` =
        ``oom``) -> pages dropped. Pinned pages go too: the calls that read
        them hold their own references."""
        with self._lock:
            dropped = len(self._pages)
            while self._pages:
                self._pages.popitem()
            self._pads.clear()
            self._bytes = 0
        if dropped:
            tm.incr("diff.device.resident_evictions", dropped, why=why)
        tm.gauge_set("diff.device.resident_bytes", 0)
        return dropped

    def pad_page(self, column, rows, make):
        """The one page of ``rows`` rows of padding that stands in for every
        page past a revision's end, ``make(column, rows)`` on first use. Not
        counted in the budget: one a page geometry (28 MB at 1,048,576
        rows)."""
        with self._lock:
            page = self._pads.get((column, rows))
        if page is None:
            page = make(column, rows)
            with self._lock:
                page = self._pads.setdefault((column, rows), page)
        return page

    def path_stride(self, tree_oid, measure):
        """The one width of every path row of the tree ``tree_oid`` (0 where
        they differ): ``measure()`` the first time a process asks, kept
        beside the pages after that — the sidecar is content-addressed, so
        the answer never changes, and it is not device memory: emptying the
        store keeps it."""
        with self._lock:
            width = self._strides.get(tree_oid)
        if width is None:
            width = measure()
            with self._lock:
                self._strides[tree_oid] = width
        return width

    def resident_bytes(self):
        with self._lock:
            return self._bytes

    def keys(self):
        """The pages kept, least recently used first (tests)."""
        with self._lock:
            return list(self._pages)


#: the process's store: a server's threads share one device
PAGES = PageStore()


def with_pages_let_go(call):
    """``call()``, a device rung; where the device refuses an allocation
    while pages are resident, the store is emptied and ``call()`` is made
    once more. The store is the process's and may hold half of the device,
    so every rung that can fall back to the host (the classify, the merge
    kernels, the mesh backend's calls, the envelope scan) goes through
    here first: a cache must not cost another program the device. The
    device still answers then, so that is no ``diff.device.fallbacks``:
    ``diff.device.resident_evictions{why=oom}`` counts the pages. Any other
    failure, a refusal with nothing resident, and the second call's failure
    are the caller's (:func:`kart_tpu.ops.diff_kernel.note_device_fallback`).

    A refusal is the runtime's one exception type with the status
    ``RESOURCE_EXHAUSTED``, which it keeps only as the message's code name:
    that name is what there is to ask."""
    import jax

    try:
        return call()
    except jax.errors.JaxRuntimeError as failure:
        if "RESOURCE_EXHAUSTED" not in str(failure) or not PAGES.drop_all("oom"):
            raise
    return call()
