"""Columnar feature blocks — the bridge from blob-world to HBM
(SURVEY.md §7 step 2).

A FeatureBlock is the SoA form of one dataset version's feature identity:

    keys : int64 (N,)   — int pk, or the top 64 bits of the path hash for
                          hash-encoded datasets (uniformly distributed;
                          collisions are detected host-side and disambiguated
                          before device work)
    oids : uint32 (N,5) — the feature blob's 20-byte content id, packed

sorted by key. Two blocks of the same dataset at different revisions align by
key, which is exactly the alignment git's tree layout provides for free via
PK-determined paths (reference: dataset3_paths.py) — re-created here as sorted
arrays so classification runs as one vectorized merge-join on device instead
of a per-feature Python loop (reference hot loop #1, rich_base_dataset.py:205).

Blocks are padded to bucketed sizes so jit traces are reused across calls
(XLA compiles per shape). The pad sentinel key is int64.max, which sorts last
and never equals a real key.
"""

import bisect
import hashlib
import threading
from collections import OrderedDict

import numpy as np

PAD_KEY = np.int64(2**63 - 1)

#: decoded vertex columns keyed by (sha1 of the raw section bytes, row
#: count) — the sidecar is content-addressed, so repeated loads of one
#: file hand back identical bytes and a digest key can never go stale
#: (docs/FORMAT.md §3.4); the bound reclaims memory. Hashing the section
#: costs milliseconds where the KTB2 decode costs hundreds — without the
#: memo every exact spatial query re-pays the full-column decode, because
#: the scan loads a fresh FeatureBlock per request.
_VERTEX_MEMO = OrderedDict()
_vertex_memo_lock = threading.Lock()
_VERTEX_MEMO_ENTRIES = 8


def bucket_size(n, minimum=1024):
    """Next 1/8-step pseudo-power-of-two >= n (>= minimum): sizes of the form
    (8..15) * 2^k. Bounds the number of distinct shapes XLA ever compiles for
    (8 per octave) while capping padding waste at 12.5% — matters because the
    classify kernel's sort cost scales with the padded size."""
    if n <= minimum:
        return minimum
    step = _grid_step(n)
    return ((n + step - 1) // step) * step


def _grid_step(n):
    """Spacing of the bucket grid in n's octave: a sixteenth of the power of
    two at or above n, so every size in (2^(m-1), 2^m] shares one step."""
    return 1 << max((n - 1).bit_length() - 4, 0)


def bucket_body(size, minimum=1024):
    """Rows that every block landing in the ``bucket_size`` bucket ``size``
    is sure to have: the bucket less one grid step (a block of n rows takes
    the first multiple of the step >= n, so n > size - step). A function of
    the bucket alone — what lets the device classify take a block as a
    zero-copy body view of this length plus one padded step-row tail without
    a compiled shape per row count. 0 at the minimum bucket, which blocks of
    any smaller size share."""
    if size <= minimum:
        return 0
    return size - _grid_step(size)


def batch_splits(key_arrays, batch_rows):
    """Key-aligned batch boundaries over N sorted key arrays.

    -> (per-side split arrays, n_chunks): chunk ``c`` of side ``s`` is rows
    ``splits[s][c]:splits[s][c+1]``. Guarantees, for every chunk:

    * **capacity** — at most ``batch_rows`` rows on *every* side (the fixed
      batch shape can always hold it);
    * **alignment** — boundaries are key *values*: a key lands in the same
      chunk on every side, so chunk-local joins equal the global join.

    Greedy: the next boundary is the smallest key that would overflow any
    side's capacity. A side with many keys below another side's boundary
    may get several chunks while the other contributes empty ones — empty
    is fine (count 0), overflow is not.
    """
    batch_rows = max(int(batch_rows), 1)
    sides = [np.asarray(k) for k in key_arrays]
    los = [0] * len(sides)
    splits = [[0] for _ in sides]
    while any(lo < len(k) for lo, k in zip(los, sides)):
        cands = [
            k[lo + batch_rows]
            for lo, k in zip(los, sides)
            if lo + batch_rows < len(k)
        ]
        if cands:
            bound = min(cands)
            # a bisection over each side's next batch_rows + 1 rows: the
            # boundary cannot lie further on. Not np.searchsorted over the
            # side: that copies an unaligned array first, and a sidecar's
            # mmap'd key section starts where its header ends — 80 MB a
            # call at 10M rows (PERF.md §6, PR 28)
            his = [
                bisect.bisect_left(k, bound, lo, min(lo + batch_rows + 1, len(k)))
                for lo, k in zip(los, sides)
            ]
        else:
            his = [len(k) for k in sides]
        for i, (lo, hi) in enumerate(zip(los, his)):
            splits[i].append(hi)
            los[i] = hi
    n_chunks = len(splits[0]) - 1
    return [np.asarray(s, dtype=np.int64) for s in splits], n_chunks


def pack_oid_hex(oids_hex):
    """list of 40-hex oids -> (N, 5) uint32 array."""
    if not len(oids_hex):
        return np.zeros((0, 5), dtype=np.uint32)
    raw = np.frombuffer(bytes.fromhex("".join(oids_hex)), dtype=np.uint8)
    return raw.reshape(-1, 5, 4).view(np.uint32).reshape(-1, 5).copy()


def unpack_oid_hex(oid_rows):
    """(N, 5) uint32 -> list of 40-hex oids. One buffer-level hex + string
    slices: the per-row bytes().hex() loop cost ~1us/row at 1M-changed
    materialisation scale."""
    if not len(oid_rows):
        return []
    h = np.ascontiguousarray(oid_rows).astype("<u4").view(np.uint8).tobytes().hex()
    return [h[i : i + 40] for i in range(0, len(h), 40)]


def oid_rows_u8(oid_rows):
    """(N, 5) uint32 -> (N, 20) uint8: the shas as bytes, one row each."""
    return np.ascontiguousarray(oid_rows).astype("<u4").view(np.uint8).reshape(-1, 20)


def unpack_oid_bytes(oid_rows):
    """(N, 5) uint32 -> list of 20-byte shas (one buffer copy + slices)."""
    if not len(oid_rows):
        return []
    b = oid_rows_u8(oid_rows).tobytes()
    return [b[i : i + 20] for i in range(0, len(b), 20)]


#: bits of a hash-keyed identity (sha256 of the filename, sign-cleared);
#: the collision tests take it down to force what 63 bits make rare
KEY_BITS = 63


def hash_keys(names):
    """Blob filenames (ByteRows) -> int64 identity keys: the first
    ``KEY_BITS`` bits of each one's sha256, big-endian. One hashlib call a
    name (``models.paths.sha256_prefixes``)."""
    from kart_tpu.models.paths import sha256_prefixes

    return (sha256_prefixes(names) >> np.uint64(64 - KEY_BITS)).astype(np.int64)


def hash_keys_for_paths(paths):
    """Feature paths (hash-encoded datasets) -> int64 identity keys: the first
    8 bytes (big-endian, sign-cleared) of sha256 of the blob *filename*.
    Uniform over [0, 2^63): collision probability at 100M keys ~ 5e-4; the
    caller must check `has_key_collisions` and disambiguate via paths."""
    from kart_tpu.models.paths import ByteRows

    return hash_keys(ByteRows.from_list([p.rpartition("/")[2].encode() for p in paths]))


class FeatureBlock:
    """One dataset version as sorted (key, oid) arrays + the path strings
    (kept host-side for value materialisation of changed rows only)."""

    __slots__ = ("keys", "oids", "paths", "count", "envelopes", "env_blocks",
                 "geom_raw", "_vertices", "tree_oid", "key_collisions")

    def __init__(self, keys, oids, paths, count, envelopes=None,
                 env_blocks=None, geom_raw=None, vertices=None, tree_oid=None,
                 key_collisions=None):
        self.keys = keys
        self.oids = oids
        self.paths = paths  # list[str], in the same (sorted) order, len == count
        self.count = count
        # optional (count, 4) float32 wsen envelope columns (sidecar-backed;
        # unpadded) — the spatially-filtered diff's prefilter input
        self.envelopes = envelopes
        # optional (agg (nb,4) f32, flags (nb,) u8, block_rows) aggregate
        # records over the envelope column — the block-pruned prefilter's
        # input; None for pre-aggregate sidecars (full scan fallback)
        self.env_blocks = env_blocks
        # optional encoded vertex-column section bytes (sidecar "geom_bytes",
        # docs/FORMAT.md §3.4), decoded on first vertex_column() call —
        # diff loads must not pay the decode they never use
        self.geom_raw = geom_raw
        self._vertices = vertices
        # the feature tree's oid where the columns are that tree's sidecar,
        # row for row (sidecar.load_block stamps it): the identity under
        # which the device keeps their pages between calls
        # (ops/resident.py). A block made any other way has none
        self.tree_oid = tree_oid
        # whether two rows share a key, as the sidecar's writer found when
        # it wrote the columns; None where nobody recorded it (a block not
        # from a sidecar, or a sidecar from before the header said)
        self.key_collisions = key_collisions

    def vertex_column(self):
        """Lazily decoded :class:`kart_tpu.geom.VertexColumn` for the
        block's ``count`` rows, or None when the sidecar has no geometry
        section. Fail open: a corrupt section decodes to None once (the
        refine stage then keeps envelope verdicts) rather than failing
        the whole block load."""
        if self._vertices is None and self.geom_raw is not None:
            raw, self.geom_raw = self.geom_raw, None
            from kart_tpu.geom import decode_vertex_column

            # bytes() copy: the stream codecs index scalars out of the
            # buffer and an mmap view would hand them np.uint8s
            data = bytes(raw)
            memo_key = (hashlib.sha1(data).digest(), self.count)
            with _vertex_memo_lock:
                hit = _VERTEX_MEMO.get(memo_key)
                if hit is not None:
                    _VERTEX_MEMO.move_to_end(memo_key)
            if hit is not None:
                self._vertices = hit
                return hit
            try:
                self._vertices, _ = decode_vertex_column(data, self.count)
            except Exception:
                self._vertices = None
            if self._vertices is not None:
                with _vertex_memo_lock:
                    _VERTEX_MEMO[memo_key] = self._vertices
                    _VERTEX_MEMO.move_to_end(memo_key)
                    while len(_VERTEX_MEMO) > _VERTEX_MEMO_ENTRIES:
                        _VERTEX_MEMO.popitem(last=False)
        return self._vertices

    @classmethod
    def from_dataset(cls, dataset, pad=True):
        paths, pk_arr, oid_u8 = dataset.feature_index()
        oid_rows = (
            oid_u8.reshape(-1, 5, 4).view(np.uint32).reshape(-1, 5)
            if len(paths)
            else np.zeros((0, 5), dtype=np.uint32)
        )
        if pk_arr is not None:
            keys = pk_arr.astype(np.int64)
        else:
            keys = hash_keys_for_paths(paths)
        return cls.from_arrays(keys, oid_rows, paths, pad=pad)

    @classmethod
    def from_arrays(cls, keys, oid_rows, paths, pad=True):
        n = len(keys)
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        oid_rows = oid_rows[order]
        paths = [paths[i] for i in order]
        if pad:
            size = bucket_size(max(n, 1))
            if size > n:
                keys = np.concatenate([keys, np.full(size - n, PAD_KEY, dtype=np.int64)])
                oid_rows = np.concatenate(
                    [oid_rows, np.zeros((size - n, 5), dtype=np.uint32)]
                )
        return cls(keys, oid_rows, paths, n)

    @property
    def padded_size(self):
        return len(self.keys)

    def has_key_collisions(self):
        """Do two rows share a key? What the sidecar recorded, else a scan
        of the key column."""
        if self.key_collisions is not None:
            return self.key_collisions
        real = self.keys[: self.count]
        return bool(np.any(real[1:] == real[:-1])) if self.count > 1 else False

    def has_pad_key(self):
        """Does a real row hold the padding key, which the kernels take for
        no row? The keys are sorted: the last real one says."""
        return bool(self.count) and int(self.keys[self.count - 1]) == int(PAD_KEY)

    def path_for_index(self, i):
        return self.paths[i]

    def __len__(self):
        return self.count

    def __repr__(self):
        return f"FeatureBlock(count={self.count}, padded={self.padded_size})"
