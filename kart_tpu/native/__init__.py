"""ctypes binding for the native spatial-filter core (native/spatial_filter.cpp).

The library is optional: :func:`load` returns None when it isn't built and
every caller falls back to the numpy implementation with identical
semantics (the same CPU-reference-path discipline the TPU kernels follow).
Build with ``make -C native`` — :func:`ensure_built` does it on demand when
a toolchain is available.
"""

import ctypes
import logging
import os
import subprocess

import numpy as np

L = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_NAME = "libkart_sf.so"
_ABI_VERSION = 2  # v2: sf_bbox_blocks_f32

_lib = None
_load_attempted = False


def _lib_path():
    override = os.environ.get("KART_TPU_NATIVE_LIB")
    if override:
        return override
    return os.path.abspath(os.path.join(_NATIVE_DIR, _LIB_NAME))


_autobuild_attempted = False


def _run_make(force=False):
    """Compile the native libraries, serialized across processes with a
    lock file (the Makefile links via temp+rename, so readers never see a
    half-written .so). ``force`` rebuilds even what looks up to date.
    Returns True when make reported success."""
    makefile_dir = os.path.abspath(_NATIVE_DIR)
    if not os.path.exists(os.path.join(makefile_dir, "Makefile")):
        return False
    lock_path = os.path.join(makefile_dir, ".build-lock")
    try:
        import fcntl

        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            result = subprocess.run(
                ["make", "-C", makefile_dir, "-k"] + (["-B"] if force else []),
                capture_output=True,
                timeout=120,
            )
        if result.returncode != 0:
            L.info(
                "native build failed (rc=%d): %s",
                result.returncode,
                result.stderr.decode(errors="replace")[-2000:],
            )
            return False
        return True
    except Exception as e:  # no toolchain / no fcntl / timeout: stay Python
        L.info("native build unavailable: %s", e)
        return False


def _autobuild():
    """One attempt per process to compile the native libraries when a lib
    file is missing (fresh checkouts): a few seconds of g++ buys the fast
    paths for the rest of the process and every later one.
    KART_NO_NATIVE_BUILD=1 disables."""
    global _autobuild_attempted
    if _autobuild_attempted or os.environ.get("KART_NO_NATIVE_BUILD") == "1":
        return
    _autobuild_attempted = True
    _run_make()


def _load_rebuilt(path):
    """CDLL the freshly-rebuilt library at ``path``. dlopen caches handles
    by *pathname* (glibc compares l_name), so re-CDLLing the original path
    after a temp+rename rebuild returns the stale in-process mapping — the
    new inode must be loaded through a one-off pathname. The copy is left
    for the OS tmp reaper: it cannot be unlinked while mapped."""
    import shutil
    import tempfile

    d = tempfile.mkdtemp(prefix="kart-native-")
    fresh = os.path.join(d, os.path.basename(path))
    shutil.copy2(path, fresh)
    return ctypes.CDLL(fresh)


def load():
    """-> configured ctypes.CDLL, or None when unavailable."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    path = _lib_path()
    if not os.path.exists(path) and not os.environ.get("KART_TPU_NATIVE_LIB"):
        _autobuild()
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.sf_abi_version.restype = ctypes.c_int
        if lib.sf_abi_version() != _ABI_VERSION:
            # stale build from an older checkout: rebuild, then load the
            # new inode through a fresh pathname (see _load_rebuilt)
            L.warning("native lib %s has stale ABI; rebuilding", path)
            if os.environ.get("KART_TPU_NATIVE_LIB") or not _run_make():
                return None
            lib = _load_rebuilt(path)
            lib.sf_abi_version.restype = ctypes.c_int
            if lib.sf_abi_version() != _ABI_VERSION:
                return None
        lib.sf_decode_envelopes.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
        ]
        lib.sf_bbox_intersects.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.sf_bbox_intersects.restype = ctypes.c_int64
        lib.sf_filter_packed.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.sf_filter_packed.restype = ctypes.c_int64
        if hasattr(lib, "sf_bbox_intersects_f32"):
            lib.sf_bbox_intersects_f32.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.sf_bbox_intersects_f32.restype = ctypes.c_int64
        if hasattr(lib, "sf_bbox_blocks_f32"):
            lib.sf_bbox_blocks_f32.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.sf_bbox_blocks_f32.restype = ctypes.c_int64
        _lib = lib
    except (OSError, AttributeError) as e:
        # AttributeError: a stale/foreign .so without the expected symbols
        L.warning("could not load native lib %s: %s", path, e)
    return _lib


def rebuild():
    """Rebuild both libraries from native/*.cpp whatever .so files are lying
    in the tree; -> True when make succeeded. For a measurement, which is
    compared with this engine and must know it was built from the checkout.
    Call it before the first load: a library already mapped stays the one
    in use."""
    global _load_attempted, _io_load_attempted, _autobuild_attempted
    _autobuild_attempted = True
    built = _run_make(force=True)
    _load_attempted = False
    _io_load_attempted = False
    return built


def ensure_built():
    """Build the libraries if a compiler is available; -> loaded sf lib or
    None. Each library is independent: a build failure of one (e.g. no zlib
    headers for the IO core) never blocks loading the other."""
    global _load_attempted, _io_load_attempted, _autobuild_attempted
    # suppress load()'s own autobuild below: one make run per ensure_built
    _autobuild_attempted = True
    if load() is not None and load_io() is not None:
        return _lib
    _run_make()
    _load_attempted = False
    _io_load_attempted = False
    load_io()
    return load()


# -- object-store IO core (native/kart_io.cpp) ------------------------------

_IO_LIB_NAME = "libkart_io.so"
_IO_ABI_VERSION = 8  # v8: io_idx_probe, io_jsonl_chunk

_io_lib = None
_io_load_attempted = False


def load_io():
    """-> configured ctypes.CDLL for the IO core, or None."""
    global _io_lib, _io_load_attempted
    if _io_lib is not None or _io_load_attempted:
        return _io_lib
    _io_load_attempted = True
    override = os.environ.get("KART_TPU_NATIVE_IO_LIB")
    path = override or os.path.abspath(
        os.path.join(_NATIVE_DIR, _IO_LIB_NAME)
    )
    if not os.path.exists(path) and not override:
        _autobuild()
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.io_abi_version.restype = ctypes.c_int
        if lib.io_abi_version() != _IO_ABI_VERSION:
            # a stale build from an older checkout: rebuild, then load the
            # new inode through a fresh pathname (see _load_rebuilt —
            # re-CDLLing the same path returns the stale cached mapping)
            L.warning("native IO lib %s has stale ABI; rebuilding", path)
            if override or not _run_make():
                return None
            lib = _load_rebuilt(path)
            lib.io_abi_version.restype = ctypes.c_int
            if lib.io_abi_version() != _IO_ABI_VERSION:
                return None
        lib.io_pack_ptrs.restype = ctypes.c_int64
        lib.io_pack_ptrs.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
        ]
        lib.io_pack_records.restype = ctypes.c_int64
        lib.io_pack_records.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.io_classify_sorted.restype = ctypes.c_int64
        lib.io_classify_sorted.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.io_tree_diff.restype = ctypes.c_int64
        lib.io_tree_diff.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.io_inflate_batch.restype = ctypes.c_int64
        lib.io_inflate_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.io_gpkg_open.restype = ctypes.c_void_p
        lib.io_gpkg_open.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ]
        lib.io_gpkg_next.restype = ctypes.c_int64
        lib.io_gpkg_next.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.io_gpkg_close.restype = None
        lib.io_gpkg_close.argtypes = [ctypes.c_void_p]
        lib.io_leaf_payloads.restype = ctypes.c_int64
        lib.io_leaf_payloads.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.io_idx_probe.restype = ctypes.c_int64
        lib.io_idx_probe.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.io_jsonl_chunk.restype = ctypes.c_int64
        lib.io_jsonl_chunk.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        _io_lib = lib
    except (OSError, AttributeError) as e:
        L.warning("could not load native IO lib %s: %s", path, e)
    return _io_lib


def classify_sorted(old_keys, old_oids_u8, new_keys, new_oids_u8):
    """Native merge-join diff classify over key-sorted columns; -> (old_class
    int8 (n_old,), new_class (n_new,), counts dict) or None when the IO lib
    isn't available. Bit-identical to the numpy reference twin (tested)."""
    lib = load_io()
    if lib is None:
        return None
    n_old, n_new = len(old_keys), len(new_keys)
    old_keys = np.ascontiguousarray(old_keys, dtype=np.int64)
    new_keys = np.ascontiguousarray(new_keys, dtype=np.int64)
    old_oids_u8 = np.ascontiguousarray(old_oids_u8, dtype=np.uint8)
    new_oids_u8 = np.ascontiguousarray(new_oids_u8, dtype=np.uint8)
    old_class = np.zeros(n_old, dtype=np.int8)
    new_class = np.zeros(n_new, dtype=np.int8)
    counts = np.zeros(3, dtype=np.int64)
    rc = lib.io_classify_sorted(
        old_keys.ctypes.data, old_oids_u8.ctypes.data, n_old,
        new_keys.ctypes.data, new_oids_u8.ctypes.data, n_new,
        old_class.ctypes.data, new_class.ctypes.data, counts.ctypes.data,
    )
    if rc != 0:
        return None
    return (
        old_class,
        new_class,
        {
            "inserts": int(counts[0]),
            "updates": int(counts[1]),
            "deletes": int(counts[2]),
        },
    )


def tree_diff_raw(a_content, b_content):
    """Raw git tree payloads -> list of differing entries
    ``(name, oid_a_hex|None, oid_b_hex|None, a_is_tree, b_is_tree)``, or
    None when the lib is unavailable / input malformed (callers fall back
    to the parse-both-trees Python path with identical results — tested).
    Only the differing entries are materialised: at 1%-edit scale ~99% of
    a touched tree's entries are equal, and the Python path paid per-entry
    object + hex costs for all of them."""
    lib = load_io()
    if lib is None:
        return None
    # worst case: every entry one-sided — each output record (43 + name)
    # bytes against (27 + name) input bytes, so 2x input covers it
    cap = 2 * (len(a_content) + len(b_content)) + 64
    out = np.empty(cap, dtype=np.uint8)
    total = lib.io_tree_diff(
        a_content, len(a_content), b_content, len(b_content),
        out.ctypes.data, cap,
    )
    if total < 0:
        return None
    result = []
    buf = out[:total].tobytes()
    i = 0
    while i < total:
        flags = buf[i]
        name_len = buf[i + 1] | (buf[i + 2] << 8)
        j = i + 3
        name = buf[j : j + name_len].decode("utf8")
        j += name_len
        oid_a = buf[j : j + 20].hex() if flags & 1 else None
        oid_b = buf[j + 20 : j + 40].hex() if flags & 2 else None
        result.append((name, oid_a, oid_b, bool(flags & 4), bool(flags & 8)))
        i = j + 40
    return result


def pack_records_batch(obj_type, type_code, contents, level=1):
    """Batch hash + deflate + pack-record framing: -> (oids (n,20) uint8,
    crcs (n,) uint32, records np.uint8 buffer, offsets (n+1) int64) —
    record i is ``records[offsets[i]:offsets[i+1]]``, complete with varint
    head, ready to append to the pack stream. None when unavailable."""
    lib = load_io()
    if lib is None or not contents:
        return None
    n = len(contents)
    try:
        joined = b"".join(contents)  # one memcpy pass beats a per-element
        # ctypes pointer-array conversion (~1us each)
    except TypeError:
        return None
    lens = np.fromiter((len(c) for c in contents), dtype=np.int64, count=n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    payload_total = len(joined)

    oids = np.empty((n, 20), dtype=np.uint8)
    crcs = np.empty(n, dtype=np.uint32)
    # zlib worst case + stored overhead + 10-byte heads, all inside 80*n
    cap = payload_total + payload_total // 512 + 80 * n + 1024
    out = np.empty(cap, dtype=np.uint8)
    out_offsets = np.empty(n + 1, dtype=np.int64)
    total = lib.io_pack_records(
        joined, offsets.ctypes.data, n, obj_type.encode(), int(type_code),
        int(level), _store_max(),
        oids.ctypes.data, crcs.ctypes.data, out.ctypes.data, cap,
        out_offsets.ctypes.data,
    )
    if total < 0:
        L.warning("native pack records failed (%d); falling back", total)
        return None
    return oids, crcs, out[:total], out_offsets


def pack_records_base(obj_type, type_code, base_u8, offsets, level=1):
    """:func:`pack_records_batch` over payloads that are ALREADY one
    contiguous buffer + offsets (the native GPKG encoder's output, or a
    tree-payload batch) — no join, no bytes objects, zero per-payload
    Python. -> same (oids, crcs, records, out_offsets) tuple, or None."""
    lib = load_io()
    if lib is None:
        return None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    if n <= 0:
        return None
    base_u8 = np.ascontiguousarray(base_u8, dtype=np.uint8)
    payload_total = int(offsets[n])
    oids = np.empty((n, 20), dtype=np.uint8)
    crcs = np.empty(n, dtype=np.uint32)
    cap = payload_total + payload_total // 512 + 80 * n + 1024
    out = np.empty(cap, dtype=np.uint8)
    out_offsets = np.empty(n + 1, dtype=np.int64)
    total = lib.io_pack_records(
        base_u8.ctypes.data_as(ctypes.c_char_p), offsets.ctypes.data, n,
        obj_type.encode(), int(type_code), int(level), _store_max(),
        oids.ctypes.data, crcs.ctypes.data, out.ctypes.data, cap,
        out_offsets.ctypes.data,
    )
    if total < 0:
        L.warning("native pack records (base) failed (%d); falling back", total)
        return None
    return oids, crcs, out[:total], out_offsets


def leaf_payloads(pks, oids_u8, branches, pk_limit):
    """Native leaf-tree payload build (io_leaf_payloads): strictly ascending
    non-negative int64 ``pks`` below ``pk_limit`` (``branches**(levels+1)``
    — above it leaf ids would need the encoder's max_trees wrap) + their
    (n, 20) blob oids -> (buf uint8, offsets int64 (n_leaves+1,), leaf_ids
    int64) where leaf k's git tree payload is
    ``buf[offsets[k]:offsets[k+1]]`` — bit-identical to the numpy plan
    path (property-tested). None when the lib is unavailable or the pks
    don't qualify (caller falls back to the Python build)."""
    lib = load_io()
    if lib is None:
        return None
    pks = np.ascontiguousarray(pks, dtype=np.int64)
    n = len(pks)
    if n == 0:
        return None
    oids_u8 = np.ascontiguousarray(oids_u8, dtype=np.uint8)
    # entry <= 7 + 16-char name + NUL + 20-byte oid = 44 bytes
    cap = n * 44 + 64
    out = np.empty(cap, dtype=np.uint8)
    offsets = np.empty(n + 1, dtype=np.int64)
    leaf_ids = np.empty(n, dtype=np.int64)
    n_leaves = ctypes.c_int64(0)
    total = lib.io_leaf_payloads(
        pks.ctypes.data, oids_u8.ctypes.data, n, int(branches),
        int(pk_limit), out.ctypes.data, cap, offsets.ctypes.data,
        leaf_ids.ctypes.data, ctypes.byref(n_leaves),
    )
    if total < 0:
        return None
    k = n_leaves.value
    return out[:total], offsets[: k + 1], leaf_ids[:k]


class GpkgReaderFallback(Exception):
    """The native GPKG encoder met a row it cannot produce bit-identically
    (geometry needing the full re-encode path, unexpected storage class):
    the caller must re-stream through the Python encoder."""


class GpkgNativeReader:
    """Native fused read+encode over a GPKG table (io_gpkg_*): each
    :meth:`next_batch` steps the prepared SELECT and returns
    ``(pks int64 (n,), buf uint8, offsets int64 (n+1,))`` — blob i is
    ``buf[offsets[i]:offsets[i+1]]``, bit-identical to the Python
    ``batch_row_encoder`` blobs. The ctypes call releases the GIL for the
    whole batch. Raises :class:`GpkgReaderFallback` on rows the native
    encoder can't handle. Use :func:`open_gpkg_reader` (returns None when
    the native lib or sqlite3 runtime is unavailable)."""

    def __init__(self, handle, lib, est_row_bytes):
        self._h = handle
        self._lib = lib
        # grown on demand (-5): start from the caller's estimate
        self._row_bytes = max(64, int(est_row_bytes))

    def next_batch(self, max_rows):
        """-> (pks, buf, offsets) or None at EOF."""
        if self._h is None:
            return None
        lib = self._lib
        while True:
            pks = np.empty(max_rows, dtype=np.int64)
            cap = max_rows * self._row_bytes + 4096
            buf = np.empty(cap, dtype=np.uint8)
            offsets = np.empty(max_rows + 1, dtype=np.int64)
            n = lib.io_gpkg_next(
                self._h, max_rows, pks.ctypes.data, buf.ctypes.data, cap,
                offsets.ctypes.data,
            )
            if n == -5:  # a single row outgrew the buffer: double and retry
                self._row_bytes *= 2
                continue
            if n == -6:
                self.close()
                raise GpkgReaderFallback()
            if n < 0:
                self.close()
                raise OSError(f"native GPKG reader failed (rc={n})")
            if n == 0:
                self.close()
                return None
            return pks[:n], buf, offsets[: n + 1]

    def close(self):
        if self._h is not None:
            self._lib.io_gpkg_close(self._h)
            self._h = None

    def __del__(self):
        self.close()


def open_gpkg_reader(db_path, sql, val_cols, kinds, pk_col, prefix,
                     geom_ext_code, est_row_bytes=256):
    """-> :class:`GpkgNativeReader` or None when the native IO lib (or the
    sqlite3 runtime it dlopens) is unavailable. ``val_cols``/``kinds``: per
    blob value (legend non-pk order) the SELECT column index and encode
    kind (0 plain / 1 geometry / 2 bool / 3 float / 4 timestamp);
    ``prefix``: the constant msgpack head every feature blob starts with."""
    lib = load_io()
    if lib is None:
        return None
    val_cols = np.ascontiguousarray(val_cols, dtype=np.int32)
    kinds_u8 = np.ascontiguousarray(kinds, dtype=np.uint8)
    n_vals = len(kinds_u8)
    prefix = bytes(prefix)
    handle = lib.io_gpkg_open(
        os.fsencode(db_path), sql.encode(), n_vals,
        val_cols.ctypes.data, kinds_u8.ctypes.data, int(pk_col),
        prefix, len(prefix), int(geom_ext_code),
    )
    if not handle:
        return None
    return GpkgNativeReader(handle, lib, est_row_bytes)


def inflate_pack_batch(pack_buf, offsets, max_total=None):
    """Bulk pack reads: mmap/bytes of a whole packfile + record offsets ->
    (n_consumed, types uint8 (n_consumed,), payload uint8 array,
    payload_offsets int64 (n_consumed+1,)), or None when the lib is
    unavailable / the pack is malformed. Non-delta records inflate with one
    reused z_stream; delta records come back as type 0 with an empty slot
    (the caller's per-object path resolves the chain).

    max_total bounds the payload buffer: only the longest record PREFIX
    whose inflated payload fits (always at least one record) is consumed —
    callers loop over the remainder, so a batch of large blobs can't
    materialise unbounded memory in one native call."""
    lib = load_io()
    if lib is None:
        return None
    buf = np.frombuffer(pack_buf, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets)
    types = np.zeros(n, dtype=np.uint8)
    cum = np.zeros(n + 1, dtype=np.int64)
    total = lib.io_inflate_batch(
        buf.ctypes.data, len(buf), offsets.ctypes.data, n,
        None, 0, cum.ctypes.data, types.ctypes.data,
    )
    if total < 0:
        return None
    take = n
    if max_total is not None and total > max_total:
        take = max(1, int(np.searchsorted(cum, max_total, side="right")) - 1)
        total = int(cum[take])
        offsets = offsets[:take]
        types = types[:take]
    out_offsets = np.zeros(take + 1, dtype=np.int64)
    if total == 0 and not types.any():
        # every record is a delta (heavily-repacked git packs): nothing to
        # inflate, skip the second native pass entirely
        return take, types, np.empty(0, dtype=np.uint8), out_offsets
    out = np.empty(int(total), dtype=np.uint8)
    rc = lib.io_inflate_batch(
        buf.ctypes.data, len(buf), offsets.ctypes.data, take,
        out.ctypes.data, int(total), out_offsets.ctypes.data,
        types.ctypes.data,
    )
    if rc < 0:
        return None
    return take, types, out, out_offsets


def idx_probe(idx_u8, shas_u8):
    """.idx v2 file as a uint8 array + (n, 20) uint8 shas -> int64 pack
    offsets (-1 where the index does not hold the sha), or None when the
    lib is unavailable or refuses the index (the numpy probe answers)."""
    lib = load_io()
    if lib is None:
        return None
    shas_u8 = np.ascontiguousarray(shas_u8, dtype=np.uint8).reshape(-1, 20)
    out = np.empty(len(shas_u8), dtype=np.int64)
    rc = lib.io_idx_probe(
        idx_u8.ctypes.data, len(idx_u8), shas_u8.ctypes.data, len(shas_u8),
        out.ctypes.data,
    )
    return out if rc == 0 else None


#: why io_jsonl_chunk declined a row (kart_io.cpp JsonlWhy), as the
#: ``why`` label of ``serialise.rows_python``
JSONL_WHY = ("", "record", "legend", "type", "geometry", "utf8", "size")


def pack_jsonl_plans(plans):
    """{legend hash: Dataset3._jsonl_plan(hash)} -> the plans blob
    io_jsonl_chunk reads (layout: kart_io.cpp parse_jsonl_plans)."""
    import struct

    out = [struct.pack("<I", len(plans))]
    for legend_hash, cols in plans.items():
        h = legend_hash.encode()
        out.append(struct.pack("<I", len(h)) + h + struct.pack("<I", len(cols)))
        for prefix, src, is_geom in cols:
            kind, idx = (0, 0) if src is None else (1 if src[0] else 2, src[1])
            pre = prefix.encode("ascii")
            out.append(struct.pack("<IIBI", kind, idx, bool(is_geom), len(pre)) + pre)
    return b"".join(out)


def jsonl_chunk(pack_bufs, old_pack, old_off, new_pack, new_off, pks, head,
                old_plans, new_plans, out):
    """Feature lines of one chunk of the json-lines row plan, written into
    the uint8 array ``out`` with the GIL released (kart_io.cpp
    io_jsonl_chunk). ``old_pack``/``new_pack`` int32: index into
    ``pack_bufs`` (mmaps of whole packfiles), -1 side absent, -2 in no pack;
    ``*_off`` int64 record offsets; ``*_plans`` from :func:`pack_jsonl_plans`.
    -> (bytes written, rows consumed, row_end int64, status uint8), rows
    consumed < len(pks) when ``out`` filled up first; None when the lib is
    unavailable or refuses the arguments."""
    lib = load_io()
    if lib is None:
        return None
    n = len(pks)
    pks = np.ascontiguousarray(pks, dtype=np.int64)
    old_pack = np.ascontiguousarray(old_pack, dtype=np.int32)
    new_pack = np.ascontiguousarray(new_pack, dtype=np.int32)
    old_off = np.ascontiguousarray(old_off, dtype=np.int64)
    new_off = np.ascontiguousarray(new_off, dtype=np.int64)
    if not (len(old_pack) == len(old_off) == len(new_pack) == len(new_off) == n):
        raise ValueError("jsonl_chunk: row arrays differ in length")
    bufs = [np.frombuffer(b, dtype=np.uint8) for b in pack_bufs]
    ptrs = (ctypes.c_void_p * max(1, len(bufs)))(*[b.ctypes.data for b in bufs])
    lens = np.array([len(b) for b in bufs], dtype=np.int64)
    row_end = np.empty(n, dtype=np.int64)
    status = np.empty(n, dtype=np.uint8)
    done = ctypes.c_int64(0)
    total = lib.io_jsonl_chunk(
        ptrs, lens.ctypes.data, len(bufs), n,
        old_pack.ctypes.data, old_off.ctypes.data,
        new_pack.ctypes.data, new_off.ctypes.data, pks.ctypes.data,
        head, len(head), old_plans, len(old_plans), new_plans, len(new_plans),
        out.ctypes.data, len(out), row_end.ctypes.data, status.ctypes.data,
        ctypes.byref(done),
    )
    if total < 0:
        return None
    return int(total), done.value, row_end, status


def _store_max():
    """Payloads at or below this many bytes are written as STORED zlib
    streams (see kart_io.cpp io_pack_ptrs): feature blobs are ~100-150B of
    msgpack that level-1 deflate barely shrinks but costs ~9us each on this
    zlib. 0 disables (always deflate)."""
    try:
        return int(os.environ.get("KART_PACK_STORE_MAX", 256))
    except ValueError:
        return 256


def pack_objects_batch(obj_type, contents, level=1):
    """Batch hash+deflate WITHOUT record framing: obj_type str, contents
    list[bytes] -> (oids (n,20) uint8, deflated list[bytes]), or None when
    the library isn't available.

    Production pack writing goes through :func:`pack_records_batch` (framed
    records, one write per batch); this unframed variant remains as the
    reference twin the native tests cross-check stream-level behavior
    against, and for callers that need streams outside pack framing.

    Zero-copy: the C side reads the bytes objects' own buffers through a
    pointer array and composes the git object headers itself."""
    lib = load_io()
    if lib is None or not contents:
        return None
    n = len(contents)
    try:
        ptrs = (ctypes.c_char_p * n)(*contents)
    except TypeError:
        # a non-bytes sneaked in: let the Python path raise the right error
        return None
    lens = np.fromiter((len(c) for c in contents), dtype=np.int64, count=n)
    payload_total = int(lens.sum())

    oids = np.empty((n, 20), dtype=np.uint8)
    # zlib worst case ~ src + src/1000 + 12 per stream; stored streams add
    # 11 + 5 per 64KB block, covered by the same 64*n headroom
    cap = payload_total + payload_total // 512 + 64 * n + 1024
    out = np.empty(cap, dtype=np.uint8)
    out_offsets = np.empty(n + 1, dtype=np.int64)
    total = lib.io_pack_ptrs(
        ptrs, lens.ctypes.data, n, obj_type.encode(), int(level),
        _store_max(),
        oids.ctypes.data, out.ctypes.data, cap, out_offsets.ctypes.data,
    )
    if total < 0:
        L.warning("native pack batch failed (%d); falling back", total)
        return None
    streams = [
        out[out_offsets[i] : out_offsets[i + 1]].tobytes() for i in range(n)
    ]
    return oids, streams


# -- high-level API (native with numpy fallback) ----------------------------


def decode_envelopes(packed):
    """(N, 10) uint8 packed envelopes -> (N, 4) float64 wsen."""
    packed = np.ascontiguousarray(packed, dtype=np.uint8)
    n = packed.shape[0]
    lib = load()
    if lib is not None:
        out = np.empty((n, 4), dtype=np.float64)
        lib.sf_decode_envelopes(
            packed.ctypes.data, n, out.ctypes.data
        )
        return out
    from kart_tpu.ops.envelope_codec import EnvelopeCodec

    return EnvelopeCodec().decode_batch(packed)


def filter_packed(packed, query_wsen):
    """(N, 10) uint8 packed envelopes + (w,s,e,n) query -> bool (N,).
    The server-side partial-clone hot path."""
    packed = np.ascontiguousarray(packed, dtype=np.uint8)
    n = packed.shape[0]
    query = np.asarray(query_wsen, dtype=np.float64)
    lib = load()
    if lib is not None:
        out = np.empty(n, dtype=np.uint8)
        lib.sf_filter_packed(
            packed.ctypes.data, n, query.ctypes.data, out.ctypes.data
        )
        return out.astype(bool)
    from kart_tpu.ops.bbox import bbox_intersects_np

    return bbox_intersects_np(decode_envelopes(packed), query)


def bbox_intersects(envelopes, query_wsen):
    """(N, 4) float64 wsen + query -> bool (N,), native when available."""
    envelopes = np.ascontiguousarray(envelopes, dtype=np.float64)
    query = np.asarray(query_wsen, dtype=np.float64)
    lib = load()
    if lib is not None:
        out = np.empty(envelopes.shape[0], dtype=np.uint8)
        lib.sf_bbox_intersects(
            envelopes.ctypes.data, envelopes.shape[0], query.ctypes.data, out.ctypes.data
        )
        return out.astype(bool)
    from kart_tpu.ops.bbox import bbox_intersects_np

    return bbox_intersects_np(envelopes, query)


def bbox_intersects_f32(envelopes_f32, query_wsen):
    """(N, 4) float32 wsen (e.g. the sidecar envelope mmap, zero copies) +
    query -> bool (N,). Falls back to the f64 path when the native lib is
    missing or predates the f32 entry point."""
    query = np.asarray(query_wsen, dtype=np.float64)
    lib = load()
    if lib is not None and hasattr(lib, "sf_bbox_intersects_f32"):
        env = np.ascontiguousarray(envelopes_f32, dtype=np.float32)
        out = np.empty(env.shape[0], dtype=np.uint8)
        lib.sf_bbox_intersects_f32(
            env.ctypes.data, env.shape[0], query.ctypes.data, out.ctypes.data
        )
        return out.view(bool)  # 0/1 bytes: reinterpret, no copy
    return bbox_intersects(np.asarray(envelopes_f32, dtype=np.float64), query)


def bbox_blocks_f32(envelopes_f32, agg_f32, flags_u8, block_rows, query_wsen):
    """Block-pruned f32 scan: (N, 4) float32 envelopes + their (nb, 4)
    float32 block aggregates / nb flag bytes (sidecar block-aggregate
    records) + query -> bool (N,). All-out blocks are classified from the
    aggregate alone — their envelope pages are never read. Bit-identical to
    :func:`bbox_intersects_f32` over the same rows (fuzz-tested); falls back
    to the numpy block scan, then to the unpruned scan."""
    query = np.asarray(query_wsen, dtype=np.float64)
    lib = load()
    if lib is not None and hasattr(lib, "sf_bbox_blocks_f32"):
        env = np.ascontiguousarray(envelopes_f32, dtype=np.float32)
        agg = np.ascontiguousarray(agg_f32, dtype=np.float32)
        flags = np.ascontiguousarray(flags_u8, dtype=np.uint8)
        n = env.shape[0]
        out = np.empty(n, dtype=np.uint8)
        rc = lib.sf_bbox_blocks_f32(
            env.ctypes.data, n, agg.ctypes.data, flags.ctypes.data,
            agg.shape[0], int(block_rows), query.ctypes.data, out.ctypes.data,
        )
        if rc >= 0:
            return out.view(bool)
    from kart_tpu.ops.bbox import bbox_blocks_np

    return bbox_blocks_np(envelopes_f32, agg_f32, flags_u8, block_rows, query)
