"""Feature-path encoding: primary key <-> blob path (reference: kart/dataset3_paths.py).

A dataset's features are spread over a fixed-fanout tree so that git tree
objects stay small at 100M+ features. V3 uses 4 levels x 64 branches:

  int scheme      : tree index = (pk // 64) % 64**4, one base64 char per level
  msgpack/hash    : first 4 chars of b64hash(msgpack(pks)) as the tree levels
  legacy (V2)     : first 2 hex-pairs of hexhash(msgpack(pks)) (256**2 trees)

The filename is always ``urlsafe_b64(msgpack(pk_values))``.

Unlike the reference (per-feature Python string work), the encoders here also
expose *batch* APIs over numpy arrays: digit extraction, msgpack int encoding
and base64 run as vectorized numpy ops, and per-item Python objects are only
materialised with a single C-level ``bytes.decode().split()`` at the end.
These batch paths feed the columnar diff engine (kart_tpu/ops) and the
sharded importer.
"""

import math

import numpy as np

from kart_tpu.core.serialise import (
    b64encode_str,
    b64decode_str,
    b64hash,
    hexhash,
    msg_pack,
    msg_unpack,
)

HEX_ALPHABET = "0123456789abcdef"
# RFC 3548 urlsafe alphabet — used for both tree names and b64 filenames.
B64_ALPHABET = "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_"


class PathEncoderError(ValueError):
    pass


class PathEncoder:
    """Base path encoder. Construct via :meth:`get`."""

    PATH_STRUCTURE_ITEM = "path-structure.json"

    @staticmethod
    def get(*, scheme, **kwargs):
        if scheme == "int":
            return IntPathEncoder(scheme=scheme, **kwargs)
        if scheme == "msgpack/hash":
            return MsgpackHashPathEncoder(scheme=scheme, **kwargs)
        raise PathEncoderError(
            f"Unsupported feature path scheme: {scheme!r}"
        )

    def __init__(self, *, scheme, levels, branches, encoding):
        self.scheme = scheme
        self.levels = levels
        self.branches = branches
        self.encoding = encoding

        if encoding == "hex":
            self.alphabet = HEX_ALPHABET
            self._hash = hexhash
        elif encoding == "base64":
            self.alphabet = B64_ALPHABET
            self._hash = b64hash
        else:
            raise PathEncoderError(f"Unsupported path encoding: {encoding!r}")

        base = len(self.alphabet)
        group_length = round(math.log(branches, base))
        if base**group_length != branches:
            raise PathEncoderError(
                f"{encoding} encoding and {branches} branches are incompatible"
            )
        self.group_length = group_length
        self.max_trees = branches**levels

        # numpy lookup table: digit value -> alphabet byte
        self._alpha_u8 = np.frombuffer(self.alphabet.encode("ascii"), dtype=np.uint8)
        self._alpha_inv = np.full(256, -1, dtype=np.int16)
        for i, ch in enumerate(self.alphabet.encode("ascii")):
            self._alpha_inv[ch] = i

    def to_dict(self):
        return {
            "scheme": self.scheme,
            "branches": self.branches,
            "levels": self.levels,
            "encoding": self.encoding,
        }

    def __eq__(self, other):
        return isinstance(other, PathEncoder) and self.to_dict() == other.to_dict()

    def __hash__(self):
        return hash(tuple(sorted(self.to_dict().items())))

    # -- filenames ---------------------------------------------------------

    def encode_filename(self, pk_values):
        return b64encode_str(msg_pack(pk_values))

    @staticmethod
    def decode_filename(filename):
        """filename -> tuple of pk values."""
        return tuple(msg_unpack(b64decode_str(filename)))

    def tree_names(self):
        """All possible single-level tree names, in alphabet order."""
        for i in range(self.branches):
            yield self._encode_tree_digit(i)

    def _encode_tree_digit(self, value):
        chars = []
        for _ in range(self.group_length):
            value, rem = divmod(value, len(self.alphabet))
            chars.append(self.alphabet[rem])
        return "".join(reversed(chars))

    def nonrecursive_diff(self, tree_a, tree_b):
        """name -> (entry_a, entry_b) for entries whose ids differ between two
        trees (either side may be None)."""
        a = {e.name: e for e in tree_a} if tree_a is not None else {}
        b = {e.name: e for e in tree_b} if tree_b is not None else {}
        out = {}
        for name in sorted(a.keys() | b.keys()):
            ea, eb = a.get(name), b.get(name)
            ia = ea.id if ea is not None else None
            ib = eb.id if eb is not None else None
            if ia != ib:
                out[name] = (ea, eb)
        return out


class IntPathEncoder(PathEncoder):
    """Modulus-based encoder for single integer pks (reference:
    dataset3_paths.py:283-299). Sequential pks land in the same subtree, which
    keeps packfiles small, and — for us — makes PK-sorted columnar blocks line
    up with subtree boundaries (the shard key for the device mesh)."""

    DISTRIBUTED_FEATURES = False

    def encode_pks_to_path(self, pk_values):
        assert len(pk_values) == 1
        pk = int(pk_values[0])
        tree_idx = (pk // self.branches) % self.max_trees
        parts = []
        for level in range(self.levels):
            shift = self.levels - 1 - level
            digit = (tree_idx // (self.branches**shift)) % self.branches
            parts.append(self._encode_tree_digit(digit))
        parts.append(self.encode_filename(pk_values))
        return "/".join(parts)

    def decode_path_to_pks(self, path):
        return self.decode_filename(path.rsplit("/", 1)[-1])

    # -- batch (numpy) -----------------------------------------------------

    _PATH_HOLE = 0xFF  # never a valid ascii path byte; stripped after tobytes

    def _path_matrix(self, pks, plen=0):
        """Shared core of the batch path encoders: the (N, plen + levels +
        b64 + 1) uint8 matrix holding every path, cells beyond each row's
        content set to ``_PATH_HOLE``. -> (matrix, end_col (N,)) where
        end_col is each row's terminator slot (caller writes its separator
        there, then strips holes)."""
        n = pks.shape[0]
        base = len(self.alphabet)
        tree_idx = (pks // self.branches) % self.max_trees

        fn_bytes, fn_len = _msgpack_single_int_batch(pks)
        b64_mat, b64_len = _b64_batch(fn_bytes, fn_len)
        b64w = b64_mat.shape[1]

        width = plen + self.levels * (self.group_length + 1) + b64w + 1
        out = np.full((n, width), self._PATH_HOLE, dtype=np.uint8)
        col = plen
        for level in range(self.levels):
            shift = self.levels - 1 - level
            digit = (tree_idx // (self.branches**shift)) % self.branches
            # split the branch digit into group_length alphabet chars (msb first)
            for g in range(self.group_length):
                gshift = self.group_length - 1 - g
                out[:, col] = self._alpha_u8[(digit // base**gshift) % base]
                col += 1
            out[:, col] = ord("/")
            col += 1
        region = out[:, col : col + b64w]
        region[:] = b64_mat
        region[np.arange(b64w)[None, :] >= b64_len[:, None]] = self._PATH_HOLE
        return out, col + b64_len

    def encode_paths_batch(self, pks):
        """int64 array (N,) -> list of N path strings, vectorized.

        Builds the whole path table as one uint8 matrix (levels + '/' + b64
        filename, newline-separated) and splits once at the end.
        """
        pks = np.asarray(pks, dtype=np.int64)
        n = pks.shape[0]
        if n == 0:
            return []
        out, end = self._path_matrix(pks)
        out[np.arange(n), end] = ord("\n")
        text = out.tobytes().replace(b"\xff", b"").decode("ascii")
        return text.split("\n")[:-1]

    def decode_paths_batch(self, filenames):
        """Sequence of filenames (or full paths) -> int64 array of pks."""
        if not isinstance(filenames, (list, tuple)):
            filenames = list(filenames)
        names = [f.rsplit("/", 1)[-1] for f in filenames]
        return _decode_single_int_filenames(names)

    def encode_paths_joined_bytes(self, pks, prefix=b"", sep=b"\x00"):
        """int64 array (N,) -> ``sep.join(prefix + path for each pk)`` as one
        bytes object, straight from the uint8 path matrix — no per-path
        Python strings (serialising a 1M-conflict merge index joins the
        whole column anyway; reference scale: kart/merge_util.py:68-346)."""
        pks = np.asarray(pks, dtype=np.int64)
        n = pks.shape[0]
        if n == 0:
            return b""
        assert len(sep) == 1 and sep != b"\xff"
        plen = len(prefix)
        out, end = self._path_matrix(pks, plen)
        if plen:
            out[:, :plen] = np.frombuffer(prefix, np.uint8)
        out[np.arange(n), end] = sep[0]
        raw = out.tobytes().replace(b"\xff", b"")
        return raw[:-1]


class MsgpackHashPathEncoder(PathEncoder):
    """Hash-distributed encoder for everything else (reference:
    dataset3_paths.py:193-215). Features are uniformly distributed over the
    tree fanout, which the sampled diff estimator exploits."""

    DISTRIBUTED_FEATURES = True

    def encode_pks_to_path(self, pk_values):
        packed = msg_pack(pk_values)
        digest = self._hash(packed)
        parts = [
            digest[i * self.group_length : (i + 1) * self.group_length]
            for i in range(self.levels)
        ]
        parts.append(b64encode_str(packed))
        return "/".join(parts)

    def decode_path_to_pks(self, path):
        return self.decode_filename(path.rsplit("/", 1)[-1])

    def expected_blobs_for_tree_samples(self, num_samples, branch_factor):
        """Inverse birthday-problem correction: observed distinct children ->
        expected feature count in a uniformly-hashed tree."""
        return math.log(1 - num_samples / branch_factor) / math.log(
            1 - 1 / branch_factor
        )


# ---------------------------------------------------------------------------
# Columns of byte strings, and the msgpack/hash layout of a column of pks
# ---------------------------------------------------------------------------


class ByteRows:
    """``n`` byte strings as one buffer: row ``i`` is
    ``data[offs[i]:offs[i + 1]]``. ``data`` may be a read-only map (a
    sidecar's path section)."""

    __slots__ = ("offs", "data")

    def __init__(self, offs, data):
        self.offs = offs
        self.data = data

    @classmethod
    def from_list(cls, items):
        lens = np.fromiter((len(b) for b in items), dtype=np.int64, count=len(items))
        offs = np.zeros(len(items) + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        return cls(offs, np.frombuffer(b"".join(items), dtype=np.uint8))

    @classmethod
    def from_matrix(cls, mat, lens):
        """Row ``i``: the first ``lens[i]`` bytes of ``mat[i]``."""
        n, w = mat.shape
        lens = np.asarray(lens, dtype=np.int64)
        offs = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        if n and bool((lens == w).all()):
            return cls(offs, np.ascontiguousarray(mat).reshape(-1))
        return cls(offs, mat[np.arange(w)[None, :] < lens[:, None]])

    @classmethod
    def concat(cls, parts):
        offs = [np.zeros(1, dtype=np.int64)]
        base = 0
        for p in parts:
            o = np.asarray(p.offs, dtype=np.int64)
            offs.append(o[1:] - o[0] + base)
            base += int(o[-1] - o[0])
        return cls(
            np.concatenate(offs),
            np.concatenate(
                [np.asarray(p.data[int(p.offs[0]):int(p.offs[-1])]) for p in parts]
                or [np.zeros(0, dtype=np.uint8)]
            ),
        )

    def __len__(self):
        return len(self.offs) - 1

    def lengths(self):
        return np.diff(np.asarray(self.offs, dtype=np.int64))

    def width(self):
        """The one length every row has, or None."""
        lens = self.lengths()
        return int(lens[0]) if len(lens) and bool((lens == lens[0]).all()) else None

    def tolist(self):
        lo = int(self.offs[0])
        buf = bytes(self.data[lo : int(self.offs[-1])])
        bounds = (np.asarray(self.offs, dtype=np.int64) - lo).tolist()
        return [buf[a:b] for a, b in zip(bounds[:-1], bounds[1:])]

    def take(self, rows, chunk=1 << 18):
        """The rows ``rows`` (an index array), in that order. Where each of
        them starts at its row number times one width (rows of one width,
        as UUID paths are) one fancy index of a matrix view; else a gather
        of the bytes, a chunk of rows at a time so the index stays small.
        Either way only the offsets of ``rows`` are read."""
        rows = np.asarray(rows, dtype=np.int64)
        starts = np.asarray(self.offs[rows], dtype=np.int64)
        lens = np.asarray(self.offs[rows + 1], dtype=np.int64) - starts
        out_offs = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum(lens, out=out_offs[1:])
        out = np.empty(int(out_offs[-1]), dtype=np.uint8)
        data = np.asarray(self.data)
        w = int(lens[0]) if len(rows) else 0
        if w and bool((lens == w).all()) and bool((starts == rows * w).all()):
            out.reshape(-1, w)[:] = data[: len(data) // w * w].reshape(-1, w)[rows]
            return ByteRows(out_offs, out)
        for lo in range(0, len(rows), chunk):
            hi = min(lo + chunk, len(rows))
            a, b = int(out_offs[lo]), int(out_offs[hi])
            idx = np.repeat(starts[lo:hi] - out_offs[lo:hi], lens[lo:hi])
            idx += np.arange(a, b, dtype=np.int64)
            out[a:b] = data[idx]
        return ByteRows(out_offs, out)

    def matrix(self):
        """-> (uint8 (n, longest row) zero-filled past each row, lengths)."""
        lens = self.lengths()
        n = len(lens)
        w = int(lens.max()) if n else 0
        lo = int(self.offs[0])
        data = np.asarray(self.data[lo : int(self.offs[-1])])
        if n and bool((lens == w).all()):
            return data.reshape(n, w).copy(), lens
        mat = np.zeros((n, w), dtype=np.uint8)
        mat[np.arange(w)[None, :] < lens[:, None]] = data
        return mat, lens


def sha256_prefixes(rows):
    """ByteRows -> uint64 (n,): the first eight bytes of each row's sha256,
    big-endian. One hashlib call a row, nothing else made per row."""
    import hashlib

    sha = hashlib.sha256
    n = len(rows)
    lo = int(rows.offs[0]) if n else 0
    buf = bytes(rows.data[lo : int(rows.offs[-1])]) if n else b""
    w = rows.width()
    if w is not None:
        digests = [sha(buf[i : i + w]).digest()[:8] for i in range(0, n * w, w)]
    else:
        bounds = (np.asarray(rows.offs, dtype=np.int64) - lo).tolist()
        digests = [sha(buf[a:b]).digest()[:8] for a, b in zip(bounds[:-1], bounds[1:])]
    return np.frombuffer(b"".join(digests), dtype=">u8").astype(np.uint64)


def msgpack_pk_rows(pk_values):
    """The msgpack of each row's pk tuple, as ``PathEncoder.encode_filename``
    packs it -> ByteRows. ``pk_values``: a sequence of pk tuples, or a numpy
    ``S`` array of one text pk column. The ASCII column whose values all
    fill their width (a UUID column) is packed as one matrix; anything else
    a row at a time."""
    arr = pk_values if isinstance(pk_values, np.ndarray) else None
    if arr is not None and arr.dtype.kind == "S" and arr.ndim == 1 and len(arr):
        w = arr.dtype.itemsize
        mat = np.ascontiguousarray(arr).view(np.uint8).reshape(len(arr), w)
        if w < 1 << 16 and bool((mat != 0).all()) and int(mat.max()) < 0x80:
            head = (
                [0x91, 0xA0 | w] if w < 32
                else [0x91, 0xD9, w] if w < 256
                else [0x91, 0xDA, w >> 8, w & 0xFF]
            )
            out = np.empty((len(arr), len(head) + w), dtype=np.uint8)
            out[:, : len(head)] = head
            out[:, len(head) :] = mat
            return ByteRows.from_matrix(out, np.full(len(arr), out.shape[1]))
    if arr is not None:
        pk_values = [
            (v.decode("utf8") if isinstance(v, bytes) else v,) for v in arr.tolist()
        ]
    return ByteRows.from_list([msg_pack(tuple(v)) for v in pk_values])


class HashRows:
    """A column of features laid out by a msgpack/hash encoder: per row its
    leaf tree (``leaf_ids``: the first ``levels`` characters of
    ``b64(sha256(msgpack(pk)))`` as digits), its filename
    (``urlsafe_b64(msgpack(pk))``: ``names`` zero-filled past ``name_lens``)
    and its sidecar identity key (``keys``: ``ops.blocks.hash_keys`` of the
    filename)."""

    __slots__ = ("leaf_ids", "names", "name_lens", "keys")

    def __init__(self, leaf_ids, names, name_lens, keys):
        self.leaf_ids = leaf_ids
        self.names = names
        self.name_lens = name_lens
        self.keys = keys

    def __len__(self):
        return len(self.leaf_ids)

    def take(self, rows):
        return HashRows(
            self.leaf_ids[rows], self.names[rows], self.name_lens[rows], self.keys[rows]
        )

    def name_rows(self):
        return ByteRows.from_matrix(self.names, self.name_lens)

    def last_wins(self):
        """Row numbers to keep, ascending: of rows with one filename (one
        pk) the last, as a tree builder's insert over an insert."""
        order = np.argsort(self.keys, kind="stable")
        same = self.keys[order[1:]] == self.keys[order[:-1]]
        at = np.flatnonzero(same)
        if len(at):  # one key: the same pk, or two pks whose hashes collide
            a, b = order[at], order[at + 1]
            same[at] = (self.name_lens[a] == self.name_lens[b]) & (
                self.names[a] == self.names[b]
            ).all(axis=1)
        return np.sort(order[~np.append(same, False)])

    def paths(self, encoder):
        """The feature paths (``A/B/C/D/<filename>``), as ByteRows."""
        n, w = self.names.shape
        lv = encoder.levels
        mat = np.empty((n, 2 * lv + w), dtype=np.uint8)
        for level in range(lv):
            digit = (self.leaf_ids >> (6 * (lv - 1 - level))) & 63
            mat[:, 2 * level] = encoder._alpha_u8[digit]
            mat[:, 2 * level + 1] = ord("/")
        mat[:, 2 * lv :] = self.names
        return ByteRows.from_matrix(mat, self.name_lens + 2 * lv)


def hash_feature_rows(packed, encoder):
    """ByteRows of msgpack'd pks -> :class:`HashRows` under ``encoder`` (one
    base64 character a level: the V3 ``GENERAL_ENCODER``): every path part
    of every row, batch by batch of numpy, two sha256 a row."""
    assert encoder.encoding == "base64" and encoder.group_length == 1, encoder.to_dict()
    mat, lens = packed.matrix()
    leaf_ids = (sha256_prefixes(packed) >> np.uint64(64 - 6 * encoder.levels)).astype(
        np.int64
    )
    names, name_lens = _b64_batch(mat, lens)
    if len(names) and int(name_lens.min()) < names.shape[1]:
        names[np.arange(names.shape[1])[None, :] >= name_lens[:, None]] = 0
    from kart_tpu.ops import blocks

    rows = HashRows(leaf_ids, names, name_lens, None)
    rows.keys = blocks.hash_keys(rows.name_rows())
    return rows


# ---------------------------------------------------------------------------
# Vectorized msgpack + base64 helpers
# ---------------------------------------------------------------------------

_MAX_MSGPACK_INT_LEN = 11  # 0x91 + 0xcf + 8 bytes


def _msgpack_single_int_batch(pks):
    """int64 array -> (uint8 matrix (N, 11), lengths (N,)) of msgpack([pk])."""
    n = pks.shape[0]
    out = np.zeros((n, _MAX_MSGPACK_INT_LEN), dtype=np.uint8)
    length = np.zeros(n, dtype=np.int64)
    out[:, 0] = 0x91  # fixarray(1)

    u = pks.astype(np.uint64)

    def be_bytes(vals, nbytes):
        # the low nbytes of each value, most significant first: one
        # truncating cast to the big-endian type (the shift-and-mask form
        # built an (N, nbytes) uint64 matrix: 1.1 s of a 4M-row column)
        return vals.astype(f">u{nbytes}").view(np.uint8).reshape(-1, nbytes)

    lo, hi = (int(pks.min()), int(pks.max())) if n else (0, -1)

    def rows_in(a, b):
        """The rows whose pk lies in [a, b]: a mask, all rows where every pk
        does (a serial column is one width: no mask is made), None where
        none can."""
        if hi < a or lo > b:
            return None
        if a <= lo and hi <= b:
            return slice(None)
        return (pks >= a) & (pks <= b)

    def fill(a, b, marker, nbytes):
        m = rows_in(a, b)
        if m is None:
            return
        if marker is None:  # a fixint is its own byte
            out[m, 1] = pks[m].astype(np.uint8)
        else:
            out[m, 1] = marker
            out[m, 2 : 2 + nbytes] = be_bytes(u[m], nbytes)
        length[m] = 2 + nbytes

    fill(-32, 0x7F, None, 0)  # negative and positive fixint
    fill(0x80, 0xFF, 0xCC, 1)
    fill(0x100, 0xFFFF, 0xCD, 2)
    fill(0x10000, 0xFFFFFFFF, 0xCE, 4)
    fill(0x100000000, (1 << 63) - 1, 0xCF, 8)
    fill(-0x80, -33, 0xD0, 1)
    fill(-0x8000, -0x81, 0xD1, 2)
    fill(-0x80000000, -0x8001, 0xD2, 4)
    fill(-(1 << 63), -0x80000001, 0xD3, 8)

    return out, length


_B64_CHARS = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_", dtype=np.uint8
)
_B64_INV = np.full(256, -1, dtype=np.int16)
for _i, _c in enumerate(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_"
):
    _B64_INV[_c] = _i


def _b64_batch(data, lengths):
    """Row-wise urlsafe base64 (with '=' padding) of a padded uint8 matrix.

    data: (N, W) uint8, row i valid up to lengths[i].
    Returns (chars (N, ceil(L/3)*4) uint8 — '=' padded per row, newline
    filler past a row's end — and out_lengths), L the longest row (W
    where there is no row).
    """
    n, w = data.shape
    if n:
        # columns no row reaches encode to filler only: a column of pks of
        # one msgpack width (6 of 11 bytes) is half the work
        w = min(w, int(lengths.max()))
        data = data[:, :w]
    groups = (w + 2) // 3
    padded = np.zeros((n, groups * 3), dtype=np.uint8)
    padded[:, :w] = data
    # the four 6-bit digits of each byte triple, in uint8 arithmetic (a
    # uint32 triple per group was 0.6 s of a 4M-row column), written
    # strided into the output
    b0, b1, b2 = padded[:, 0::3], padded[:, 1::3], padded[:, 2::3]
    chars = np.empty((n, groups * 4), dtype=np.uint8)
    chars[:, 0::4] = _B64_CHARS[b0 >> 2]
    chars[:, 1::4] = _B64_CHARS[((b0 & 0x03) << 4) | (b1 >> 4)]
    chars[:, 2::4] = _B64_CHARS[((b1 & 0x0F) << 2) | (b2 >> 6)]
    chars[:, 3::4] = _B64_CHARS[b2 & 0x3F]

    out_len = ((lengths + 2) // 3) * 4
    col = np.arange(groups * 4)[None, :]
    # valid b64 chars for row i: ceil(len/3)*4, but with '=' padding applied to
    # the last (3 - len%3) % 3 positions of the final group.
    n_equals = (3 - lengths % 3) % 3
    if n_equals.any():
        is_pad = (col >= (out_len - n_equals)[:, None]) & (col < out_len[:, None])
        chars[is_pad] = ord("=")
    if n and int(out_len.min()) < groups * 4:
        chars[col >= out_len[:, None]] = ord("\n")
    return chars, out_len


def _decode_single_int_filenames(names):
    """List of b64(msgpack([int])) filenames -> int64 array. Vectorized: one
    join, one frombuffer, table-driven base64 + msgpack decode."""
    n = len(names)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    widths = np.fromiter((len(s) for s in names), count=n, dtype=np.int64)
    w = int(widths.max())
    blob = "\n".join(names).encode("ascii")
    mat = np.full((n, w), ord("="), dtype=np.uint8)
    flat = np.frombuffer(blob, dtype=np.uint8)
    starts = np.zeros(n, dtype=np.int64)
    np.cumsum(widths[:-1] + 1, out=starts[1:])
    for col in range(w):
        take = col < widths
        mat[take, col] = flat[starts[take] + col]

    vals = _B64_INV[mat]
    vals[vals < 0] = 0
    groups = w // 4
    q = vals[:, : groups * 4].reshape(n, groups, 4).astype(np.uint32)
    triple = (q[..., 0] << 18) | (q[..., 1] << 12) | (q[..., 2] << 6) | q[..., 3]
    raw = np.stack(
        [(triple >> 16) & 0xFF, (triple >> 8) & 0xFF, triple & 0xFF], axis=-1
    ).reshape(n, groups * 3)

    assert np.all(raw[:, 0] == 0x91), "not a single-pk filename batch"
    marker = raw[:, 1]
    out = np.zeros(n, dtype=np.int64)

    def be_read(rows, start, nbytes):
        # raw is only as wide as the longest filename needs; a size-class mask
        # that matches nothing must not index beyond that width
        acc = np.zeros(int(rows.sum()), dtype=np.uint64)
        if not len(acc):
            return acc
        for b in range(nbytes):
            acc = (acc << np.uint64(8)) | raw[rows, start + b].astype(np.uint64)
        return acc

    m = marker <= 0x7F
    out[m] = marker[m]
    m = marker >= 0xE0  # negative fixint
    out[m] = marker[m].astype(np.int64) - 0x100
    m = marker == 0xCC
    if m.any():
        out[m] = raw[m, 2]
    m = marker == 0xCD
    out[m] = be_read(m, 2, 2).astype(np.int64)
    m = marker == 0xCE
    out[m] = be_read(m, 2, 4).astype(np.int64)
    m = marker == 0xCF
    out[m] = be_read(m, 2, 8).astype(np.int64)
    m = marker == 0xD0
    if m.any():
        out[m] = raw[m, 2].astype(np.int8)
    m = marker == 0xD1
    out[m] = be_read(m, 2, 2).astype(np.uint16).astype(np.int16)
    m = marker == 0xD2
    out[m] = be_read(m, 2, 4).astype(np.uint32).astype(np.int32)
    m = marker == 0xD3
    if m.any():
        out[m] = be_read(m, 2, 8).view(np.int64)
    return out


# Canonical encoder instances (reference: dataset3_paths.py:473-486)
PathEncoder.LEGACY_ENCODER = PathEncoder.get(
    scheme="msgpack/hash", branches=256, levels=2, encoding="hex"
)
PathEncoder.INT_PK_ENCODER = PathEncoder.get(
    scheme="int", branches=64, levels=4, encoding="base64"
)
PathEncoder.GENERAL_ENCODER = PathEncoder.get(
    scheme="msgpack/hash", branches=64, levels=4, encoding="base64"
)


def encoder_for_schema(schema):
    """Pick the canonical encoder for a new dataset with the given schema."""
    pk_cols = schema.pk_columns
    if len(pk_cols) == 1 and pk_cols[0].data_type == "integer":
        return PathEncoder.INT_PK_ENCODER
    return PathEncoder.GENERAL_ENCODER
