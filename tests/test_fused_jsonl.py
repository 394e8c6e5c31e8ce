"""Fused json-lines materialisation (ISSUE 1 tentpole, part 2): the
columnar row plan + compiled per-legend serialisers must emit bytes
identical to the generic delta/dict/encoder path, across value types,
escaping edge cases, and delta shapes (insert/update/delete)."""

import io
import json
import math

import pytest

from helpers import edit_commit, make_imported_repo


def jsonl(repo, fused):
    import os

    from kart_tpu.diff.writers import JsonLinesDiffWriter

    os.environ["KART_FUSED_JSONL"] = "1" if fused else "0"
    try:
        out = io.StringIO()
        w = JsonLinesDiffWriter(repo, "HEAD^...HEAD", output_path=out)
        changed = w.write_diff()
    finally:
        os.environ.pop("KART_FUSED_JSONL", None)
    return out.getvalue(), changed


def test_fused_jsonl_byte_identical_mixed_deltas(tmp_path):
    from kart_tpu.geometry import Geometry

    repo, ds_path = make_imported_repo(tmp_path, n=30)
    ds = repo.datasets()[ds_path]
    edit_commit(
        repo, ds_path,
        inserts=[
            {"fid": 100, "geom": Geometry.from_wkt("POINT (1 2)"),
             "name": 'quote " backslash \\ newline \n unicode ☃', "rating": 1.25},
            {"fid": 101, "geom": None, "name": None, "rating": None},
        ],
        updates=[
            {**ds.get_feature([3]), "rating": float("inf")},
            {**ds.get_feature([4]), "rating": float("nan")},
            {**ds.get_feature([5]), "name": "\x00\x1f control"},
        ],
        deletes=[7, 8],
        message="mixed edits",
    )
    fused, changed1 = jsonl(repo, True)
    plain, changed2 = jsonl(repo, False)
    assert fused == plain
    assert changed1 is True and changed2 is True
    # sanity: every line parses, and NaN/Infinity came through as json.dumps
    # emits them
    lines = fused.strip().splitlines()
    assert any('"rating":Infinity' in ln for ln in lines)
    assert any('"rating":NaN' in ln for ln in lines)
    for ln in lines:
        json.loads(ln, parse_constant=lambda c: c)


def test_fused_columnar_fast_path_mixed_deltas(tmp_path):
    """A repo big enough to carry sidecars (>= SIDECAR_MIN_FEATURES) takes
    the columnar row-plan path in the fused writer; output must stay
    byte-identical to the delta path across inserts/updates/deletes."""
    from kart_tpu.diff.engine import get_feature_diff_rows
    from kart_tpu.geometry import Geometry

    repo, ds_path = make_imported_repo(tmp_path, n=12_000)
    ds = repo.datasets()[ds_path]
    edit_commit(
        repo, ds_path,
        inserts=[
            {"fid": 20_001, "geom": Geometry.from_wkt("POINT (5 6)"),
             "name": "inserted", "rating": 2.5},
        ],
        updates=[
            {**ds.get_feature([10]), "name": "upd"},
            {**ds.get_feature([11_999]), "rating": -1.0},
        ],
        deletes=[500, 501],
        message="mixed at sidecar scale",
    )
    base_rs = repo.structure("HEAD^")
    target_rs = repo.structure("HEAD")
    rows = get_feature_diff_rows(base_rs, target_rs, ds_path)
    assert rows is not None and rows["count"] == 5  # the fast path is live
    assert (rows["old_rows"] >= 0).sum() == 4  # updates + deletes
    assert (rows["new_rows"] >= 0).sum() == 3  # updates + insert
    fused, _ = jsonl(repo, True)
    plain, _ = jsonl(repo, False)
    assert fused == plain
    assert fused.count('"type":"feature"') == 5


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_chunk_workers_byte_identical(tmp_path, monkeypatch, workers):
    """The chunk workers (native calls on pool threads, buffers written in
    row order) emit exactly the delta path's bytes — with chunks of a few
    rows and an output buffer that holds fewer, so chunks come back in
    several calls, on 1, 2 and 4 workers under a short switch interval."""
    import os
    import sys

    from kart_tpu import telemetry as tm
    from kart_tpu.diff.writers import JsonLinesDiffWriter

    repo, ds_path = make_imported_repo(tmp_path, n=11_000)
    ds = repo.datasets()[ds_path]
    edit_commit(
        repo, ds_path,
        updates=[
            {**ds.get_feature([fid]), "name": f"u{fid}"}
            for fid in range(10, 200)
        ],
        deletes=[5000, 5001],
        inserts=[{"fid": 20_000, "geom": None, "name": "in", "rating": 0.5}],
        message="edits",
    )
    repo.gc()  # the edit commit's loose blobs into a pack: native rows
    plain, _ = jsonl(repo, False)
    monkeypatch.setattr(os, "cpu_count", lambda: workers)
    monkeypatch.setattr(JsonLinesDiffWriter, "PREFETCH_CHUNK", 16)
    monkeypatch.setattr(JsonLinesDiffWriter, "CHUNK_BUFFER_BYTES", 1500)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    tm.enable(metrics=True)
    try:
        fused, _ = jsonl(repo, True)
        counters = {
            name: v for (name, _), v in tm.counters_snapshot().items()
        }
    finally:
        sys.setswitchinterval(interval)
        tm.reset()
    assert fused == plain
    assert fused.count('"type":"feature"') == 193
    assert counters.get("serialise.rows_native") == 193
    assert "serialise.rows_python" not in counters


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_map_in_order_bounds_results_in_flight(workers):
    """map_in_order: results in item order, at most workers + 1 made and
    not yet given back, an fn error raised at its item's turn."""
    import threading

    from kart_tpu.utils import map_in_order

    lock = threading.Lock()
    state = {"made": 0, "taken": 0, "most": 0}

    def fn(i):
        with lock:
            state["made"] += 1
            state["most"] = max(state["most"], state["made"] - state["taken"])
        if i == 40:
            raise ValueError("item 40")
        return i * i

    got = []
    with pytest.raises(ValueError, match="item 40"):
        for result in map_in_order(fn, range(100), workers, "kart-test"):
            got.append(result)
            with lock:
                state["taken"] += 1
    assert got == [i * i for i in range(40)]
    assert 1 <= state["most"] <= workers + 1
    assert state["made"] <= 40 + workers + 1  # nothing runs on past the error


# -- the native line against feature_json_str_from_data ----------------------

GEOM_ID, A_ID, B_ID, C_ID = "g0", "a0", "b0", "c0"


def stub_dataset(legends, columns=None):
    """A Dataset3 that serves a schema and legends from memory: fid (pk),
    geom, a, b, c unless ``columns`` says otherwise. ``legends``: lists of
    non-pk column ids. -> (dataset, [legend hash])."""
    from kart_tpu.models.dataset import Dataset3
    from kart_tpu.models.schema import ColumnSchema, Legend, Schema

    columns = columns or [
        ColumnSchema(id="pk0", name="fid", data_type="integer", pk_index=0,
                     extra_type_info={"size": 64}),
        ColumnSchema(id=GEOM_ID, name="geom", data_type="geometry", pk_index=None,
                     extra_type_info={"geometryType": "GEOMETRY"}),
        ColumnSchema(id=A_ID, name='a "quoted" \u2603 name', data_type="text",
                     pk_index=None, extra_type_info={}),
        ColumnSchema(id=B_ID, name="b", data_type="float", pk_index=None,
                     extra_type_info={"size": 64}),
        ColumnSchema(id=C_ID, name="c", data_type="blob", pk_index=None,
                     extra_type_info={}),
    ]
    ds = Dataset3.__new__(Dataset3)
    ds._meta_cache = {"__schema__": Schema(columns)}
    hashes = []
    for non_pk in legends:
        legend = Legend(["pk0"], non_pk)
        ds._meta_cache[f"__legend__{legend.hexhash()}"] = legend
        hashes.append(legend.hexhash())
    return ds, hashes


def pack_of(blobs):
    """Plain blob records of a pack, in memory -> (pack bytes, offsets)."""
    import zlib

    from test_packs import _varint_header

    pack, offsets = bytearray(b"PACK" + b"\0" * 8), []
    for blob in blobs:
        offsets.append(len(pack))
        pack += _varint_header(3, len(blob)) + zlib.compress(blob, 1)
    return bytes(pack), offsets


def native_lines(ds, blobs, planned=None):
    """Each blob as the new side of one row (pk = its position) through
    native.jsonl_chunk -> [line bytes | why it was declined]."""
    import numpy as np

    from kart_tpu import native

    pack, offsets = pack_of(blobs)
    n = len(blobs)
    hashes = planned if planned is not None else [
        k[len("__legend__"):] for k in ds._meta_cache if k.startswith("__legend__")
    ]
    plans = native.pack_jsonl_plans({h: ds._jsonl_plan(h) for h in hashes})
    out = np.empty(64 << 20, dtype=np.uint8)
    total, done, row_end, status = native.jsonl_chunk(
        [pack], np.full(n, -1, np.int32), np.zeros(n, np.int64),
        np.zeros(n, np.int32), np.asarray(offsets, np.int64),
        np.arange(n, dtype=np.int64), b"HEAD:", plans, plans, out,
    )
    assert done == n
    text = out[:total].tobytes()
    ends = row_end.tolist()
    return [
        text[(ends[r - 1] if r else 0):ends[r]] if not status[r]
        else native.JSONL_WHY[status[r]]
        for r in range(n)
    ]


def python_lines(ds, blobs, skip=()):
    """The same rows by feature_json_str_from_data (None at ``skip``)."""
    return [
        None if pk in skip else
        ('HEAD:"+":' + ds.feature_json_str_from_data((pk,), blob) + "}}\n").encode()
        for pk, blob in enumerate(blobs)
    ]


def _random_floats():
    import random
    import struct

    rnd = random.Random(31)
    bits = [rnd.getrandbits(64) for _ in range(100_000)]
    return (
        [struct.unpack("<d", struct.pack("<Q", b))[0] for b in bits]
        + [rnd.uniform(-1e6, 1e6) for _ in range(10_000)]
        + [float(rnd.randint(-10**17, 10**17)) for _ in range(10_000)]
    )


def _random_strings():
    import random

    rnd = random.Random(32)
    planes = [(0, 0x7F), (0x80, 0x7FF), (0x800, 0xD7FF), (0xE000, 0xFFFF),
              (0x10000, 0x10FFFF)]
    return [
        "".join(chr(rnd.randint(*rnd.choice(planes))) for _ in range(rnd.randint(0, 30)))
        for _ in range(5_000)
    ]


VALUE_CASES = {
    "floats_named": lambda: [
        0.0, -0.0, 1e16, 9999999999999998.0, 1e-5, 0.0001, 5e-324,
        1.7976931348623157e308, float("nan"), float("inf"), float("-inf"),
        1.0, 100.0, 0.1, 1 / 3, 1e15, 1e21, 1e22, 1.5e-7, 123456.789,
    ],
    "floats_random_bits": _random_floats,
    "ints": lambda: [
        0, 1, -1, 127, 128, -32, -33, 255, 256, 65535, 65536, 2**31 - 1,
        2**31, 2**32, -(2**31), -(2**31) - 1, 2**63 - 1, -(2**63), 2**63,
        2**64 - 1,
    ],
    "strings_named": lambda: [
        "", "plain", 'quote " backslash \\ slash /', "\n\r\t\b\f",
        "\x00\x01\x1f control", "DEL \x7f", "2-byte \u00e9\u07ff",
        "3-byte \u0800\u2603\uffff", "4-byte \U00010000\U0001f600\U0010ffff",
        "x" * 300, "\u2603" * 70_000,
    ],
    "strings_random": _random_strings,
    "none_and_bools": lambda: [None, True, False],
    "bin": lambda: [b"", b"\x00\xff\x10", bytes(range(256)), b"z" * 70_000],
}


@pytest.mark.parametrize("case", sorted(VALUE_CASES))
def test_native_line_equals_python_values(case):
    """Every scalar a feature blob can hold, in a plain column: the native
    line is the compiled Python serialiser's line, byte for byte."""
    from kart_tpu.core.serialise import msg_pack

    ds, (h,) = stub_dataset([[A_ID]])
    blobs = [msg_pack([h, [v]]) for v in VALUE_CASES[case]()]
    assert native_lines(ds, blobs) == python_lines(ds, blobs)


def test_native_line_float32():
    import random
    import struct

    from kart_tpu.core.serialise import msg_pack

    ds, (h,) = stub_dataset([[B_ID]])
    rnd = random.Random(33)
    blobs = [
        b"\x92" + msg_pack(h) + b"\x91\xca" + struct.pack(">I", rnd.getrandbits(32))
        for _ in range(20_000)
    ]
    assert native_lines(ds, blobs) == python_lines(ds, blobs)


def _gpkg(flags, envelope_doubles, wkb):
    import struct

    return (
        b"GP\x00" + bytes([flags]) + struct.pack("<i", 4326)
        + struct.pack(f"<{envelope_doubles}d", *range(envelope_doubles)) + wkb
    )


_POINT_LE = bytes.fromhex("0101000000000000000000F03F0000000000000040")
_POINT_BE = bytes.fromhex("00000000013FF00000000000004000000000000000")

GEOMETRY_CASES = {
    # name: (gpkg blob, declined why | None)
    "no_envelope": (_gpkg(0x01, 0, _POINT_LE), None),
    "envelope_xy": (_gpkg(0x03, 4, _POINT_LE), None),
    "envelope_xyz": (_gpkg(0x05, 6, _POINT_LE), None),
    "envelope_xym": (_gpkg(0x07, 6, _POINT_LE), None),
    "envelope_xyzm": (_gpkg(0x09, 8, _POINT_LE), None),
    "empty_flag_set": (_gpkg(0x11, 0, _POINT_LE), None),
    "no_wkb_after_header": (_gpkg(0x03, 4, b""), None),
    "big_endian_header_le_wkb": (_gpkg(0x00, 0, _POINT_LE), None),
    "big_endian_wkb": (_gpkg(0x01, 0, _POINT_BE), "geometry"),
    "extended": (_gpkg(0x21, 0, _POINT_LE), "geometry"),
    "envelope_code_5": (_gpkg(0x0B, 0, _POINT_LE), "geometry"),
}


@pytest.mark.parametrize("case", sorted(GEOMETRY_CASES))
def test_native_line_geometry(case):
    """Upper-hex WKB past the GPKG header exactly where gpkg_hex_wkb's fast
    path applies; anything else is declined, never approximated."""
    import msgpack

    from kart_tpu.core.serialise import GEOMETRY_EXT_CODE, msg_pack

    gpkg, why = GEOMETRY_CASES[case]
    ds, (h,) = stub_dataset([[GEOM_ID, B_ID]])
    blob = msg_pack([h, [msgpack.ExtType(GEOMETRY_EXT_CODE, gpkg), 1.5]])
    if why is None:
        assert native_lines(ds, [blob]) == python_lines(ds, [blob])
    else:
        assert native_lines(ds, [blob]) == [why]


def _legend_cases():
    import msgpack

    from kart_tpu.core.serialise import GEOMETRY_EXT_CODE, msg_pack

    point = msgpack.ExtType(GEOMETRY_EXT_CODE, _gpkg(0x01, 0, _POINT_LE))
    full = [GEOM_ID, A_ID, B_ID, C_ID]
    short = [GEOM_ID, A_ID]  # b, c added since: trailing nulls
    swapped = [B_ID, GEOM_ID]  # another order, a and c absent
    return {
        # name: (legends, blobs as (legend index, values), planned legend
        # indexes | None = all, expected declines)
        "legend_shorter_than_schema": ([short], [(0, [point, "s"])], None, {}),
        "two_legends": (
            [full, swapped],
            [(0, [point, "s", 2.5, b"\x01"]), (1, [0.25, point]),
             (0, [None, None, None, None]), (1, [None, None])],
            None, {},
        ),
        "blob_shorter_than_legend": ([full], [(0, [point, "s"])], None, {}),
        "blob_longer_than_legend": ([short], [(0, [point, "s", 7, "extra"])], None, {}),
        "unplanned_legend": (
            [full, swapped],
            [(0, [point, "s", 2.5, b"\x01"]), (1, [0.25, point]), (0, [None] * 4)],
            [0], {1: "legend"},
        ),
        "geometry_ext_in_plain_column": ([full], [(0, [point, point, 1.0, b""])], None, {0: "type"}),
        "text_in_geometry_column": ([full], [(0, ["s", "s", 1.0, b""])], None, {0: "type"}),
        "nested_array": ([full], [(0, [point, [1, 2], 1.0, b""])], None, {0: "type"}),
        "nested_map": ([full], [(0, [point, {"k": 1}, 1.0, b""])], None, {0: "type"}),
        "other_ext_code": ([full], [(0, [point, msgpack.ExtType(5, b"x"), 1.0, b""])], None, {0: "type"}),
    }


@pytest.mark.parametrize("case", sorted(_legend_cases()))
def test_native_line_legends_and_declines(case):
    """Column resolution through the legend's plan (columns added since,
    another order, a blob shorter or longer than its legend), and the rows
    the walk must decline: a legend without a plan, a value it does not
    cover."""
    from kart_tpu.core.serialise import msg_pack

    legends, rows, planned, declined = _legend_cases()[case]
    ds, hashes = stub_dataset(legends)
    blobs = [msg_pack([hashes[k], values]) for k, values in rows]
    got = native_lines(
        ds, blobs,
        planned=None if planned is None else [hashes[k] for k in planned],
    )
    want = python_lines(ds, blobs, skip=declined)
    assert got == [declined.get(r, line) for r, line in enumerate(want)]


@pytest.mark.parametrize(
    "raw",
    [b"\xff", b"\xc0\x80", b"\xc1\xbf", b"\xe0\x80\x80", b"\xed\xa0\x80",
     b"\xf0\x80\x80\x80", b"\xf4\x90\x80\x80", b"\xf5\x80\x80\x80",
     b"\xe2\x98", b"\x80", b"\xc2"],
    ids=lambda raw: raw.hex(),
)
def test_native_line_declines_invalid_utf8(raw):
    """What msgpack's strict decode refuses the walk declines — in a column
    it reads and in a value no column reads."""
    import msgpack

    from kart_tpu.core.serialise import msg_pack

    ds, (h,) = stub_dataset([[A_ID]])
    text = bytes([0xA0 | len(raw)]) + raw
    for blob in (
        b"\x92" + msg_pack(h) + b"\x91" + text,
        b"\x92" + msg_pack(h) + b"\x92\xc0" + text,
    ):
        with pytest.raises(ValueError):
            msgpack.unpackb(blob, raw=False)
        assert native_lines(ds, [blob]) == ["utf8"]


def test_native_line_malformed_blobs_declined():
    from kart_tpu.core.serialise import msg_pack

    ds, (h,) = stub_dataset([[A_ID]])
    blobs = [
        b"", b"\x92", msg_pack([h]), msg_pack([h, 5]), msg_pack([5, [1]]),
        msg_pack([h, [1]]) + b"\x00", msg_pack([h, [1]])[:-1] + b"\xd9",
        msg_pack([h, ["abc"]])[:-1],
    ]
    assert native_lines(ds, blobs) == [
        "legend", "legend", "legend", "type", "legend", "type", "type", "type",
    ]


def test_native_line_larger_than_buffer_declined():
    """A line, or a blob by its record header alone, that the whole output
    buffer cannot hold is declined (`size`); rows around it are written, and
    a buffer that fills up hands the rest back to the caller."""
    import numpy as np

    from kart_tpu import native
    from kart_tpu.core.serialise import msg_pack

    ds, (h,) = stub_dataset([[A_ID]])
    blobs = [msg_pack([h, [v]]) for v in ("a", "b" * 150, "c", "d" * 400, "e")]
    pack, offsets = pack_of(blobs)
    plans = native.pack_jsonl_plans({h: ds._jsonl_plan(h)})
    want = python_lines(ds, blobs)
    n = len(blobs)
    got, pos = [], 0
    while pos < n:
        out = np.empty(300, dtype=np.uint8)  # row 1 fits alone, row 3 never
        total, done, row_end, status = native.jsonl_chunk(
            [pack], np.full(n - pos, -1, np.int32), np.zeros(n - pos, np.int64),
            np.zeros(n - pos, np.int32), np.asarray(offsets[pos:], np.int64),
            np.arange(pos, n, dtype=np.int64), b"HEAD:", plans, plans, out,
        )
        assert done >= 1
        ends = [0] + row_end[:done].tolist()
        got += [
            native.JSONL_WHY[status[r]] if status[r]
            else out[ends[r]:ends[r + 1]].tobytes()
            for r in range(done)
        ]
        pos += done
    assert got == [want[0], want[1], want[2], "size", want[4]]


# -- the native .idx probe against the numpy probe ---------------------------

def _index_of(tmp_path, offsets_by_sha):
    from kart_tpu.core.packs import PackIndex, write_pack_index

    path = str(tmp_path / "probe.idx")
    write_pack_index(
        path, [(sha, 0, off) for sha, off in offsets_by_sha.items()], b"\0" * 20
    )
    return PackIndex(path)


def _shas(n, seed):
    import hashlib

    return [hashlib.sha1(b"%d-%d" % (seed, i)).digest() for i in range(n)]


PROBE_CASES = {
    # name: (offsets of the indexed shas, extra probes that miss)
    "hits_and_misses": (lambda s: {x: 12 + 7 * i for i, x in enumerate(s)}, 300),
    "offsets_past_2gib": (
        lambda s: {x: (1 << 31) * (i % 3) + (5 << 32) * (i % 5 == 0) + i
                   for i, x in enumerate(s)},
        50,
    ),
    "one_entry": (lambda s: {s[0]: 12}, 20),
    "no_entries": (lambda s: {}, 20),
}


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_idx_probe_native_equals_numpy(tmp_path, case):
    """PackIndex.offsets_of_batch: the native probe answers as the numpy
    searchsorted probe does — hits, misses, the first and the last entry of
    the table, neighbours of an entry that differ in the last byte, offsets
    through the 64-bit table — for a list of shas and for an array."""
    import numpy as np

    from kart_tpu import native

    if native.load_io() is None:
        pytest.skip("libkart_io not built")
    make_offsets, n_miss = PROBE_CASES[case]
    held = make_offsets(_shas(2_000, 1))
    index = _index_of(tmp_path, held)
    ordered = sorted(held)
    probes = ordered[:1] + ordered[-1:] + list(held) + _shas(n_miss, 2)
    for sha in ordered[:1] + ordered[-1:]:
        for delta in (-1, 1):  # just outside an entry
            probes.append(sha[:-1] + bytes([(sha[-1] + delta) % 256]))
    probes += [b"\x00" * 20, b"\xff" * 20]
    want = [held.get(sha, -1) for sha in probes]
    arr = np.frombuffer(b"".join(probes), dtype=np.uint8).reshape(-1, 20)
    assert index.offsets_of_batch(probes).tolist() == want
    assert index.offsets_of_batch(arr).tolist() == want
    assert index._offsets_of_batch_numpy(arr.view("S20").ravel()).tolist() == want
    assert index.offsets_of_batch([]).tolist() == []


# -- declined rows in the middle of a chunk ----------------------------------

def _edited_repo(tmp_path):
    """12,000 imported rows (sidecars: the row plan is live), one commit
    with 40 updates, 2 deletes, 2 inserts."""
    repo, ds_path = make_imported_repo(tmp_path, n=12_000)
    ds = repo.datasets()[ds_path]
    edit_commit(
        repo, ds_path,
        updates=[
            {**ds.get_feature([fid]), "name": f"u{fid}", "rating": fid / 7}
            for fid in range(100, 140)
        ],
        deletes=[50, 9_000],
        inserts=[
            {"fid": 20_001, "geom": None, "name": "ins \u2603", "rating": None},
            {"fid": 20_002, "geom": None, "name": None, "rating": 1e300},
        ],
        message="edits",
    )
    return repo, ds_path


def _new_blob_oid(repo, ds_path, fid):
    ds = repo.structure("HEAD").datasets[ds_path]
    return ds.feature_tree.get(ds.encode_1pk_to_path(fid, relative=True)[len("feature/"):]).oid


def _loose_path(repo, oid):
    import os

    return os.path.join(repo.gitdir, "objects", oid[:2], oid[2:])


def _keep_loose(repo, oids):
    """gc everything but ``oids`` into a pack."""
    import os

    for oid in oids:
        os.rename(_loose_path(repo, oid), _loose_path(repo, oid) + ".aside")
    repo.gc()
    for oid in oids:
        os.makedirs(os.path.dirname(_loose_path(repo, oid)), exist_ok=True)
        os.rename(_loose_path(repo, oid) + ".aside", _loose_path(repo, oid))


def _as_delta_record(repo, oid):
    """Move blob ``oid`` out of the loose store into a pack of its own that
    holds it as an OFS_DELTA against a base record."""
    import hashlib
    import os
    import struct
    import zlib
    from binascii import crc32

    from kart_tpu.core.packs import write_pack_index
    from test_packs import _make_delta, _obj_sha, _ofs_backref, _varint_header

    content = repo.odb.read_blob(oid)
    half = len(content) // 2
    base = content[:half] * 2  # the delta copies base[:half], inserts the rest
    records, body = [], bytearray()
    rec = _varint_header(3, len(base)) + zlib.compress(base)
    records.append((_obj_sha("blob", base), rec, 12))
    body += rec
    delta = _make_delta(base, content)
    rec = _varint_header(6, len(delta)) + _ofs_backref(len(body)) + zlib.compress(delta)
    records.append((bytes.fromhex(oid), rec, 12 + len(body)))
    body += rec
    pack = b"PACK" + struct.pack(">II", 2, 2) + bytes(body)
    pack_sha = hashlib.sha1(pack).digest()
    stem = os.path.join(repo.gitdir, "objects", "pack", f"pack-{pack_sha.hex()}")
    with open(stem + ".pack", "wb") as f:
        f.write(pack + pack_sha)
    write_pack_index(
        stem + ".idx",
        [(sha, crc32(rec) & 0xFFFFFFFF, off) for sha, rec, off in records],
        pack_sha,
    )
    os.remove(_loose_path(repo, oid))
    repo.odb.packs.refresh()
    assert repo.odb.read_blob(oid) == content


def _decline_loose(repo, ds_path, monkeypatch):
    _keep_loose(repo, [_new_blob_oid(repo, ds_path, fid) for fid in (110, 111, 20_001)])
    return {"record": 3}


def _decline_delta(repo, ds_path, monkeypatch):
    oid = _new_blob_oid(repo, ds_path, 120)
    _keep_loose(repo, [oid])
    _as_delta_record(repo, oid)
    return {"record": 1}


def _decline_unplanned_legend(repo, ds_path, monkeypatch):
    """The old side's dataset hands the native walk no plan: every row with
    an old side is Python's, the two inserts stay native."""
    from kart_tpu import native
    from kart_tpu.models.dataset import Dataset3

    repo.gc()
    base_tree = repo.structure("HEAD^").datasets[ds_path].tree.oid
    planned = Dataset3.jsonl_native_plans
    monkeypatch.setattr(
        Dataset3, "jsonl_native_plans",
        lambda self: native.pack_jsonl_plans({})
        if self.tree.oid == base_tree else planned(self),
    )
    return {"legend": 42}


def _decline_no_library(repo, ds_path, monkeypatch):
    """KART_TPU_NATIVE_IO_LIB pointing nowhere: every row is Python's."""
    from kart_tpu import native

    repo.gc()
    monkeypatch.setenv("KART_TPU_NATIVE_IO_LIB", "/nonexistent/libkart_io.so")
    monkeypatch.setattr(native, "_io_lib", None)
    monkeypatch.setattr(native, "_io_load_attempted", False)
    return {"no_native": 44}


@pytest.mark.parametrize(
    "decline",
    [_decline_loose, _decline_delta, _decline_unplanned_legend, _decline_no_library],
    ids=lambda f: f.__name__[len("_decline_"):],
)
def test_declined_rows_spliced_in_order(tmp_path, monkeypatch, decline):
    """Rows the native walk declines in the middle of a chunk — a loose
    object, a delta record, a legend without a plan, no library at all —
    are made by today's Python code and come out in place: the delta path's
    bytes, and serialise.rows_python{why} counts them."""
    from kart_tpu import native
    from kart_tpu import telemetry as tm

    repo, ds_path = _edited_repo(tmp_path)
    want_python = decline(repo, ds_path, monkeypatch)
    tm.enable(metrics=True)
    try:
        fused, _ = jsonl(repo, True)
        counters = tm.counters_snapshot()
    finally:
        tm.reset()
        # the next test loads the library afresh
        native._io_lib, native._io_load_attempted = None, False
    monkeypatch.undo()
    plain, _ = jsonl(repo, False)
    assert fused == plain
    assert fused.count('"type":"feature"') == 44
    got_python = {
        dict(labels)["why"]: v
        for (name, labels), v in counters.items()
        if name == "serialise.rows_python"
    }
    assert got_python == want_python
    assert counters.get(("serialise.rows_native", ()), 0) == 44 - sum(
        want_python.values()
    )


def test_fused_jsonl_no_changes(tmp_path):
    repo, ds_path = make_imported_repo(tmp_path, n=5)
    edit_commit(
        repo, ds_path,
        updates=[{**repo.datasets()[ds_path].get_feature([2]), "name": "x"}],
        message="one edit",
    )
    fused, _ = jsonl(repo, True)
    plain, _ = jsonl(repo, False)
    assert fused == plain


def test_serializer_matches_generic_dict_encoder(tmp_path):
    """feature_json_str_from_data == compact-JSON of feature_json_from_data
    for every feature blob in the repo (the unit-level parity the writer
    test exercises end-to-end)."""
    repo, ds_path = make_imported_repo(tmp_path, n=12)
    ds = repo.datasets()[ds_path]
    enc = json.JSONEncoder(separators=(",", ":"), ensure_ascii=True).encode
    feature_tree = ds.feature_tree
    odb = feature_tree.odb
    n = 0
    for path, entry in feature_tree.walk_blobs():
        pks = ds.decode_path_to_pks(path)
        data = odb.read_blob(entry.oid)
        fused = ds.feature_json_str_from_data(pks, data)
        generic = enc(ds.feature_json_from_data(pks, data))
        assert fused == generic, path
        n += 1
    assert n == 12


def test_attributes_dataset_fused(tmp_path):
    """Geometry-less datasets (int/str/bool columns) take the fused path
    too, byte-identically."""
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.importer import ImportSource
    from kart_tpu.importer.importer import import_sources

    from helpers import create_attributes_gpkg

    gpkg = create_attributes_gpkg(str(tmp_path / "attrs.gpkg"), n=20)
    repo = KartRepo.init_repository(tmp_path / "repo")
    repo.config.set_many({"user.name": "T", "user.email": "t@example.com"})
    import_sources(repo, ImportSource.open(gpkg))
    ds_path = "records"
    # edit_commit assumes a 'fid' pk; this table's pk is 'id'
    from kart_tpu.diff.structs import (
        DatasetDiff,
        Delta,
        DeltaDiff,
        KeyValue,
        RepoDiff,
    )

    structure = repo.structure("HEAD")
    ds = structure.datasets[ds_path]
    feature_diff = DeltaDiff()
    for pk, change in ((2, {"code": "edited"}), (3, {"flag": False})):
        old = ds.get_feature([pk])
        feature_diff.add_delta(
            Delta.update(KeyValue((pk, old)), KeyValue((pk, {**old, **change})))
        )
    ds_diff = DatasetDiff()
    ds_diff["feature"] = feature_diff
    repo_diff = RepoDiff()
    repo_diff[ds_path] = ds_diff
    structure.commit_diff(repo_diff, "attr edits")
    fused, _ = jsonl(repo, True)
    plain, _ = jsonl(repo, False)
    assert fused == plain
