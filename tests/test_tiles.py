"""Tile read-serving off the columnar store (ISSUE 10): grid math, the
block-pruned row selection, clip/quantize, payload determinism (cold vs
cached vs across processes), the commit-addressed cache + drop hook, the
parity contract against the spatial-filtered reference path, and the
endpoint's shed semantics (tiles ARE shed; /api/v1/stats is not)."""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from kart_tpu import telemetry, tiles
from kart_tpu.core.repo import KartRepo
from kart_tpu.tiles.grid import (
    MERC_MAX_LAT,
    TileAddressError,
    parse_zoom_spec,
    tile_bounds_wsen,
    tile_query_wsen,
    tile_range_for_bbox,
    validate_tile,
)
from kart_tpu.transport.http import make_server

from helpers import edit_commit, make_imported_repo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_metrics():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in (
        "KART_FAULTS",
        "KART_TILE_CACHE",
        "KART_TILE_MAX_FEATURES",
        "KART_SERVE_TILES",
        "KART_SERVE_MAX_INFLIGHT",
    ):
        monkeypatch.delenv(var, raising=False)


@pytest.fixture()
def served_points(tmp_path):
    """An imported points repo (real blobs, real point geometry) served
    over in-thread localhost HTTP."""
    repo, ds_path = make_imported_repo(tmp_path, n=40)
    repo.config["receive.denyCurrentBranch"] = "ignore"
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield repo, ds_path, url
    server.shutdown()
    server.server_close()


@pytest.fixture()
def synth_spatial(tmp_path):
    """A 200k-row spatial synth repo: envelope sidecar columns + block
    aggregates present, feature blobs promised (the partial-clone /
    bench-scale state — the columnar bin layer must serve without them)."""
    from kart_tpu.synth import synth_repo

    repo, info = synth_repo(
        str(tmp_path / "synth"), 200_000, spatial=True, blobs="promised"
    )
    return repo, info


def http_get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def counter(name, **labels):
    for n, l, v in telemetry.snapshot()["counters"]:
        if n == name and l == labels:
            return v
    return 0


# ---------------------------------------------------------------------------
# grid math
# ---------------------------------------------------------------------------


def test_tile_bounds_world_and_quadrants():
    assert tile_bounds_wsen(0, 0, 0) == pytest.approx(
        (-180.0, -MERC_MAX_LAT, 180.0, MERC_MAX_LAT)
    )
    w, s, e, n = tile_bounds_wsen(1, 1, 1)  # south-east quadrant
    assert (w, e) == (0.0, 180.0)
    assert n == 0.0 and s == pytest.approx(-MERC_MAX_LAT)


def test_tile_bounds_adjacent_tiles_share_edges():
    *_, e0, _ = tile_bounds_wsen(3, 2, 3)
    w1, *_ = tile_bounds_wsen(3, 3, 3)
    assert e0 == w1
    _, s_up, _, _ = tile_bounds_wsen(3, 2, 3)
    _, _, _, n_down = tile_bounds_wsen(3, 2, 4)
    assert s_up == n_down


def test_tile_query_pads_but_stays_legal():
    w, s, e, n = tile_query_wsen(0, 0, 0)
    assert w < -180.0 and e > 180.0  # lon pad pokes past (handled cyclically)
    assert s >= -90.0 and n <= 90.0


def test_validate_tile_rejects_bad_addresses():
    for bad in [(-1, 0, 0), (2, 4, 0), (2, 0, -1), (31, 0, 0), ("z", 0, 0)]:
        with pytest.raises(TileAddressError):
            validate_tile(*bad)


def test_parse_zoom_spec():
    assert parse_zoom_spec("3") == [3]
    assert parse_zoom_spec("2-5") == [2, 3, 4, 5]
    assert parse_zoom_spec("5-2") == [2, 3, 4, 5]
    with pytest.raises(TileAddressError):
        parse_zoom_spec("x")


def test_polar_features_served_by_edge_tile_rows():
    """Regression (review finding): the documented latitude-clamp policy —
    features polewards of ±85.05° are *served by* the top/bottom tile rows,
    never dropped — must hold in the selection math. The membership
    rectangle of an edge row extends to the pole."""
    from kart_tpu.ops.bbox import bbox_intersects_np
    from kart_tpu.tiles.clip import clip_quantize
    from kart_tpu.tiles.grid import tile_cover_wsen

    polar = np.array([[10.0, 88.0, 10.001, 88.001]], dtype=np.float32)
    # z2 row 0 covers lon 0..90 at x=2: the lat-88 feature must be in it
    for z, x, y, want in [(2, 2, 0, True), (2, 2, 1, False), (0, 0, 0, True)]:
        query = np.asarray(tile_query_wsen(z, x, y))
        hit = bool(bbox_intersects_np(polar, query)[0])
        if hit:
            rows, boxes = clip_quantize(polar, np.array([0]), z, x, y)
            hit = len(rows) == 1
            if hit:
                # quantizes onto the tile's top edge (clamped), inside the
                # buffered square
                assert -64 <= boxes[0][1] <= 4096 + 64
        assert hit == want, (z, x, y)
    # the south pole symmetrically
    south = np.array([[10.0, -89.0, 10.001, -88.9]], dtype=np.float32)
    q = np.asarray(tile_query_wsen(1, 1, 1))
    assert bool(bbox_intersects_np(south, q)[0])
    w, s, e, n = tile_cover_wsen(1, 1, 1)
    assert s == -90.0 and n == 0.0


def test_tile_range_for_bbox_covers_and_clamps():
    x0, y0, x1, y1 = tile_range_for_bbox(2, (-10.0, -10.0, 10.0, 10.0))
    assert (x0, x1) == (1, 2)
    assert y0 <= 2 <= y1
    # wrapping/non-finite lon -> full row
    assert tile_range_for_bbox(1, (170.0, 0.0, -170.0, 10.0))[::2] == (0, 1)


# ---------------------------------------------------------------------------
# the serving path: determinism, cache, pruning
# ---------------------------------------------------------------------------


def test_tile_payload_cold_vs_cached_byte_identical(served_points):
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/2/3/2"
    s1, h1, cold = http_get(t)
    s2, h2, cached = http_get(t)
    assert s1 == s2 == 200
    assert cold == cached
    assert h1["ETag"] == h2["ETag"]
    header, layers = tiles.parse_payload(cold)
    assert header["count"] > 0
    assert set(layers) == {"bin", "geojson"}
    assert counter("tiles.cache.hits") == 1
    assert counter("tiles.cache.misses") == 1


def test_cached_tile_serves_without_touching_the_odb(served_points):
    """ISSUE 10 acceptance: a cache hit returns memoized bytes — no blob
    read, no sidecar/envelope page fault (asserted on the counters)."""
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1"
    status, _, cold = http_get(t)
    assert status == 200
    blobs_before = counter("odb.blobs_read")
    blocks_before = counter("tiles.blocks_read")
    status, _, cached = http_get(t)
    assert status == 200 and cached == cold
    assert counter("odb.blobs_read") == blobs_before
    assert counter("tiles.blocks_read") == blocks_before
    assert counter("tiles.cache.hits") == 1


def test_tile_stable_across_two_server_processes(served_points, tmp_path):
    """The payload for one (commit, dataset, z/x/y, layers) key is
    byte-identical between an in-process server and a separate `kart
    export tiles` process (one wire format, no process-local state)."""
    repo, ds_path, url = served_points
    status, _, served = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/2/3/2")
    assert status == 200

    out = tmp_path / "pyramid"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "kart_tpu.cli",
            "-C", str(repo.workdir or repo.gitdir),
            "export", "tiles", "HEAD", "--dataset", ds_path,
            "--zoom", "2", "-o", str(out),
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    with open(out / "2" / "3" / "2.ktile", "rb") as f:
        exported = f.read()
    assert exported == served


def test_tile_etag_conditional_get(served_points):
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0"
    status, headers, _ = http_get(t)
    assert status == 200
    etag = headers["ETag"]
    status, headers2, body = http_get(t, headers={"If-None-Match": etag})
    assert status == 304 and body == b""
    assert headers2["ETag"] == etag
    # RFC 9110 forms a revalidating proxy/browser may send (review
    # finding): validator lists, weak prefixes, and *
    for value in (f'"zzz", {etag}', f"W/{etag}", "*"):
        assert http_get(t, headers={"If-None-Match": value})[0] == 304, value
    assert http_get(t, headers={"If-None-Match": '"zzz"'})[0] == 200
    # a NEVER-ENCODED tile answers 304 from the key alone (no source
    # build): compute the validator client-side
    cold_etag, _ = tiles.tile_etag(repo, "HEAD", ds_path, 3, 6, 4)
    blobs_before = counter("odb.blobs_read")
    status, _, body = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/3/6/4",
        headers={"If-None-Match": cold_etag},
    )
    assert status == 304 and body == b""
    assert counter("odb.blobs_read") == blobs_before
    assert counter("tiles.cache.misses") == 1  # only the initial 0/0/0 GET


def test_concurrent_cold_requests_build_one_source(served_points, monkeypatch):
    """Review finding: concurrent cold requests for DIFFERENT tiles of one
    commit must construct ONE TileSource (the O(N) sidecar/envelope build
    is per revision, not per request) — source_for single-flights."""
    import time as _time

    from kart_tpu.tiles import source as source_mod

    repo, ds_path, url = served_points
    source_mod.drop_sources()
    builds = []
    real_init = source_mod.TileSource.__init__

    def counting_init(self, *args, **kwargs):
        builds.append(threading.get_ident())
        _time.sleep(0.2)  # hold the build open so the others provably race
        real_init(self, *args, **kwargs)

    monkeypatch.setattr(source_mod.TileSource, "__init__", counting_init)
    results = []

    def get(z, x, y):
        results.append(
            http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/{z}/{x}/{y}")[0]
        )

    threads = [
        threading.Thread(target=get, args=a)
        for a in [(1, 1, 1), (2, 3, 2), (0, 0, 0)]
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [200, 200, 200]
    assert len(builds) == 1, f"{len(builds)} TileSource builds for one commit"


def test_tile_of_pinned_commit_survives_ref_update(served_points):
    """Keys are commit-addressed: after HEAD moves, the old commit's tile
    is still servable by oid and is byte-identical; HEAD's tile changes."""
    repo, ds_path, url = served_points
    old_oid = repo.head_commit_oid
    _, _, old_head = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    edit_commit(repo, ds_path, deletes=[1], message="move HEAD")
    _, _, by_oid = http_get(f"{url}/api/v1/tiles/{old_oid}/{ds_path}/0/0/0")
    assert by_oid == old_head
    _, _, new_head = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    h_old, _ = tiles.parse_payload(old_head)
    h_new, _ = tiles.parse_payload(new_head)
    assert h_new["commit"] != h_old["commit"]
    assert h_new["count"] == h_old["count"] - 1


def test_ref_update_drop_hook_releases_tile_cache(served_points):
    """The explicit drop hook next to apply_ref_updates: a ref update
    empties the tile cache (memory hygiene — keys can't go stale, but
    tiles of abandoned commits are dead weight)."""
    from kart_tpu.tiles.cache import tile_cache_for
    from kart_tpu.transport.service import apply_ref_updates

    repo, ds_path, url = served_points
    status, _, _ = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    assert status == 200
    assert tile_cache_for(repo).stats()["entries"] == 1
    head = repo.head_commit_oid
    result = apply_ref_updates(
        repo,
        {"updates": [{"ref": "refs/heads/tmp", "old": None, "new": head}]},
    )
    assert result[0] == "ok"
    assert tile_cache_for(repo).stats() == {"entries": 0, "bytes": 0}


def test_concurrent_same_tile_single_flights(served_points, monkeypatch):
    """Two concurrent requests for one cold tile run ONE encode: the
    second blocks on the first's fill and hits."""
    import time as _time

    repo, ds_path, url = served_points
    real_encode = tiles.encode_tile
    started = threading.Event()

    def slow_encode(*args, **kwargs):
        started.set()
        _time.sleep(0.3)
        return real_encode(*args, **kwargs)

    monkeypatch.setattr("kart_tpu.tiles.encode_tile", slow_encode)
    results = []

    def get():
        results.append(http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1"))

    t1 = threading.Thread(target=get)
    t1.start()
    started.wait(5)
    t2 = threading.Thread(target=get)
    t2.start()
    t1.join()
    t2.join()
    assert [s for s, _, _ in results] == [200, 200]
    assert results[0][2] == results[1][2]
    assert counter("tiles.cache.misses") == 1
    assert counter("tiles.cache.hits") == 1
    assert counter("tiles.cache.singleflight_waits") == 1


def test_block_pruning_faults_only_boundary_and_in_blocks(synth_spatial):
    """ISSUE 10 acceptance (small-scale twin of the bench assertion): a
    tile over the 200k-row synth layer classifies the sidecar's ~49
    envelope blocks and reads only the boundary/in survivors — and the
    pruned selection is row-identical to the unpruned full scan."""
    from kart_tpu.ops.bbox import bbox_intersects_np

    repo, info = synth_spatial
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), "synth"
    )
    query = tile_query_wsen(4, 3, 5)
    rows, stats = src.rows_for_bbox(query)
    assert stats["blocks_total"] == -(-200_000 // 4096)
    assert stats["blocks_read"] < stats["blocks_total"] // 2
    assert stats["blocks_pruned"] + stats["blocks_read"] == stats["blocks_total"]
    # parity: pruned == unpruned full scan
    full = np.flatnonzero(
        bbox_intersects_np(np.asarray(src.envelopes()), np.asarray(query))
    )
    assert np.array_equal(rows, full)


def test_bin_layer_serves_from_promised_blobs(synth_spatial):
    """The columnar layer needs zero blob reads — it serves a partial
    clone (promised blobs); the geojson layer correctly refuses."""
    repo, info = synth_spatial
    payload, _, _ = tiles.serve_tile(repo, "HEAD", "synth", 3, 4, 3,
                                     layers="bin")
    header, layers = tiles.parse_payload(payload)
    assert header["count"] > 0
    keys, boxes = tiles.decode_bin_layer(layers["bin"])
    assert len(keys) == header["count"] == len(boxes)
    assert list(keys) == sorted(keys)  # ascending identity order
    assert boxes.dtype == np.int32
    with pytest.raises(tiles.TileDataUnavailable):
        tiles.serve_tile(repo, "HEAD", "synth", 3, 4, 3, layers="geojson")


def test_non_spatial_dataset_rejected(tmp_path):
    from kart_tpu.synth import synth_repo

    repo, _ = synth_repo(str(tmp_path / "r"), 100, spatial=False)
    with pytest.raises(tiles.TileSourceError, match="geometry"):
        tiles.serve_tile(repo, "HEAD", "synth", 0, 0, 0, layers="bin")


def test_unknown_dataset_and_bad_address_reported(served_points):
    repo, ds_path, url = served_points
    assert http_get(f"{url}/api/v1/tiles/HEAD/nope/0/0/0")[0] == 404
    assert http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/1/5/0")[0] == 400
    assert http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0")[0] == 400
    status, _, body = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0?layers=nope"
    )
    assert status == 400 and b"Unknown tile layer" in body


def test_max_features_ceiling_413(served_points, monkeypatch):
    monkeypatch.setenv("KART_TILE_MAX_FEATURES", "5")
    repo, ds_path, url = served_points
    status, _, body = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    assert status == 413
    payload = json.loads(body)
    assert payload["limit"] == 5 and payload["count"] > 5


def test_tiles_endpoint_disabled_by_env(served_points, monkeypatch):
    monkeypatch.setenv("KART_SERVE_TILES", "0")
    repo, ds_path, url = served_points
    status, _, body = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    assert status == 404 and b"disabled" in body


# ---------------------------------------------------------------------------
# shed semantics (ISSUE 10 satellite): tiles ARE shed, stats is not
# ---------------------------------------------------------------------------


def test_shed_tile_request_carries_retry_after(served_points, monkeypatch):
    """Regression: /api/v1/stats gained never-shed status in PR 7 — the
    tiles endpoint has the opposite, explicit semantics: a shed tile
    request is a 429 WITH Retry-After."""
    repo, ds_path, url = served_points
    monkeypatch.setenv("KART_SERVE_RETRY_AFTER", "7")
    monkeypatch.setenv("KART_FAULTS", "server.shed:1")
    status, headers, _ = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0")
    assert status == 429
    assert headers["Retry-After"] == "7"
    # stats stays never-shed even with the shed fault re-armed
    monkeypatch.setenv("KART_FAULTS", "server.shed:1")
    status, _, _ = http_get(f"{url}/api/v1/stats")
    assert status == 200


# ---------------------------------------------------------------------------
# parity: the tile's features == the spatial-filtered reference path
# ---------------------------------------------------------------------------


def _reference_pks(repo, ds_path, z, x, y):
    """The reference feature set for a tile: a spatial-filtered
    diff-against-empty at the same commit, clipped to the tile bbox —
    every delta the full-fidelity path emits inside the rectangle."""
    from kart_tpu.diff.engine import get_dataset_diff
    from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

    w, s, e, n = tile_bounds_wsen(z, x, y)
    spec = ResolvedSpatialFilterSpec.from_spec_string(
        f"EPSG:4326;POLYGON(({w} {s},{e} {s},{e} {n},{w} {n},{w} {s}))"
    )
    rs = repo.structure("HEAD")
    ds = rs.datasets[ds_path]
    sf = spec.resolve_for_dataset(ds)
    diff = get_dataset_diff(None, rs, ds_path)
    return {
        delta.new_key
        for delta in diff["feature"].values()
        if sf.matches(delta.new_value)
    }


@pytest.mark.parametrize("tile", [(0, 0, 0), (2, 3, 2), (5, 24, 19), (5, 25, 19)])
def test_tile_features_match_spatial_filtered_reference(served_points, tile):
    """ISSUE 10 satellite: every feature a tile emits matches the
    reference path (point data, so envelope precision == exact
    precision), in both layers, and the geojson lines parse to the
    committed feature values."""
    repo, ds_path, url = served_points
    z, x, y = tile
    status, _, payload = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/{z}/{x}/{y}"
    )
    assert status == 200
    header, layers = tiles.parse_payload(payload)
    keys, _boxes = tiles.decode_bin_layer(layers["bin"])
    expected = _reference_pks(repo, ds_path, z, x, y)
    assert set(int(k) for k in keys) == expected

    lines = layers["geojson"].decode().splitlines()
    assert len(lines) == header["count"] == len(keys)
    ds = repo.structure("HEAD").datasets[ds_path]
    for key, line in zip(keys, lines):
        feature = json.loads(line)
        assert feature["fid"] == int(key)
        committed = ds.get_feature([int(key)])
        assert feature["name"] == committed["name"]
        assert feature["rating"] == committed["rating"]


def test_pyramid_export_writes_every_nonempty_tile(served_points, tmp_path):
    from kart_tpu.tiles.pyramid import export_pyramid

    repo, ds_path, url = served_points
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), ds_path
    )
    stats = export_pyramid(src, [0, 1, 2], str(tmp_path / "out"))
    # all 40 points live in one lon/lat cluster: exactly one tile per zoom
    assert stats["tiles_written"] == 3
    assert stats["features_out"] == 40 * 3
    for z, x, y in [(0, 0, 0), (2, 3, 2)]:
        with open(tmp_path / "out" / str(z) / str(x) / f"{y}.ktile", "rb") as f:
            header, _ = tiles.parse_payload(f.read())
        assert header["count"] == 40


# ---------------------------------------------------------------------------
# ISSUE 15: the KTB2/MVT/props layers, stream parity, negotiation, goldens,
# bounds checks, and the parallel pyramid export
# ---------------------------------------------------------------------------

GOLDEN_DIR = os.path.join(REPO_ROOT, "tests", "golden", "tiles")


class FakeSource:
    """The minimal TileSource surface the blob-free layers need — lets the
    parity tests drive encode_tile over hand-crafted envelope shapes
    (anti-meridian wraps, polar clamps, degenerate boxes) no import can
    easily produce."""

    def __init__(self, envelopes, keys=None):
        from types import SimpleNamespace

        self._env = np.asarray(envelopes, dtype=np.float32).reshape(-1, 4)
        if keys is None:
            keys = (1 << 24) + np.arange(len(self._env), dtype=np.int64)
        self.block = SimpleNamespace(keys=np.asarray(keys, dtype=np.int64))
        self.commit_oid = "ab" * 20
        self.ds_path = "fake"

    def envelopes(self):
        return self._env

    def rows_for_bbox(self, query):
        from kart_tpu.ops.bbox import bbox_intersects_np

        hits = bbox_intersects_np(self._env, np.asarray(query, np.float64))
        return np.flatnonzero(hits).astype(np.int64), {}


def _decode_all(payload):
    """One payload -> {layer: decoded} for every columnar layer present."""
    header, layers = tiles.parse_payload(payload)
    out = {"header": header}
    if "bin" in layers:
        out["bin"] = tiles.decode_bin_layer(layers["bin"])
    if "ktb2" in layers:
        out["ktb2"] = tiles.decode_ktb2_layer(layers["ktb2"])
    if "mvt" in layers:
        out["mvt"] = tiles.decode_mvt_layer(layers["mvt"])
    if "props" in layers:
        out["props"] = tiles.decode_props_layer(layers["props"])
    return out


@pytest.mark.parametrize(
    "tile,desc",
    [
        ((0, 0, 0), "world"),
        ((3, 0, 3), "west edge (anti-meridian seam)"),
        ((3, 7, 3), "east edge (anti-meridian seam)"),
        ((2, 1, 0), "polar top row"),
        ((2, 1, 3), "polar bottom row"),
        ((4, 9, 7), "empty interior"),
    ],
)
def test_ktb2_mvt_parity_weird_geometry(tile, desc):
    """ISSUE 15 satellite: KTB2 decode == KTB1 decode (and MVT ids/types
    agree) across anti-meridian-wrapping, polar-clamped, degenerate and
    empty tiles."""
    env = np.array(
        [
            [170.0, -10.0, -170.0, 10.0],   # anti-meridian wrap (e < w)
            [10.0, 88.0, 10.001, 88.001],   # beyond the north clamp
            [10.0, -89.0, 10.5, -88.5],     # beyond the south clamp
            [20.0, 5.0, 20.0, 5.0],         # degenerate point envelope
            [-170.0, -5.0, -169.0, 5.0],    # ordinary box, west side
            [175.0, 30.0, 179.0, 31.0],     # ordinary box, east side
        ],
        dtype=np.float32,
    )
    src = FakeSource(env)
    z, x, y = tile
    payload, stats = tiles.encode_tile(
        src, z, x, y, layers="bin,ktb2,mvt", max_features=0
    )
    got = _decode_all(payload)
    k1, b1 = got["bin"]
    k2, b2 = got["ktb2"]
    assert np.array_equal(k1, k2), desc
    assert np.array_equal(b1, b2), desc
    assert got["header"]["count"] == len(k1) == stats["count"]
    mvt_ids = [f["id"] for f in got["mvt"]["features"]]
    assert mvt_ids == [int(k) for k in k1], desc
    # the wrap row, when present, spans the full buffered width
    wrap_rows = np.flatnonzero(np.isin(k1, src.block.keys[[0]]))
    for r in wrap_rows:
        assert b1[r][0] == -64 and b1[r][2] == 4096 + 64


def test_encoding_ladder_branches_round_trip_in_tiles():
    """Tiles whose columns drive each stream encoding (constant -> RLE/FOR,
    sorted dense keys -> delta family) still decode identically to KTB1."""
    from kart_tpu.tiles.streams import ENCODING_NAMES

    n = 500
    # a vertical stack of identical-x envelopes: constant box columns
    env = np.tile(np.array([[10.0, 10.0, 10.5, 10.5]], np.float32), (n, 1))
    src = FakeSource(env)
    payload, _ = tiles.encode_tile(src, 0, 0, 0, layers="bin,ktb2",
                                   max_features=0)
    got = _decode_all(payload)
    assert np.array_equal(got["bin"][0], got["ktb2"][0])
    assert np.array_equal(got["bin"][1], got["ktb2"][1])
    _header, layers = tiles.parse_payload(payload)
    # the chosen encodings are recorded in the stream headers: the keys
    # stream is delta-coded, the constant box columns collapse
    ktb2 = layers["ktb2"]
    key_stream_enc = ktb2[9]
    assert ENCODING_NAMES[key_stream_enc] in ("dvarint", "dfor", "for")
    assert len(ktb2) < len(layers["bin"]) / 4


def test_mvt_truncated_geometry_raises_tile_encode_error():
    """Review regression: a command word claiming more points than the
    geometry buffer holds must raise TileEncodeError (the decoder's
    bounds-checked contract), not a bare IndexError."""
    def uvarint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            if n:
                out += bytes([b | 0x80])
            else:
                return out + bytes([b])

    def field(num, payload):
        return uvarint((num << 3) | 2) + uvarint(len(payload)) + payload

    # MoveTo with a claimed count of 3 points, but only one (dx, dy) pair
    geom = uvarint((3 << 3) | 1) + uvarint(2) + uvarint(2)
    feature = field(4, geom)
    layer = field(1, b"t") + field(2, feature)
    tile = field(3, layer)
    with pytest.raises(tiles.TileEncodeError, match="Truncated MVT geometry"):
        tiles.decode_mvt_layer(tile)

    # a 10-byte feature-id varint >= 2**64 must also raise TileEncodeError,
    # not leak numpy's OverflowError
    feature = uvarint(1 << 3) + b"\xff" * 9 + b"\x7f"
    tile = field(3, field(1, b"t") + field(2, feature))
    with pytest.raises(tiles.TileEncodeError, match="exceeds uint64"):
        tiles.decode_mvt_layer(tile)

    # a geometry ending mid-varint (dangling continuation byte after a
    # valid point command) must raise, not silently drop the tail
    geom = uvarint((1 << 3) | 1) + uvarint(2) + uvarint(2) + b"\x80"
    tile = field(3, field(1, b"t") + field(2, field(4, geom)))
    with pytest.raises(tiles.TileEncodeError, match="Truncated MVT geometry"):
        tiles.decode_mvt_layer(tile)

    # invalid command ids (here 4), zero-count move/line words, and
    # ClosePath with count != 1 must raise, not decode to silently
    # wrong geometry
    for bad_word in ((1 << 3) | 4, (0 << 3) | 1, (2 << 3) | 7, (0 << 3) | 7):
        geom = uvarint(bad_word) + uvarint(2) + uvarint(2)
        tile = field(3, field(1, b"t") + field(2, field(4, geom)))
        with pytest.raises(tiles.TileEncodeError, match="Malformed MVT"):
            tiles.decode_mvt_layer(tile)

    # a feature id delivered length-delimited (wire type 2) must raise
    # TileEncodeError, not leak a TypeError from the uint64 guard
    feature = field(1, b"xx")
    tile = field(3, field(1, b"t") + field(2, feature))
    with pytest.raises(tiles.TileEncodeError, match="non-varint wire type"):
        tiles.decode_mvt_layer(tile)


def test_props_layer_matches_geojson(served_points):
    """props is the dictionary-coded form of exactly the geojson lines
    (same compiled serialisers, row-aligned with the bin keys)."""
    repo, ds_path, url = served_points
    status, _, payload = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0?layers=bin,geojson,props"
    )
    assert status == 200
    got = _decode_all(payload)
    geojson_lines = [
        l.encode() for l in
        tiles.parse_payload(payload)[1]["geojson"].decode().splitlines()
    ]
    assert got["props"] == geojson_lines
    assert len(got["props"]) == len(got["bin"][0])


def test_ktb2_served_payload_cold_cached_two_processes(served_points, tmp_path):
    """ISSUE 15 acceptance: KTB2/MVT payloads byte-identical cold vs
    cached and across two processes (in-thread server vs `kart export
    tiles` subprocess), decoding to exactly the KTB1 feature set."""
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/2/3/2?layers=ktb2,mvt"
    s1, h1, cold = http_get(t)
    s2, h2, cached = http_get(t)
    assert s1 == s2 == 200 and cold == cached
    assert h1["ETag"] == h2["ETag"]

    out = tmp_path / "pyramid"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "kart_tpu.cli",
            "-C", str(repo.workdir or repo.gitdir),
            "export", "tiles", "HEAD", "--dataset", ds_path,
            "--zoom", "2", "-o", str(out), "--layers", "ktb2,mvt",
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    with open(out / "2" / "3" / "2.ktile", "rb") as f:
        exported = f.read()
    assert exported == cold
    # and the compressed columns decode to the KTB1 feature set
    sbin, _, bin_payload = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/2/3/2?layers=bin"
    )
    assert sbin == 200
    k1, b1 = _decode_all(bin_payload)["bin"]
    k2, b2 = _decode_all(cold)["ktb2"]
    assert np.array_equal(k1, k2) and np.array_equal(b1, b2)


# -- negotiation -------------------------------------------------------------


def test_layer_negotiation_etags_differ(served_points):
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1"
    _, h_default, _ = http_get(t)
    _, h_ktb2, _ = http_get(t + "?layers=ktb2")
    assert h_default["ETag"] != h_ktb2["ETag"]
    assert h_ktb2["Vary"] == "Accept"


def test_accept_header_negotiates_raw_mvt(served_points):
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1"
    mime = "application/vnd.mapbox-vector-tile"
    status, headers, body = http_get(t, headers={"Accept": mime})
    assert status == 200
    assert headers["Content-Type"] == mime
    assert headers["ETag"].endswith('-raw"')
    # the body IS bare MVT protobuf: our reader decodes it directly
    doc = tiles.decode_mvt_layer(body)
    assert doc["name"] == ds_path and doc["version"] == 2
    assert len(doc["features"]) > 0
    # the raw validator revalidates (304), and differs from the framed one
    status, h2, b2 = http_get(
        t, headers={"Accept": mime, "If-None-Match": headers["ETag"]}
    )
    assert status == 304 and b2 == b""
    _, framed_headers, framed = http_get(t + "?layers=mvt")
    assert framed_headers["ETag"] != headers["ETag"]
    # one cache entry backs both: the framed payload embeds the raw body
    assert tiles.parse_payload(framed)[1]["mvt"] == body


def test_format_mvt_param_serves_raw(served_points):
    repo, ds_path, url = served_points
    status, headers, body = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1?format=mvt"
    )
    assert status == 200
    assert headers["Content-Type"] == "application/vnd.mapbox-vector-tile"
    assert tiles.decode_mvt_layer(body)["name"] == ds_path
    # format=mvt with a contradictory layer set is a 400, as is junk format
    s, _, b = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1?format=mvt&layers=bin"
    )
    assert s == 400
    s, _, _ = http_get(f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1?format=png")
    assert s == 400


def test_kart_tile_encoding_env_sets_default_layers(served_points, monkeypatch):
    repo, ds_path, url = served_points
    monkeypatch.setenv("KART_TILE_ENCODING", "ktb2")
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0"
    status, _, payload = http_get(t)
    assert status == 200
    header, layers = tiles.parse_payload(payload)
    assert set(layers) == {"ktb2"}
    # malformed config falls back to the stock default, never 500s
    monkeypatch.setenv("KART_TILE_ENCODING", "nope,bad")
    status, _, payload = http_get(t)
    assert status == 200
    assert set(tiles.parse_payload(payload)[1]) == {"bin", "geojson"}


# -- bounds checks (fuzz) ----------------------------------------------------


def test_parse_payload_prefix_fuzz(served_points):
    """ISSUE 15 satellite: every strict prefix of a real payload raises
    TileEncodeError from parse_payload or the layer decoders — a
    truncated count must never silently short-read via np.frombuffer."""
    repo, ds_path, url = served_points
    _, _, payload = http_get(
        f"{url}/api/v1/tiles/HEAD/{ds_path}/0/0/0?layers=bin,ktb2"
    )
    for cut in range(len(payload)):
        clipped = payload[:cut]
        try:
            header, layers = tiles.parse_payload(clipped)
            # frame parsed => some layer must fail to decode
            for name, decoder in (
                ("bin", tiles.decode_bin_layer),
                ("ktb2", tiles.decode_ktb2_layer),
            ):
                decoder(layers[name])
        except tiles.TileEncodeError:
            continue
        raise AssertionError(f"prefix {cut} of {len(payload)} decoded silently")
    # oversized count in the bin layer: same error, not a short read
    header, layers = tiles.parse_payload(payload)
    bin_layer = bytearray(layers["bin"])
    import struct as _struct

    _struct.pack_into("<I", bin_layer, 4, header["count"] + 1000)
    with pytest.raises(tiles.TileEncodeError):
        tiles.decode_bin_layer(bytes(bin_layer))


# -- golden fixtures ---------------------------------------------------------


class TestGoldenPayloads:
    """tests/golden/tiles (regenerate: python tests/golden/tiles/regen.py).
    ktb1_v1.ktile pins DECODE backward-compat for v1-era payloads; the
    layer fixtures pin current-encoder BYTE stability across refactors —
    bytes changing means PAYLOAD_VERSION must bump (TILES.md §4.3)."""

    @pytest.fixture(autouse=True)
    def _expected(self):
        with open(os.path.join(GOLDEN_DIR, "expected.json")) as f:
            self.expected = json.load(f)

    def _read(self, name):
        with open(os.path.join(GOLDEN_DIR, name), "rb") as f:
            return f.read()

    def test_v1_payload_still_decodes(self):
        header, layers = tiles.parse_payload(self._read("ktb1_v1.ktile"))
        assert header["v"] == 1
        assert header["commit"] == self.expected["commit"]
        keys, boxes = tiles.decode_bin_layer(layers["bin"])
        assert [int(k) for k in keys] == self.expected["keys"]
        assert boxes.tolist() == self.expected["boxes"]

    def test_ktb2_bytes_stable(self):
        from kart_tpu.tiles.encode import encode_ktb2_layer

        golden = self._read("ktb2_layer.bin")
        keys = np.asarray(self.expected["keys"], np.int64)
        boxes = np.asarray(self.expected["boxes"], np.int32)
        assert encode_ktb2_layer(keys, boxes) == golden
        got_keys, got_boxes = tiles.decode_ktb2_layer(golden)
        assert [int(k) for k in got_keys] == self.expected["keys"]
        assert got_boxes.tolist() == self.expected["boxes"]

    def test_mvt_bytes_stable(self):
        from kart_tpu.tiles.encode import encode_mvt_layer

        golden = self._read("mvt_layer.bin")
        keys = np.asarray(self.expected["keys"], np.int64)
        boxes = np.asarray(self.expected["boxes"], np.int32)
        assert encode_mvt_layer(
            self.expected["dataset"], keys, boxes
        ) == golden
        doc = tiles.decode_mvt_layer(golden)
        assert [f["id"] for f in doc["features"]] == self.expected["keys"]
        assert [f["type"] for f in doc["features"]] == self.expected["mvt_types"]

    def test_props_bytes_stable(self):
        from kart_tpu.tiles.encode import encode_props_layer

        golden = self._read("props_layer.bin")
        props = [p.encode() for p in self.expected["props"]]
        assert encode_props_layer(props) == golden
        assert tiles.decode_props_layer(golden) == props


# -- the parallel pyramid export ---------------------------------------------


def _pyramid_digest(out_dir):
    from kart_tpu.tiles.pyramid import tree_digest

    return tree_digest(out_dir)


def test_batch_encoder_matches_serving_encoder(synth_spatial):
    """encode_tile_batch (the exporter's path) is byte-identical to
    encode_tile (the serving path) for every tile of the cover."""
    from kart_tpu.tiles.encode import encode_tile, encode_tile_batch
    from kart_tpu.tiles.pyramid import tile_cover

    repo, info = synth_spatial
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), "synth"
    )
    addrs = list(tile_cover(src, [0, 2, 4]))
    results = encode_tile_batch(
        src, addrs, layers="bin,ktb2,mvt", max_features=0
    )
    checked = 0
    for (z, x, y), (status, payload, _count) in zip(addrs, results):
        single, stats = encode_tile(
            src, z, x, y, layers="bin,ktb2,mvt", max_features=0
        )
        if status == "ok":
            assert payload == single, (z, x, y)
            checked += 1
        else:
            assert status == "empty" and stats["count"] == 0
    assert checked > 10


def test_pool_export_matches_serial_and_honours_workers(synth_spatial, tmp_path):
    repo, info = synth_spatial
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), "synth"
    )
    from kart_tpu.tiles.pyramid import export_pyramid

    s1 = export_pyramid(src, [0, 1, 2, 3], str(tmp_path / "w1"),
                        layers=("ktb2",), workers=1)
    s2 = export_pyramid(src, [0, 1, 2, 3], str(tmp_path / "w2"),
                        layers=("ktb2",), workers=2)
    assert s1["export_workers"] == 1 and s2["export_workers"] == 2
    assert s1["tiles_written"] == s2["tiles_written"] > 0
    assert _pyramid_digest(str(tmp_path / "w1")) == _pyramid_digest(
        str(tmp_path / "w2")
    )


def test_device_seam_projection_is_byte_deterministic():
    """The device-mesh projection path (shard_map over the feature axis)
    quantizes bit-identically to the host path — the verify-and-patch
    contract in clip.quantize_from_merc, exercised on the 8-device
    virtual CPU platform."""
    from kart_tpu.diff.backend import BACKENDS, sharded_merc_envelopes
    from kart_tpu.runtime import jax_ready
    from kart_tpu.tiles.clip import quantize_from_merc

    if not jax_ready():
        pytest.skip("no jax backend in this environment")
    rng = np.random.RandomState(11)
    env = np.column_stack(
        [
            rng.uniform(-180, 180, 50_000),
            rng.uniform(-88, 88, 50_000),
            rng.uniform(-180, 180, 50_000),
            rng.uniform(-88, 88, 50_000),
        ]
    )
    host = BACKENDS["host_native"].merc_envelopes(env)
    dev = sharded_merc_envelopes(env)
    for z in (0, 4, 11, 18):
        x = y = (1 << z) // 2
        bh = quantize_from_merc(env, host, z, x, y)
        bd = quantize_from_merc(env, dev, z, x, y)
        assert np.array_equal(bh, bd), f"zoom {z}"


def test_export_strict_fails_on_skipped_tiles(served_points, tmp_path):
    """ISSUE 15 satellite: a tiles_too_large skip leaves an incomplete
    pyramid — --strict exits non-zero naming the tiles; the default path
    exits 0 with a one-line warning."""
    repo, ds_path, url = served_points
    env = dict(os.environ, JAX_PLATFORMS="cpu", KART_TILE_MAX_FEATURES="5")
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    base = [
        sys.executable, "-m", "kart_tpu.cli",
        "-C", str(repo.workdir or repo.gitdir),
        "export", "tiles", "HEAD", "--dataset", ds_path, "--zoom", "0",
        "--layers", "bin",
    ]
    proc = subprocess.run(
        base + ["-o", str(tmp_path / "default")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "warning:" in proc.stderr and "skipped" in proc.stderr

    proc = subprocess.run(
        base + ["-o", str(tmp_path / "strict"), "--strict"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "0/0/0" in proc.stderr and "incomplete" in proc.stderr


def test_export_stats_record_skipped_tiles(served_points, tmp_path):
    from kart_tpu.tiles.pyramid import export_pyramid

    repo, ds_path, url = served_points
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), ds_path
    )
    stats = export_pyramid(
        src, [0, 1], str(tmp_path / "out"), layers=("bin",), max_features=5
    )
    assert stats["tiles_too_large"] == 2  # the one populated tile per zoom
    assert sorted(stats["tiles_skipped"]) == [(0, 0, 0), (1, 1, 1)]


def test_ktb2_decode_bomb_guard():
    """Review regression: a few-byte crafted KTB2 layer claiming billions
    of RLE-expanded rows is rejected by the decode ceiling instead of
    allocating gigabytes (KTB1 cross-checks count against byte length;
    compressed layers need the explicit bound)."""
    import struct as _struct

    from kart_tpu.tiles.encode import KTB2_MAGIC, MAX_DECODE_ROWS
    from kart_tpu.tiles.streams import RLE, _STREAM_HEADER, varint_encode

    huge = MAX_DECODE_ROWS + 1
    run = (
        varint_encode(np.asarray([1], np.uint64))       # one run
        + varint_encode(np.asarray([huge], np.uint64))  # of `huge` length
        + varint_encode(np.asarray([0], np.uint64))     # value 0 (zigzag)
    )
    stream = _STREAM_HEADER.pack(RLE, len(run)) + run
    crafted = KTB2_MAGIC + _struct.pack("<BI", 0, huge) + stream * 5
    assert len(crafted) < 100  # a few dozen bytes claiming ~4 GB of rows
    with pytest.raises(tiles.TileEncodeError, match="ceiling"):
        tiles.decode_ktb2_layer(crafted)
    # a deliberate larger ceiling still decodes honest payloads
    keys = np.arange(10, dtype=np.int64)
    boxes = np.zeros((10, 4), np.int32)
    from kart_tpu.tiles.encode import encode_ktb2_layer

    k, b = tiles.decode_ktb2_layer(encode_ktb2_layer(keys, boxes))
    assert np.array_equal(k, keys)


def test_warm_layers_follow_negotiated_default(monkeypatch):
    """Review regression: the warm-then-announce pass must warm the cache
    keys default requests actually compute — a KART_TILE_ENCODING=ktb2
    fleet warming only ("bin",) would make every warm fill a dead key."""
    from kart_tpu.events.warm import warm_layers

    monkeypatch.delenv("KART_TILE_ENCODING", raising=False)
    assert warm_layers() == ("bin",)  # stock default minus geojson
    monkeypatch.setenv("KART_TILE_ENCODING", "ktb2")
    assert warm_layers() == ("ktb2",)
    monkeypatch.setenv("KART_TILE_ENCODING", "ktb2,props")
    assert warm_layers() == ("ktb2",)  # blob-needing layers stay lazy
    monkeypatch.setenv("KART_TILE_ENCODING", "geojson")
    assert warm_layers() == ("bin",)  # all-blob default: fall back


def test_accept_q_zero_refuses_raw_mvt(served_points):
    """Review regression: a client that explicitly refuses MVT
    (``;q=0``) must get the framed default, not the bare protobuf; a
    positive q (any case) still negotiates raw."""
    repo, ds_path, url = served_points
    t = f"{url}/api/v1/tiles/HEAD/{ds_path}/1/1/1"
    mime = "application/vnd.mapbox-vector-tile"
    status, headers, body = http_get(
        t, headers={"Accept": f"{mime};q=0, application/x-kart-tile"}
    )
    assert status == 200
    assert headers["Content-Type"] == "application/x-kart-tile"
    tiles.parse_payload(body)  # framed, parses
    status, headers, body = http_get(
        t, headers={"Accept": f"{mime.upper()}; q=0.8, */*;q=0.1"}
    )
    assert status == 200
    assert headers["Content-Type"] == mime
    assert tiles.decode_mvt_layer(body)["name"] == ds_path


def test_project_envelopes_respects_mesh_readiness(monkeypatch):
    """Review regression: the export projection seam consults the classify
    path's full readiness ladder (kart_tpu.routing) — on a CPU-default box the
    shard_map route must NOT engage, and the host transform serves."""
    from kart_tpu.diff import backend as B

    calls = []
    real = B.ShardedJaxBackend.merc_envelopes

    def spying(self, env):
        calls.append(len(env))
        return real(self, env)

    monkeypatch.setattr(B.ShardedJaxBackend, "merc_envelopes", spying)
    monkeypatch.setattr("kart_tpu.routing.mesh_open", lambda n: False)
    env = np.random.RandomState(0).uniform(-80, 80, (2000, 4))
    host = B.BACKENDS["host_native"].merc_envelopes(env)
    got = B.project_envelopes(env)
    assert not calls  # the sharded route never engaged
    for h, g in zip(host, got):
        assert np.array_equal(h, g)
    # and when the ladder says yes, the sharded backend is consulted
    monkeypatch.setattr("kart_tpu.routing.mesh_open", lambda n: True)
    B.project_envelopes(env)
    assert calls == [2000]
