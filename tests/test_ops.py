import numpy as np
import pytest

from kart_tpu.ops.blocks import (
    PAD_KEY,
    FeatureBlock,
    bucket_body,
    bucket_size,
    pack_oid_hex,
    unpack_oid_hex,
)
from kart_tpu.ops.bbox import bbox_intersects, bbox_intersects_np
from kart_tpu.ops.diff_kernel import (
    DELETE,
    INSERT,
    UNCHANGED,
    UPDATE,
    changed_indices,
    classify_blocks,
    classify_blocks_reference,
)
from kart_tpu.ops.envelope_codec import EnvelopeCodec


def make_block(pk_oid_pairs):
    keys = np.array([p for p, _ in pk_oid_pairs], dtype=np.int64)
    oids = pack_oid_hex([o for _, o in pk_oid_pairs])
    paths = [f"path/{p}" for p, _ in pk_oid_pairs]
    return FeatureBlock.from_arrays(keys, oids, paths)


OID_A = "aa" * 20
OID_B = "bb" * 20
OID_C = "cc" * 20


def test_bucket_size():
    assert bucket_size(0) == 1024
    assert bucket_size(1024) == 1024
    assert bucket_size(1025) == 1152  # 9 * 2^7: 1/8-step granularity
    for n in (2048, 4097, 10_000_000):
        b = bucket_size(n)
        assert b >= n
        assert (b - n) / n <= 0.125  # waste cap above the minimum floor


@pytest.mark.parametrize(
    "n", [0, 1, 1024, 1025, 1152, 1153, 2048, 2049, 10_000_000, 2**24, 2**24 + 1]
)
def test_bucket_body_is_inside_every_block_of_the_bucket(n):
    """The split the device classify relies on: the body of n's bucket is a
    whole number of grid steps short of it and strictly inside the n rows,
    whatever n of that bucket — so it can be a view of an unpadded block."""
    bucket = bucket_size(n)
    body = bucket_body(bucket)
    if n <= 1024:
        assert (bucket, body) == (1024, 0)
    else:
        assert body < n <= bucket
        step = bucket - body
        assert step & (step - 1) == 0 and 9 <= bucket // step <= 16
        # the first and the last row count of this bucket agree on it
        assert bucket_size(body + 1) == bucket == bucket_size(bucket)


def test_pack_unpack_oids():
    oids = [OID_A, OID_B, "0123456789abcdef0123456789abcdef01234567"]
    assert unpack_oid_hex(pack_oid_hex(oids)) == oids


def test_classify_basic():
    old = make_block([(1, OID_A), (2, OID_A), (3, OID_A)])
    new = make_block([(2, OID_B), (3, OID_A), (4, OID_C)])
    old_class, new_class, counts = classify_blocks(old, new)
    assert counts == {"inserts": 1, "updates": 1, "deletes": 1}
    assert old_class.tolist() == [DELETE, UPDATE, UNCHANGED]
    assert new_class.tolist() == [UPDATE, UNCHANGED, INSERT]


def test_classify_empty_sides():
    empty = make_block([])
    full = make_block([(1, OID_A), (2, OID_B)])
    _, new_class, counts = classify_blocks(empty, full)
    assert counts == {"inserts": 2, "updates": 0, "deletes": 0}
    old_class, _, counts = classify_blocks(full, empty)
    assert counts == {"inserts": 0, "updates": 0, "deletes": 2}
    assert old_class.tolist() == [DELETE, DELETE]


def test_classify_jit_matches_reference_random():
    rng = np.random.default_rng(42)
    n = 5000
    pks = rng.choice(np.arange(n * 3, dtype=np.int64), size=n, replace=False)
    oid_pool = [f"{i:040x}" for i in range(64)]
    old_pairs = [(int(pk), oid_pool[rng.integers(64)]) for pk in pks]
    # new version: drop ~10%, modify ~10%, add ~10%
    new_pairs = []
    for pk, oid in old_pairs:
        r = rng.random()
        if r < 0.1:
            continue
        if r < 0.2:
            new_pairs.append((pk, oid_pool[rng.integers(64)]))
        else:
            new_pairs.append((pk, oid))
    added = rng.choice(np.arange(n * 3, n * 4, dtype=np.int64), size=n // 10, replace=False)
    for pk in added:
        new_pairs.append((int(pk), oid_pool[rng.integers(64)]))

    old = make_block(old_pairs)
    new = make_block(new_pairs)
    old_class, new_class, counts = classify_blocks(old, new)
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)

    # brute-force dict check
    old_map = dict(zip(old.keys[: old.count].tolist(), map(tuple, old.oids[: old.count])))
    new_map = dict(zip(new.keys[: new.count].tolist(), map(tuple, new.oids[: new.count])))
    expected = {
        "inserts": len(set(new_map) - set(old_map)),
        "deletes": len(set(old_map) - set(new_map)),
        "updates": sum(
            1 for k in set(old_map) & set(new_map) if old_map[k] != new_map[k]
        ),
    }
    assert counts == expected


def test_changed_indices():
    old = make_block([(1, OID_A), (2, OID_A)])
    new = make_block([(2, OID_B), (3, OID_C)])
    old_class, new_class, _ = classify_blocks(old, new)
    oi, ni = changed_indices(old_class, new_class)
    assert old.keys[oi].tolist() == [1, 2]  # delete + update
    assert new.keys[ni].tolist() == [2, 3]  # update + insert


def test_bbox_basic():
    envelopes = np.array(
        [
            [10, 10, 20, 20],  # inside query
            [30, 30, 40, 40],  # outside
            [0, 0, 11, 11],  # overlaps corner
        ],
        dtype=np.float64,
    )
    query = (5, 5, 25, 25)
    expected = [True, False, True]
    assert bbox_intersects_np(envelopes, query).tolist() == expected
    assert bbox_intersects(envelopes, query).tolist() == expected


def test_bbox_antimeridian():
    # envelope crossing the anti-meridian: w=170, e=-170
    envelopes = np.array(
        [
            [170.0, -10.0, -170.0, 10.0],  # crosses AM
            [160.0, -10.0, 165.0, 10.0],  # west of it
        ]
    )
    # query near 175E
    q_east = (174.0, -5.0, 179.0, 5.0)
    assert bbox_intersects_np(envelopes, q_east).tolist() == [True, False]
    assert bbox_intersects(envelopes, q_east).tolist() == [True, False]
    # query near 175W (i.e. -175)
    q_west = (-179.0, -5.0, -172.0, 5.0)
    assert bbox_intersects_np(envelopes, q_west).tolist() == [True, False]
    assert bbox_intersects(envelopes, q_west).tolist() == [True, False]
    # query itself crossing the AM
    q_cross = (179.0, -5.0, -179.0, 5.0)
    assert bbox_intersects_np(envelopes, q_cross).tolist() == [True, False]
    assert bbox_intersects(envelopes, q_cross).tolist() == [True, False]


def test_bbox_jnp_matches_np_random():
    rng = np.random.default_rng(7)
    n = 3000
    w = rng.uniform(-180, 180, n)
    e = rng.uniform(-180, 180, n)  # some will "wrap"
    s = rng.uniform(-90, 85, n)
    nn = s + rng.uniform(0, 5, n)
    envelopes = np.stack([w, s, e, nn], axis=1)
    query = (-20.0, -30.0, 40.0, 10.0)
    ref = bbox_intersects_np(envelopes, query)
    got = bbox_intersects(envelopes, query)
    np.testing.assert_array_equal(got, ref)


def test_envelope_codec_scalar_roundtrip():
    codec = EnvelopeCodec()
    env = (174.5, -41.3, 175.0, -41.0)
    data = codec.encode(env)
    assert len(data) == 10
    w, s, e, n = codec.decode(data)
    # decoded envelope must CONTAIN the original (floor/ceil outward rounding)
    assert w <= env[0] and s <= env[1] and e >= env[2] and n >= env[3]
    assert abs(w - env[0]) < 0.001 and abs(n - env[3]) < 0.001


def test_envelope_codec_batch_matches_scalar():
    codec = EnvelopeCodec()
    rng = np.random.default_rng(0)
    w = rng.uniform(-180, 179, 500)
    e = np.minimum(w + rng.uniform(0, 1, 500), 180)
    s = rng.uniform(-90, 89, 500)
    n = np.minimum(s + rng.uniform(0, 1, 500), 90)
    envs = np.stack([w, s, e, n], axis=1)
    batch = codec.encode_batch(envs)
    for i in range(0, 500, 37):
        assert batch[i].tobytes() == codec.encode(tuple(envs[i]))
    decoded = codec.decode_batch(batch)
    for i in range(0, 500, 37):
        assert tuple(decoded[i]) == pytest.approx(codec.decode(batch[i].tobytes()))


def test_envelope_codec_edge_values():
    codec = EnvelopeCodec()
    env = (-180.0, -90.0, 180.0, 90.0)
    assert codec.decode(codec.encode(env)) == pytest.approx(env)
    batch = codec.encode_batch(np.array([env]))
    assert batch[0].tobytes() == codec.encode(env)


def test_feature_block_from_dataset(tmp_path):
    from helpers import make_imported_repo

    repo, ds_path = make_imported_repo(tmp_path, n=50)
    ds = repo.datasets()[ds_path]
    block = FeatureBlock.from_dataset(ds)
    assert block.count == 50
    assert block.padded_size == 1024
    assert block.keys[:50].tolist() == sorted(range(1, 51))
    assert not block.has_key_collisions()


@pytest.mark.parametrize("kernel_name", ["sort", "window"])
def test_jitted_kernels_match_reference_directly(kernel_name):
    """The size threshold routes small classify_blocks calls to numpy — so
    drive every jitted variant directly (they must stay bit-compatible with
    the reference; the sort path re-checks its 64-bit oid fold, the
    windowed join compares all 160 bits)."""
    import jax

    from kart_tpu.ops import diff_kernel

    jax.config.update("jax_enable_x64", True)  # as lazy_jit sets it
    kernel = {
        "sort": diff_kernel._classify_padded,
        "window": jax.jit(diff_kernel._classify_mergesort_core_window),
    }[kernel_name]

    rng = np.random.default_rng(7)
    n = 3000
    pks = np.sort(rng.choice(np.arange(n * 3, dtype=np.int64), size=n, replace=False))
    old_pairs = [(int(pk), f"{rng.integers(2**32):040x}") for pk in pks]
    new_pairs = [
        (pk, f"{rng.integers(2**32):040x}" if i % 9 == 0 else oid)
        for i, (pk, oid) in enumerate(old_pairs)
        if i % 7 != 0
    ]
    old = make_block(old_pairs)
    new = make_block(new_pairs)
    ref_old, ref_new = classify_blocks_reference(old, new)

    oc, nc, *rest = kernel(
        old.keys, old.oids, new.keys, new.oids, old.count, new.count
    )
    if kernel_name == "window":
        dense_tiles, overflow_tiles = np.asarray(rest[1])
        assert overflow_tiles == 0  # no overflow: the classes stand
        assert dense_tiles > 0  # every 7th row dropped: the dense join ran
    np.testing.assert_array_equal(np.asarray(oc)[: old.count], ref_old)
    np.testing.assert_array_equal(np.asarray(nc)[: new.count], ref_new)


def test_bbox_jit_kernel_matches_reference_directly():
    from kart_tpu.ops.bbox import bbox_intersects_jnp, pad_envelopes

    rng = np.random.default_rng(3)
    env = np.stack(
        [
            rng.uniform(-180, 170, 2000),
            rng.uniform(-90, 80, 2000),
            rng.uniform(-180, 180, 2000),
            rng.uniform(-90, 90, 2000),
        ],
        axis=1,
    )
    env[:, 2] = np.maximum(env[:, 2], env[:, 0])  # mostly non-wrapping
    env[:, 3] = np.maximum(env[:, 3], env[:, 1])
    query = (-20.0, -20.0, 40.0, 30.0)
    w, s, e, n, count = pad_envelopes(env)
    got = np.asarray(
        bbox_intersects_jnp(w, s, e, n, np.asarray(query, dtype=np.float32))
    )[:count]
    np.testing.assert_array_equal(got, bbox_intersects_np(env, query))


def test_columnar_equal_jit():
    from kart_tpu.ops.diff_kernel import columnar_equal

    old = np.asarray([[1, 2, 3], [4, 5, 6]], dtype=np.int64)
    new = np.asarray([[1, 9, 3], [4, 5, 6]], dtype=np.int64)
    mask_o = np.zeros((2, 3), dtype=bool)
    mask_n = np.zeros((2, 3), dtype=bool)
    got = np.asarray(columnar_equal(old, new, mask_o, mask_n))
    assert got.tolist() == [True, False, True]


def test_sort_kernel_detects_oid_fold_collision():
    """The sort path streams a 64-bit fold of each oid through the sort, then
    re-verifies fold-equal pairs against the full 160-bit oids (ADVICE r2:
    without that, a fold collision silently classified a changed feature as
    unchanged). Construct a real collision: for any a0, the oid
    [a0, 0, lo32(a0*C1), hi32(a0*C1), 0] folds to 0 — as does the all-zero
    oid — so these two *different* oids under one key must classify UPDATE."""
    from kart_tpu.ops.diff_kernel import _classify_padded, _fold_oids

    C1 = 0x9E3779B97F4A7C15
    a0 = 0xDEADBEEF
    m = (a0 * C1) % (1 << 64)
    oid_a = np.zeros((1, 5), dtype=np.uint32)
    oid_b = np.array(
        [[a0, 0, m & 0xFFFFFFFF, m >> 32, 0]], dtype=np.uint32
    )
    assert not np.array_equal(oid_a, oid_b)

    import jax.numpy as jnp

    folds_a = np.asarray(_fold_oids(jnp.asarray(oid_a)))
    folds_b = np.asarray(_fold_oids(jnp.asarray(oid_b)))
    assert folds_a[0] == folds_b[0] == 0  # genuine fold collision

    pad = 1024
    keys = np.full(pad, 2**62, dtype=np.int64)
    keys[0] = 7
    oids = np.zeros((pad, 5), dtype=np.uint32)
    old_oids = oids.copy()
    old_oids[0] = oid_a[0]
    new_oids = oids.copy()
    new_oids[0] = oid_b[0]
    oc, nc, _, counts = _classify_padded(
        keys, old_oids, keys, new_oids, 1, 1
    )
    assert int(np.asarray(oc)[0]) == UPDATE
    assert int(np.asarray(nc)[0]) == UPDATE
    assert np.asarray(counts).tolist() == [0, 1, 0]


def test_native_classify_matches_reference():
    """classify_blocks_host (native C++ merge-join) is bit-identical to the
    numpy reference twin, including empty sides and all-change blocks."""
    import numpy as np

    from kart_tpu.ops.blocks import FeatureBlock
    from kart_tpu.ops.diff_kernel import (
        classify_blocks_host,
        classify_blocks_reference,
    )

    rng = np.random.default_rng(11)

    def block(keys, oids_u8):
        rows = (
            np.ascontiguousarray(oids_u8).view(np.uint32).reshape(-1, 5)
            if len(keys)
            else np.zeros((0, 5), np.uint32)
        )
        return FeatureBlock.from_arrays(
            np.asarray(keys, np.int64), rows, [""] * len(keys)
        )

    n = 5000
    keys = np.sort(rng.choice(50_000, n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 256, (n, 20), dtype=np.uint8)
    new_keys = np.concatenate([keys[10:], np.array([60_001, 60_002])])
    new_oids = np.concatenate(
        [oids[10:], rng.integers(0, 256, (2, 20), dtype=np.uint8)]
    )
    new_oids[::50] = rng.integers(0, 256, (len(new_oids[::50]), 20), np.uint8)

    for a, b in [
        (block(keys, oids), block(new_keys, new_oids)),
        (block([], np.zeros((0, 20), np.uint8)), block(keys, oids)),
        (block(keys, oids), block([], np.zeros((0, 20), np.uint8))),
    ]:
        ho, hn, hc = classify_blocks_host(a, b)
        ro, rn = classify_blocks_reference(a, b)
        assert np.array_equal(ho[: a.count], ro)
        assert np.array_equal(hn[: b.count], rn)
        assert hc["inserts"] == int(np.sum(rn == 1))
        assert hc["updates"] == int(np.sum(ro == 2))
        assert hc["deletes"] == int(np.sum(ro == 3))


def test_bbox_resident_cache():
    """cache_key keeps envelope columns device-resident: identical results,
    one upload, bounded cache."""
    import numpy as np

    from kart_tpu.ops import bbox

    rng = np.random.default_rng(3)
    n = 4096
    env = np.stack(
        [
            rng.uniform(-180, 179, n),
            rng.uniform(-90, 89, n),
            rng.uniform(-180, 180, n),
            rng.uniform(-90, 90, n),
        ],
        axis=1,
    )
    env[:, 2] = np.maximum(env[:, 2], env[:, 0])
    env[:, 3] = np.maximum(env[:, 3], env[:, 1])
    query = (-20.0, -20.0, 40.0, 30.0)
    ref = bbox.bbox_intersects_np(env, query)

    from kart_tpu import routing

    old_min = routing.DEVICE_MIN_ENVELOPES
    routing.DEVICE_MIN_ENVELOPES = 1
    try:
        bbox._RESIDENT_CACHE.clear()
        key = ("test", 1)
        got = bbox.bbox_intersects(env, query, cache_key=key)
        assert np.array_equal(got, ref)
        entry = bbox._RESIDENT_CACHE[key]
        got2 = bbox.bbox_intersects(env, query, cache_key=key)
        assert np.array_equal(got2, ref)
        assert bbox._RESIDENT_CACHE[key] is entry  # no re-upload
        # a different query against the same cached columns
        ref2 = bbox.bbox_intersects_np(env, (100.0, 40.0, 120.0, 60.0))
        got3 = bbox.bbox_intersects(env, (100.0, 40.0, 120.0, 60.0), cache_key=key)
        assert np.array_equal(got3, ref2)
        # eviction keeps the cache bounded
        for i in range(bbox._RESIDENT_CACHE_MAX + 2):
            bbox.bbox_intersects(env, query, cache_key=("test", 100 + i))
        assert len(bbox._RESIDENT_CACHE) <= bbox._RESIDENT_CACHE_MAX
        # a changed envelope set under the same key re-uploads
        env2 = env[: n // 2]
        got4 = bbox.bbox_intersects(env2, query, cache_key=key)
        assert np.array_equal(got4, bbox.bbox_intersects_np(env2, query))
    finally:
        routing.DEVICE_MIN_ENVELOPES = old_min
        bbox._RESIDENT_CACHE.clear()


def test_native_classify_duplicate_keys_match_reference():
    """Hash-key collisions produce duplicate sorted keys; the native
    merge-join must classify them exactly as the numpy searchsorted
    reference (first-row pairing) so output never depends on whether the
    native lib is built."""
    import numpy as np

    from kart_tpu.ops.blocks import FeatureBlock
    from kart_tpu.ops.diff_kernel import (
        classify_blocks_host,
        classify_blocks_reference,
    )

    rng = np.random.default_rng(5)
    keys = np.array([1, 5, 5, 5, 9, 12, 12], dtype=np.int64)
    oids = rng.integers(0, 256, (len(keys), 20), dtype=np.uint8)
    new_keys = np.array([5, 5, 9, 12, 20], dtype=np.int64)
    new_oids = rng.integers(0, 256, (len(new_keys), 20), dtype=np.uint8)
    new_oids[2] = oids[4]  # key 9 unchanged
    new_oids[0] = oids[1]  # first of the 5-run matches first old 5

    def block(k, o):
        return FeatureBlock.from_arrays(
            k, np.ascontiguousarray(o).view(np.uint32).reshape(-1, 5), [""] * len(k)
        )

    a, b = block(keys, oids), block(new_keys, new_oids)
    ho, hn, hc = classify_blocks_host(a, b)
    ro, rn = classify_blocks_reference(a, b)
    assert np.array_equal(ho[: a.count], ro)
    assert np.array_equal(hn[: b.count], rn)
    assert hc["updates"] == int(np.sum(ro == 2))
    assert hc["inserts"] == int(np.sum(rn == 1))
    assert hc["deletes"] == int(np.sum(ro == 3))


def test_classify_streamed_matches_reference():
    """The double-buffered chunked path must be bit-identical to the
    monolithic kernel / numpy reference, including across chunk boundaries
    (updates, inserts, deletes in every chunk; uneven side sizes)."""
    from kart_tpu.ops.diff_kernel import classify_blocks_streamed

    rng = np.random.default_rng(3)
    n = 5000
    old_keys = np.sort(rng.choice(20_000, size=n, replace=False)).astype(np.int64)
    old_oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    # new side: drop 10%, change 10%, add 500 fresh keys
    keep = rng.random(n) > 0.1
    new_keys = old_keys[keep]
    new_oids = old_oids[keep].copy()
    change = rng.random(len(new_keys)) < 0.1
    new_oids[change, 0] ^= 1
    fresh = np.setdiff1d(
        rng.choice(40_000, size=1000, replace=False), old_keys
    )[:500].astype(np.int64)
    new_keys = np.concatenate([new_keys, fresh])
    new_oids = np.concatenate(
        [new_oids, rng.integers(0, 2**32, size=(len(fresh), 5), dtype=np.uint32)]
    )
    old = FeatureBlock.from_arrays(old_keys, old_oids, [str(k) for k in old_keys])
    new = FeatureBlock.from_arrays(new_keys, new_oids, [str(k) for k in new_keys])

    ref_old, ref_new = classify_blocks_reference(old, new)
    for chunk_rows in (256, 1024, 10_000):  # 20 chunks, 5 chunks, 1 chunk
        got_old, got_new, counts = classify_blocks_streamed(
            old, new, chunk_rows=chunk_rows
        )
        np.testing.assert_array_equal(got_old, ref_old)
        np.testing.assert_array_equal(got_new, ref_new)
        assert counts == {
            "inserts": int(np.sum(ref_new == INSERT)),
            "updates": int(np.sum(ref_old == UPDATE)),
            "deletes": int(np.sum(ref_old == DELETE)),
        }


def test_classify_streamed_one_side_empty():
    from kart_tpu.ops.diff_kernel import classify_blocks_streamed

    keys = np.arange(2000, dtype=np.int64)
    oids = np.ones((2000, 5), dtype=np.uint32)
    full = FeatureBlock.from_arrays(keys, oids, [str(k) for k in keys])
    empty = FeatureBlock.from_arrays(
        np.zeros(0, dtype=np.int64), np.zeros((0, 5), dtype=np.uint32), []
    )
    _, new_class, counts = classify_blocks_streamed(empty, full, chunk_rows=512)
    assert counts == {"inserts": 2000, "updates": 0, "deletes": 0}
    assert (new_class == INSERT).all()
    old_class, _, counts = classify_blocks_streamed(full, empty, chunk_rows=512)
    assert counts == {"inserts": 0, "updates": 0, "deletes": 2000}
    assert (old_class == DELETE).all()


def test_device_open_cost_model(monkeypatch):
    """Routing: CPU backends go host at every size (r3 post-mortem: XLA-CPU
    lost 13.6x to the native engine at 100M rows); small blocks go host on
    any backend; KART_DIFF_DEVICE forces either way."""
    import kart_tpu.runtime as runtime
    from kart_tpu.routing import device_open

    monkeypatch.delenv("KART_DIFF_DEVICE", raising=False)
    # small: host, decided before any backend probe
    monkeypatch.setattr(runtime, "_probe_result", None)
    assert not device_open(10)
    assert runtime._probe_result is None  # no probe happened

    # big + cpu backend: host
    monkeypatch.setattr(
        runtime,
        "_probe_result",
        {"ok": True, "backend": "cpu", "device_kind": "cpu", "n_devices": 1,
         "init_seconds": 0.0, "error": None},
    )
    assert not device_open(10**9)
    # big + accelerator: device
    monkeypatch.setattr(
        runtime,
        "_probe_result",
        {"ok": True, "backend": "tpu", "device_kind": "TPU v5", "n_devices": 1,
         "init_seconds": 0.0, "error": None},
    )
    assert device_open(10**9)
    # wedged: host
    monkeypatch.setattr(
        runtime,
        "_probe_result",
        {"ok": False, "backend": None, "device_kind": None, "n_devices": 0,
         "init_seconds": 0.0, "error": "simulated"},
    )
    assert not device_open(10**9)
    # forced
    monkeypatch.setenv("KART_DIFF_DEVICE", "0")
    monkeypatch.setattr(
        runtime,
        "_probe_result",
        {"ok": True, "backend": "tpu", "device_kind": "TPU v5", "n_devices": 1,
         "init_seconds": 0.0, "error": None},
    )
    assert not device_open(10**9)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(
        runtime,
        "_probe_result",
        {"ok": True, "backend": "cpu", "device_kind": "cpu", "n_devices": 1,
         "init_seconds": 0.0, "error": None},
    )
    assert device_open(10)


def test_classify_streamed_disjoint_key_ranges():
    """Renumbered-PK shape: all new keys above the old range. Bounds must
    come from the combined population, so chunks stay balanced instead of
    one chunk swallowing a whole side."""
    from kart_tpu.ops.diff_kernel import classify_blocks_streamed

    n = 4000
    old_keys = np.arange(n, dtype=np.int64)
    new_keys = np.arange(n, 2 * n, dtype=np.int64)
    oids = np.ones((n, 5), dtype=np.uint32)
    old = FeatureBlock.from_arrays(old_keys, oids, [str(k) for k in old_keys])
    new = FeatureBlock.from_arrays(new_keys, oids.copy(), [str(k) for k in new_keys])
    old_class, new_class, counts = classify_blocks_streamed(old, new, chunk_rows=500)
    assert counts == {"inserts": n, "updates": 0, "deletes": n}
    assert (old_class == DELETE).all() and (new_class == INSERT).all()


# -- the monolithic device route takes the block's own pages (ISSUE 27) -----


def _sorted_block(n, seed, stride=3):
    """An unpadded FeatureBlock of ``n`` rows with sorted keys, as
    ``sidecar.load_block(pad=False)`` hands the diff its blocks."""
    rng = np.random.default_rng(seed)
    keys = np.sort(
        rng.choice(np.arange(max(n, 1) * stride, dtype=np.int64), size=n, replace=False)
    )
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    return FeatureBlock(keys, oids, None, n)


def _edited(block, n_new, seed):
    """A second revision of ``block`` with ``n_new`` rows: every 7th row
    dropped, every 9th rewritten, fresh keys above the old range appended
    (or rows cut) to reach ``n_new``."""
    rng = np.random.default_rng(seed)
    keep = np.arange(block.count) % 7 != 3
    keys, oids = block.keys[keep], block.oids[keep].copy()
    oids[::9, 0] ^= 1
    if n_new <= len(keys):
        keys, oids = keys[:n_new], oids[:n_new]
    else:
        extra = n_new - len(keys)
        top = int(block.keys[-1]) + 1 if block.count else 0
        keys = np.concatenate([keys, np.arange(top, top + extra, dtype=np.int64)])
        oids = np.concatenate(
            [oids, rng.integers(0, 2**32, size=(extra, 5), dtype=np.uint32)]
        )
    return FeatureBlock(keys, oids, None, n_new)


def _padded(block):
    size = bucket_size(max(block.count, 1))
    keys = np.full(size, PAD_KEY, dtype=np.int64)
    keys[: block.count] = block.keys[: block.count]
    oids = np.zeros((size, 5), dtype=np.uint32)
    oids[: block.count] = block.oids[: block.count]
    return FeatureBlock(keys, oids, None, block.count)


def _as_sidecar_view(block):
    """The block's columns as the sidecar gives them: read-only
    ``np.frombuffer`` views at an odd offset into one buffer."""
    n = block.count
    buf = b"KCOL1\n{}\n" + block.keys.tobytes() + block.oids.tobytes()
    keys = np.frombuffer(buf, dtype="<i8", count=n, offset=9)
    oids = (
        np.frombuffer(buf, dtype=np.uint8, count=20 * n, offset=9 + 8 * n)
        .reshape(n, 5, 4).view(np.uint32).reshape(n, 5)
    )
    assert not keys.flags.aligned and not keys.flags.writeable
    return FeatureBlock(keys, oids, None, n)


_BODY_5120 = bucket_body(5120)  # 4608: buckets 5120 and its step 512

SPLIT_CASES = {
    "empty_old": (0, 40, None),
    "empty_both": (0, 0, None),
    "one_row": (1, 1, None),
    "under_minimum": (700, 650, None),
    "exactly_body": (_BODY_5120 + 200, _BODY_5120, None),
    "body_plus_one": (_BODY_5120 + 1, _BODY_5120 + 1, None),
    "exactly_bucket": (5120, 5120, None),
    "different_buckets": (5000, 9000, None),
    "already_padded": (5000, 4800, _padded),
    "sidecar_view": (5000, 4900, _as_sidecar_view),
}


def _split_case(case):
    n_old, n_new, shape = SPLIT_CASES[case]
    old = _sorted_block(n_old, seed=11)
    new = _edited(old, n_new, seed=12)
    if shape is not None:
        old, new = shape(old), shape(new)
    return old, new


# -- what the windowed join must get right beyond attribute-only commits
# (ISSUE 29; two benchmark cells send two of these shapes since ISSUE 32):
# inserts and deletes, appended ranges, hashed keys, the overflow branch


def _block(keys, oids):
    return FeatureBlock(
        np.ascontiguousarray(keys, dtype=np.int64),
        np.ascontiguousarray(oids, dtype=np.uint32),
        None,
        len(keys),
    )


def _rewritten(block, every=9, word=0):
    oids = block.oids[: block.count].copy()
    oids[::every, word] ^= 0x80000001
    return _block(block.keys[: block.count], oids)


def _churned(block, fraction, seed):
    """``fraction`` of the rows deleted and as many fresh keys inserted,
    both uniformly; 1% of the survivors rewritten."""
    rng = np.random.default_rng(seed)
    n = block.count
    keys, oids = block.keys[:n], block.oids[:n]
    keep = rng.random(n) >= fraction
    kept_oids = oids[keep].copy()
    kept_oids[rng.random(len(kept_oids)) < 0.01, 2] ^= 7
    fresh = np.setdiff1d(
        rng.integers(
            max(int(keys[0]) - 50, -(2**63)),
            min(int(keys[-1]) + 50, int(PAD_KEY)),  # exclusive: never PAD_KEY
            size=2 * (n - keep.sum()),
        ),
        keys,
    )[: n - keep.sum()]
    new_keys = np.concatenate([keys[keep], fresh])
    new_oids = np.concatenate(
        [kept_oids, rng.integers(0, 2**32, size=(len(fresh), 5), dtype=np.uint32)]
    )
    order = np.argsort(new_keys, kind="stable")
    return _block(new_keys[order], new_oids[order])


def _without(block, lo, hi):
    keep = np.ones(block.count, dtype=bool)
    keep[lo:hi] = False
    return _block(block.keys[: block.count][keep], block.oids[: block.count][keep])


def _with_run(block, at, n_rows, seed=3):
    """``n_rows`` fresh consecutive keys inserted before row ``at`` (the
    block's keys are spaced to leave the room)."""
    rng = np.random.default_rng(seed)
    keys, oids = block.keys[: block.count], block.oids[: block.count]
    start = int(keys[at - 1]) + 1 if at else int(keys[0]) - n_rows
    assert at == block.count or start + n_rows <= int(keys[at])
    run = np.arange(start, start + n_rows, dtype=np.int64)
    return _block(
        np.concatenate([keys[:at], run, keys[at:]]),
        np.concatenate(
            [oids[:at], rng.integers(0, 2**32, size=(n_rows, 5), dtype=np.uint32), oids[at:]]
        ),
    )


def _spaced_block(n, seed, gap=2048):
    """Sorted keys ``gap`` apart: room for a run of fresh keys anywhere."""
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64) * gap
    return _block(keys, rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32))


def _hashed_block(n, seed):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(-(2**63), 2**63 - 2, size=n, dtype=np.int64))
    return _block(keys, rng.integers(0, 2**32, size=(len(keys), 5), dtype=np.uint32))


def _join_slack():
    from kart_tpu.ops.diff_kernel import JOIN_LANES, JOIN_TILE, JOIN_WINDOW

    # rows that can be inserted inside one tile's key span whatever line
    # offset its window starts at
    return JOIN_WINDOW - JOIN_TILE - (JOIN_LANES - 1)


def _slack_case(extra):
    """A run of slack + ``extra`` keys inserted inside tile 1's span, with
    the tile's lower bound on the last lane of a line (the worst offset):
    slack + 0 is the most that always fits."""

    def build():
        from kart_tpu.ops.diff_kernel import JOIN_LANES, JOIN_TILE

        old = _spaced_block(3000, seed=21)
        # JOIN_LANES - 1 rows prepended: old row JOIN_TILE (tile 1's first)
        # falls on new row JOIN_TILE + 127, the last lane of its line
        new = _with_run(old, 0, JOIN_LANES - 1)
        at = JOIN_LANES - 1 + JOIN_TILE + 5
        return old, _with_run(new, at, _join_slack() + extra, seed=4)

    return build


def _near_pad_case():
    keys = np.concatenate(
        [
            np.arange(-(2**63), -(2**63) + 300, dtype=np.int64),
            np.arange(-150, 150, dtype=np.int64),
            np.arange(2**32 - 100, 2**32 + 100, dtype=np.int64),
            np.arange(2**63 - 301, 2**63 - 1, dtype=np.int64),  # PAD_KEY - 1 is last
        ]
    )
    rng = np.random.default_rng(8)
    old = _block(keys, rng.integers(0, 2**32, size=(len(keys), 5), dtype=np.uint32))
    assert old.keys[-1] == PAD_KEY - 1
    return old, _churned(_rewritten(old, every=5), 0.1, seed=9)


def _one_word_case(word):
    def build():
        old = _sorted_block(3000, seed=31)
        oids = old.oids.copy()
        oids[::3, word] ^= 1  # the lowest bit of one word, nothing else
        return old, _block(old.keys, oids)

    return build


#: name -> (builder of (old, new), whether the windowed join overflows)
JOIN_CASES = {
    "attribute_only": (lambda: (b := _sorted_block(5000, seed=41), _rewritten(b)), False),
    "churn_0.1pct": (lambda: (b := _sorted_block(5000, seed=42), _churned(b, 0.001, 1)), False),
    "churn_5pct": (lambda: (b := _sorted_block(5000, seed=43), _churned(b, 0.05, 2)), False),
    "churn_40pct": (lambda: (b := _sorted_block(5000, seed=44), _churned(b, 0.4, 3)), False),
    "hashed_keys_churn": (lambda: (b := _hashed_block(5000, seed=45), _churned(b, 0.05, 4)), False),
    "appended_range": (
        lambda: (b := _spaced_block(3000, seed=46), _with_run(b, 3000, 2500)),
        False,
    ),
    "prepended_range": (
        lambda: (b := _spaced_block(3000, seed=47), _with_run(b, 0, 2500)),
        False,
    ),
    "bulk_insert_mid": (
        lambda: (b := _spaced_block(3000, seed=48), _with_run(b, 1500, 1500)),
        True,
    ),
    "bulk_delete_mid": (
        lambda: (b := _sorted_block(5000, seed=49), _without(b, 1000, 2500)),
        True,
    ),
    "slack_less_one": (_slack_case(-1), False),
    "slack_exactly": (_slack_case(0), False),
    "slack_plus_one": (_slack_case(1), True),
    "bucket_edge_1024": (lambda: (b := _sorted_block(1024, seed=50), _churned(b, 0.05, 5)), False),
    "bucket_edge_1152": (lambda: (b := _sorted_block(1152, seed=51), _churned(b, 0.05, 6)), False),
    "bodyless_bucket": (lambda: (b := _sorted_block(900, seed=52), _churned(b, 0.05, 7)), False),
    "one_row_deleted": (lambda: (_sorted_block(1, seed=53), _sorted_block(0, seed=53)), False),
    "window_at_array_start": (
        lambda: (b := _sorted_block(3000, seed=54), _without(b, 0, 600)),
        False,
    ),
    "window_at_array_end": (
        lambda: (b := _sorted_block(3000, seed=55), _without(b, 2400, 3000)),
        False,
    ),
    "negative_and_near_pad_keys": (_near_pad_case, False),
    "oid_word_0_only": (_one_word_case(0), False),
    "oid_word_4_only": (_one_word_case(4), False),
}


@pytest.mark.parametrize("n", [1, 255, 256, 1024, 70_000])
def test_join_lower_bounds_equal_searchsorted(n):
    """The windowed join's three-level boundary search is
    ``np.searchsorted(side="left")``: on keys present and absent, below the
    first and above the last, on ``PAD_KEY`` itself, at every level's edge."""
    import jax

    from kart_tpu.ops.diff_kernel import _join_lower_bounds

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(n)
    keys = np.unique(rng.integers(-(2**63), 2**63 - 2, size=n, dtype=np.int64))
    padded = np.concatenate([keys, np.full(37, PAD_KEY, dtype=np.int64)])
    probes = np.concatenate(
        [
            keys[:: max(len(keys) // 300, 1)],
            keys[:: max(len(keys) // 300, 1)] + 1,
            rng.integers(-(2**63), 2**63 - 2, size=200, dtype=np.int64),
            np.array([-(2**63), keys[0], keys[-1], PAD_KEY - 1, PAD_KEY], dtype=np.int64),
        ]
    )
    got = jax.jit(_join_lower_bounds)(padded, probes)
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(padded, probes, side="left")
    )


@pytest.mark.parametrize("route", ["sort", "window"])
@pytest.mark.parametrize("case", list(SPLIT_CASES) + list(JOIN_CASES))
def test_device_classify_split_matches_reference(case, route, monkeypatch):
    """classify_blocks on the monolithic device route equals the numpy
    reference — at every edge of the body/tail split and on every shape of
    commit the windowed join has to get right: classes, counts, and never
    by way of the host fallback. ``sort`` is the route as any backend but
    a TPU takes it (XLA-CPU here: the sort-join through ``_classify_split``);
    ``window`` is the TPU's (the backend's name forced,
    the Pallas kernel interpreted), down to the overflow branch: a tile
    whose partners do not fit its window sends the call to the sort-join,
    which is counted and is no fallback."""
    from kart_tpu import runtime
    from kart_tpu import telemetry as tm
    from kart_tpu.ops.diff_kernel import DELETE, INSERT, UPDATE

    if case in SPLIT_CASES:
        (old, new), overflows = _split_case(case), False
    else:
        build, overflows = JOIN_CASES[case]
        old, new = build()
    ref_old, ref_new = classify_blocks_reference(old, new)

    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    if route == "window":
        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        old_class, new_class, counts = classify_blocks(old, new)
        counters = tm.counters_snapshot()
        kernel = [e["args"] for e in tm.drain_events() if e["name"] == "diff.device.kernel"]
    finally:
        tm.reset()
    assert [k for k in counters if k[0] == "diff.device.fallbacks"] == []
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    assert counts == {
        "inserts": int(np.sum(ref_new == INSERT)),
        "updates": int(np.sum(ref_old == UPDATE)),
        "deletes": int(np.sum(ref_old == DELETE)),
    }
    (attrs,) = kernel
    overflowed = counters.get(("diff.device.join_overflows", ()), 0)
    if route == "window":
        from kart_tpu.ops.diff_kernel import (
            JOIN_STEP_TILES,
            JOIN_TILE,
            join_census_reference,
        )

        # the entry that answered: the sort-join where a tile overflowed
        assert attrs["program"] == ("sort_join" if overflows else "window_join")
        assert attrs["join"] == ("sort" if overflows else "window")
        # the tiles of the grid: whole steps of 32 over the bucket's tiles
        assert attrs["tiles"] % (2 * JOIN_STEP_TILES) == 0
        assert 0 <= attrs["tiles"] // 2 - -(-attrs["bucket"] // JOIN_TILE) < JOIN_STEP_TILES
        assert overflowed == (1 if overflows else 0)
        # the kernel's own census equals a numpy recount of the same blocks
        assert (attrs["dense_tiles"], attrs["overflow_tiles"]) == (
            join_census_reference(old, new)
        )
        assert (attrs["overflow_tiles"] > 0) == overflows
        assert attrs.get("window_ran", False) == overflows
        assert counters.get(("diff.device.join_dense_tiles", ()), 0) == (
            attrs["dense_tiles"]
        )
    else:
        assert attrs["program"] == "sort_join" and "join" not in attrs
        assert overflowed == 0


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_window_join_random_runs_match_reference(seed, monkeypatch):
    """Random commits on the accelerator's route: runs of 1 to 700 rows
    deleted and inserted anywhere (some overflow a window, most do not),
    over dense, negative, hashed and few-distinct-low-word keys, in both
    directions. Whatever program answers, classes equal the reference."""
    from kart_tpu import runtime
    from kart_tpu import telemetry as tm

    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(seed)
    for kind in ("dense", "negative", "hashed", "low_words"):
        size = 3000
        if kind == "dense":
            keys = np.sort(rng.choice(np.arange(size * 3, dtype=np.int64), size, replace=False))
        elif kind == "negative":
            keys = np.sort(rng.choice(np.arange(-size * 2, size * 2, dtype=np.int64), size, replace=False))
        elif kind == "hashed":
            keys = np.unique(rng.integers(-(2**63), 2**63 - 2, size, dtype=np.int64))
        else:  # four low words under a hundred high words: a key is both
            keys = np.unique(
                (rng.integers(-50, 50, size).astype(np.int64) << 32)
                | (rng.integers(0, 4, size).astype(np.int64) * 0x7FFFFFFF & 0xFFFFFFFF)
            )
        old = _block(keys, rng.integers(0, 2**32, (len(keys), 5), dtype=np.uint32))
        keep = np.ones(old.count, dtype=bool)
        for _ in range(rng.integers(1, 8)):
            at = rng.integers(0, old.count)
            keep[at : at + int(rng.choice([1, 5, 40, 300, 700]))] = False
        new_keys, new_oids = old.keys[keep], old.oids[keep].copy()
        new_oids[rng.random(len(new_keys)) < 0.05, rng.integers(0, 5)] ^= 1 << 31
        runs = [
            np.arange(base, base + int(rng.choice([1, 5, 40, 300, 700])), dtype=np.int64)
            for base in rng.choice(keys[keys < PAD_KEY - 1000], rng.integers(1, 8))
        ]
        fresh = np.setdiff1d(np.concatenate(runs), new_keys)
        new_keys = np.concatenate([new_keys, fresh])
        new_oids = np.concatenate(
            [new_oids, rng.integers(0, 2**32, (len(fresh), 5), dtype=np.uint32)]
        )
        order = np.argsort(new_keys, kind="stable")
        new = _block(new_keys[order], new_oids[order])
        for a, b in ((old, new), (new, old)):
            ref_a, ref_b = classify_blocks_reference(a, b)
            tm.reset()
            tm.enable(metrics=True)
            try:
                got_a, got_b, _ = classify_blocks(a, b)
                fallbacks = [
                    k for k in tm.counters_snapshot() if k[0] == "diff.device.fallbacks"
                ]
            finally:
                tm.reset()
            assert fallbacks == []
            np.testing.assert_array_equal(got_a, ref_a, err_msg=kind)
            np.testing.assert_array_equal(got_b, ref_b, err_msg=kind)


@pytest.mark.parametrize("n", [700, _BODY_5120 + 1, 5000, 5120, 9000])
@pytest.mark.parametrize("shape", [None, _padded, _as_sidecar_view])
def test_page_parts_copy_one_tail_and_view_the_rest(n, shape):
    """``_page_parts`` of a revision of one page: the parts spell the padded
    block row for row; a block that fills its bucket, padded or by its own
    rows, is one view; any other is a body that is the caller's own memory
    and one freshly padded grid step of the column's rows."""
    from kart_tpu.ops.diff_kernel import _page_parts, page_rows

    block = _sorted_block(n, seed=5)
    if shape is not None:
        block = shape(block)
    bucket = bucket_size(n)
    assert page_rows(n) == bucket  # far below a chunk: its own bucket
    body = bucket_body(bucket)
    want = _padded(block)
    whole = len(block.keys) >= bucket
    copied = 0
    for values, padded, row_bytes in ((block.keys, want.keys, 8), (block.oids, want.oids, 20)):
        parts = _page_parts(values, block.count, 0, bucket)
        np.testing.assert_array_equal(np.concatenate(parts), padded)
        copied += sum(a.nbytes for a in parts if a.flags.owndata)
        if whole:
            assert len(parts) == 1 and np.shares_memory(parts[0], values)
        else:
            assert [len(a) for a in parts] == [body, bucket - body]
            assert parts[1].flags.owndata and parts[1].nbytes == (bucket - body) * row_bytes
            assert not body or np.shares_memory(parts[0], values)
    assert copied == (0 if whole else (bucket - body) * 28)


def test_device_classify_pack_span_counts_only_the_tails(monkeypatch):
    """The ``diff.device.pack`` span's ``bytes`` is what the host copied —
    at most 2 x step x 28 — while ``diff.device.transfer`` still carries
    both whole buckets."""
    from kart_tpu import telemetry as tm

    old = _as_sidecar_view(_sorted_block(5000, seed=1))
    new = _as_sidecar_view(_edited(old, 4900, seed=2))
    step = 5120 - _BODY_5120
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    tm.reset()
    tm.enable(trace=True)
    try:
        classify_blocks(old, new)
        events = {e["name"]: e["args"] for e in tm.drain_events()}
    finally:
        tm.reset()
    assert events["diff.device.pack"]["bucket"] == 5120
    assert events["diff.device.pack"]["bytes"] == 2 * step * 28
    assert events["diff.device.transfer"]["bytes"] == 2 * 5120 * 28
    assert events["diff.device.kernel"]["program"] == "sort_join"


# -- the device route is a pipeline of key-range chunks (ISSUE 36) ----------

_CHUNK = 10_240  # on the bucket grid: a full chunk is its bucket (step 1,024)
_DEVICE_SPANS = [
    "diff.device.pack", "diff.device.transfer", "diff.device.kernel",
    "diff.device.fetch",
]


def _bulk_insert_case():
    old = _spaced_block(12_000, seed=66, gap=40_000)
    return old, _with_run(old, 6_000, 25_000)


#: name -> (builder of (old, new), chunks, chunks the sort-join answers on
#: the windowed route)
CHUNK_CASES = {
    # three full chunks and a ragged last one
    "aligned_sides": (
        lambda: (b := _sorted_block(35_000, seed=61), _rewritten(b)), 4, [],
    ),
    "aligned_sidecar_views": (
        lambda: (
            _as_sidecar_view(b := _sorted_block(35_000, seed=61)),
            _as_sidecar_view(_rewritten(b)),
        ),
        4, [],
    ),
    "uniform_churn": (
        lambda: (b := _sorted_block(35_000, seed=62), _churned(b, 0.05, 8)), 4, [],
    ),
    # 800 rows gone inside chunk 1: more than a window holds at any offset
    "hole_inside_a_chunk": (
        lambda: (b := _sorted_block(35_000, seed=63), _without(b, 12_000, 12_800)),
        4, [1],
    ),
    # the hole ends chunk 0 and starts chunk 1: no tile has it inside its span
    "hole_across_a_boundary": (
        lambda: (b := _sorted_block(35_000, seed=64), _without(b, _CHUNK - 400, _CHUNK + 400)),
        4, [],
    ),
    # 1,500 rows gone: the new side of chunk 1 is shorter than the bucket's
    # body (copied whole before the chunks were cut from pages)
    "hole_wider_than_a_grid_step": (
        lambda: (b := _sorted_block(35_000, seed=65), _without(b, 12_000, 13_500)),
        4, [1],
    ),
    # 25,000 keys inserted between two old keys: chunks 1 and 2 have no old rows
    "bulk_insert_empty_chunks": (_bulk_insert_case, 4, []),
    "empty_old_side": (
        lambda: (_sorted_block(0, seed=67), _sorted_block(25_000, seed=67)), 3, [],
    ),
    "one_chunk": (
        lambda: (b := _sorted_block(5_000, seed=68), _churned(b, 0.05, 9)), 1, [],
    ),
    # blocks that arrive padded to their bucket
    "padded_blocks": (
        lambda: (_padded(b := _sorted_block(35_000, seed=69)), _padded(_churned(b, 0.05, 10))),
        4, [],
    ),
}


def _page_traffic(plan, blocks, chunk_rows):
    """What each chunk of ``plan`` costs in bytes by the rule alone ->
    [(copied on the host, put)]: a chunk puts every page its rows lie in
    that no earlier chunk has put; a full page is a view; a revision's last
    page is put at its own bucket, one grid step of it copied."""
    from kart_tpu.ops.diff_kernel import page_rows

    seen, costs = set(), []
    for *sides, _ in plan:
        copied = put = 0
        for s, (block, (lo, hi)) in enumerate(zip(blocks, sides)):
            rows = page_rows(block.count, chunk_rows)
            for page in range(lo // rows, -(-hi // rows) if hi > lo else 0):
                if (s, page) in seen:
                    continue
                seen.add((s, page))
                if len(block.keys) - page * rows >= rows:
                    put += 28 * rows
                    continue
                size = bucket_size(block.count - page * rows)
                put += 28 * size
                copied += 28 * (size - bucket_body(size))
        costs.append((copied, put))
    return costs


@pytest.mark.parametrize("route", ["sort", "window"])
@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_device_classify_chunks_match_reference(case, route, monkeypatch):
    """The device route cut into key-range chunks (a small chunk size, the
    production code) equals the numpy reference whatever the commit does to
    the key set, never by way of the host fallback; each chunk has its own
    spans and its own tile census (a numpy recount of that chunk's rows); a
    tile overflow sends its chunk, and no other, to the sort-join; a call of
    one chunk is the four spans it always was."""
    from kart_tpu import runtime
    from kart_tpu import telemetry as tm
    from kart_tpu.ops import diff_kernel
    from kart_tpu.ops.diff_kernel import classify_chunk_plan, join_census_reference

    build, n_chunks, sorted_chunks = CHUNK_CASES[case]
    old, new = build()
    ref_old, ref_new = classify_blocks_reference(old, new)
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    if route == "window":
        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    plan = classify_chunk_plan(old, new)
    assert len(plan) == n_chunks
    traffic = _page_traffic(plan, (old, new), _CHUNK)
    view_chunks = sum(not copied for copied, _ in traffic)
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        with tm.span("diff.classify"):
            old_class, new_class, counts = classify_blocks(old, new)
        counters = {k[0]: v for k, v in tm.counters_snapshot().items()}
        events = tm.drain_events()
    finally:
        tm.reset()
    assert "diff.device.fallbacks" not in counters
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    assert counts == {
        "inserts": int(np.sum(ref_new == INSERT)),
        "updates": int(np.sum(ref_old == UPDATE)),
        "deletes": int(np.sum(ref_old == DELETE)),
    }
    assert counters["diff.device.chunks"] == n_chunks
    assert counters.get("diff.device.view_chunks", 0) == view_chunks
    (classify,) = [e["args"] for e in events if e["name"] == "diff.classify"]
    assert (classify["chunks"], classify["view_chunks"]) == (n_chunks, view_chunks)

    clock = [e for e in events if e["name"] == "diff.device.clock"]
    device = [
        e for e in events if e["name"].startswith("diff.device.") and e not in clock
    ]
    assert len(clock) == 2  # span events are on: the two pings, tested below
    if n_chunks == 1:
        assert [e["name"] for e in device] == _DEVICE_SPANS
        assert not any("chunk" in e["args"] for e in device)
    else:
        by_name = {}
        for e in device:
            by_name.setdefault(e["name"], []).append(e)
        assert set(by_name) == set(_DEVICE_SPANS) | {"diff.device.enqueue"}
        for name, spans in by_name.items():
            assert [e["args"]["chunk"] for e in spans] == list(range(n_chunks)), name
        # three deep: chunk c's inputs are waited for once chunk c+1 is on
        # its way, and chunk c is drained once chunk c+1 has landed
        starts = {name: [e["ts"] for e in spans] for name, spans in by_name.items()}
        for c in range(n_chunks - 1):
            assert starts["diff.device.enqueue"][c + 1] < starts["diff.device.transfer"][c]
            if c < n_chunks - 2:
                assert starts["diff.device.transfer"][c + 1] < starts["diff.device.kernel"][c]
        for c in range(n_chunks):
            assert starts["diff.device.transfer"][c] < starts["diff.device.kernel"][c]
            assert starts["diff.device.kernel"][c] < starts["diff.device.fetch"][c]
        # each page is shipped once, by the first chunk that reads it
        assert [e["args"]["bytes"] for e in by_name["diff.device.enqueue"]] == [
            e["args"]["bytes"] for e in by_name["diff.device.transfer"]
        ] == [put for _, put in traffic]
        assert not any(e["args"]["resident"] for e in by_name["diff.device.transfer"])
    kernels = [e["args"] for e in device if e["name"] == "diff.device.kernel"]
    packs = [e["args"] for e in device if e["name"] == "diff.device.pack"]
    # only a revision's last page costs a host copy: one grid step a column
    assert [pack["bytes"] for pack in packs] == [copied for copied, _ in traffic]
    assert sum(copied for copied, _ in traffic) <= 2 * 28 * _CHUNK // 8
    for c, ((old_rows, new_rows, sizes), kernel, pack) in enumerate(zip(plan, kernels, packs)):
        assert kernel["program"] == (
            "window_join" if route == "window" and c not in sorted_chunks else "sort_join"
        )
        assert kernel["bucket"] == max(sizes)
        assert pack["rows"] == old_rows[1] - old_rows[0] + new_rows[1] - new_rows[0]
        if route == "sort":
            assert "join" not in kernel
            continue
        sides = [
            _block(b.keys[lo:hi], b.oids[lo:hi])
            for b, (lo, hi) in ((old, old_rows), (new, new_rows))
        ]
        assert (kernel["dense_tiles"], kernel["overflow_tiles"]) == (
            join_census_reference(*sides, sizes)
        ), c
        assert kernel["join"] == ("sort" if c in sorted_chunks else "window"), c
        assert (kernel["overflow_tiles"] > 0) == (c in sorted_chunks)
        assert kernel.get("window_ran", False) == (c in sorted_chunks)
    assert counters.get("diff.device.join_overflows", 0) == (
        len(sorted_chunks) if route == "window" else 0
    )
    if case == "bulk_insert_empty_chunks":
        assert [rows[0][1] - rows[0][0] for rows in plan] == [6_000, 0, 0, 6_000]


# -- the clock pings and what the copy hid (ISSUE 37) -------------------------

def _counted_probe(monkeypatch):
    """``_clock_probe`` counted: -> the list its dispatches are noted in."""
    from kart_tpu.ops import diff_kernel

    dispatched, real = [], diff_kernel._clock_probe

    def counting():
        dispatched.append(1)
        return real()

    monkeypatch.setattr(diff_kernel, "_clock_probe", counting)
    return dispatched


def _streamed(old, new, **layers):
    """classify_blocks on the device route under a ``diff.classify`` span
    with the telemetry ``layers`` on -> (classes and counts, events,
    counters by name, names of the spans aggregated)."""
    from kart_tpu import telemetry as tm

    tm.reset()
    if layers:
        tm.enable(**layers)
    try:
        with tm.span("diff.classify"):
            answer = classify_blocks(old, new)
        counters = {k[0]: v for k, v in tm.counters_snapshot().items()}
        names = {name for name, _, _ in tm.snapshot()["histograms"]}
        return answer, tm.drain_events(), counters, names
    finally:
        tm.reset()


@pytest.mark.parametrize(
    "layers", [{}, {"spans": True}, {"metrics": True}], ids=["off", "spans", "metrics"]
)
@pytest.mark.parametrize("rows", [5_000, 35_000], ids=["one_chunk", "four_chunks"])
def test_no_clock_ping_unless_span_events_are_recorded(rows, layers, monkeypatch):
    """With tracing off — telemetry off, or only the aggregation the
    benchmark's untraced window leaves on (``enable(metrics=True)``) — the
    device route dispatches no probe and records no ``diff.device.clock``:
    the pings cost a bool test."""
    from kart_tpu.ops import diff_kernel

    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    dispatched = _counted_probe(monkeypatch)
    old = _sorted_block(rows, seed=71)
    new = _churned(old, 0.05, 11)
    (old_class, new_class, _), events, _, names = _streamed(old, new, **layers)
    assert dispatched == [] and events == []
    assert "diff.device.clock" not in names
    assert ("diff.device.kernel" in names) == bool(layers)
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)


@pytest.mark.parametrize("route", ["sort", "window"])
@pytest.mark.parametrize("rows", [5_000, 35_000], ids=["one_chunk", "four_chunks"])
def test_a_traced_call_is_bracketed_by_two_clock_pings(rows, route, monkeypatch):
    """While span events are recorded a streamed call, of one chunk or of
    several, runs the probe twice: once before anything is put and once
    after the last drain, each dispatched and waited for inside its own
    ``diff.device.clock`` span, a child of ``diff.classify`` on the thread
    that called. The classes are bit for bit those of an untraced call."""
    import threading

    from kart_tpu import runtime
    from kart_tpu.ops import diff_kernel

    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    if route == "window":
        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    dispatched = _counted_probe(monkeypatch)
    old = _sorted_block(rows, seed=72)
    new = _churned(old, 0.05, 12)
    untraced, no_events, _, _ = _streamed(old, new)
    assert dispatched == [] and no_events == []
    traced, events, _, _ = _streamed(old, new, trace=True)
    assert len(dispatched) == 2
    np.testing.assert_array_equal(traced[0], untraced[0])
    np.testing.assert_array_equal(traced[1], untraced[1])
    assert traced[2] == untraced[2]

    clock = [e for e in events if e["name"] == "diff.device.clock"]
    assert [e["args"]["at"] for e in clock] == ["start", "end"]
    (classify,) = [e for e in events if e["name"] == "diff.classify"]
    for e in clock:
        assert e["args"]["parent"] == "diff.classify"
        assert e["tid"] == classify["tid"] == threading.get_ident()
        assert classify["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= classify["ts"] + classify["dur"]
    others = [e for e in events if e["name"].startswith("diff.device.") and e not in clock]
    assert clock[0]["ts"] + clock[0]["dur"] <= min(e["ts"] for e in others)
    assert max(e["ts"] + e["dur"] for e in others) <= clock[1]["ts"]


def test_the_probe_program_is_not_a_classify_program():
    """The device trace names a program after its function: the clock
    readers look for ``jit__clock_probe*`` and the old ``idle.*`` pair K
    kernel spans with K ``jit__classify_*`` programs, so the probe may not
    be one of those."""
    import jax

    from kart_tpu.ops.diff_kernel import _clock_probe

    assert _clock_probe.__wrapped__.__name__ == "_clock_probe"
    text = jax.jit(_clock_probe.__wrapped__).lower().as_text()
    assert "jit__clock_probe" in text and "jit__classify_" not in text
    assert _clock_probe().shape == ()  # one scalar, nothing copied to make it


@pytest.mark.parametrize("route", ["sort", "window"])
@pytest.mark.parametrize("case", ["aligned_sides", "hole_inside_a_chunk", "one_chunk"])
@pytest.mark.parametrize("layers", [{"trace": True}, {"metrics": True}], ids=["trace", "metrics"])
def test_what_the_copy_hid_is_counted_a_chunk_at_a_time(case, route, layers, monkeypatch):
    """Every ``diff.device.transfer`` and ``diff.device.kernel`` span says
    whether what it waits for was already there (``ready`` = 1 | 0, asked
    without waiting); ``diff.classify`` gets the sums beside ``chunks`` and
    the counter ``diff.device.hidden_programs`` adds the programs up. A call
    of one chunk puts and calls under those spans: nothing can be ready."""
    from kart_tpu import runtime
    from kart_tpu.ops import diff_kernel

    build, n_chunks, _ = CHUNK_CASES[case]
    old, new = build()
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    if route == "window":
        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    (old_class, new_class, _), events, counters, _ = _streamed(old, new, **layers)
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    most = n_chunks if n_chunks > 1 else 0
    if "metrics" in layers:
        assert events == []  # aggregation only: the counter is what is left
        assert 0 <= counters["diff.device.hidden_programs"] <= most
        assert counters["diff.device.chunks"] == n_chunks
        return
    (classify,) = [e["args"] for e in events if e["name"] == "diff.classify"]
    assert classify["chunks"] == n_chunks
    ready = {
        name: [e["args"]["ready"] for e in events if e["name"] == name]
        for name in ("diff.device.transfer", "diff.device.kernel")
    }
    for name, flags in ready.items():
        assert len(flags) == n_chunks and set(flags) <= {0, 1}, name
    assert classify["landed_ahead"] == sum(ready["diff.device.transfer"])
    assert classify["hidden_programs"] == sum(ready["diff.device.kernel"])
    assert 0 <= classify["hidden_programs"] <= most
    assert 0 <= classify["landed_ahead"] <= most


def test_full_pages_go_over_as_views():
    """Every page of a revision but its last is handed to the device as one
    array a column that owns no data: the caller's pages (a sidecar's
    mapping, unaligned and read-only), the first byte to the last. The last
    page is a body view and one fresh tail a column."""
    from kart_tpu.ops.diff_kernel import _page_parts, page_rows

    block = _as_sidecar_view(_sorted_block(35_000, seed=61))
    rows = page_rows(block.count, _CHUNK)
    assert rows == _CHUNK
    for page in range(3):
        lo = page * rows
        for values in (block.keys, block.oids):
            (view,) = _page_parts(values, block.count, lo, rows)
            assert not view.flags.owndata and not view.flags.writeable
            assert np.shares_memory(view, values)
            np.testing.assert_array_equal(view, values[lo : lo + rows])
    lo, size = 3 * rows, bucket_size(35_000 - 3 * rows)
    keys_body, keys_tail = _page_parts(block.keys, block.count, lo, rows)
    oids_body, oids_tail = _page_parts(block.oids, block.count, lo, rows)
    assert [a.flags.owndata for a in (keys_body, keys_tail, oids_body, oids_tail)] == [
        False, True, False, True,
    ]
    assert len(keys_body) == len(oids_body) == bucket_body(size)
    assert len(keys_tail) == len(oids_tail) == size - bucket_body(size)
    assert keys_tail[35_000 - lo - bucket_body(size)] == PAD_KEY


@pytest.mark.parametrize("n,lo,rows", [
    (30_000, 10_240, 10_240),  # a full page: one view
    (30_000, 20_480, 10_240),  # the last page, nearly full: a body and a tail
    (25_000, 20_480, 10_240),  # the last page at a smaller bucket
    (20_481, 20_480, 10_240),  # one row: a bodyless bucket
    (31_000, 30_720, 10_240),  # under the minimum bucket
])
def test_page_parts_of_a_page(n, lo, rows):
    """``_page_parts`` of one page of a longer revision spells the page's
    rows at their own bucket, padded from the revision's count on, reads
    nothing past it, and copies only one step of the bucket grid."""
    from kart_tpu.ops.diff_kernel import _page_parts

    block = _sorted_block(n, seed=6)
    have = min(n - lo, rows)
    size = rows if have == rows else bucket_size(have)
    for values, fill in ((block.keys, PAD_KEY), (block.oids, 0)):
        parts = _page_parts(values, block.count, lo, rows)
        page = np.concatenate(parts)
        assert len(page) == size <= rows
        np.testing.assert_array_equal(page[:have], values[lo : lo + have])
        assert np.all(page[have:] == fill)
        copied = sum(a.nbytes for a in parts if a.flags.owndata)
        row_bytes = values[:1].nbytes
        assert copied == (0 if have == rows else (size - bucket_body(size)) * row_bytes)
