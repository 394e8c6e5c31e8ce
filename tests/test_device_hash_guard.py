"""The hash-keyed count's cross-version collision guard on the device
(``DeviceJaxBackend.guarded_counts`` → ``classify_blocks_guarded``: a
revision's path rows as a third column of pages, a ``jit__hash_guard``
program a chunk over the classes its classify left on the device) against
the host guard it replaces there (``engine.host_guard`` over the numpy
reference's classes), on the CPU, where the sort-join entry answers unless a
case makes the windowed join run interpreted.

One parametrised test; each case builds its revisions, asks both guards and
holds the device's verdict, counts and route to the host's."""

import numpy as np
import pytest

from kart_tpu import telemetry as tm
from kart_tpu.diff import engine
from kart_tpu.diff.backend import BACKENDS
from kart_tpu.models.paths import ByteRows
from kart_tpu.ops import blocks, diff_kernel, resident
from kart_tpu.ops.blocks import FeatureBlock
from kart_tpu.ops.diff_kernel import DELETE, INSERT, UPDATE, classify_blocks_reference

WIDTH = 60  # bytes of a UUID-keyed layer's feature path
CHUNK = 10_240  # on the bucket grid: a full chunk is its bucket


def _paths(rng, n, width=WIDTH):
    return rng.integers(ord("A"), ord("Z") + 1, size=(n, width), dtype=np.uint8)


def _block(keys, oids, mat, tree_oid, lens=None):
    n = len(keys)
    paths = (
        ByteRows.from_matrix(mat, np.full(n, mat.shape[1]))
        if lens is None
        else ByteRows.from_list([bytes(r[:k]) for r, k in zip(mat, lens)])
    )
    return FeatureBlock(
        np.ascontiguousarray(keys, dtype=np.int64),
        np.ascontiguousarray(oids, dtype=np.uint32),
        paths, n, tree_oid=tree_oid, key_collisions=False,
    )


def revisions(n, seed, n_upd, n_del, n_ins, hole=None, forged=0, wide=0):
    """A base of ``n`` rows (sorted random keys, random oids, paths of one
    width) and a republish of it: ``n_upd`` rows get another oid, ``n_del``
    are deleted (uniformly, or the rows ``hole`` = (lo, hi) besides),
    ``n_ins`` fresh rows inserted. ``forged`` of the inserted rows take the
    key of a deleted one with another path (a 63-bit hash collision across
    the versions: the join reads an update); ``wide`` of them get a path
    four bytes wider."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 2**62, size=n + n_ins + 64, dtype=np.int64))
    fresh_keys, keys = keys[n:n + n_ins], np.sort(keys[:n])
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    mat = _paths(rng, n)
    old = _block(keys, oids, mat, f"old-{seed}")

    rows = rng.permutation(n)
    upd, gone = rows[:n_upd], rows[n_upd:n_upd + n_del]
    keep = np.ones(n, dtype=bool)
    keep[gone] = False
    if hole is not None:
        keep[hole[0]:hole[1]] = False
    new_oids = oids.copy()
    new_oids[upd, 1] ^= 0x5A5A5A5A
    ins_keys = fresh_keys.copy()
    ins_keys[:forged] = keys[gone[:forged]]
    ins_mat = _paths(rng, n_ins, WIDTH + 4 if wide else WIDTH)
    all_keys = np.concatenate([keys[keep], ins_keys])
    order = np.argsort(all_keys, kind="stable")
    all_oids = np.concatenate(
        [new_oids[keep], rng.integers(0, 2**32, size=(n_ins, 5), dtype=np.uint32)]
    )[order]
    if wide:
        width = ins_mat.shape[1]
        kept = np.zeros((keep.sum(), width), dtype=np.uint8)
        kept[:, :WIDTH] = mat[keep]
        lens = np.concatenate(
            [np.full(keep.sum(), WIDTH), np.where(np.arange(n_ins) < wide, width, WIDTH)]
        )
        new = _block(all_keys[order], all_oids, np.concatenate([kept, ins_mat])[order],
                     f"new-{seed}", lens=lens[order])
    else:
        new = _block(all_keys[order], all_oids, np.concatenate([mat[keep], ins_mat])[order],
                     f"new-{seed}")
    return old, new


def host_answer(old, new):
    """The oracle: the numpy reference's classes, the host guard's pairs."""
    old_class, new_class = classify_blocks_reference(old, new)
    counts = {
        "inserts": int(np.sum(new_class == INSERT)),
        "updates": int(np.sum(old_class == UPDATE)),
        "deletes": int(np.sum(old_class == DELETE)),
    }
    return counts, engine.host_guard(old, new, old_class, new_class)


def device_answer(old, new):
    """``guarded_counts`` of the one-device backend -> (counts, verdict,
    counters, span events)."""
    tm.reset()
    tm.enable(metrics=True, trace=True)
    try:
        counts, verdict = BACKENDS["device_jax"].guarded_counts(old, new)
        counters = dict(tm.counters_snapshot())
        events = tm.drain_events()
    finally:
        tm.reset()
    return counts, verdict, counters, events


def route(counters):
    return {
        dict(labels)["where"]: v for (name, labels), v in counters.items()
        if name == "diff.hash_guard.route"
    }


def agrees(old, new, where="device"):
    """The device backend's answer equals the host's, by the route
    ``where``, with no device fallback -> its span events."""
    want_counts, want = host_answer(old, new)
    counts, verdict, counters, events = device_answer(old, new)
    assert counts == want_counts
    assert verdict == want
    assert route(counters) == {where: 1}
    assert not any(name == "diff.device.fallbacks" for name, _ in counters)
    (guard,) = [e["args"] for e in events if e["name"] == "diff.hash_guard"]
    assert (guard["where"], guard["pairs"], guard["collisions"]) == (where, *want)
    (classify,) = [e["args"] for e in events if e["name"] == "diff.classify"]
    assert classify["counts_only"] == (where == "device")
    fetched = [e["args"]["bytes"] for e in events if e["name"] == "diff.device.fetch"]
    guards = [e for e in events if e["name"] == "diff.device.guard"]
    if where == "device":
        # the classes stayed on the device: only each chunk's counts came home
        assert set(fetched) == {3 * 8} and len(guards) == len(fetched)
    else:
        assert not guards
    return events


# -- the cases ----------------------------------------------------------------------

def no_collision(tmp_path, monkeypatch):
    old, new = revisions(5_000, seed=1, n_upd=300, n_del=50, n_ins=70)
    agrees(old, new)


def forged_collision(tmp_path, monkeypatch):
    """A deleted feature and an inserted one given one key (sixteen-bit keys,
    a twin pk of the victim's width) in a repository: the device guard finds
    the pair of two paths and the count goes to the exact path, which
    answers what the plain reference counts."""
    import test_hash_keyed as hk
    from helpers import edit_commit
    from kart_tpu.diff import sidecar
    from kart_tpu.models.paths import hash_feature_rows, msgpack_pk_rows

    monkeypatch.setattr(blocks, "KEY_BITS", 16)
    rng = np.random.default_rng(16)

    def keys(pks):
        return hash_feature_rows(msgpack_pk_rows(np.array(pks, dtype="S36")), hk.ENC).keys

    while True:
        ids = hk.uuids(rng, 50)
        if len(np.unique(keys([s.encode() for s in ids]))) == len(ids):
            break
    victim = ids[7]
    twins = [f"TWIN-{i:031d}".encode() for i in range(1 << 18)]
    twin = twins[int(np.flatnonzero(keys(twins) == keys([victim.encode()])[0])[0])].decode()
    repo = hk.import_repo(tmp_path, ids, monkeypatch)
    edit_commit(repo, hk.DS, inserts=[hk.feature(twin, 1, 0.5)], deletes=[victim])
    head = repo.structure("HEAD").datasets[hk.DS]
    if not sidecar.has_sidecar(repo, head):
        sidecar.build_sidecar(repo, head)
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 28))
    tm.reset()
    tm.enable(metrics=True)
    try:
        assert hk.cli_count(repo, hk.DEVICE) == hk.reference_count(repo, "HEAD^", "HEAD") == 2
        counters = dict(tm.counters_snapshot())
    finally:
        tm.reset()
    # the count route's guard on the device; the delta path's, which wants
    # the classes home anyway, on the host
    assert route(counters) == {"device": 1, "host": 1}
    assert {dict(k)["why"]: v for (n, k), v in counters.items()
            if n == "diff.hash_guard.fallbacks"} == {"across": 2}


def several_chunks(tmp_path, monkeypatch):
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", CHUNK)
    old, new = revisions(35_000, seed=3, n_upd=2_000, n_del=300, n_ins=400, forged=2)
    events = agrees(old, new)
    guards = [e["args"] for e in events if e["name"] == "diff.device.guard"]
    assert [g["chunk"] for g in guards] == [0, 1, 2, 3]
    assert all(g["cap"] >= g["updates"] for g in guards)


def overflow_to_the_sort_join(tmp_path, monkeypatch):
    """The windowed join (interpreted) overflows in the chunk an 800-row
    hole lies in: that chunk's classes are the sort-join's, and the guard
    reads those."""
    from kart_tpu import runtime

    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", CHUNK)
    monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    old, new = revisions(35_000, seed=4, n_upd=900, n_del=0, n_ins=0,
                         hole=(12_000, 12_800))
    events = agrees(old, new)
    kernels = [e["args"] for e in events if e["name"] == "diff.device.kernel"]
    assert [k["program"] for k in kernels] == ["window_join", "sort_join",
                                               "window_join", "window_join"]


def pages_evicted_and_a_small_budget(tmp_path, monkeypatch):
    """The path pages a call left are found by the next; evicted between two
    calls they are put again; under a budget below one revision's path
    bytes they are not kept at all — the verdict the same each time."""
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", CHUNK)
    old, new = revisions(35_000, seed=5, n_upd=1_500, n_del=200, n_ins=200)

    def path_pages(store):
        return [k for k in store.keys() if k[1] == "paths"]

    store = resident.PageStore(budget_bytes=1 << 28)
    monkeypatch.setattr(resident, "PAGES", store)
    agrees(old, new)
    kept = path_pages(store)
    assert {k[0] for k in kept} == {old.tree_oid, new.tree_oid}
    assert len(kept) == 2 * 4  # four pages a revision of 35,000 rows
    agrees(old, new)  # a hit
    assert path_pages(store) == kept
    store.discard(kept)
    agrees(old, new)
    assert sorted(path_pages(store)) == sorted(kept)

    small = resident.PageStore(budget_bytes=old.count * WIDTH // 2)
    monkeypatch.setattr(resident, "PAGES", small)
    agrees(old, new)
    assert len(path_pages(small)) < len(kept) // 2


def two_widths(tmp_path, monkeypatch):
    """One inserted path four bytes wider: the path column has no single
    stride, so the classes come home and the host guard answers."""
    old, new = revisions(5_000, seed=6, n_upd=200, n_del=20, n_ins=30, wide=1)
    assert diff_kernel.path_word_view(old) is not None
    assert diff_kernel.path_word_view(new) is None
    agrees(old, new, where="host")


CASES = {
    f.__name__: f
    for f in (no_collision, forged_collision, several_chunks, overflow_to_the_sort_join,
              pages_evicted_and_a_small_budget, two_widths)
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_guard_agrees_with_the_host_guard(case, tmp_path, monkeypatch):
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    monkeypatch.setattr(resident, "PAGES", resident.PageStore(budget_bytes=1 << 28))
    CASES[case](tmp_path, monkeypatch)
