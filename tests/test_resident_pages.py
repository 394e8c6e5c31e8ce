"""A revision's key and oid columns stay on the device (ISSUE 38): the
device classify reads pages keyed by the feature tree's oid
(``kart_tpu/ops/resident.py``) and a call ships only the pages the device
does not hold. Everything here runs the production route forced onto
XLA-CPU (the sort-join entry through the same pages), at a small chunk
size so that a call has several chunks and a revision several pages."""

import threading

import numpy as np
import pytest

from kart_tpu import telemetry as tm
from kart_tpu.ops import diff_kernel, resident
from kart_tpu.ops.blocks import PAD_KEY, FeatureBlock, bucket_body, bucket_size
from kart_tpu.ops.diff_kernel import (
    DELETE,
    INSERT,
    UPDATE,
    classify_blocks,
    classify_blocks_reference,
    classify_chunk_plan,
    page_rows,
)

_CHUNK = 10_240  # on the bucket grid: a page is a full chunk's rows


@pytest.fixture
def store(monkeypatch):
    """The device route forced, a small chunk, and a store of this test's
    own with room for everything (``store.budget`` shrinks it)."""
    monkeypatch.setenv("KART_DIFF_DEVICE", "1")
    monkeypatch.setattr(diff_kernel, "CLASSIFY_CHUNK_ROWS", _CHUNK)
    pages = resident.PageStore(budget_bytes=1 << 30)
    monkeypatch.setattr(resident, "PAGES", pages)
    tm.reset()
    tm.enable(metrics=True, trace=True)
    yield pages
    tm.reset()


def _revision(keys, oids, name):
    """A block as ``sidecar.load_block`` hands it over: unpadded columns
    and the feature tree's oid."""
    return FeatureBlock(
        np.ascontiguousarray(keys, dtype=np.int64),
        np.ascontiguousarray(oids, dtype=np.uint32),
        None, len(keys), tree_oid=name and name * 40,
    )


def _base(n, seed, name="a", stride=3):
    rng = np.random.default_rng(seed)
    keys = np.arange(n, dtype=np.int64) * stride + 5
    return _revision(keys, rng.integers(0, 2**32, (n, 5), dtype=np.uint32), name)


def _rewritten(block, name, every=9):
    oids = block.oids.copy()
    oids[::every, 0] ^= 0x80000001
    return _revision(block.keys, oids, name)


def _churned(block, name, fraction, seed):
    """``fraction`` of the rows deleted and as many keys inserted between
    the old ones, uniformly; every eleventh survivor rewritten."""
    rng = np.random.default_rng(seed)
    keep = rng.random(block.count) >= fraction
    keys, oids = block.keys[keep], block.oids[keep].copy()
    oids[::11, 1] ^= 1
    fresh = rng.choice(block.keys[:-1], int(fraction * block.count), replace=False) + 1
    fresh = np.setdiff1d(fresh, block.keys)  # keys are unique within a revision
    keys = np.concatenate([keys, fresh])
    oids = np.concatenate(
        [oids, rng.integers(0, 2**32, (len(fresh), 5), dtype=np.uint32)]
    )
    order = np.argsort(keys, kind="stable")
    return _revision(keys[order], oids[order], name)


def _without(block, name, lo, hi):
    keep = np.ones(block.count, dtype=bool)
    keep[lo:hi] = False
    return _revision(block.keys[keep], block.oids[keep], name)


#: name -> builder of (old, new); each revision names its tree
CASES = {
    "identical_keys": lambda: (b := _base(35_000, 1), _rewritten(b, "b")),
    "uniform_churn": lambda: (b := _base(35_000, 2), _churned(b, "b", 0.05, 3)),
    # 800 consecutive rows gone inside chunk 1: on an accelerator its window
    # overflows; on any backend the pages of the two sides drift apart
    "bulk_hole": lambda: (b := _base(35_000, 4), _without(b, "b", 12_000, 12_800)),
    # the old side ends in the new side's second page
    "a_side_runs_out": lambda: (_base(12_000, 5), _churned(_base(33_000, 5), "b", 0.02, 6)),
    "one_chunk": lambda: (b := _base(5_000, 7), _churned(b, "b", 0.05, 8)),
    # a revision of one small page against one of several: its chunk is
    # longer than the page it is cut from
    "small_against_large": lambda: (_base(3_000, 9), _churned(_base(30_000, 9), "b", 0.02, 10)),
}


def _classify(old, new):
    """One call under a ``diff.classify`` span -> (classes and counts, the
    span's attributes, events by name, counters by name and labels)."""
    with tm.span("diff.classify"):
        answer = classify_blocks(old, new)
    events = tm.drain_events()
    (classify,) = [e["args"] for e in events if e["name"] == "diff.classify"]
    return answer, classify, events, tm.counters_snapshot()


def _refusal():
    """What the runtime raises when the device has no room: its one
    exception type, the status as the message's code name."""
    import jax

    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Attempting to allocate 28.00M. That was not possible."
    )


def _counter(counters, name, **labels):
    return counters.get((name, tuple(sorted(labels.items()))), 0)


def _assert_is_the_reference(answer, old, new):
    old_class, new_class, counts = answer
    ref_old, ref_new = classify_blocks_reference(old, new)
    np.testing.assert_array_equal(old_class, ref_old)
    np.testing.assert_array_equal(new_class, ref_new)
    assert counts == {
        "inserts": int(np.sum(ref_new == INSERT)),
        "updates": int(np.sum(ref_old == UPDATE)),
        "deletes": int(np.sum(ref_old == DELETE)),
    }


def _rows_bytes(*blocks):
    return sum(28 * b.count for b in blocks)


@pytest.mark.parametrize("route", ["sort", "window"])
@pytest.mark.parametrize("case", list(CASES))
def test_cold_warm_and_evicted_calls_equal_the_reference(case, route, store, monkeypatch):
    """Classes and counts equal the numpy reference on a cold call (every
    page put), on the warm call after it (nothing put, every page found)
    and on the call after the store was emptied — never by way of the host
    engine. ``sort`` is the route as XLA-CPU takes it, ``window`` the
    accelerator's (the backend's name forced, the Pallas kernel
    interpreted: the bulk hole overflows a window there and its chunk is
    answered by the sort-join from the same pages)."""
    from kart_tpu import runtime

    if route == "window":
        monkeypatch.setattr(runtime, "default_backend", lambda: "tpu")
    old, new = CASES[case]()
    total = _rows_bytes(old, new)

    answer, classify, events, _ = _classify(old, new)
    _assert_is_the_reference(answer, old, new)
    assert (classify["input_bytes"], classify["resident_bytes"]) == (total, 0)
    transfers = [e["args"] for e in events if e["name"] == "diff.device.transfer"]
    assert len(transfers) == len(classify_chunk_plan(old, new))
    assert sum(t["resident"] for t in transfers) == 0
    cold_put = sum(t["bytes"] for t in transfers)
    # each row shipped once, and of padding at most one grid step a column
    assert total <= cold_put <= total + 2 * 28 * bucket_size(_CHUNK) // 8
    kept = store.resident_bytes()
    assert kept >= total

    answer, classify, events, counters = _classify(old, new)
    _assert_is_the_reference(answer, old, new)
    assert (classify["input_bytes"], classify["resident_bytes"]) == (total, total)
    transfers = [e["args"] for e in events if e["name"] == "diff.device.transfer"]
    assert [t["bytes"] for t in transfers] == [0] * len(transfers)
    assert sum(t["resident"] for t in transfers) == len(store.keys())
    # every stage span of the pipeline is still there on a hit
    names = {e["name"] for e in events}
    assert {"diff.device.pack", "diff.device.transfer", "diff.device.kernel",
            "diff.device.fetch"} <= names
    assert ("diff.device.enqueue" in names) == (len(transfers) > 1)
    assert _counter(counters, "diff.device.resident_hit_bytes") == total
    assert _counter(counters, "diff.device.resident_put_bytes") == cold_put
    assert store.resident_bytes() == kept
    overflowed = _counter(counters, "diff.device.join_overflows")
    assert overflowed == (2 if (case, route) == ("bulk_hole", "window") else 0)

    pages = len(store.keys())
    assert store.drop_all("oom") == pages and store.resident_bytes() == 0
    answer, classify, _, counters = _classify(old, new)
    _assert_is_the_reference(answer, old, new)
    assert classify["resident_bytes"] == 0
    assert _counter(counters, "diff.device.resident_put_bytes") == 2 * cold_put
    assert not [k for k in counters if k[0] == "diff.device.fallbacks"]


def test_a_page_put_for_one_diff_serves_the_next(store):
    """B made resident by ``A...B`` is not put again by ``B...C``, though C
    inserts and deletes and so every chunk boundary moves:
    ``diff.device.resident_put_bytes`` rises by C's bytes only."""
    a = _base(35_000, 11, "a")
    b = _churned(a, "b", 0.03, 12)
    c = _churned(b, "c", 0.04, 13)
    assert [rows for rows, *_ in classify_chunk_plan(a, b)] != [
        rows for _, rows, _ in classify_chunk_plan(b, c)
    ]  # B's chunks are cut elsewhere when C is the partner

    answer, classify, _, counters = _classify(a, b)
    _assert_is_the_reference(answer, a, b)
    first_put = _counter(counters, "diff.device.resident_put_bytes")
    assert first_put >= _rows_bytes(a, b)

    answer, classify, events, counters = _classify(b, c)
    _assert_is_the_reference(answer, b, c)
    assert classify["input_bytes"] == _rows_bytes(b, c)
    assert classify["resident_bytes"] == _rows_bytes(b)
    put = _counter(counters, "diff.device.resident_put_bytes") - first_put
    assert _rows_bytes(c) <= put <= _rows_bytes(c) + 28 * bucket_size(_CHUNK) // 8
    assert _counter(counters, "diff.device.resident_hit_bytes") == _rows_bytes(b)
    assert sum(
        e["args"]["bytes"] for e in events if e["name"] == "diff.device.transfer"
    ) == put
    assert {key[0] for key in store.keys()} == {"a" * 40, "b" * 40, "c" * 40}


def test_a_block_without_identity_retains_nothing(store):
    """The filtered route's survivors, a test's arrays: the same code, the
    same answer, every page put each time and none kept — the gauge returns
    to its value."""
    a = _base(35_000, 14, "a")
    _classify(a, _rewritten(a, "b"))
    before = store.resident_bytes()
    keys_before = store.keys()
    assert before > 0

    old = _base(30_000, 15, name=None)
    new = _churned(old, None, 0.05, 16)
    assert old.tree_oid is None and new.tree_oid is None
    for _ in range(2):
        answer, classify, events, counters = _classify(old, new)
        _assert_is_the_reference(answer, old, new)
        assert classify["input_bytes"] == _rows_bytes(old, new)
        assert classify["resident_bytes"] == 0
        put = sum(e["args"]["bytes"] for e in events if e["name"] == "diff.device.transfer")
        assert put >= _rows_bytes(old, new)
    assert store.resident_bytes() == before and store.keys() == keys_before
    assert _counter(counters, "diff.device.resident_hit_bytes") == 0


def test_budget_eviction_is_lru_and_never_takes_a_pinned_page():
    """The store alone: least recently used first, pinned pages stay, and a
    page that finds no room is not kept."""
    import jax

    page = lambda: jax.device_put(np.zeros(1000, dtype=np.int64))  # 8,000 B
    store = resident.PageStore(budget_bytes=3 * 8000)
    keys = [resident.page_key("t" * 40, "keys", p, 1000) for p in range(5)]
    tm.reset()
    tm.enable(metrics=True)
    try:
        for key in keys[:3]:
            _, kept = store.keep(key, page())
            assert kept
        store.unpin(keys[:3])
        assert store.resident_bytes() == 24_000

        assert store.pin(keys[0]) is not None  # now the most recent, and pinned
        _, kept = store.keep(keys[3], page())  # evicts the oldest unpinned: 1
        assert kept and store.keys() == [keys[2], keys[0], keys[3]]
        store.unpin([keys[3]])
        assert store.pin(keys[2]) is not None
        _, kept = store.keep(keys[4], page())  # 0 and 2 pinned: 3 goes
        assert kept and store.keys() == [keys[0], keys[2], keys[4]]
        # all three pinned now: nothing can go, the page is not kept
        array, kept = store.keep(keys[1], page())
        assert not kept and array is not None
        assert store.keys() == [keys[0], keys[2], keys[4]]
        assert store.resident_bytes() == 24_000
        store.unpin([keys[0], keys[2], keys[4]])
        _, kept = store.keep(keys[1], page())
        assert kept and store.keys() == [keys[2], keys[4], keys[1]]
        counters = tm.counters_snapshot()
        assert _counter(counters, "diff.device.resident_evictions", why="budget") == 3
        gauges = {name: v for name, _, v in tm.snapshot()["gauges"]}
        assert gauges["diff.device.resident_bytes"] == 24_000
    finally:
        tm.reset()


def test_a_call_larger_than_the_budget_streams(store):
    """Pages of the running call are pinned, so a call whose pages do not
    fit keeps the first of them and streams the rest: the answer is the
    reference's, and the next call finds what was kept."""
    store._budget = _CHUNK * (4 * 8 + 2 * 20)  # four key pages, two oid pages
    old, new = CASES["uniform_churn"]()
    answer, classify, _, _ = _classify(old, new)
    _assert_is_the_reference(answer, old, new)
    assert 0 < store.resident_bytes() <= store._budget
    kept = store.keys()
    answer, classify, _, counters = _classify(old, new)
    _assert_is_the_reference(answer, old, new)
    assert 0 < classify["resident_bytes"] < classify["input_bytes"]
    assert store.keys() == kept  # pinned by the call that reads them
    assert _counter(counters, "diff.device.resident_evictions", why="budget") == 0


def test_allocation_failure_empties_the_store_and_answers_on_the_device(store, monkeypatch):
    """The device refuses an allocation while resident pages exist: the
    store is emptied, the call is made once more and answers from the
    device — counted as evictions, not as a fallback."""
    a = _base(35_000, 17, "a")
    _classify(a, _rewritten(a, "b"))
    pages_before = len(store.keys())
    assert pages_before

    c = _churned(a, "c", 0.05, 18)
    real = diff_kernel.classify_blocks_streamed
    calls = []

    def refusing_once(old, new):
        calls.append(1)
        if len(calls) == 1:
            raise _refusal()
        return real(old, new)

    monkeypatch.setattr(diff_kernel, "classify_blocks_streamed", refusing_once)
    answer, _, _, counters = _classify(a, c)
    _assert_is_the_reference(answer, a, c)
    assert len(calls) == 2
    assert _counter(counters, "diff.device.resident_evictions", why="oom") == pages_before
    assert not [k for k in counters if k[0] == "diff.device.fallbacks"]
    assert {key[0] for key in store.keys()} == {"a" * 40, "c" * 40}

    # with nothing resident there is nothing to let go: the host rung
    store.drop_all("oom")
    calls.clear()
    monkeypatch.setattr(
        diff_kernel, "classify_blocks_streamed",
        lambda old, new: (_ for _ in ()).throw(_refusal()),
    )
    answer, _, _, counters = _classify(a, c)
    _assert_is_the_reference(answer, a, c)
    assert _counter(counters, "diff.device.fallbacks", what="device_classify") == 1


def _refusing_once(real):
    calls = []

    def program(*args, **kwargs):
        calls.append(1)
        if len(calls) == 1:
            raise _refusal()
        return real(*args, **kwargs)

    return program, calls


def _merge_triple(n=3000, seed=3):
    anc = _base(n, seed, None)
    ours = FeatureBlock(anc.keys, anc.oids.copy(), None, anc.count)
    theirs = FeatureBlock(anc.keys, anc.oids.copy(), None, anc.count)
    ours.oids[::10, 0] ^= 1
    theirs.oids[::10, 0] ^= 2
    return anc, ours, theirs


def _merge_rung(monkeypatch, broken, device, sharded):
    from kart_tpu.diff.backend import merge_classify

    blocks = _merge_triple()
    monkeypatch.setenv("KART_DIFF_DEVICE", "0")
    monkeypatch.setenv("KART_DIFF_SHARDED", "0")
    want = merge_classify(*blocks)
    monkeypatch.setenv("KART_DIFF_DEVICE", device)
    monkeypatch.setenv("KART_DIFF_SHARDED", sharded)

    def check(got):
        for g, w in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(g, w)
        assert got[3] == want[3] and got[3]["conflicts"] == 300

    return broken, lambda: merge_classify(*blocks), check


def _mesh_classify_rung(monkeypatch, counts_only):
    from kart_tpu.diff.backend import BACKENDS

    old = _base(30_000, 31, None)
    new = _churned(old, None, 0.05, 32)
    want = classify_blocks_reference(old, new)

    def check(got):
        counts = got if counts_only else got[2]
        assert counts["updates"] == int(np.sum(want[0] == UPDATE))
        assert counts["inserts"] == int(np.sum(want[1] == INSERT))

    backend = BACKENDS["sharded_jax"]
    run = backend.counts if counts_only else backend.classify
    return (
        "kart_tpu.diff.device_batch.classify_blocks_batched",
        lambda: run(old, new),
        check,
    )


def _bbox_rung(monkeypatch):
    from kart_tpu import routing
    from kart_tpu.ops import bbox

    rng = np.random.default_rng(33)
    west = rng.uniform(-170, 160, 4096)
    south = rng.uniform(-80, 70, 4096)
    envelopes = np.stack([west, south, west + 1, south + 1], axis=1)
    query = np.array([0.0, 0.0, 50.0, 40.0])
    want = bbox._bbox_host(envelopes, query)
    monkeypatch.setattr(routing, "runtime_ready", lambda n, floor: True)
    return (
        "kart_tpu.ops.bbox.bbox_intersects_jnp",
        lambda: bbox.bbox_intersects(envelopes, query),
        lambda got: np.testing.assert_array_equal(got, want),
    )


RUNGS = {
    "merge_on_one_device": lambda mp: _merge_rung(
        mp, "kart_tpu.ops.diff_kernel.classify_blocks_streamed", "1", "0"
    ),
    "merge_on_the_mesh": lambda mp: _merge_rung(
        mp, "kart_tpu.diff.device_batch.classify_blocks_batched", "0", "1"
    ),
    "mesh_classify": lambda mp: _mesh_classify_rung(mp, False),
    "mesh_counts": lambda mp: _mesh_classify_rung(mp, True),
    "envelope_scan": _bbox_rung,
}


@pytest.mark.parametrize("rung", list(RUNGS))
def test_every_device_rung_lets_the_pages_go_before_it_falls_back(
    rung, store, monkeypatch
):
    """The store is the process's: where another device program is refused
    an allocation while the classify's pages are resident, they are let go
    and that program runs once more and answers from the device — counted
    as evictions, and no rung is taken."""
    import importlib

    a = _base(35_000, 29, "a")
    _classify(a, _rewritten(a, "b"))
    pages_before = len(store.keys())
    assert pages_before

    broken, run, check = RUNGS[rung](monkeypatch)
    module, name = broken.rsplit(".", 1)
    module = importlib.import_module(module)
    program, calls = _refusing_once(getattr(module, name))
    monkeypatch.setattr(module, name, program)
    tm.reset()
    tm.enable(metrics=True)
    check(run())
    counters = tm.counters_snapshot()
    # the refused call and the call made again; a merge's second diff once
    assert len(calls) == (3 if rung.startswith("merge") else 2)
    assert store.keys() == [] and store.resident_bytes() == 0
    assert _counter(counters, "diff.device.resident_evictions", why="oom") == pages_before
    assert not [k for k in counters if k[0] == "diff.device.fallbacks"]


def test_only_a_refused_allocation_with_pages_resident_is_tried_again(store):
    """Any other failure, a refusal with nothing to let go, and the second
    call's failure are the caller's."""
    a = _base(35_000, 30, "a")

    def failing(with_what, calls):
        def call():
            calls.append(1)
            raise with_what

        return call

    _classify(a, _rewritten(a, "b"))
    kept = store.keys()
    calls = []
    with pytest.raises(RuntimeError, match="went away"):
        resident.with_pages_let_go(failing(RuntimeError("the device went away"), calls))
    # the status in another exception's text is not the runtime's refusal
    with pytest.raises(ValueError):
        resident.with_pages_let_go(failing(ValueError("RESOURCE_EXHAUSTED"), calls))
    assert len(calls) == 2 and store.keys() == kept

    calls.clear()
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        resident.with_pages_let_go(failing(_refusal(), calls))
    assert len(calls) == 2 and store.keys() == []  # let go, tried again, failed again

    calls.clear()
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        resident.with_pages_let_go(failing(_refusal(), calls))
    assert len(calls) == 1  # nothing resident: nothing to let go


def test_a_failed_call_forgets_the_pages_it_put(store, monkeypatch):
    """A copy may never have landed when a call fails mid-way: what it put
    is dropped from the store, what was there before stays."""
    a = _base(35_000, 19, "a")
    b = _rewritten(a, "b")
    _classify(a, b)
    before = store.keys()
    c = _churned(a, "c", 0.05, 20)

    real = diff_kernel._classify_split
    seen = []

    def third_chunk_fails(*args, **kwargs):
        seen.append(1)
        if len(seen) == 3:
            raise RuntimeError("the device went away")
        return real(*args, **kwargs)

    monkeypatch.setattr(diff_kernel, "_classify_split", third_chunk_fails)
    with pytest.raises(RuntimeError, match="went away"):
        diff_kernel.classify_blocks_streamed(a, c)
    assert sorted(store.keys()) == sorted(before)
    assert store.resident_bytes() == sum(
        _CHUNK * (8 if key[1] == "keys" else 20) for key in before
    )
    assert all(entry[1] == 0 for entry in store._pages.values())  # nothing left pinned


def test_a_warm_call_compiles_nothing(store):
    """Hit or miss, a chunk runs the same programs: after a cold call the
    warm one compiles nothing (``jax.monitoring`` durations, as
    ``benchmarks/run.py CompileLog``) — the benchmark's first window command
    after a set-up miss."""
    import jax

    compiled, listening = [], [True]

    def on_duration(event, duration, **kwargs):
        if listening and event == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kwargs.get("fun_name")))

    # (a listener cannot be taken off again: it goes deaf when the test ends)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        # a shape of its own, so that the cold call is seen to compile
        old = _base(37_000, 21, "a")
        new = _churned(old, "b", 0.05, 22)
        _classify(old, new)
        assert any("_classify_mergesort_core_split" in name for name in compiled)
        compiled.clear()
        answer, classify, _, _ = _classify(old, new)
        _assert_is_the_reference(answer, old, new)
        assert classify["resident_bytes"] == classify["input_bytes"]
        assert compiled == []
    finally:
        listening.clear()


def test_two_threads_classifying_the_same_pair_agree_with_the_reference(store):
    """One lock around the map and immutable pages: two threads that miss
    and hit the same pages at once both answer as the reference does, and
    the store ends with each page once and nothing pinned."""
    import sys

    old, new = CASES["uniform_churn"]()
    ref_old, ref_new = classify_blocks_reference(old, new)
    results, errors = {}, []

    def work(i):
        try:
            for _ in range(3):
                results[i] = classify_blocks(old, new)
        except Exception as e:  # the assertion below reports it
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    for old_class, new_class, _ in results.values():
        np.testing.assert_array_equal(old_class, ref_old)
        np.testing.assert_array_equal(new_class, ref_new)
    keys = store.keys()
    assert len(keys) == len(set(keys)) == 2 * sum(
        -(-b.count // page_rows(b.count)) for b in (old, new)
    )
    assert all(entry[1] == 0 for entry in store._pages.values())


def test_the_last_page_is_made_whole_on_the_device(store):
    """A revision's last, partial page is put as a body view and one padded
    grid step and reaches the store as a page of the revision's page rows,
    padding from its count on; the padding page stands in past its end."""
    old, new = CASES["identical_keys"]()
    _classify(old, new)
    rows = page_rows(old.count)
    assert rows == _CHUNK
    last = old.count // rows
    have = old.count - last * rows
    keys_page = np.asarray(store.pin(resident.page_key(old.tree_oid, "keys", last, rows)))
    oids_page = np.asarray(store.pin(resident.page_key(old.tree_oid, "oids", last, rows)))
    assert keys_page.shape == (rows,) and oids_page.shape == (rows, 5)
    np.testing.assert_array_equal(keys_page[:have], old.keys[last * rows :])
    np.testing.assert_array_equal(oids_page[:have], old.oids[last * rows :])
    assert np.all(keys_page[have:] == PAD_KEY) and not np.any(oids_page[have:])
    size = bucket_size(have)
    assert size - bucket_body(size) < rows  # what the host copied of it


def test_load_block_stamps_the_feature_trees_oid(tmp_path):
    """``sidecar.load_block`` / ``ensure_block`` name the tree their columns
    are the sidecar of, padded or not; a block made any other way has no
    identity."""
    from kart_tpu.diff import sidecar
    from kart_tpu.synth import synth_repo

    repo, _ = synth_repo(str(tmp_path / "repo"), 300)
    ds = repo.datasets("HEAD")["synth"]
    for pad in (True, False):
        block = sidecar.ensure_block(repo, ds, pad=pad)
        assert block.tree_oid == ds.feature_tree.oid
        assert sidecar.load_block(repo, ds, pad=pad).tree_oid == ds.feature_tree.oid
    assert FeatureBlock.from_arrays(
        block.keys[:10].copy(), block.oids[:10].copy(), [None] * 10
    ).tree_oid is None
