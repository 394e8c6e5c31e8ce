"""Fault-tolerant transport: the KART_FAULTS injection matrix, retry with
capped backoff, resumable fetch (remainder-only re-transfer), receive-pack
quarantine (a torn/rejected push leaves the server store byte-identical),
hung-transport watchdogs, and stale-crash-leftover sweeping.

The production claims these tests pin down: a transfer killed at *any*
frame boundary leaves an fsck-clean store and resumes on retry shipping
only the missing remainder; a push torn mid-pack changes nothing on the
server; no network verb can hang forever."""

import hashlib
import io
import os
import threading
import time

import pytest

from kart_tpu import faults, transport
from kart_tpu.core.objects import hash_object
from kart_tpu.core.repo import KartRepo
from kart_tpu.transport.http import HttpRemote, HttpTransportError, make_server
from kart_tpu.transport.pack import PackFormatError, write_pack
from kart_tpu.transport.remote import FETCH_RESUME_FILE, RemoteError
from kart_tpu.transport.retry import (
    RetryPolicy,
    drain_pack_salvaging,
    is_transient,
)

from helpers import edit_commit, make_imported_repo

pytestmark = pytest.mark.faults


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def fsck_objects(repo):
    """Every object physically in the store parses and hashes to its name
    (the object-store half of `kart fsck`). -> object count."""
    count = 0
    for oid in repo.odb.iter_oids():
        obj_type, content = repo.odb.read_raw(oid)
        assert hash_object(obj_type, content) == oid, f"corrupt object {oid}"
        count += 1
    return count


def store_snapshot(repo):
    """{relpath: sha256} of every file under the repo's objects dir —
    byte-identical means equal snapshots."""
    objects_dir = repo.odb.objects_dir
    snap = {}
    for dirpath, _, filenames in os.walk(objects_dir):
        for fn in filenames:
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                snap[os.path.relpath(p, objects_dir)] = hashlib.sha256(
                    f.read()
                ).hexdigest()
    return snap


@pytest.fixture()
def served_repo(tmp_path):
    """A two-commit points repo served over in-thread localhost HTTP."""
    repo, ds_path = make_imported_repo(tmp_path, n=6)
    edit_commit(
        repo,
        ds_path,
        updates=[{"fid": 1, "geom": None, "name": "renamed", "rating": 9.0}],
        message="second commit",
    )
    repo.config["receive.denyCurrentBranch"] = "ignore"
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}/"
    yield repo, ds_path, url
    server.shutdown()
    server.server_close()


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Fault tests must not sleep through real backoff."""
    monkeypatch.setenv("KART_TRANSPORT_RETRY_BASE", "0.01")
    monkeypatch.setenv("KART_TRANSPORT_RETRY_CAP", "0.05")
    monkeypatch.delenv("KART_FAULTS", raising=False)


# ---------------------------------------------------------------------------
# faults.py unit
# ---------------------------------------------------------------------------


def test_fault_hook_unarmed_is_none(monkeypatch):
    monkeypatch.delenv("KART_FAULTS", raising=False)
    assert faults.hook("transport.read.frame") is None


def test_fault_fires_on_nth_hit_then_disarms(monkeypatch):
    monkeypatch.setenv("KART_FAULTS", "p.x:3")
    h = faults.hook("p.x")
    h()
    h()
    with pytest.raises(faults.InjectedFault) as exc:
        h()
    assert exc.value.point == "p.x" and exc.value.hit == 3
    # one-shot: a retry after the injected failure sails through
    for _ in range(10):
        h()
    # other points unarmed
    assert faults.hook("p.other") is None


def test_fault_spec_change_resets(monkeypatch):
    monkeypatch.setenv("KART_FAULTS", "p.y:1")
    with pytest.raises(faults.InjectedFault):
        faults.fire("p.y")
    monkeypatch.setenv("KART_FAULTS", "p.y:2")  # new spec: counters reset
    faults.fire("p.y")
    with pytest.raises(faults.InjectedFault):
        faults.fire("p.y")
    assert is_transient(faults.InjectedFault("p.y", 2))  # an OSError


# ---------------------------------------------------------------------------
# retry policy unit
# ---------------------------------------------------------------------------


def test_retry_policy_backoff_capped_exponential():
    sleeps = []
    p = RetryPolicy(attempts=5, base_delay=1.0, max_delay=3.0, sleep=sleeps.append)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 5:
            raise ConnectionResetError("boom")
        return "ok"

    assert p.call(flaky) == "ok"
    assert sleeps == [1.0, 2.0, 3.0, 3.0]  # doubled, then capped


def test_retry_policy_gives_up_and_skips_non_transient():
    sleeps = []
    p = RetryPolicy(attempts=3, base_delay=0.5, sleep=sleeps.append)
    with pytest.raises(ConnectionResetError):
        p.call(lambda: (_ for _ in ()).throw(ConnectionResetError()))
    assert len(sleeps) == 2  # attempts-1 backoffs

    sleeps.clear()
    with pytest.raises(ValueError):  # not transient: no retry at all
        p.call(lambda: (_ for _ in ()).throw(ValueError("deterministic")))
    assert sleeps == []
    # server-reported op errors are explicitly non-transient
    assert not is_transient(HttpTransportError("op failed"))
    assert is_transient(HttpTransportError("conn", transient=True))


def test_retry_policy_from_config_env_precedence(tmp_path, monkeypatch):
    repo = KartRepo.init_repository(tmp_path / "r")
    repo.config.set_many(
        {"remote.origin.retries": "7", "remote.origin.retrybasedelay": "0.5"}
    )
    monkeypatch.delenv("KART_TRANSPORT_RETRY_BASE", raising=False)
    monkeypatch.delenv("KART_TRANSPORT_RETRY_CAP", raising=False)
    p = RetryPolicy.from_config(repo.config, "origin")
    assert p.attempts == 7 and p.base_delay == 0.5
    monkeypatch.setenv("KART_TRANSPORT_RETRIES", "2")
    assert RetryPolicy.from_config(repo.config, "origin").attempts == 2


# ---------------------------------------------------------------------------
# torn packstreams (satellite: truncation + corrupted trailer)
# ---------------------------------------------------------------------------


def _pack_bytes(objects):
    buf = io.BytesIO()
    write_pack(buf, iter(objects))
    return buf.getvalue()


@pytest.fixture()
def empty_repo(tmp_path):
    return KartRepo.init_repository(tmp_path / "dst")


OBJECTS = [("blob", b"alpha"), ("blob", b"beta"), ("blob", b"gamma" * 100)]


def test_truncated_packstream_salvages_and_resumes(empty_repo):
    raw = _pack_bytes(OBJECTS)
    # cut mid-way: some objects land, the rest is gone
    received = set()
    with pytest.raises(PackFormatError):
        drain_pack_salvaging(empty_repo.odb, io.BytesIO(raw[: len(raw) // 2]), received)
    n_salvaged = fsck_objects(empty_repo)  # fsck-clean whatever landed
    assert n_salvaged == len(received) < len(OBJECTS)
    # retry with the full stream succeeds; store complete and clean
    drain_pack_salvaging(empty_repo.odb, io.BytesIO(raw), received)
    assert fsck_objects(empty_repo) == len(OBJECTS)
    for _, content in OBJECTS:
        assert empty_repo.odb.contains(hash_object("blob", content))


def test_corrupt_checksum_trailer_raises_cleanly(empty_repo):
    raw = bytearray(_pack_bytes(OBJECTS))
    raw[-1] ^= 0xFF  # flip a trailer byte: framing checksum mismatch
    with pytest.raises(PackFormatError, match="checksum"):
        drain_pack_salvaging(empty_repo.odb, io.BytesIO(bytes(raw)), set())
    # the records themselves were individually verified: all salvaged, clean
    assert fsck_objects(empty_repo) == len(OBJECTS)
    drain_pack_salvaging(empty_repo.odb, io.BytesIO(_pack_bytes(OBJECTS)), set())
    assert fsck_objects(empty_repo) == len(OBJECTS)  # dedupe: no growth


def test_truncation_before_any_object_leaves_store_empty(empty_repo):
    raw = _pack_bytes(OBJECTS)
    with pytest.raises(PackFormatError):
        drain_pack_salvaging(empty_repo.odb, io.BytesIO(raw[:4]), set())
    assert fsck_objects(empty_repo) == 0
    assert not os.path.isdir(os.path.join(empty_repo.odb.objects_dir, "pack")) or not [
        n
        for n in os.listdir(os.path.join(empty_repo.odb.objects_dir, "pack"))
        if not n.startswith(".")
    ]


# ---------------------------------------------------------------------------
# the fault matrix: fetch killed at every frame boundary, then resumed
# ---------------------------------------------------------------------------


def test_fetch_killed_at_every_frame_boundary_resumes_remainder_only(
    served_repo, tmp_path, monkeypatch
):
    """The acceptance criterion: for every frame boundary N, a fetch_pack
    killed there leaves an fsck-clean partial store, and the retry —
    re-negotiated with the salvaged oids excluded — ships exactly the
    missing remainder (asserted by object counts)."""
    repo, ds_path, url = served_repo

    # ground truth: a clean full fetch
    ref = KartRepo.init_repository(tmp_path / "ref")
    http = HttpRemote(url, retry=RetryPolicy(attempts=1))
    info = http.ls_refs()
    wants = list(info["heads"].values()) + list(info["tags"].values())
    total = http.fetch_pack(ref, wants)["object_count"]
    assert total > 5

    for n in range(1, total + 2):  # +1: the END-record boundary
        dst = KartRepo.init_repository(tmp_path / f"kill{n}")
        client = HttpRemote(url, retry=RetryPolicy(attempts=1))
        monkeypatch.setenv("KART_FAULTS", f"transport.read.frame:{n}")
        with pytest.raises((faults.InjectedFault, PackFormatError)):
            client.fetch_pack(dst, wants)
        monkeypatch.delenv("KART_FAULTS")
        received = fsck_objects(dst)  # salvage is fsck-clean
        assert received == n - 1  # everything before the killed frame landed
        # resume: exclude what we already hold; only the remainder ships
        header = client.fetch_pack(
            dst, wants, exclude=set(dst.odb.iter_oids())
        )
        assert header["object_count"] == total - received
        assert fsck_objects(dst) == total


def test_clone_retries_transparently_through_fault(served_repo, tmp_path, monkeypatch):
    """End-to-end: with retry enabled (the default), a mid-transfer
    disconnect is invisible — clone just succeeds, resumed."""
    repo, ds_path, url = served_repo
    monkeypatch.setenv("KART_FAULTS", "transport.read.frame:5")
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    assert clone.head_commit_oid == repo.head_commit_oid
    assert len(list(clone.datasets("HEAD")[ds_path].features())) == 6
    fsck_objects(clone)
    # transfer completed: the resume marker is gone
    assert clone.read_gitdir_file(FETCH_RESUME_FILE) is None


def test_interrupted_clone_kept_and_resumed_by_fetch(
    served_repo, tmp_path, monkeypatch
):
    """A clone whose transfer dies (with retries exhausted) keeps the
    partial repo + FETCH_RESUME marker — `kart fetch` resumes it instead of
    restarting from zero."""
    repo, ds_path, url = served_repo
    monkeypatch.setenv("KART_TRANSPORT_RETRIES", "1")  # no auto-retry
    monkeypatch.setenv("KART_FAULTS", "transport.read.frame:6")
    directory = tmp_path / "partial"
    with pytest.raises(RemoteError, match="resume"):
        transport.clone(url, directory, do_checkout=False)
    monkeypatch.delenv("KART_FAULTS")

    resumed = KartRepo(str(directory))
    marker = resumed.read_gitdir_file(FETCH_RESUME_FILE)
    assert marker is not None
    salvaged = fsck_objects(resumed)
    assert salvaged == 5
    # the marker records remote + the salvaged oids, so resume doesn't
    # rescan the store
    lines = marker.splitlines()
    assert lines[0] == "origin"
    assert sorted(lines[1:]) == sorted(resumed.odb.iter_oids())

    updated = transport.fetch(resumed, "origin")
    assert updated.get("refs/remotes/origin/main") == repo.head_commit_oid
    assert resumed.read_gitdir_file(FETCH_RESUME_FILE) is None
    assert fsck_objects(resumed) == fsck_objects(repo)


# ---------------------------------------------------------------------------
# receive-pack quarantine
# ---------------------------------------------------------------------------


def quarantine_entries(repo):
    q = os.path.join(repo.odb.objects_dir, "quarantine")
    return os.listdir(q) if os.path.isdir(q) else []


def test_torn_push_leaves_server_store_byte_identical(
    served_repo, tmp_path, monkeypatch
):
    """The acceptance criterion: a push killed mid-pack changes nothing on
    the server — no new loose objects, no new packs, no ref movement, no
    quarantine debris — and succeeds when retried."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    new_oid = edit_commit(clone, ds_path, deletes=[2], message="to push")

    before = store_snapshot(repo)
    ref_before = repo.refs.get("refs/heads/main")
    # the server's quarantine drain is the only read_pack in a push flow
    monkeypatch.setenv("KART_FAULTS", "transport.read.frame:2")
    with pytest.raises(RemoteError):
        transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")

    assert store_snapshot(repo) == before
    assert repo.refs.get("refs/heads/main") == ref_before
    assert quarantine_entries(repo) == []
    fsck_objects(repo)

    # retried push succeeds and lands exactly the new objects
    assert transport.push(clone, "origin") == {"refs/heads/main": new_oid}
    assert repo.refs.get("refs/heads/main") == new_oid
    assert repo.odb.contains(new_oid)
    assert quarantine_entries(repo) == []


def test_rejected_push_leaves_server_store_byte_identical(served_repo, tmp_path):
    """A push failing its preconditions (the contended rebase hits real
    conflicts) discards the quarantine: the server store holds no trace of
    the rejected objects — not even the classifier's scratch trees or the
    quarantine temp ref."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    edit_commit(
        repo, ds_path,
        updates=[{"fid": 4, "geom": None, "name": "srv", "rating": 1.0}],
        message="upstream moved",
    )
    local_oid = edit_commit(
        clone, ds_path,
        updates=[{"fid": 4, "geom": None, "name": "loc", "rating": 2.0}],
        message="local change",
    )

    before = store_snapshot(repo)
    with pytest.raises(RemoteError, match="conflict"):
        transport.push(clone, "origin")
    assert store_snapshot(repo) == before
    assert not repo.odb.contains(local_oid)
    assert quarantine_entries(repo) == []


# ---------------------------------------------------------------------------
# contended-push rebase kill matrix (ISSUE 9: server.rebase / server.ref_cas)
# ---------------------------------------------------------------------------


def _contended_push_setup(served_repo, tmp_path, name):
    """A clone whose push will lose the CAS: the server tip moves (disjoint
    edit) after the clone, so landing the push requires the server-side
    rebase. -> (clone, its local commit oid, the moved server tip)."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / name, do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    local_oid = edit_commit(clone, ds_path, deletes=[5], message="contender")
    moved_tip = edit_commit(repo, ds_path, deletes=[4], message="tip moved")
    return clone, local_oid, moved_tip


@pytest.mark.parametrize("frame", [1, 2, 3])
def test_rebase_killed_at_every_frame_leaves_store_byte_identical(
    served_repo, tmp_path, monkeypatch, frame
):
    """ISSUE 9 acceptance: a crash at ANY frame of the server-side rebase —
    1 = ancestry/classifier run, 2 = merge-commit write, 3 = quarantine
    temp-ref write — discards the quarantine: live store byte-identical,
    refs unmoved, zero quarantine debris; the client simply re-pushes and
    the (now unarmed) rebase lands both edits."""
    repo, ds_path, url = served_repo
    clone, local_oid, moved_tip = _contended_push_setup(
        served_repo, tmp_path, f"kill{frame}"
    )
    before = store_snapshot(repo)
    monkeypatch.setenv("KART_TRANSPORT_RETRIES", "1")
    monkeypatch.setenv("KART_FAULTS", f"server.rebase:{frame}")
    with pytest.raises(RemoteError, match="InjectedFault"):
        transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")
    monkeypatch.delenv("KART_TRANSPORT_RETRIES")

    assert store_snapshot(repo) == before
    assert repo.refs.get("refs/heads/main") == moved_tip
    assert quarantine_entries(repo) == []
    fsck_objects(repo)

    # resumable: the identical re-push now rebases and lands
    updated = transport.push(clone, "origin")
    tip = repo.refs.get("refs/heads/main")
    assert updated == {"refs/heads/main": tip}
    assert repo.odb.read_commit(tip).parents == (moved_tip, local_oid)
    assert quarantine_entries(repo) == []


@pytest.mark.parametrize("frame", [1, 2])
def test_ref_cas_killed_at_every_frame_leaves_store_byte_identical(
    served_repo, tmp_path, monkeypatch, frame
):
    """server.ref_cas kill matrix: a crash at the locked landing frames —
    1 = the CAS (re-)validation, 2 = just before quarantine migrate —
    leaves the store byte-identical and the push lock released (the
    re-push must not deadlock), and the retried push lands."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / f"cas{frame}", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    new_oid = edit_commit(clone, ds_path, deletes=[5], message="to land")

    before = store_snapshot(repo)
    ref_before = repo.refs.get("refs/heads/main")
    monkeypatch.setenv("KART_TRANSPORT_RETRIES", "1")
    monkeypatch.setenv("KART_FAULTS", f"server.ref_cas:{frame}")
    with pytest.raises(RemoteError, match="InjectedFault"):
        transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")
    monkeypatch.delenv("KART_TRANSPORT_RETRIES")

    assert store_snapshot(repo) == before
    assert repo.refs.get("refs/heads/main") == ref_before
    assert quarantine_entries(repo) == []
    fsck_objects(repo)

    assert transport.push(clone, "origin") == {"refs/heads/main": new_oid}
    assert repo.refs.get("refs/heads/main") == new_oid
    assert quarantine_entries(repo) == []


def test_rebase_kill_then_conflicting_rebase_still_terminal(
    served_repo, tmp_path, monkeypatch
):
    """Sequence the crash with a real conflict: after an injected rebase
    kill, a *conflicting* re-push is rejected terminally (exactly one
    attempt — the retry policy must not re-push a terminal verdict) with
    the store still byte-identical."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "seq", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    edit_commit(
        clone, ds_path,
        updates=[{"fid": 3, "geom": None, "name": "loc", "rating": 2.0}],
        message="contender",
    )
    edit_commit(
        repo, ds_path,
        updates=[{"fid": 3, "geom": None, "name": "srv", "rating": 1.0}],
        message="tip moved",
    )
    monkeypatch.setenv("KART_FAULTS", "server.rebase:1")
    with pytest.raises(RemoteError, match="InjectedFault"):
        transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")
    before = store_snapshot(repo)
    sleeps = []
    from kart_tpu.transport.remote import network_remote

    # count retry sleeps through a custom policy: terminal ⇒ zero retries
    policy = RetryPolicy(attempts=5, base_delay=0.01, sleep=sleeps.append)
    with pytest.raises(RemoteError, match="conflict"):
        clone_url = clone.config.get("remote.origin.url")
        net = network_remote(clone_url, retry=policy)
        try:
            from kart_tpu.transport.remote import _push_network

            _push_network(
                clone, "origin", net, ["main:main"],
                force=False, set_upstream=False,
            )
        finally:
            net.close()
    assert sleeps == []  # terminal: surfaced once, never blindly re-pushed
    assert store_snapshot(repo) == before
    assert quarantine_entries(repo) == []


# ---------------------------------------------------------------------------
# timeouts + watchdog + close
# ---------------------------------------------------------------------------


def test_http_timeout_env(monkeypatch):
    from kart_tpu.transport.http import DEFAULT_HTTP_TIMEOUT, http_timeout

    monkeypatch.delenv("KART_HTTP_TIMEOUT", raising=False)
    assert http_timeout() == DEFAULT_HTTP_TIMEOUT
    monkeypatch.setenv("KART_HTTP_TIMEOUT", "2.5")
    assert http_timeout() == 2.5
    monkeypatch.setenv("KART_HTTP_TIMEOUT", "junk")
    assert http_timeout() == DEFAULT_HTTP_TIMEOUT


def test_http_dead_server_fails_fast(monkeypatch):
    """A server that accepts but never answers must fail in ~the socket
    timeout, not hang forever."""
    import socket

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    monkeypatch.setenv("KART_HTTP_TIMEOUT", "0.5")
    client = HttpRemote(f"http://127.0.0.1:{port}/", retry=RetryPolicy(attempts=1))
    t0 = time.monotonic()
    with pytest.raises(HttpTransportError) as exc:
        client.ls_refs()
    assert time.monotonic() - t0 < 10
    assert exc.value.transient
    srv.close()


def test_receive_pack_retries_only_pre_write(monkeypatch):
    """Connection refused is pre-write (the server saw nothing): the one
    failure mode a non-idempotent push RPC retries."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]  # nothing listens here now

    sleeps = []
    client = HttpRemote(
        f"http://127.0.0.1:{port}/",
        retry=RetryPolicy(attempts=3, base_delay=0.01, sleep=sleeps.append),
    )
    with pytest.raises(HttpTransportError):
        client.receive_pack([], [{"ref": "refs/heads/x", "old": None, "new": None}])
    assert len(sleeps) == 2  # refused ⇒ pre-write ⇒ retried to exhaustion


def _install_sleeper_ssh(tmp_path, monkeypatch):
    """A fake ssh that never speaks the protocol — a hung tunnel."""
    script = tmp_path / "hung-ssh"
    script.write_text("#!/bin/sh\nexec sleep 600\n")
    script.chmod(0o755)
    monkeypatch.setenv("KART_SSH", str(script))


def test_stdio_watchdog_kills_hung_ssh(tmp_path, monkeypatch):
    from kart_tpu.transport.stdio import StdioRemote, StdioTransportError

    _install_sleeper_ssh(tmp_path, monkeypatch)
    monkeypatch.setenv("KART_STDIO_TIMEOUT", "0.5")
    client = StdioRemote("testhost:/srv/repo", retry=RetryPolicy(attempts=1))
    t0 = time.monotonic()
    with pytest.raises(StdioTransportError, match="did not respond"):
        client.ls_refs()
    assert time.monotonic() - t0 < 30
    client.close()


def test_stdio_close_is_bounded_and_idempotent(tmp_path, monkeypatch):
    from kart_tpu.transport.stdio import StdioRemote

    _install_sleeper_ssh(tmp_path, monkeypatch)
    client = StdioRemote("testhost:/srv/repo")
    proc = client._ensure()
    assert proc.poll() is None
    t0 = time.monotonic()
    client.close(timeout=0.5)  # sleep ignores the pipe close: must kill
    assert time.monotonic() - t0 < 10
    assert proc.poll() is not None  # dead and reaped: no zombie
    client.close()  # double-close is a no-op
    client.close(timeout=0.0)
    # and __del__ after close must not raise either
    client.__del__()


# ---------------------------------------------------------------------------
# stale crash-leftover sweep (gc + fsck)
# ---------------------------------------------------------------------------


def test_gc_sweeps_stale_crash_leftovers(tmp_path):
    repo = KartRepo.init_repository(tmp_path / "r")
    gitdir = repo.gitdir
    old = time.time() - 7200

    def make(path, mtime=None, directory=False):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if directory:
            os.makedirs(path, exist_ok=True)
        else:
            with open(path, "w") as f:
                f.write("x")
        if mtime is not None:
            os.utime(path, (mtime, mtime))
        return path

    stale = [
        make(os.path.join(gitdir, "objects", "ab", "cd" * 19 + ".tmp123"), old),
        make(os.path.join(gitdir, "objects", "pack", ".tmp-pack-xyz"), old),
        make(os.path.join(gitdir, "refs", "heads", "main.lock999"), old),
        make(os.path.join(gitdir, "config.lock123"), old),
        make(
            os.path.join(gitdir, "objects", "quarantine", "incoming-dead"),
            old,
            directory=True,
        ),
    ]
    fresh = make(os.path.join(gitdir, "refs", "heads", "topic.lock1"))
    real_ref = make(os.path.join(gitdir, "refs", "heads", "keepme"), old)

    found = set(repo.find_stale_leftovers())
    assert found == set(stale)

    stats = repo.gc()
    assert stats["pruned"] == len(stale)
    for p in stale:
        assert not os.path.exists(p)
    assert os.path.exists(fresh)  # inside the grace period: survives
    assert os.path.exists(real_ref)  # not a temp name: never touched

    # --prune-now ignores the grace period
    stats = repo.gc("--prune-now")
    assert stats["pruned"] == 1
    assert not os.path.exists(fresh)


def test_fsck_reports_stale_leftovers(tmp_path, monkeypatch):
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    repo, _ = make_imported_repo(tmp_path, n=3)
    old = time.time() - 7200
    p = os.path.join(repo.gitdir, "refs", "heads", "main.lock999")
    with open(p, "w") as f:
        f.write("x")
    os.utime(p, (old, old))

    monkeypatch.chdir(repo.workdir)
    r = CliRunner().invoke(cli, ["fsck"])
    assert r.exit_code == 0, r.output  # debris is a warning, not corruption
    assert "stale" in r.output and "main.lock999" in r.output

    r = CliRunner().invoke(cli, ["gc"])
    assert r.exit_code == 0, r.output
    assert not os.path.exists(p)


# ---------------------------------------------------------------------------
# odb / pack finalisation fault points
# ---------------------------------------------------------------------------


def test_fetch_with_server_killed_mid_write_frame_resumes(
    served_repo, tmp_path, monkeypatch
):
    """transport.write.frame kill matrix: the *sender* (here the server
    serialising the fetch pack) dying at a frame boundary surfaces as a
    server-reported op error — deliberately non-transient, so the client
    keeps a resumable partial instead of hammering a broken server, and
    `kart fetch` completes the transfer. The read-side matrix above covers
    the receiver half."""
    repo, ds_path, url = served_repo
    directory = tmp_path / "partial"
    monkeypatch.setenv("KART_FAULTS", "transport.write.frame:4")
    with pytest.raises(RemoteError, match="resume"):
        transport.clone(url, directory, do_checkout=False)
    monkeypatch.delenv("KART_FAULTS")

    resumed = KartRepo(str(directory))
    assert resumed.read_gitdir_file(FETCH_RESUME_FILE) is not None
    salvaged = fsck_objects(resumed)  # whatever landed is fsck-clean
    total = fsck_objects(repo)
    assert salvaged < total
    updated = transport.fetch(resumed, "origin")
    assert updated.get("refs/remotes/origin/main") == repo.head_commit_oid
    assert fsck_objects(resumed) == total
    assert resumed.read_gitdir_file(FETCH_RESUME_FILE) is None


def test_push_killed_mid_write_frame_leaves_server_untouched(
    served_repo, tmp_path, monkeypatch
):
    """transport.write.frame on the push side: the client dying while
    serialising its pack never reaches the wire — the server stays
    byte-identical and a retried push lands the objects."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@example.com"})
    new_oid = edit_commit(clone, ds_path, deletes=[2], message="to push")

    before = store_snapshot(repo)
    ref_before = repo.refs.get("refs/heads/main")
    monkeypatch.setenv("KART_TRANSPORT_RETRIES", "1")  # surface the kill
    monkeypatch.setenv("KART_FAULTS", "transport.write.frame:1")
    with pytest.raises(Exception):
        transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")
    monkeypatch.delenv("KART_TRANSPORT_RETRIES")

    assert store_snapshot(repo) == before
    assert repo.refs.get("refs/heads/main") == ref_before
    assert quarantine_entries(repo) == []
    assert transport.push(clone, "origin") == {"refs/heads/main": new_oid}
    assert repo.refs.get("refs/heads/main") == new_oid


def test_idx_write_fault_leaves_no_half_indexed_pack(tmp_path, monkeypatch):
    """idx.write kill matrix: a crash during idx serialisation (after the
    pack body renamed into place) must leave the pack invisible to readers
    — an unindexed pack is never a source of truth — and the same write
    retried lands cleanly."""
    repo = KartRepo.init_repository(tmp_path / "r")
    monkeypatch.setenv("KART_FAULTS", "idx.write:1")
    with pytest.raises(faults.InjectedFault):
        with repo.odb.bulk_pack():
            repo.odb.write_raw("blob", b"doomed")
    monkeypatch.delenv("KART_FAULTS")
    assert fsck_objects(repo) == 0  # nothing readable landed
    # retry after the injected crash: the identical pack bytes rename over
    # the orphan and this time the idx completes
    with repo.odb.bulk_pack():
        oid = repo.odb.write_raw("blob", b"doomed")
    assert repo.odb.contains(oid)
    assert fsck_objects(repo) == 1


def test_write_raw_fault_leaves_store_unchanged(tmp_path, monkeypatch):
    """odb.write_raw kill matrix: the injection fires at call entry (a
    disk-full / crash before anything lands) — the store is untouched, not
    even debris, and the retried write succeeds."""
    repo = KartRepo.init_repository(tmp_path / "r")
    monkeypatch.setenv("KART_FAULTS", "odb.write_raw:1")
    with pytest.raises(faults.InjectedFault):
        repo.odb.write_raw("blob", b"precious")
    monkeypatch.delenv("KART_FAULTS")
    assert fsck_objects(repo) == 0
    oid = repo.odb.write_raw("blob", b"precious")
    assert repo.odb.contains(oid)
    assert fsck_objects(repo) == 1


def test_bulk_pack_exit_fault_leaves_sweepable_debris(tmp_path, monkeypatch):
    """odb.bulk_pack kill matrix: dying on bulk-context exit — after every
    object was added but before the pack finalises — leaves only
    `.tmp-pack-*` debris the sweeper claims; the retried bulk write lands
    the objects."""
    repo = KartRepo.init_repository(tmp_path / "r")
    monkeypatch.setenv("KART_FAULTS", "odb.bulk_pack:1")
    with pytest.raises(faults.InjectedFault):
        with repo.odb.bulk_pack():
            repo.odb.write_raw("blob", b"doomed")
    monkeypatch.delenv("KART_FAULTS")
    assert fsck_objects(repo) == 0
    pack_dir = os.path.join(repo.odb.objects_dir, "pack")
    leftovers = os.listdir(pack_dir) if os.path.isdir(pack_dir) else []
    assert all(n.startswith(".tmp-pack-") for n in leftovers)
    with repo.odb.bulk_pack():
        oid = repo.odb.write_raw("blob", b"doomed")
    assert repo.odb.contains(oid)
    assert fsck_objects(repo) == 1
    # the sweeper claims exactly the crash debris, nothing else
    assert repo.gc("--prune-now")["pruned"] == len(leftovers)


def test_bulk_pack_finalise_fault_leaves_sweepable_debris(tmp_path, monkeypatch):
    """A crash between pack body and finalisation must leave only temp
    debris the sweeper recognises — never a half-valid pack the reader
    would trust."""
    repo = KartRepo.init_repository(tmp_path / "r")
    monkeypatch.setenv("KART_FAULTS", "pack.finalise:1")
    with pytest.raises(faults.InjectedFault):
        with repo.odb.bulk_pack():
            repo.odb.write_raw("blob", b"doomed")
    monkeypatch.delenv("KART_FAULTS")
    pack_dir = os.path.join(repo.odb.objects_dir, "pack")
    leftovers = os.listdir(pack_dir)
    assert all(n.startswith(".tmp-pack-") for n in leftovers)
    assert fsck_objects(repo) == 0
    # the sweeper claims exactly that debris
    assert repo.gc("--prune-now")["pruned"] == len(leftovers)
    assert os.listdir(pack_dir) == []


# ---------------------------------------------------------------------------
# pipelined-import fault points (import.encode / import.pack_stream)
# ---------------------------------------------------------------------------


def _clean_import_tree(tmp_path, gpkg, name):
    """Root tree of a never-faulted import of ``gpkg`` — the byte-identical
    ground truth the post-fault re-run must reproduce."""
    from kart_tpu.importer import ImportSource
    from kart_tpu.importer.importer import import_sources

    ref = KartRepo.init_repository(tmp_path / name)
    commit_oid = import_sources(ref, ImportSource.open(gpkg))
    return ref.odb.read_commit(commit_oid).tree


def _assert_no_half_written_pack(repo):
    """The crash contract: nothing readable landed and the pack dir holds
    at most sweepable ``.tmp-pack-*`` debris — never a live pack/idx pair
    a reader would trust."""
    assert fsck_objects(repo) == 0
    pack_dir = os.path.join(repo.odb.objects_dir, "pack")
    leftovers = os.listdir(pack_dir) if os.path.isdir(pack_dir) else []
    assert all(n.startswith(".tmp-pack-") for n in leftovers)
    return leftovers


@pytest.mark.parametrize(
    "spec", ["import.encode:1", "import.pack_stream:1"]
)
def test_import_pipeline_stage_kill_is_clean_and_rerunnable(
    tmp_path, monkeypatch, spec
):
    """import.encode / import.pack_stream kill matrix: a pipelined import
    killed in either stage propagates the fault out of every pipeline
    thread, aborts the bulk pack (no half-written pack/idx, HEAD untouched,
    only sweepable debris) — and the same import simply re-run lands a
    tree byte-identical to a never-faulted import."""
    from kart_tpu.importer import ImportSource
    from kart_tpu.importer.importer import import_sources

    from helpers import create_points_gpkg

    gpkg = create_points_gpkg(str(tmp_path / "pts.gpkg"), n=120)
    expected_tree = _clean_import_tree(tmp_path, gpkg, "ref")

    repo = KartRepo.init_repository(tmp_path / "r")
    monkeypatch.setenv("KART_IMPORT_PIPELINE", "1")  # force on a tiny import
    monkeypatch.setenv("KART_FAULTS", spec)  # arms import.encode:1 / import.pack_stream:1
    with pytest.raises(faults.InjectedFault):
        import_sources(repo, ImportSource.open(gpkg))
    monkeypatch.delenv("KART_FAULTS")

    assert repo.head_is_unborn  # the ref update never ran
    leftovers = _assert_no_half_written_pack(repo)
    # cleanly re-runnable: the retried import succeeds on the same repo and
    # reproduces the ground-truth tree bit-for-bit
    commit_oid = import_sources(repo, ImportSource.open(gpkg))
    assert repo.odb.read_commit(commit_oid).tree == expected_tree
    # the sweeper claims exactly the crash debris, nothing else
    assert repo.gc("--prune-now")["pruned"] == len(leftovers)


def test_import_pipeline_generic_source_kill_is_clean(tmp_path, monkeypatch):
    """The same contract on the generic (non-GPKG) pipeline producer: a CSV
    import killed at the pack stream leaves no readable objects and
    re-runs cleanly."""
    from kart_tpu.importer import ImportSource
    from kart_tpu.importer.importer import import_sources

    csv_path = tmp_path / "rows.csv"
    csv_path.write_text(
        "id,name\n" + "".join(f"{i},row-{i}\n" for i in range(1, 90))
    )
    expected_tree = _clean_import_tree(tmp_path, str(csv_path), "ref-csv")

    repo = KartRepo.init_repository(tmp_path / "r2")
    monkeypatch.setenv("KART_IMPORT_PIPELINE", "1")
    # bare point (no :n) so the spec *string* differs from the GPKG matrix
    # above — the faults module resets its one-shot state on spec change
    monkeypatch.setenv("KART_FAULTS", "import.pack_stream")
    with pytest.raises(faults.InjectedFault):
        import_sources(repo, ImportSource.open(str(csv_path)))
    monkeypatch.delenv("KART_FAULTS")
    assert repo.head_is_unborn
    _assert_no_half_written_pack(repo)
    commit_oid = import_sources(repo, ImportSource.open(str(csv_path)))
    assert repo.odb.read_commit(commit_oid).tree == expected_tree


def test_fetch_blobs_retry_refetches_only_missing(served_repo, tmp_path, monkeypatch):
    """Promisor backfill is idempotent: after a torn attempt the retry
    re-requests only the oids that didn't land."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    blob_oids = [
        e.oid
        for _, e in repo.datasets("HEAD")[ds_path].feature_tree.walk_blobs()
    ]
    assert len(blob_oids) >= 3
    dst = KartRepo.init_repository(tmp_path / "blobs")
    client = HttpRemote(url)  # default policy: retries enabled
    monkeypatch.setenv("KART_FAULTS", "transport.read.frame:2")
    fetched = client.fetch_blobs(dst, blob_oids)
    assert fetched == len(set(blob_oids))
    for oid in blob_oids:
        assert dst.odb.contains(oid)


# ---------------------------------------------------------------------------
# sharded diff backend: host->device transfer faults (ISSUE 6)
# ---------------------------------------------------------------------------


def _edited_block_pair(n=3000, seed=13):
    """(old, new) FeatureBlocks with an insert/update/delete mix — the
    classify input shape of the device backend, no repo needed."""
    import numpy as np

    from kart_tpu.ops.blocks import FeatureBlock

    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(20 * n, size=n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    old = FeatureBlock(keys.copy(), oids.copy(), None, n)
    keep = np.setdiff1d(np.arange(n), rng.choice(n, size=37, replace=False))
    nk, no = keys[keep], oids[keep].copy()
    no[::29] = rng.integers(0, 2**32, size=(len(no[::29]), 5), dtype=np.uint32)
    ins_k = np.arange(30 * n, 30 * n + 23, dtype=np.int64)
    ins_o = rng.integers(0, 2**32, size=(23, 5), dtype=np.uint32)
    new = FeatureBlock(
        np.concatenate([nk, ins_k]), np.concatenate([no, ins_o]), None, n - 37 + 23
    )
    return old, new


def test_device_transfer_fault_falls_back_bit_identical(monkeypatch):
    """A crash mid host->device transfer must not kill the diff: the
    sharded backend abandons the device attempt and the host-native
    fallback result is bit-identical to an uninjected run."""
    import numpy as np

    from kart_tpu.diff.backend import BACKENDS
    from kart_tpu.ops.diff_kernel import classify_blocks_host

    old, new = _edited_block_pair()
    want_old, want_new, want_counts = classify_blocks_host(old, new)
    # bare point (no :n): the spec *string* must differ from the per-round
    # matrix below — one-shot state only resets when the spec changes
    monkeypatch.setenv("KART_FAULTS", "diff.device_transfer")
    got_old, got_new, got_counts = BACKENDS["sharded_jax"].classify(old, new)
    monkeypatch.delenv("KART_FAULTS")
    assert got_counts == want_counts
    np.testing.assert_array_equal(got_old, want_old)
    np.testing.assert_array_equal(got_new, want_new)


def _full_rounds_block_pair(n=2 * 256 * 3 + 100, seed=14):
    """(old, new) over one key column, attribute edits only: every chunk but
    the last is full on both sides, so all rounds but the last are handed
    to the devices as views of these columns."""
    import numpy as np

    from kart_tpu.ops.blocks import FeatureBlock

    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(20 * n, size=n, replace=False)).astype(np.int64)
    oids = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
    new_oids = oids.copy()
    new_oids[::31, 3] ^= 1
    for a in (keys, oids, new_oids):
        a.flags.writeable = False  # as a sidecar's mapping is
    return FeatureBlock(keys, oids, None, n), FeatureBlock(keys, new_oids, None, n)


@pytest.mark.parametrize(
    "make_pair,view_rounds",
    [(_edited_block_pair, 0), (_full_rounds_block_pair, 3)],
    ids=["copied_rounds", "view_rounds"],
)
def test_device_transfer_killed_at_every_round_leaves_no_partial_state(
    monkeypatch, make_pair, view_rounds
):
    """Kill matrix over transfer rounds: for every round N of a multi-round
    batched classify, an injected crash at round N's host->device transfer
    raises out of the device attempt with nothing published, and the very
    next (uninjected) call over the same blocks is bit-identical to
    host-native — no partial state survives the crash. A round of views
    (nothing packed, the columns themselves handed over) aborts like a
    copied one."""
    import jax
    import numpy as np

    from kart_tpu import telemetry
    from kart_tpu.diff.device_batch import batch_splits, classify_blocks_batched
    from kart_tpu.ops.diff_kernel import classify_blocks_host
    from kart_tpu.parallel.mesh import make_mesh

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    old, new = make_pair()
    want = classify_blocks_host(old, new)
    n_shards, batch_rows = 2, 256
    _, n_chunks = batch_splits(
        (old.keys[: old.count], new.keys[: new.count]), batch_rows
    )
    n_rounds = -(-n_chunks // n_shards)
    assert n_rounds >= 3, "fixture too small to exercise mid-stream rounds"
    mesh = make_mesh(n_shards)
    def views_counted():
        return telemetry.counters_snapshot().get(("diff.device.view_rounds", ()), 0)

    telemetry.reset()
    telemetry.enable(metrics=True)
    try:
        for r in range(1, n_rounds + 1):
            monkeypatch.setenv("KART_FAULTS", f"diff.device_transfer:{r}")
            with pytest.raises(faults.InjectedFault):
                classify_blocks_batched(old, new, mesh=mesh, batch_rows=batch_rows)
            monkeypatch.delenv("KART_FAULTS")
            # an aborted call counts nothing: the counters move with results
            assert views_counted() == (r - 1) * view_rounds
            got = classify_blocks_batched(old, new, mesh=mesh, batch_rows=batch_rows)
            assert views_counted() == r * view_rounds
            assert got[2] == want[2]
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
    finally:
        telemetry.reset()


def test_cli_diff_survives_device_transfer_fault(tmp_path, monkeypatch):
    """End-to-end: a real `kart diff` forced onto the sharded backend with
    the transfer fault armed completes via the host-native fallback and its
    output is byte-identical to an unfaulted host run."""
    import jax

    from click.testing import CliRunner

    from kart_tpu.cli import cli

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from helpers import make_repo_with_edits

    repo_path, _ = make_repo_with_edits(tmp_path)
    monkeypatch.setenv("KART_DIFF_ENGINE", "columnar")
    monkeypatch.setenv("KART_DIFF_BACKEND", "host_native")
    host = CliRunner().invoke(
        cli, ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json"],
        catch_exceptions=False,
    )
    assert host.exit_code == 0, host.output

    monkeypatch.setenv("KART_DIFF_BACKEND", "sharded_jax")
    monkeypatch.setenv("KART_FAULTS", "diff.device_transfer:1")
    faulted = CliRunner().invoke(
        cli, ["-C", repo_path, "diff", "HEAD^...HEAD", "-o", "json"],
        catch_exceptions=False,
    )
    monkeypatch.delenv("KART_FAULTS")
    assert faulted.exit_code == 0, faulted.output

    def diff_payload(output):
        """The pretty-printed JSON document, shorn of any fallback-warning
        log lines the test runner's stream capture interleaves."""
        import json as _json

        lines = output.splitlines()
        lo = lines.index("{")
        hi = len(lines) - 1 - lines[::-1].index("}")
        return _json.loads("\n".join(lines[lo : hi + 1]))

    assert diff_payload(faulted.output) == diff_payload(host.output)


# ---------------------------------------------------------------------------
# concurrent object server: enum-cache + shed fault points (ISSUE 7)
# ---------------------------------------------------------------------------


def test_poisoned_enum_cache_fill_is_never_served(
    served_repo, tmp_path, monkeypatch
):
    """A fault at the cache-publish frame poisons nothing: the entry is
    never inserted, the failing request surfaces its error, and the next
    identical request re-walks cleanly instead of hitting a corpse."""
    from kart_tpu import telemetry

    repo, _, url = served_repo
    telemetry.reset(disable=False)  # fresh counters; keep metrics enabled
    client = HttpRemote(url, retry=RetryPolicy(attempts=1))
    wants = list(client.ls_refs()["heads"].values())

    monkeypatch.setenv("KART_FAULTS", "server.enum_cache:1")  # publish frame
    dst1 = KartRepo.init_repository(tmp_path / "dst1")
    with pytest.raises(HttpTransportError, match="InjectedFault"):
        client.fetch_pack(dst1, wants)
    monkeypatch.delenv("KART_FAULTS")

    dst2 = KartRepo.init_repository(tmp_path / "dst2")
    header = client.fetch_pack(dst2, wants)
    assert fsck_objects(dst2) == header["object_count"]

    def count(name):
        for n, l, v in telemetry.snapshot()["counters"]:
            if n == name and not l:
                return v
        return 0

    # both requests were misses (the poisoned fill published nothing);
    # nothing was ever served from a poisoned entry
    assert count("server.enum_cache.misses") == 2
    assert count("server.enum_cache.hits") == 0


def test_server_killed_mid_cached_stream_client_resumes_via_kart_fetch(
    tmp_path, monkeypatch
):
    """ISSUE 7 kill matrix: a server dying while streaming a *cached* pack
    (KART_FAULTS=server.enum_cache mid-chunk truncates the response like a
    process kill) leaves the interrupted clone resumable — the kept partial
    repo completes via `kart fetch`, shipping only the remainder."""
    from kart_tpu.synth import synth_repo

    src, _ = synth_repo(
        str(tmp_path / "src"), 30_000, blobs="real", edit_frac=0.0
    )
    server = make_server(src)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}/"
    try:
        # warm the cache with one full clone
        warm = transport.clone(url, tmp_path / "warm", do_checkout=False)
        assert warm.head_commit_oid == src.head_commit_oid

        # the next clone is served from the cache and torn after the first
        # 1MB chunk; a single-attempt policy makes the tear fatal in-process
        monkeypatch.setenv("KART_TRANSPORT_RETRIES", "1")
        monkeypatch.setenv("KART_FAULTS", "server.enum_cache:2")
        with pytest.raises(RemoteError, match="partial clone kept"):
            transport.clone(url, tmp_path / "torn", do_checkout=False)
        monkeypatch.delenv("KART_FAULTS")
        monkeypatch.delenv("KART_TRANSPORT_RETRIES")

        torn = KartRepo(str(tmp_path / "torn"))
        salvaged = sum(1 for _ in torn.odb.iter_oids())
        assert salvaged > 0, "nothing salvaged from the torn cached stream"
        assert torn.read_gitdir_file(FETCH_RESUME_FILE) is not None

        # `kart fetch` resumes: remainder only, store completes fsck-clean
        transport.fetch(torn, "origin")
        assert torn.read_gitdir_file(FETCH_RESUME_FILE) is None
        total = fsck_objects(torn)
        assert total == fsck_objects(warm)
        assert salvaged < total  # the resume shipped a remainder, not a restart
        tip = src.head_commit_oid
        assert torn.refs.get("refs/remotes/origin/main") == tip
    finally:
        server.shutdown()
        server.server_close()


def test_shed_fault_is_retried_honouring_retry_after(
    served_repo, monkeypatch
):
    """An armed KART_FAULTS=server.shed sheds one request with 429 +
    Retry-After; the client policy retries after (at least) the advertised
    floor and the verb completes transparently."""
    repo, _, url = served_repo
    monkeypatch.setenv("KART_SERVE_RETRY_AFTER", "3")
    monkeypatch.setenv("KART_FAULTS", "server.shed:1")
    sleeps = []
    client = HttpRemote(
        url, retry=RetryPolicy(attempts=2, base_delay=0.01, sleep=sleeps.append)
    )
    info = client.ls_refs()  # first attempt shed, second succeeds
    monkeypatch.delenv("KART_FAULTS")
    assert info["heads"]
    assert sleeps == [3.0]  # the server's Retry-After floored the backoff


def test_shed_push_is_retried_transparently(served_repo, tmp_path, monkeypatch):
    """A shedding 429 provably precedes any server-side processing, so even
    the non-idempotent receive-pack retries it: a push caught by the load
    shedder joins the paced queue instead of hard-failing."""
    repo, ds_path, url = served_repo
    clone = transport.clone(url, tmp_path / "clone", do_checkout=False)
    clone.config.set_many({"user.name": "C", "user.email": "c@x"})
    oid = edit_commit(clone, ds_path, deletes=[5], message="shed push")
    # hit 1 is the push's ls-refs admission; hit 2 sheds the receive-pack
    monkeypatch.setenv("KART_FAULTS", "server.shed:2")
    updated = transport.push(clone, "origin")
    monkeypatch.delenv("KART_FAULTS")
    assert updated == {"refs/heads/main": oid}
    assert repo.refs.get("refs/heads/main") == oid


# ---------------------------------------------------------------------------
# tile serving: encode + cache fault points (ISSUE 10)
# ---------------------------------------------------------------------------


def _get_tile(url, path):
    """GET <url><path> -> (status, body bytes)."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url.rstrip("/") + path, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.mark.parametrize("frame", [1, 2])
def test_tile_encode_killed_at_every_frame_publishes_nothing(
    served_repo, monkeypatch, frame
):
    """ISSUE 10 kill matrix: a crash at either tiles.encode frame (1 = the
    block-pruned row selection done, 2 = layers built, payload not yet
    assembled) surfaces as an error with nothing published — the cache
    holds no entry, and the retried request serves the exact payload a
    never-faulted server would."""
    from kart_tpu import telemetry
    from kart_tpu.tiles.cache import tile_cache_for

    repo, ds_path, url = served_repo
    telemetry.reset(disable=False)
    tile = f"/api/v1/tiles/HEAD/{ds_path}/1/0/0"

    monkeypatch.setenv("KART_FAULTS", f"tiles.encode:{frame}")
    status, body = _get_tile(url, tile)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert tile_cache_for(repo).stats()["entries"] == 0

    status, payload = _get_tile(url, tile)
    assert status == 200
    # byte-identical to a clean single-process encode of the same key
    from kart_tpu import tiles

    clean, _etag, _ = tiles.serve_tile(repo, "HEAD", ds_path, 1, 0, 0)
    assert payload == clean


def test_poisoned_tile_cache_fill_is_never_served(served_repo, monkeypatch):
    """A fault at the tile cache's publish frame poisons nothing: the
    entry is never inserted, the failing request surfaces its error, and
    the next identical request re-encodes cleanly — a poisoned tile is
    never served (ISSUE 10 satellite)."""
    from kart_tpu import telemetry, tiles
    from kart_tpu.tiles.cache import tile_cache_for

    repo, ds_path, url = served_repo
    telemetry.reset(disable=False)
    tile = f"/api/v1/tiles/HEAD/{ds_path}/0/0/0"

    monkeypatch.setenv("KART_FAULTS", "tiles.cache:1")
    status, body = _get_tile(url, tile)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert tile_cache_for(repo).stats() == {"entries": 0, "bytes": 0}

    status, payload = _get_tile(url, tile)
    assert status == 200
    header, layers = tiles.parse_payload(payload)
    assert header["count"] > 0

    def count(name):
        for n, l, v in telemetry.snapshot()["counters"]:
            if n == name and not l:
                return v
        return 0

    # both requests were misses; nothing was served from a poisoned entry
    assert count("tiles.cache.misses") == 2
    assert count("tiles.cache.hits") == 0
    # and now the clean entry is cached: a third request hits
    status, again = _get_tile(url, tile)
    assert status == 200 and again == payload
    assert count("tiles.cache.hits") == 1


def test_ktb2_stream_encode_fault_publishes_nothing(served_repo, monkeypatch):
    """ISSUE 15 kill matrix: a crash in the KTB2 stream codec
    (tiles.streams frame, fired at encode_ktb2_layer entry) surfaces as an
    error with nothing published — no cache entry, and the retried request
    serves the exact payload a never-faulted server would."""
    from kart_tpu import tiles
    from kart_tpu.tiles.cache import tile_cache_for

    repo, ds_path, url = served_repo
    tile = f"/api/v1/tiles/HEAD/{ds_path}/0/0/0?layers=ktb2"

    monkeypatch.setenv("KART_FAULTS", "tiles.streams:1")
    status, body = _get_tile(url, tile)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert tile_cache_for(repo).stats()["entries"] == 0

    status, payload = _get_tile(url, tile)
    assert status == 200
    clean, _etag, _ = tiles.serve_tile(
        repo, "HEAD", ds_path, 0, 0, 0, layers="ktb2"
    )
    assert payload == clean


def test_ktb2_stream_decode_fault_is_clean(monkeypatch):
    """The decode frame of tiles.streams: an armed client-side decode
    raises InjectedFault (an OSError like every injected failure) without
    corrupting state — a second decode of the same bytes succeeds."""
    import numpy as np

    from kart_tpu.faults import InjectedFault
    from kart_tpu.tiles.encode import decode_ktb2_layer, encode_ktb2_layer

    keys = np.arange(100, dtype=np.int64)
    boxes = np.zeros((100, 4), dtype=np.int32)
    # hit 2: the encode entry consumes hit 1, the decode entry fires (a
    # distinct spec string from the encode test — re-arming an identical
    # spec does not reset a fired counter, by design)
    monkeypatch.setenv("KART_FAULTS", "tiles.streams:2")
    data = encode_ktb2_layer(keys, boxes)
    with pytest.raises(InjectedFault):
        decode_ktb2_layer(data)
    got_keys, got_boxes = decode_ktb2_layer(data)  # disarmed: clean decode
    assert np.array_equal(got_keys, keys)
    assert np.array_equal(got_boxes, boxes)


@pytest.mark.parametrize("frame", [1, 2])
def test_pyramid_export_killed_at_batch_boundary(tmp_path, monkeypatch, frame):
    """ISSUE 15 kill matrix: a crash at any tiles.export batch boundary
    leaves every previously-written tile complete (each parses and
    decodes), no temp debris the gc sweep wouldn't claim, and the re-run
    overwrites to a pyramid byte-identical to a never-faulted export."""
    from kart_tpu import tiles
    from kart_tpu.faults import InjectedFault
    from kart_tpu.tiles.pyramid import export_pyramid, tree_digest as digest

    repo, ds_path = make_imported_repo(tmp_path, n=12)
    src = tiles.source_for(
        repo, tiles.resolve_tile_commit(repo, "HEAD"), ds_path
    )

    clean_dir = str(tmp_path / "clean")
    export_pyramid(src, [0, 1, 2], clean_dir, layers=("ktb2",),
                   workers=1, batch_tiles=1)

    out = str(tmp_path / "faulted")
    monkeypatch.setenv("KART_FAULTS", f"tiles.export:{frame}")
    with pytest.raises(InjectedFault):
        export_pyramid(src, [0, 1, 2], out, layers=("ktb2",),
                       workers=1, batch_tiles=1)
    monkeypatch.delenv("KART_FAULTS")
    # every file present is a complete, decodable payload; no temp debris
    for dirpath, _dirs, filenames in os.walk(out):
        for name in filenames:
            assert name.endswith(".ktile"), name
            with open(os.path.join(dirpath, name), "rb") as f:
                header, layers = tiles.parse_payload(f.read())
            tiles.decode_ktb2_layer(layers["ktb2"])
    # the re-run completes and lands byte-identical to the clean export
    export_pyramid(src, [0, 1, 2], out, layers=("ktb2",),
                   workers=1, batch_tiles=1)
    assert digest(out) == digest(clean_dir)


# ---------------------------------------------------------------------------
# fleet: the replica sync + write-proxy kill matrices (ISSUE 13)
# ---------------------------------------------------------------------------


def _fleet_pair(served_repo, tmp_path):
    """A replica (already synced once) of the served primary, plus its own
    in-thread server — the fleet kill-matrix fixture."""
    from kart_tpu import fleet as fleet_mod
    from kart_tpu.core.repo import KartRepo
    from kart_tpu.transport.http import make_server

    repo, ds_path, url = served_repo
    replica = KartRepo.init_repository(str(tmp_path / "replica"))
    node = fleet_mod.FleetNode(replica, primary_url=url)
    node.sync.sync_once()
    server = make_server(replica, fleet=node)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    rurl = f"http://127.0.0.1:{server.server_address[1]}"
    return repo, ds_path, replica, node, server, rurl


def _refs_and_digest(repo):
    refs = dict(repo.refs.iter_refs("refs/"))
    h = hashlib.sha256()
    for oid in sorted(repo.odb.iter_oids()):
        h.update(oid.encode())
    return refs, h.hexdigest()


@pytest.mark.parametrize("frame", [1, 2, 3])
def test_replica_sync_killed_at_every_frame_converges(
    served_repo, tmp_path, monkeypatch, frame
):
    """A replica killed at any fleet.sync frame — the pack-migrate
    boundary (1) or before each ref advance (2+) — restarts, re-runs the
    cycle, and converges byte-identical to the primary; every
    intermediate state is consistent (no ref ever names a missing
    object)."""
    from helpers import edit_commit as _edit

    repo, ds_path, replica, node, server, rurl = _fleet_pair(
        served_repo, tmp_path
    )
    try:
        # two refs move this round, so frame 3 (the second ref advance)
        # exists: a mid-advance kill leaves one ref new, one old
        _edit(
            repo, ds_path,
            updates=[{"fid": 2, "geom": None, "name": "k", "rating": 1.0}],
            message="kill-matrix commit",
        )
        repo.refs.set(
            "refs/heads/dev", repo.refs.get("refs/heads/main"),
            log_message="branch",
        )
        monkeypatch.setenv("KART_FAULTS", f"fleet.sync:{frame}")
        with pytest.raises(faults.InjectedFault):
            node.sync.sync_once()
        monkeypatch.delenv("KART_FAULTS")
        # the torn state is consistent: every local ref resolves
        for ref, oid in replica.refs.iter_refs("refs/"):
            assert replica.odb.contains(oid), f"{ref} dangles after kill"
        # the restarted cycle converges byte-identical
        node.sync.sync_once()
        assert _refs_and_digest(replica) == _refs_and_digest(repo)
        fsck_objects(replica)
    finally:
        server.shutdown()
        server.server_close()


def test_proxy_killed_before_upstream_leaves_primary_identical(
    served_repo, tmp_path, monkeypatch
):
    """fleet.proxy frame 1 fires before any request byte reaches the
    primary: the primary's store and refs are byte-identical after the
    kill, and the client's retry lands the push exactly once."""
    from helpers import edit_commit as _edit

    from kart_tpu import transport
    from kart_tpu.transport.remote import RemoteError

    repo, ds_path, replica, node, server, rurl = _fleet_pair(
        served_repo, tmp_path
    )
    try:
        clone = transport.clone(rurl, str(tmp_path / "c"), do_checkout=False)
        clone.config.set_many(
            {"user.name": "w", "user.email": "w@example.com"}
        )
        new_oid = _edit(
            clone, ds_path,
            updates=[{"fid": 1, "geom": None, "name": "p", "rating": 1.0}],
            message="proxied",
        )
        before_snap = store_snapshot(repo)
        before_refs = dict(repo.refs.iter_refs("refs/"))
        monkeypatch.setenv("KART_FAULTS", "fleet.proxy:1")
        with pytest.raises(RemoteError):
            transport.push(clone, "origin")
        monkeypatch.delenv("KART_FAULTS")
        assert store_snapshot(repo) == before_snap
        assert dict(repo.refs.iter_refs("refs/")) == before_refs
        # the retry lands once
        updated = transport.push(clone, "origin")
        assert updated["refs/heads/main"] == new_oid
        assert repo.refs.get("refs/heads/main") == new_oid
    finally:
        server.shutdown()
        server.server_close()


def test_proxy_killed_mid_relay_push_landed_retry_idempotent(
    served_repo, tmp_path, monkeypatch
):
    """fleet.proxy frame 2 fires after the primary answered: the push IS
    landed upstream; the client sees a torn response and its explicit
    retry is absorbed idempotently (same commit, same ref — exactly one
    new commit on the primary, no duplicate)."""
    from helpers import edit_commit as _edit

    from kart_tpu import transport
    from kart_tpu.transport.remote import RemoteError

    repo, ds_path, replica, node, server, rurl = _fleet_pair(
        served_repo, tmp_path
    )
    try:
        clone = transport.clone(rurl, str(tmp_path / "c"), do_checkout=False)
        clone.config.set_many(
            {"user.name": "w", "user.email": "w@example.com"}
        )
        new_oid = _edit(
            clone, ds_path,
            updates=[{"fid": 1, "geom": None, "name": "m", "rating": 2.0}],
            message="mid-relay",
        )
        monkeypatch.setenv("KART_FAULTS", "fleet.proxy:2")
        with pytest.raises(RemoteError):
            transport.push(clone, "origin")
        monkeypatch.delenv("KART_FAULTS")
        # the push landed upstream despite the torn relay
        assert repo.refs.get("refs/heads/main") == new_oid
        count_before = sum(1 for _ in repo.odb.iter_oids())
        # the client's retry is absorbed: no duplicate commit, no new
        # objects, ref unchanged
        updated = transport.push(clone, "origin")
        assert updated["refs/heads/main"] == new_oid
        assert repo.refs.get("refs/heads/main") == new_oid
        assert sum(1 for _ in repo.odb.iter_oids()) == count_before
        fsck_objects(repo)
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# events.emit / events.warm — the live-update emission frames
# (docs/EVENTS.md §3–§4)
# ---------------------------------------------------------------------------


def _wait(pred, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.parametrize("frame", [1, 2])
def test_event_emission_killed_at_every_frame_replays(
    tmp_path, monkeypatch, frame
):
    """``KART_FAULTS=events.emit:<n>`` — frame 1 kills the CDC
    computation, frame 2 the event-log append (the announce). At either
    frame: refs and object store stay byte-identical, the tip is NOT
    announced (fully announced or not at all), and a restarted emitter
    over the same gitdir replays the missed emission."""
    from kart_tpu import events as events_mod

    repo, ds_path = make_imported_repo(tmp_path, n=6)
    emitter = events_mod.emitter_for(repo)  # adopts the current tip
    assert emitter.log.head() == 0
    oid = edit_commit(
        repo, ds_path,
        updates=[{"fid": 1, "geom": None, "name": "k", "rating": 1.0}],
        message="emission kill",
    )
    snap = store_snapshot(repo)
    refs_before = dict(repo.refs.iter_refs("refs/"))
    monkeypatch.setenv("KART_FAULTS", f"events.emit:{frame}")
    assert emitter.reconcile() == 1
    # the emission fails on the worker thread: wait for the booking to
    # drain, then assert nothing was announced and nothing was written
    _wait(
        lambda: emitter.status_dict()["pending_refs"] == 0
        and emitter.status_dict()["queue_depth"] == 0,
        what="emission failure to drain",
    )
    assert emitter.log.head() == 0, "a killed emission must announce nothing"
    assert store_snapshot(repo) == snap
    assert dict(repo.refs.iter_refs("refs/")) == refs_before
    monkeypatch.delenv("KART_FAULTS")
    # the restarted server replays the missed emission from the on-disk
    # announced-tips state
    events_mod.drop_emitters(repo.gitdir)
    emitter2 = events_mod.emitter_for(repo)
    _wait(lambda: emitter2.log.head() == 1, what="replayed announcement")
    events, _head, _reset = emitter2.events_since(0)
    assert events[0]["new"] == oid and events[0]["replay"] is True
    fsck_objects(repo)


def test_event_warm_kill_keeps_announcement_and_clean_cache(
    tmp_path, monkeypatch
):
    """``KART_FAULTS=events.warm:1`` — the pre-warm pass dies before any
    tile encodes. Warming is best-effort: the event is STILL announced
    (with the error counted), the store/refs untouched, and the dirty
    tile served afterwards is byte-identical to a clean encode — nothing
    was poisoned into the tile cache."""
    from helpers import gpkg_point

    from kart_tpu import events as events_mod
    from kart_tpu import tiles
    from kart_tpu.geometry import Geometry

    repo, ds_path = make_imported_repo(tmp_path, n=6)
    emitter = events_mod.emitter_for(repo)
    oid = edit_commit(
        repo, ds_path,
        updates=[{"fid": 1, "geom": Geometry(gpkg_point(120.0, -40.0)),
                  "name": "warmkill", "rating": 2.0}],
        message="warm kill",
    )
    snap = store_snapshot(repo)
    monkeypatch.setenv("KART_FAULTS", "events.warm:1")
    assert emitter.reconcile() == 1
    _wait(lambda: emitter.log.head() == 1, what="announcement despite kill")
    events, _head, _reset = emitter.events_since(0)
    assert events[0]["new"] == oid
    assert events[0]["warm"]["errors"] >= 1
    assert events[0]["warm"]["tiles"] == 0
    monkeypatch.delenv("KART_FAULTS")
    assert store_snapshot(repo) == snap
    # nothing poisoned: the served tile equals a from-scratch encode
    payload, _etag, _cached = tiles.serve_tile(
        repo, oid, ds_path, 0, 0, 0, commit_oid=oid
    )
    from kart_tpu.tiles.encode import encode_tile

    fresh, _stats = encode_tile(
        tiles.source_for(repo, oid, ds_path), 0, 0, 0
    )
    assert payload == fresh
    events_mod.drop_emitters(repo.gitdir)
    fsck_objects(repo)


# ---------------------------------------------------------------------------
# ISSUE 16: the query-lane kill matrix (query.scan / query.join)
# ---------------------------------------------------------------------------


@pytest.fixture()
def served_query_repo(tmp_path):
    """A blobs-real synth repo served over HTTP: the scan's blob-decode
    batches (query.scan frame 2+) need readable feature blobs."""
    from kart_tpu import telemetry
    from kart_tpu.query import cache as qcache
    from kart_tpu.synth import synth_repo

    repo, info = synth_repo(str(tmp_path / "q"), 400, blobs="real")
    with qcache._query_caches_lock:
        qcache._QUERY_CACHES.clear()
    telemetry.reset(disable=False)
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield repo, info, url
    server.shutdown()
    server.server_close()
    telemetry.reset()


@pytest.mark.parametrize("frame", [1, 2])
def test_query_scan_killed_at_every_frame_publishes_nothing(
    served_query_repo, monkeypatch, frame
):
    """ISSUE 16 kill matrix: a crash at either query.scan frame (1 = scan
    entry, 2 = the first blob-decode batch) surfaces as a 500 with nothing
    published — the result cache holds no entry — and the retried query
    serves the exact bytes a never-faulted server would."""
    import json as _json
    from urllib.parse import quote

    from kart_tpu.query import run_query
    from kart_tpu.query.cache import query_cache_for

    repo, info, url = served_query_repo
    base = info["base_commit"]
    where = "rating >= 42"
    path = (
        f"/api/v1/query?ref={base}&dataset=synth"
        f"&where={quote(where, safe='')}&output=json"
    )

    monkeypatch.setenv("KART_FAULTS", f"query.scan:{frame}")
    status, body = _get_tile(url, path)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert query_cache_for(repo).stats() == {"entries": 0, "bytes": 0}

    status, payload = _get_tile(url, path)
    assert status == 200
    clean = run_query(repo, base, "synth", where=where, output="json")
    assert payload == _json.dumps(clean, sort_keys=True).encode()


@pytest.fixture()
def served_join_repo(tmp_path):
    """A spatial synth repo served over HTTP for the join kill matrix."""
    from kart_tpu import telemetry
    from kart_tpu.query import cache as qcache
    from kart_tpu.synth import synth_repo

    repo, info = synth_repo(
        str(tmp_path / "j"), 5000, spatial=True, blobs="changed"
    )
    with qcache._query_caches_lock:
        qcache._QUERY_CACHES.clear()
    telemetry.reset(disable=False)
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield repo, info, url
    server.shutdown()
    server.server_close()
    telemetry.reset()


@pytest.mark.parametrize("frame", [1, 2])
def test_query_join_killed_at_every_frame_publishes_nothing(
    served_join_repo, monkeypatch, frame
):
    """ISSUE 16 kill matrix: a crash at either query.join frame (1 = join
    entry, 2 = the first build-side tile) publishes nothing — no result
    cache entry, nothing a peer could have cached — and the retried join
    is byte-identical to a clean single-process run."""
    import json as _json

    from kart_tpu.query import run_query
    from kart_tpu.query.cache import query_cache_for

    repo, info, url = served_join_repo
    base, edit = info["base_commit"], info["edit_commit"]
    path = f"/api/v1/query?ref={base}&dataset=synth&intersects={edit}:synth"

    monkeypatch.setenv("KART_FAULTS", f"query.join:{frame}")
    status, body = _get_tile(url, path)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert query_cache_for(repo).stats() == {"entries": 0, "bytes": 0}

    status, payload = _get_tile(url, path)
    assert status == 200
    clean = run_query(repo, base, "synth", intersects=(edit, "synth"))
    assert payload == _json.dumps(clean, sort_keys=True).encode()
    assert _json.loads(payload)["pairs"] == clean["pairs"]


def test_query_refine_killed_publishes_nothing(served_join_repo, monkeypatch):
    """ISSUE 20 kill matrix: a crash in the exact-refine stage
    (query.refine, fired before any refine verdict lands) surfaces as a
    500 with nothing published — the result cache holds no entry — and
    the retried query serves the exact bytes a never-faulted server
    would."""
    import json as _json

    from kart_tpu.query import run_query
    from kart_tpu.query.cache import query_cache_for

    repo, info, url = served_join_repo
    base = info["base_commit"]
    path = (
        f"/api/v1/query?ref={base}&dataset=synth&bbox=-180,-90,180,90"
    )

    monkeypatch.setenv("KART_FAULTS", "query.refine:1")
    status, body = _get_tile(url, path)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert query_cache_for(repo).stats() == {"entries": 0, "bytes": 0}

    status, payload = _get_tile(url, path)
    assert status == 200
    clean = run_query(repo, base, "synth", bbox="-180,-90,180,90")
    assert clean["exact"] is True and clean["stats"]["pairs_refined"] > 0
    assert payload == _json.dumps(clean, sort_keys=True).encode()


@pytest.fixture()
def served_polygon_repo(tmp_path):
    """A real-blob polygon repo (sidecar carries no geometry section, so
    the geom tile layer runs the blob-fallback vertex extraction) served
    over HTTP."""
    from kart_tpu.synth import synth_polygon_repo
    from kart_tpu.tiles.cache import _TILE_CACHES, _tile_caches_lock
    from kart_tpu.tiles.source import drop_sources

    repo, info = synth_polygon_repo(str(tmp_path / "p"), 120, seed=5)
    with _tile_caches_lock:
        _TILE_CACHES.clear()
    drop_sources()
    server = make_server(repo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    yield repo, info, url
    server.shutdown()
    server.server_close()
    drop_sources()


def test_geom_extract_killed_publishes_nothing(
    served_polygon_repo, monkeypatch
):
    """ISSUE 20 kill matrix: a crash in the vertex extraction
    (geom.extract, fired before any rows are built — here via the geom
    tile layer's blob-fallback build) surfaces as a 500 with nothing
    published: no tile cache entry, no memoized partial vertex column.
    The retried request re-runs the extraction and serves the exact
    payload a never-faulted server would."""
    from kart_tpu import tiles
    from kart_tpu.tiles.cache import tile_cache_for
    from kart_tpu.tiles.encode import decode_mvt_layer

    repo, info, url = served_polygon_repo
    tile = "/api/v1/tiles/HEAD/polys/0/0/0?layers=geom"

    monkeypatch.setenv("KART_FAULTS", "geom.extract:1")
    status, body = _get_tile(url, tile)
    monkeypatch.delenv("KART_FAULTS")
    assert status == 500
    assert b"InjectedFault" in body
    assert tile_cache_for(repo).stats() == {"entries": 0, "bytes": 0}

    status, payload = _get_tile(url, tile)
    assert status == 200
    clean, _etag, _ = tiles.serve_tile(
        repo, "HEAD", "polys", 0, 0, 0, layers="geom"
    )
    assert payload == clean
    header, layer_bytes = tiles.parse_payload(payload)
    assert header["count"] > 0
    assert len(decode_mvt_layer(layer_bytes["geom"])["features"]) > 0
