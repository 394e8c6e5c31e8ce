"""Reference for ``kart merge theirs`` over the builder ``int_pk_merge_layer``,
from the builder's edit sets alone: nothing of the merge engine is imported.

The command of the cell is the dry run, whose whole answer is one JSON
document with the number of conflicting features; ``check`` holds it to the
builder's count and to having changed nothing. A count says little about a
merge, so ``check`` then makes the real one, once a run, in set-up: ``kart
merge theirs`` through the entry point a user calls, after which

* ``MERGE_INDEX`` is read back with the reader below (both encodings,
  docs in ``kart_tpu/merge/index.py``): the conflicting pks are exactly the
  builder's, each with the builder's ancestor, ours and theirs blob — or
  its absence;
* the merged tree it names holds, at the dataset's ``feature`` path, the
  feature tree of the builder's expected merged columns (ours, with theirs'
  clean changes), whose oid :func:`feature_tree_oid` works out below from
  the Datasets-V3 layout and git's tree format, with ``hashlib`` alone: no
  tree builder, path encoder or object store of the program is asked;
* the program's counters ``merge.conflicts`` / ``merge.take_theirs`` moved by
  the builder's numbers;
* ``kart merge --abort`` leaves the repository in its normal state with
  ``HEAD`` where it was.

It does not pin which join answers an overflowing chunk."""

import base64
import hashlib
import json
import os
import struct

import msgpack
import numpy as np

DS_PATH = "layer"
FEATURE_TREE = f"{DS_PATH}/.table-dataset/feature"
VERSIONS = ("ancestor", "ours", "theirs")
STATE_FILES = ("MERGE_HEAD", "MERGE_INDEX", "MERGE_MSG", "MERGE_BRANCH")

_MAGIC = b"KMIX2\n"
_PATH_REF = 0xFFFFFFFFFFFFFFFF
_DERIVED = (0xFFFFFFFFFFFFFFFE, 0xFFFFFFFFFFFFFFFD)  # paths, labels

_BRANCHES, _LEVELS = 64, 4
_B64_DIGITS = b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_"


def expected_document(info):
    return {
        "kart.merge/v1": {
            "conflicts": {DS_PATH: {"feature": int(info["conflicts"])}},
            "state": "merging",
            "dryRun": True,
        }
    }


def _tree(mode, entries):
    """The 20-byte oid of the git tree object holding ``entries``: (name,
    20-byte oid) pairs, all of one ``mode``. git orders a tree by name as
    bytes (a shorter name before any it is a prefix of, which holds for
    files; directory names here are one character each)."""
    body = b"".join(
        mode + b" " + name + b"\x00" + oid for name, oid in sorted(entries)
    )
    return hashlib.sha1(b"tree %d\x00" % len(body) + body).digest()


def feature_tree_oid(pks, oids):
    """The hex oid of the Datasets-V3 feature tree of an int-pk dataset with
    these rows (``pks`` int64 (n,), ``oids`` uint8 (n, 20)), from the
    layout's definition (upstream ``kart/dataset3_paths.py``, scheme ``int``,
    64 branches, 4 levels, base64): a feature lies at ``a/b/c/d/<name>``
    where ``abcd`` are the four urlsafe-base64 digits of ``(pk // 64) %
    64**4``, most significant first, and ``<name>`` is
    ``urlsafe_b64(msgpack([pk]))``, padding kept."""
    raw = np.ascontiguousarray(oids, dtype=np.uint8).tobytes()
    below = {}  # leaf number -> its files
    for i, pk in enumerate(np.asarray(pks).tolist()):
        name = base64.urlsafe_b64encode(msgpack.packb([pk]))
        below.setdefault((pk // _BRANCHES) % _BRANCHES**_LEVELS, []).append(
            (name, raw[20 * i : 20 * i + 20])
        )
    nodes = {number: _tree(b"100644", files) for number, files in below.items()}
    for _ in range(_LEVELS):
        below = {}
        for number, oid in nodes.items():
            digit = _B64_DIGITS[number % _BRANCHES : number % _BRANCHES + 1]
            below.setdefault(number // _BRANCHES, []).append((digit, oid))
        nodes = {number: _tree(b"40000", dirs) for number, dirs in below.items()}
    (root,) = nodes.values()
    return root.hex()


def read_merge_index(raw):
    """``MERGE_INDEX`` bytes -> (merged tree oid, conflict pks int64 (c,),
    present bool (3, c), oids uint8 (3, c, 20)), rows sorted by pk. The
    columnar encoding (``KMIX2``) and the JSON one small merges get."""
    if not raw.startswith(_MAGIC):
        body = json.loads(raw.decode())["kart.merge_index/v1"]
        rows = sorted(
            (int(label.rsplit(":", 1)[1]), versions)
            for label, versions in body["conflicts"].items()
        )
        present = np.zeros((3, len(rows)), dtype=bool)
        oids = np.zeros((3, len(rows), 20), dtype=np.uint8)
        for i, (_, versions) in enumerate(rows):
            for v, name in enumerate(VERSIONS):
                if versions[name] is not None:
                    present[v, i] = True
                    oids[v, i] = np.frombuffer(
                        bytes.fromhex(versions[name]["oid"]), dtype=np.uint8
                    )
        pks = np.array([pk for pk, _ in rows], dtype=np.int64)
        return body["mergedTree"], pks, present, oids

    pos = len(_MAGIC)
    (header_len,) = struct.unpack_from("<I", raw, pos)
    header = json.loads(raw[pos + 4 : pos + 4 + header_len].decode())
    pos += 4 + header_len
    n = header["n"]

    def block():
        """One length-prefixed column -> ("plain" | "ref" | "derived", bytes)."""
        nonlocal pos
        (length,) = struct.unpack_from("<Q", raw, pos)
        pos += 8
        if length == _PATH_REF:
            pos += 8
            return "ref", b""
        kind = "plain"
        if length in _DERIVED:
            (length,) = struct.unpack_from("<Q", raw, pos)
            pos += 8
            kind = "derived"
        data = raw[pos : pos + length]
        pos += length
        return kind, data

    kind, labels = block()
    if kind == "derived":  # u32 spec length, {"ds_path"}, the pks
        (spec_len,) = struct.unpack_from("<I", labels, 0)
        pks = np.frombuffer(labels[4 + spec_len :], dtype="<i8")
    else:
        pks = np.array(
            [int(l.rsplit(b":", 1)[1]) for l in labels.split(b"\x00")], dtype=np.int64
        )
    present, oids = [], []
    for _ in VERSIONS:
        present.append(np.frombuffer(block()[1], dtype=np.uint8).astype(bool))
        oids.append(np.frombuffer(block()[1], dtype=np.uint8).reshape(n, 20))
        block()  # the paths: a function of the pk
    order = np.argsort(pks, kind="stable")
    return (
        header["mergedTree"], pks[order],
        np.stack(present)[:, order], np.stack(oids)[:, order],
    )


def _counters():
    from kart_tpu import telemetry as tm

    totals = {"merge.conflicts": 0, "merge.take_theirs": 0}
    for (name, _), value in tm.counters_snapshot().items():
        if name in totals:
            totals[name] += value
    return totals


def _kart(repo_path, *args):
    from click.testing import CliRunner

    from kart_tpu.cli import cli

    result = CliRunner().invoke(
        cli, ["-C", repo_path, *args], catch_exceptions=False
    )
    return result.exit_code


def _is_normal(repo_path, info):
    """No merge under way and ``HEAD`` on ours' commit."""
    from kart_tpu.core.repo import KartRepo

    repo = KartRepo(repo_path)
    return (
        not any(os.path.exists(repo.gitdir_file(name)) for name in STATE_FILES)
        and repo.head_commit_oid == info["head"]
    )


def check_index(raw, info, odb):
    """-> {check name: bool} for the bytes of a real merge's ``MERGE_INDEX``."""
    merged_tree, pks, present, oids = read_merge_index(raw)
    want_present = np.asarray(info["conflict_present"], dtype=bool)
    want_oids = np.asarray(info["conflict_oids"], dtype=np.uint8)
    same_pks = np.array_equal(pks, info["conflict_pks"])
    node = odb.tree(merged_tree).get_or_none(FEATURE_TREE)
    return {
        "conflict_pks_are_the_builders": same_pks,
        "versions_are_the_builders": same_pks
        and np.array_equal(present, want_present)
        and np.array_equal(oids[present], want_oids[want_present]),
        "merged_tree_is_the_builders": node is not None
        and node.oid == feature_tree_oid(info["merged_pks"], info["merged_oids"]),
    }


def check(output, info):
    """-> {check name: bool} for the bytes of ``kart merge theirs --dry-run
    -o json``; makes the real merge and aborts it (see the module)."""
    from kart_tpu.core.repo import KartRepo

    repo_path = info["repo"]
    try:
        named = json.loads(output.decode()) == expected_document(info)
    except ValueError:
        named = False
    checks = {
        "names_the_conflict_count": named,
        "dry_run_changed_nothing": _is_normal(repo_path, info),
    }
    before = _counters()
    checks["real_merge_exit_0"] = _kart(repo_path, "merge", "theirs") == 0
    after = _counters()
    repo = KartRepo(repo_path)
    try:
        with open(repo.gitdir_file("MERGE_INDEX"), "rb") as f:
            raw = f.read()
        checks.update(check_index(raw, info, repo.odb))
    except (OSError, ValueError, KeyError, IndexError, struct.error):
        checks.update(dict.fromkeys(
            ("conflict_pks_are_the_builders", "versions_are_the_builders",
             "merged_tree_is_the_builders"), False,
        ))
    checks["stats_are_the_builders"] = (
        after["merge.conflicts"] - before["merge.conflicts"] == info["conflicts"]
        and after["merge.take_theirs"] - before["merge.take_theirs"]
        == info["take_theirs"]
    )
    aborted = _kart(repo_path, "merge", "--abort") == 0
    checks["abort_restores_normal"] = aborted and _is_normal(repo_path, info)
    return checks
