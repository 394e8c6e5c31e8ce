"""Bulk import: sources -> dataset trees -> one commit
(reference: kart/fast_import.py).

The reference shards features over N ``git fast-import`` subprocesses and
merges the resulting trees (fast_import.py:286-399). Here all object writes
go into packfiles, not per-feature loose files: serial imports append every
blob/tree into one new pack (``ObjectDb.bulk_pack``); shardable sources
(int-pk GPKG, see importer/parallel.py) fan out over N worker processes that
each write their own pack of feature blobs + leaf trees, joined by one
TreeBuilder spine rewrite. The commit object is written loose *after* the
packs are fsync'd, so a crash mid-import never leaves a dangling ref.
"""

import gc
import logging
import time

import numpy as np

from kart_tpu import telemetry as tm
from kart_tpu.core.structure import RepoStructure
from kart_tpu.core.tree_builder import TreeBuilder
from kart_tpu.models.dataset import Dataset3
from kart_tpu.core.serialise import msg_pack
from kart_tpu.models.paths import encoder_for_schema
from kart_tpu.utils import chunked, paused_gc

L = logging.getLogger(__name__)

BATCH_SIZE = 10000
# below this, the tree-walk diff path is so cheap that a sidecar isn't worth
# the disk; above it, first-diff latency matters
SIDECAR_MIN_FEATURES = 10000

#: per-phase *self* seconds of the most recent import in this process —
#: {"source_read", "encode", "hash_deflate", "tree_build", "total"}.
#: Populated by the serial streaming path (the bench's phase-breakdown
#: record); the parallel fan-out interleaves phases across workers and
#: reports only the total. Accounting runs on a telemetry span stack
#: (:class:`kart_tpu.telemetry.Phases`): nested phases book wall-clock into
#: the innermost phase only, so the recorded self-times can never sum past
#: the total (the old ``phases[key] +=`` dict pattern double-booked
#: whenever phases overlapped).
LAST_IMPORT_PHASES = None

#: the phase keys the bench's ``import_phase_*`` record reads — stable
#: across the telemetry refactor
PHASE_KEYS = ("source_read", "encode", "hash_deflate", "tree_build")

#: per-stage *busy* seconds of the most recent pipelined import —
#: {"read", "encode", "hash", "pack", "tree", "wall"}. Unlike LAST_IMPORT_PHASES
#: (a single-threaded span stack whose self-times sum <= total by
#: construction), these are measured on four concurrent stage threads, so
#: their sum EXCEEDING wall is the overlap working; the bench records the
#: ratio. None when the last import ran serial/parallel.
LAST_IMPORT_PIPELINE = None


def _new_phases():
    p = tm.Phases("importer")
    for key in PHASE_KEYS:  # every key present even when a path is skipped
        p.self_s.setdefault(key, 0.0)
        p.cum_s.setdefault(key, 0.0)
    return p


class ImportError_(RuntimeError):
    pass


def _timed_iter(it, phases, key="source_read"):
    """Wrap an iterator, accumulating its pull time into phase ``key``
    (leaf accounting: two clock reads per pull, no span objects in the
    per-item loop)."""
    it = iter(it)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            phases.add(key, time.perf_counter() - t0)
            return
        phases.add(key, time.perf_counter() - t0)
        yield item


def import_sources(
    repo,
    sources,
    *,
    message=None,
    replace_existing=False,
    replace_ids=None,
    log=None,
):
    """Import each source as a dataset; -> the new commit oid.

    replace_ids: iterable of pk values — incremental re-import (reference:
    fast_import.py:462-476): the existing dataset tree is kept, each listed
    id is deleted and then re-imported when the source still has it (so a
    listed id absent from the source becomes a delete). Implies
    replace_existing; an empty list re-imports nothing but still updates
    meta."""
    sources = list(sources)
    head_tree = repo.head_tree_oid
    structure = repo.structure("HEAD") if not repo.head_is_unborn else None
    existing_paths = (
        set(structure.datasets.paths()) if structure is not None else set()
    )

    from kart_tpu.importer.pk_generation import PkGeneratingImportSource

    from kart_tpu.diff.sidecar import SidecarCapture

    if replace_ids is not None:
        replace_existing = True  # implied, as in the reference CLI
        if len(sources) != 1:
            raise ImportError_(
                "--replace-ids requires a single-table import (the id list "
                "would be applied to every table)"
            )
    tb = TreeBuilder(repo.odb, head_tree)
    ds_paths = []
    captures = {}
    total = 0
    phases = _new_phases()
    global LAST_IMPORT_PIPELINE
    LAST_IMPORT_PIPELINE = None  # set by _run_import_pipeline when taken
    t0 = time.monotonic()
    with tm.span("importer.import_sources", sources=len(sources)), repo.odb.bulk_pack():
        for source in sources:
            # PK-less sources get stable generated PKs
            # (reference: kart/pk_generation.py)
            source = PkGeneratingImportSource.wrap_if_needed(source, repo)
            ds_path = source.dest_path.strip("/")
            if ds_path in existing_paths and not replace_existing:
                raise ImportError_(
                    f"Dataset {ds_path!r} already exists — use --replace-existing"
                )
            if replace_existing and replace_ids is None:
                tb.remove(ds_path)
            existing_ds = (
                structure.datasets.get(ds_path) if structure is not None else None
            )
            capture = (
                SidecarCapture() if replace_ids is None else ReplaceIdsCapture()
            )
            count = _import_single_source(
                repo,
                tb,
                source,
                ds_path,
                log=log,
                capture=capture,
                replace_ids=replace_ids,
                existing_ds=existing_ds,
                phases=phases,
            )
            total += count
            ds_paths.append(ds_path)
            captures[ds_path] = (capture, existing_ds)

        with phases.span("tree_build"):
            new_tree = tb.flush()

    # commit + ref update only after the pack is durable (fsync'd) on disk:
    # a crash mid-import leaves an aborted tmp pack and an untouched HEAD,
    # never a dangling ref (reference analog: temp refs refs/kart-import/,
    # fast_import.py:307)
    if message is None:
        message = f"Import {len(ds_paths)} dataset(s): " + ", ".join(ds_paths)
    parents = [repo.head_commit_oid] if repo.head_commit_oid else []
    commit_oid = repo.create_commit("HEAD", new_tree, message, parents)

    # columnar sidecars, straight from the captured import stream — big
    # datasets get O(1) FeatureBlock loads on their first diff. replace-ids
    # imports derive the new sidecar from the old one + the change set
    # (O(changed)), so incremental re-imports keep the columnar cache.
    from kart_tpu.diff import sidecar as sidecar_mod

    root = repo.odb.tree(new_tree)
    for ds_path, (capture, existing_ds) in captures.items():
        node = root.get_or_none(
            f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature"
        )
        if node is None:
            continue
        if isinstance(capture, ReplaceIdsCapture):
            enc = getattr(existing_ds, "path_encoder", None) if existing_ds else None
            if enc is None or enc.scheme != "int":
                continue  # hash-keyed: would need per-path bookkeeping
            old_block = sidecar_mod.load_block(repo, existing_ds)
            if old_block is None:
                continue  # no cache to derive from; rebuilt lazily on use
            sidecar_mod.derive_sidecar(
                repo,
                old_block,
                node.oid,
                capture.removed_pks,
                dict(capture.added),
            )
            continue
        if capture.count < SIDECAR_MIN_FEATURES:
            continue
        capture.save(repo, node.oid)
    dt = time.monotonic() - t0
    global LAST_IMPORT_PHASES
    LAST_IMPORT_PHASES = {**phases.self_seconds(), "total": dt}
    tm.incr("importer.features_imported", total)
    if log:
        rate = total / dt if dt > 0 else float("inf")
        log(f"Imported {total} features in {dt:.2f}s ({rate:.0f} features/s)")
    return commit_oid


def _sanitise_pk(schema, pk):
    """CLI-supplied id (a string) -> the pk column's value type."""
    col = schema.pk_columns[0]
    if col.data_type == "integer":
        try:
            return int(pk)
        except (TypeError, ValueError):
            raise ImportError_(f"Invalid integer primary key: {pk!r}")
    return pk


def _check_replace_ids_compatible(existing_ds, schema, encoder):
    """--replace-ids keeps the existing tree, so the new feature paths must
    land where the old ones live: the path encoder and pk column must match
    the existing dataset, or deletes silently miss and unlisted features
    become unreachable under the rewritten meta."""
    if existing_ds is None:
        return
    old_enc = getattr(existing_ds, "path_encoder", None)
    if old_enc is not None and old_enc.to_dict() != encoder.to_dict():
        raise ImportError_(
            "--replace-ids cannot change the feature path encoding "
            f"({old_enc.to_dict()} -> {encoder.to_dict()}); re-import the "
            "whole dataset with --replace-existing instead"
        )
    old_pks = existing_ds.schema.pk_columns
    new_pks = schema.pk_columns
    if [(c.name, c.data_type) for c in old_pks] != [
        (c.name, c.data_type) for c in new_pks
    ]:
        raise ImportError_(
            "--replace-ids cannot change the primary key "
            f"({[(c.name, c.data_type) for c in old_pks]} -> "
            f"{[(c.name, c.data_type) for c in new_pks]}); re-import the "
            "whole dataset with --replace-existing instead"
        )


class HashedColumns:
    """A msgpack/hash dataset's rows as the import streams them: the
    msgpack of each pk and its blob oid. The feature tree is written from
    them in one columnar pass when the stream ends
    (:func:`kart_tpu.core.feature_tree.write_hash_feature_tree`), as the
    int-pk branch writes its own, instead of a tree-builder insert a path."""

    def __init__(self):
        self.packed = []
        self.oid_chunks = []

    def add(self, packed, oids_u8):
        self.packed.extend(packed)
        self.oid_chunks.append(np.asarray(oids_u8, dtype=np.uint8).reshape(-1, 20))

    def clear(self):
        self.packed.clear()
        self.oid_chunks.clear()

    def write_tree(self, odb, encoder, capture):
        """Write the feature tree (a pk given twice: the last row wins, as a
        tree builder's insert over an insert) and hand its columns to a
        SidecarCapture; -> the tree's hex oid."""
        from kart_tpu.core.feature_tree import write_hash_feature_tree
        from kart_tpu.diff.sidecar import SidecarCapture
        from kart_tpu.models.paths import ByteRows, hash_feature_rows

        rows = hash_feature_rows(ByteRows.from_list(self.packed), encoder)
        oids_u8 = np.concatenate(self.oid_chunks)
        keep = rows.last_wins()
        if len(keep) < len(rows):
            rows, oids_u8 = rows.take(keep), oids_u8[keep]
        root = write_hash_feature_tree(odb, rows, oids_u8, encoder)
        if isinstance(capture, SidecarCapture):
            capture.set_hashed_columns(rows.keys, oids_u8, rows.paths(encoder))
        return root


class ReplaceIdsCapture:
    """What a --replace-ids import changed, for the O(changed) sidecar
    derivation (the incremental-import workflow must not lose the columnar
    cache and fall back to full tree walks)."""

    def __init__(self):
        self.removed_pks = []
        self.added = []  # (pk int, oid hex)


def _import_replace_ids(
    repo, tb, source, schema, encoder, prefix, replace_ids, *,
    log=None, existing_ds=None, capture=None,
):
    """Incremental re-import: delete every listed id's path, re-import the
    ones the source still has. Everything unlisted keeps its existing blob
    and subtree (reference: fast_import.py:462-476 — 'D <path>' per id, then
    stream source.get_features(ids, ignore_missing=True))."""
    if len(schema.pk_columns) != 1:
        raise ImportError_(
            "--replace-ids requires the dataset to have a single-column "
            "primary key"
        )
    _check_replace_ids_compatible(existing_ds, schema, encoder)
    pks = [_sanitise_pk(schema, pk) for pk in replace_ids]
    for pk in pks:
        tb.remove(prefix + encoder.encode_pks_to_path((pk,)))
    if capture is not None:
        capture.removed_pks = pks

    count = 0
    for batch in chunked(
        source.get_features(pks, ignore_missing=True), BATCH_SIZE
    ):
        encoded = [schema.encode_feature_blob(f) for f in batch]
        rel_paths = [encoder.encode_pks_to_path(pkv) for pkv, _ in encoded]
        oids = repo.odb.write_blobs([blob for _, blob in encoded])
        tb.insert_many((prefix + rel for rel in rel_paths), oids)
        if capture is not None:
            capture.added.extend(
                (pkv[0], oid) for (pkv, _), oid in zip(encoded, oids)
            )
        count += len(batch)
    if log:
        log(
            f"  replaced {count} of {len(pks)} listed id(s); "
            f"{len(pks) - count} deleted"
        )
    return count


def _import_single_source(
    repo, tb, source, ds_path, *, log=None, capture=None, replace_ids=None,
    existing_ds=None, phases=None,
):
    from kart_tpu.diff.sidecar import SidecarCapture

    if phases is None:
        phases = _new_phases()

    schema = source.schema
    encoder = encoder_for_schema(schema)
    meta = source.meta_items()
    meta_blobs = Dataset3.new_dataset_meta_blobs(
        ds_path,
        schema,
        title=meta.get("title"),
        description=meta.get("description"),
        crs_defs=source.crs_definitions(),
        path_encoder=encoder,
    )
    for path, data in meta_blobs:
        tb.insert(path, repo.odb.write_blob(data))

    from kart_tpu.importer import parallel as par
    from kart_tpu.importer import pipeline as pipe

    prefix = f"{ds_path}/{Dataset3.DATASET_DIRNAME}/{Dataset3.FEATURE_PATH}"

    if replace_ids is not None:
        return _import_replace_ids(
            repo, tb, source, schema, encoder, prefix, replace_ids,
            log=log, existing_ds=existing_ds, capture=capture,
        )

    # --- path routing: pipelined, parallel fan-out, or serial ------------
    # A native-read-capable source takes the pipeline: its fused
    # read+encode stage runs GIL-free at >1M rows/s, which beats the
    # process fan-out's per-worker interpreter encode on any core count we
    # can measure (teaching the fan-out workers to use the native reader
    # per shard is the open item). Fan-out remains the big-box path for
    # python-encoded sources.
    mode = pipe.pipeline_mode()
    n_workers = par.default_workers()
    if n_workers > 1:
        # satellite fix: never more workers than the import has work for —
        # a pool member costs a spawned interpreter + full module import
        n_workers = par.clamp_workers(n_workers, source.feature_count)
    native_pipe = mode != "off" and pipe.native_read_capable(source, encoder)
    if (
        mode != "force"
        and not native_pipe
        and n_workers > 1
        and par.shardable(source, encoder, n_workers)
    ):
        count = par.run_parallel_import(
            repo, tb, source, ds_path, encoder, prefix, n_workers,
            log=log, capture=capture,
        )
        return count

    use_pipeline = mode == "force" or (
        mode == "auto" and source.feature_count >= pipe.PIPELINE_MIN_FEATURES
    )
    if use_pipeline and repo.odb._bulk_writer is None:
        use_pipeline = False  # the pipeline pack stage needs the bulk writer

    count = 0
    use_batch_paths = encoder.scheme == "int"
    hashed = None if use_batch_paths else HashedColumns()
    # int-pk fast path: (pks, oid bytes) -> vectorized tree build. When a
    # SidecarCapture is running it already holds these columns; only
    # accumulate separately without one (a 100M import must not hold two
    # 2.8GB copies)
    collect_local = use_batch_paths and not isinstance(capture, SidecarCapture)
    pk_chunks = []
    oid_chunks = []
    # the streaming loop allocates short-lived, acyclic objects by the
    # million: pause the cyclic collector (~8% measured). Source adapters
    # may create cycles internally, so bound their growth with a manual
    # collection every ~1M rows rather than trusting full acyclicity.
    # Fast pre-encoded stream (int-pk GPKG): the source yields whole
    # (pk_list, blob_list) batches and oids stay columnar end-to-end — no
    # per-feature dicts, no per-row tuples, no hex round trips (see
    # GPKGImportSource.encoded_feature_batches).
    fast_batches = None
    if use_batch_paths and not use_pipeline:
        fast = getattr(source, "encoded_feature_batches", None)
        if fast is not None:
            fast_batches = fast(schema)

    stream_root = None
    with paused_gc():
        gc_batch = 0
        if use_pipeline:
            count, stream_root = _run_import_pipeline(
                repo, tb, source, schema, encoder, prefix,
                capture=capture,
                collect_local=collect_local,
                pk_chunks=pk_chunks,
                oid_chunks=oid_chunks,
                use_batch_paths=use_batch_paths,
                hashed=hashed,
                log=log,
                ds_path=ds_path,
            )
        elif fast_batches is not None:
            # phase timing: the generator fuses source read + encode; its
            # own phase_seconds split (the GPKG source keeps one) is folded
            # in below — here the generator pull is accounted as encode
            # and rebalanced from the source's accumulators afterwards
            for pk_list, blobs in _timed_iter(fast_batches, phases, "encode"):
                gc_batch += 1
                if gc_batch % 100 == 0:
                    gc.collect()
                with phases.span("hash_deflate"):
                    oids_u8 = repo.odb.write_blobs_raw(blobs)
                pks = np.asarray(pk_list, dtype=np.int64)
                if collect_local:
                    pk_chunks.append(pks)
                    oid_chunks.append(oids_u8.tobytes())
                if capture is not None:
                    capture.add_int_raw(pks, oids_u8.tobytes())
                count += len(pk_list)
                if log and count % 100000 == 0:
                    log(f"  {ds_path}: {count} features...")
            src_phases = getattr(source, "phase_seconds", None)
            if src_phases:
                read_s = min(
                    src_phases.get("source_read", 0.0),
                    phases.self_s.get("encode", 0.0),
                )
                phases.move("encode", "source_read", read_s)
        else:
            for batch in chunked(_timed_iter(source.features(), phases), BATCH_SIZE):
                gc_batch += 1
                if gc_batch % 100 == 0:
                    gc.collect()
                with phases.span("encode"):
                    encoded = [schema.encode_feature_blob(f) for f in batch]
                with phases.span("hash_deflate"):
                    oids = repo.odb.write_blobs([blob for _, blob in encoded])
                if use_batch_paths:
                    pks = np.fromiter(
                        (pk_values[0] for pk_values, _ in encoded),
                        dtype=np.int64,
                        count=len(encoded),
                    )
                    # no per-path TreeBuilder inserts: the whole feature tree
                    # is built in one vectorized pass after the stream
                    if collect_local:
                        pk_chunks.append(pks)
                        oid_chunks.append(bytes.fromhex("".join(oids)))
                else:
                    hashed.add(
                        [msg_pack(pk_values) for pk_values, _ in encoded],
                        np.frombuffer(bytes.fromhex("".join(oids)), dtype=np.uint8),
                    )
                if capture is not None and use_batch_paths:
                    capture.add_int_batch(pks, oids)
                count += len(batch)
                if log and count % 100000 == 0:
                    log(f"  {ds_path}: {count} features...")

    if hashed is not None and count:
        from kart_tpu.core.objects import MODE_TREE

        with phases.span("tree_build"):
            tb.insert(
                f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature",
                hashed.write_tree(repo.odb, encoder, capture),
                mode=MODE_TREE,
            )
    elif use_batch_paths and count and stream_root is not None:
        # the pipeline already built (and wrote) the feature tree from the
        # sorted stream — the strictly-increasing pk guarantee it enforces
        # also rules out duplicate pks, so no last-wins resolution needed
        from kart_tpu.core.objects import MODE_TREE

        with phases.span("tree_build"):
            tb.insert(
                f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature",
                stream_root,
                mode=MODE_TREE,
            )
    elif use_batch_paths and count:
        from kart_tpu.core.feature_tree import build_int_feature_tree
        from kart_tpu.core.objects import MODE_TREE

        cols = capture.int_columns() if isinstance(capture, SidecarCapture) else None
        if cols is not None:
            pks_arr, oids_u8 = cols
        else:
            pks_arr = np.concatenate(pk_chunks)
            oids_u8 = np.frombuffer(b"".join(oid_chunks), dtype=np.uint8).reshape(
                -1, 20
            )
        # duplicate pks in the source: last occurrence wins (git fast-import
        # semantics, matching the TreeBuilder dict path). One stable sort
        # both detects and resolves them.
        if len(pks_arr) > 1:
            order = np.argsort(pks_arr, kind="stable")
            sorted_pks = pks_arr[order]
            is_last = np.append(sorted_pks[1:] != sorted_pks[:-1], True)
            if not is_last.all():
                keep = np.sort(order[is_last])
                pks_arr = pks_arr[keep]
                oids_u8 = oids_u8[keep]
                if isinstance(capture, SidecarCapture):
                    # the sidecar must mirror the committed tree, not the
                    # raw stream — a stale duplicate row would later pair
                    # against the live head in the columnar merge-join and
                    # surface as a spurious UPDATE
                    capture.replace_int_columns(pks_arr, oids_u8)
        with phases.span("tree_build"):
            ftree = build_int_feature_tree(repo.odb, pks_arr, oids_u8, encoder)
            tb.insert(
                f"{ds_path}/{Dataset3.DATASET_DIRNAME}/feature",
                ftree,
                mode=MODE_TREE,
            )

    # meta items that only exist after the feature stream has run (e.g.
    # generated-pks.json from PK synthesis)
    late_meta = source.post_import_meta_items()
    if late_meta:
        from kart_tpu.core.serialise import json_pack

        inner = f"{ds_path}/{Dataset3.DATASET_DIRNAME}"
        for name, value in late_meta.items():
            data = json_pack(value) if not isinstance(value, bytes) else value
            tb.insert(f"{inner}/{Dataset3.META_PATH}{name}", repo.odb.write_blob(data))

    if log:
        log(f"  {ds_path}: {count} features")
    return count


def _run_import_pipeline(
    repo, tb, source, schema, encoder, prefix, *,
    capture, collect_local, pk_chunks, oid_chunks, use_batch_paths,
    log, ds_path, hashed=None,
):
    """Stream one source through the bounded 4-stage pipeline
    (:mod:`kart_tpu.importer.pipeline`): fused read+encode (ONE native
    call per batch for GPKG int-pk sources — io_gpkg_*) || native
    hash+deflate || pack write, with (pk, oid) columns collected on this
    thread in stream order. The sorted pk stream also drives the leaf-tree
    build *during* the stream: completed leaves are serialised here
    (:class:`~kart_tpu.core.feature_tree.StreamingLeafEmitter`) and
    injected through the hash/pack stages on the pipeline's side channel,
    so the Merkle build that used to run as a serial tail overlaps the
    feature stream. Byte-identical to the serial path (same blobs, same
    leaf payloads, same root oid — property tested); stage busy seconds
    land in :data:`LAST_IMPORT_PIPELINE`.
    -> (feature count, stream-built feature-root hex oid or None)."""
    import time as _time

    from kart_tpu import native
    from kart_tpu.core.feature_tree import StreamingLeafEmitter
    from kart_tpu.core.packs import TYPE_CODES
    from kart_tpu.importer.pipeline import run_pipeline

    writer = repo.odb._bulk_writer
    level = writer.level
    blob_code = TYPE_CODES["blob"]
    tree_code = TYPE_CODES["tree"]

    # --- fused read+encode producer ---------------------------------------
    # Read and encode share one thread on purpose. For native-capable GPKG
    # sources both run inside one GIL-free ctypes call per batch
    # (native_encoded_batches). The Python producers fuse them too: both
    # are GIL-bound, so a thread split buys no parallelism and costs a GIL
    # ping-pong per batch (see kart_tpu/importer/pipeline.py); the split is
    # preserved in *accounting* via phase_seconds and read/encode spans.
    def _make_producer(allow_native):
        if use_batch_paths and allow_native:
            nat = getattr(source, "native_encoded_batches", None)
            if nat is not None:
                from kart_tpu.importer.pipeline import batch_rows

                # None when native read is unavailable
                producer = nat(schema, batch_rows=batch_rows())
                if producer is not None:
                    return producer
        fast = getattr(source, "encoded_feature_batches", None)
        fb = fast(schema) if (use_batch_paths and fast is not None) else None
        if fb is not None:
            return (("py",) + tuple(item) for item in fb)
        # generic sources: stream features, encode through the compiled
        # per-legend blob serialiser (models/dataset.py)
        from kart_tpu.models.dataset import compiled_blob_encoder

        blob_enc = compiled_blob_encoder(schema)

        def _generic_producer():
            for batch in chunked(source.features(), BATCH_SIZE):
                with tm.span("importer.encode", rows=len(batch)):
                    keys = []
                    blobs = []
                    for feature in batch:
                        pk_values, blob = blob_enc(feature)
                        keys.append(
                            pk_values[0] if use_batch_paths
                            else msg_pack(pk_values)
                        )
                        blobs.append(blob)
                yield ("py", keys, blobs)

        return _generic_producer()

    # --- streamed leaf-tree build -----------------------------------------
    # only engaged when the native IO core can hash/deflate the injected
    # payload batches; without it the end-of-stream build is just as fast
    # as a Python side channel would be
    leaf_stream = None
    if use_batch_paths and native.load_io() is not None:
        leaf_stream = StreamingLeafEmitter(encoder)
        if not leaf_stream.ok:
            leaf_stream = None

    # --- hash + pack stage functions --------------------------------------
    def hash_fn(item):
        tag = item[0]
        if tag == "enc":
            _, pks, buf, offs = item
            framed = native.pack_records_base("blob", blob_code, buf, offs, level)
            if framed is not None:
                return ("bf", pks, framed)
            # native lib lost mid-run (never in practice): slice + retry
            blobs = [
                buf[offs[i] : offs[i + 1]].tobytes() for i in range(len(pks))
            ]
            return (
                "pyf", pks, blobs,
                native.pack_records_batch("blob", blob_code, blobs, level),
            )
        if tag == "py":
            _, keys, blobs = item
            return (
                "pyf", keys, blobs,
                native.pack_records_batch("blob", blob_code, blobs, level),
            )
        # "tree": an injected leaf-payload batch from the side channel
        _, buf, offs, leaf_ids = item
        framed = native.pack_records_base("tree", tree_code, buf, offs, level)
        return ("tf", leaf_ids, framed, buf, offs)

    def pack_fn(item):
        tag = item[0]
        if tag == "bf":
            _, pks, framed = item
            return ("f", pks, writer.append_framed(framed))
        if tag == "pyf":
            _, keys, blobs, framed = item
            if framed is not None:
                return ("f", keys, writer.append_framed(framed))
            hexes = [writer.add("blob", b) for b in blobs]
            return (
                "f", keys,
                np.frombuffer(
                    bytes.fromhex("".join(hexes)), dtype=np.uint8
                ).reshape(-1, 20),
            )
        # "tf"
        _, leaf_ids, framed, buf, offs = item
        if framed is not None:
            return ("t", leaf_ids, writer.append_framed(framed))
        payloads = [
            buf[offs[i] : offs[i + 1]].tobytes()
            for i in range(len(leaf_ids))
        ]
        hexes = writer.add_batch("tree", payloads)
        return (
            "t", leaf_ids,
            np.frombuffer(
                bytes.fromhex("".join(hexes)), dtype=np.uint8
            ).reshape(-1, 20),
        )

    # --- main-thread collector --------------------------------------------
    count = 0
    gc_batch = 0
    tree_oid_chunks = []  # (n, 20) leaf oids, in leaf emission order
    tree_busy = 0.0

    def consume(item, inject=None):
        nonlocal count, gc_batch, tree_busy
        if item[0] == "t":
            tree_oid_chunks.append(item[2])
            return
        _, keys, oids_u8 = item
        gc_batch += 1
        if gc_batch % 100 == 0:
            gc.collect()  # bound any source-adapter cycles (gc is paused)
        if use_batch_paths:
            pks = (
                keys if isinstance(keys, np.ndarray)
                else np.asarray(keys, dtype=np.int64)
            )
            if collect_local:
                pk_chunks.append(pks)
                oid_chunks.append(oids_u8.tobytes())
            if capture is not None:
                capture.add_int_raw(pks, oids_u8.tobytes())
            if leaf_stream is not None and leaf_stream.ok and inject is not None:
                t0 = _time.perf_counter()
                with tm.span("importer.tree"):
                    out = leaf_stream.feed(pks, oids_u8)
                tree_busy += _time.perf_counter() - t0
                if out is not None:
                    buf, offs, leaf_ids = out
                    inject(("tree", buf, offs, leaf_ids))
        else:
            hashed.add(keys, oids_u8)
        count += len(keys)
        if log and count % 100000 < len(keys):
            log(f"  {ds_path}: {count} features...")

    def on_feat_done(inject):
        nonlocal tree_busy
        if leaf_stream is not None and leaf_stream.ok:
            t0 = _time.perf_counter()
            out = leaf_stream.finish()
            tree_busy += _time.perf_counter() - t0
            if out is not None:
                buf, offs, leaf_ids = out
                inject(("tree", buf, offs, leaf_ids))

    # --- drive the pipeline (one native-reader fallback retry) ------------
    # A row the native fused reader can't reproduce bit-identically raises
    # GpkgReaderFallback out of the producer mid-stream: reset every
    # collector the partial run touched and re-stream through the Python
    # encoder. Blobs already appended dedupe in the pack writer, so the
    # restart costs only the re-read, never a corrupt repo.
    cap_mark = capture.mark() if capture is not None else None
    base_pk_chunks = len(pk_chunks)
    base_oid_chunks = len(oid_chunks)

    def _reset_collectors():
        nonlocal count, gc_batch, tree_busy, leaf_stream
        count = 0
        gc_batch = 0
        tree_busy = 0.0
        tree_oid_chunks.clear()
        if hashed is not None:
            hashed.clear()
        del pk_chunks[base_pk_chunks:]
        del oid_chunks[base_oid_chunks:]
        if capture is not None:
            capture.rewind(cap_mark)
        if leaf_stream is not None:
            # leaves already emitted reference the abandoned stream: start a
            # fresh emitter (stale leaf objects in the pack are benign)
            leaf_stream = StreamingLeafEmitter(encoder)
            if not leaf_stream.ok:
                leaf_stream = None

    t0 = _time.perf_counter()
    with tm.span("importer.pipeline", source=type(source).__name__):
        allow_native = True
        while True:
            producer = _make_producer(allow_native)
            try:
                stage_s = run_pipeline(
                    producer,
                    [("hash", hash_fn), ("pack", pack_fn)],
                    consume,
                    producer_span=False,
                    side_stage="hash" if leaf_stream is not None else None,
                    on_feat_done=(
                        on_feat_done if leaf_stream is not None else None
                    ),
                )
                break
            except native.GpkgReaderFallback:
                if not allow_native:
                    raise  # the Python encoder never raises this
                allow_native = False
                L.warning(
                    "native GPKG reader met a row it cannot reproduce "
                    "bit-identically; restarting import stream through "
                    "the Python encoder"
                )
                _reset_collectors()
    wall = _time.perf_counter() - t0

    # the stream-built feature tree: every leaf the emitter serialised came
    # back hashed; the upper spine is built here (cheap — branches^-1 of
    # the leaf count) now the stage threads have quiesced
    stream_root = None
    if leaf_stream is not None and leaf_stream.ok and count:
        n_leaves = sum(len(c) for c in leaf_stream.leaf_id_chunks)
        n_hashed = sum(len(c) for c in tree_oid_chunks)
        assert n_leaves == n_hashed, (n_leaves, n_hashed)
        with tm.span("importer.tree"):
            stream_root = leaf_stream.build_root(repo.odb, tree_oid_chunks)

    # split the fused producer's busy time back into read/encode when the
    # source kept its own phase accounting (the fast GPKG generators do)
    produce_s = stage_s.get("produce", 0.0)
    src_phases = getattr(source, "phase_seconds", None) or {}
    read_s = min(src_phases.get("source_read", 0.0), produce_s)
    global LAST_IMPORT_PIPELINE
    LAST_IMPORT_PIPELINE = {
        "read": read_s,
        "encode": produce_s - read_s,
        "hash": stage_s.get("hash", 0.0),
        "pack": stage_s.get("pack", 0.0),
        "tree": tree_busy,
        "wall": wall,
    }
    tm.incr("importer.pipeline_batches", gc_batch)
    return count, stream_root
