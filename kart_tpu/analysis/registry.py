"""Machine-readable registries for the cross-cutting contracts `kart lint`
enforces (docs/ANALYSIS.md).

These are *declarations*: the rules in :mod:`kart_tpu.analysis.rules` check
the actual tree against them in both directions — an ``os.environ`` read of
an undeclared ``KART_*`` name is a finding (KTL001), and so is a declared
name nothing reads any more. The registries deliberately live in one small
data-only module so a PR that grows the surface (a new env var, a new fault
point) touches the declaration, the docs index, and the code in the same
diff — that co-location is the contract.
"""

import re

# ---------------------------------------------------------------------------
# KTL001 — the KART_* environment-variable surface
# ---------------------------------------------------------------------------

#: scopes: "source" = read somewhere under kart_tpu/ or bench.py (the lint
#: targets); "tests" = read only by the test suite / conftest. Both must
#: appear in docs/OBSERVABILITY.md §7; only "source" entries must have a
#: live read site.
ENV_VARS = {
    # telemetry / logging (docs/OBSERVABILITY.md §7 "Telemetry / logging")
    "KART_TRACE": "source",
    "KART_METRICS": "source",
    "KART_LOG": "source",
    # request-scoped observability (docs/OBSERVABILITY.md §8-§11)
    "KART_SLOW_REQUEST_SECONDS": "source",
    "KART_ACCESS_LOG": "source",
    "KART_STATS_WINDOWS": "source",
    # transport (ROBUSTNESS.md §1-§4)
    "KART_TRANSPORT_RETRIES": "source",
    "KART_TRANSPORT_RETRY_BASE": "source",
    "KART_TRANSPORT_RETRY_CAP": "source",
    "KART_HTTP_TIMEOUT": "source",
    "KART_STDIO_TIMEOUT": "source",
    "KART_SSH": "source",
    "KART_SSH_KART": "source",
    # serving (docs/SERVING.md)
    "KART_SERVE_ENUM_CACHE": "source",
    "KART_SERVE_MAX_INFLIGHT": "source",
    "KART_SERVE_RETRY_AFTER": "source",
    "KART_SERVE_REBASE_ATTEMPTS": "source",
    "KART_SERVE_MERGE_QUEUE": "source",
    "KART_SERVE_TILES": "source",
    # tiles (docs/TILES.md)
    "KART_TILE_CACHE": "source",
    "KART_TILE_MAX_FEATURES": "source",
    "KART_TILE_ENCODING": "source",
    "KART_EXPORT_WORKERS": "source",
    "KART_EXPORT_BATCH_TILES": "source",
    # fleet (docs/FLEET.md)
    "KART_REPLICA_OF": "source",
    "KART_REPLICA_POLL_SECONDS": "source",
    "KART_REPLICA_MAX_LAG": "source",
    "KART_PEER_CACHE": "source",
    # live-update events (docs/EVENTS.md)
    "KART_SERVE_EVENTS": "source",
    "KART_EVENTS_LOG_SIZE": "source",
    "KART_EVENTS_WARM_BUDGET": "source",
    "KART_WATCH_TIMEOUT": "source",
    # faults / maintenance (ROBUSTNESS.md §5-§6)
    "KART_FAULTS": "source",
    "KART_GC_GRACE": "source",
    # diff engine / kernels
    "KART_DIFF_ENGINE": "source",
    "KART_DIFF_BACKEND": "source",
    "KART_DIFF_DEVICE": "source",
    "KART_DIFF_SHARDED": "source",
    "KART_DEVICE_BATCH_ROWS": "source",
    "KART_BLOCK_PRUNE": "source",
    "KART_FUSED_JSONL": "source",
    # import / store
    "KART_IMPORT_WORKERS": "source",
    "KART_IMPORT_FAST": "source",
    "KART_IMPORT_PIPELINE": "source",
    "KART_IMPORT_QUEUE_BATCHES": "source",
    "KART_IMPORT_NATIVE_READ": "source",
    "KART_IMPORT_BATCH_ROWS": "source",
    "KART_PACK_STORE_MAX": "source",
    # runtime / JAX
    "KART_NO_JAX": "source",
    "KART_JAX_INIT_TIMEOUT": "source",
    "KART_JAX_REPROBE": "source",
    "KART_NO_XLA_CACHE": "source",
    "KART_PROBE_CACHE": "source",
    "KART_TESTS_ON_TPU": "tests",
    # native library
    "KART_TPU_NATIVE_LIB": "source",
    "KART_TPU_NATIVE_IO_LIB": "source",
    "KART_NO_NATIVE_BUILD": "source",
    # query (docs/QUERY.md)
    "KART_QUERY_BATCH_ROWS": "source",
    "KART_QUERY_PAGE_SIZE": "source",
    "KART_QUERY_SCATTER": "source",
    "KART_QUERY_CACHE": "source",
    # geometry / exact refine (docs/QUERY.md §4b, docs/TILES.md §6)
    "KART_GEOM_REFINE": "source",
    "KART_GEOM_BATCH_ROWS": "source",
    "KART_GEOM_SIMPLIFY": "source",
    # misc
    "KART_REPO": "source",
    "KART_NTV2_GRID_DIR": "source",
}

#: prefix wildcards: any KART_<prefix>* read is declared by one entry here
#: and one ``KART_<prefix>*`` row in the docs index (bench.py's per-section
#: knobs would otherwise need a dozen rows nobody reads).
ENV_PREFIXES = {
    "KART_BENCH_": "source",
}

#: where the human-readable index lives; KTL001 round-trips against the
#: ```KART_*`` names in this section (repo-relative path, section heading).
ENV_DOC = ("docs/OBSERVABILITY.md", "environment variable index")


def env_declared(name):
    """Is ``name`` declared, directly or via a prefix wildcard?"""
    return name in ENV_VARS or any(name.startswith(p) for p in ENV_PREFIXES)


# ---------------------------------------------------------------------------
# KTL003 — fault-injection points (kart_tpu/faults.py)
# ---------------------------------------------------------------------------

#: every ``faults.hook``/``faults.fire`` point in the tree. Each must also
#: be exercised by the tests/test_faults.py kill matrix — a fault point
#: nobody injects is untested crash-handling code.
FAULT_POINTS = frozenset(
    {
        "transport.read.frame",
        "transport.write.frame",
        "odb.write_raw",
        "odb.bulk_pack",
        "pack.finalise",
        "idx.write",
        "import.encode",
        "import.pack_stream",
        "diff.device_transfer",
        "server.enum_cache",
        "server.shed",
        "server.rebase",
        "server.ref_cas",
        "tiles.encode",
        "tiles.cache",
        "tiles.streams",
        "tiles.export",
        "fleet.sync",
        "fleet.proxy",
        "events.emit",
        "events.warm",
        "query.scan",
        "query.join",
        "query.refine",
        "geom.extract",
    }
)

#: the kill matrix that must reference every point above.
FAULT_TESTS = "tests/test_faults.py"

# ---------------------------------------------------------------------------
# KTL004 — crash-leftover file patterns the gc/fsck sweep covers
# ---------------------------------------------------------------------------

#: mirror of kart_tpu.core.repo._STALE_FILE_RE — KTL004 asserts the two
#: stay textually identical (a drift means code writes temp files gc can no
#: longer recognise). Covers ``<name>.tmp<pid>``, ``<name>.lock<pid>`` and
#: PackWriter's ``.tmp-pack-*`` mkstemp prefix.
GC_SWEEP_RE = re.compile(r"(\.(tmp|lock)\d*$)|(^\.tmp-)")

# ---------------------------------------------------------------------------
# KTL011 — deliberate blocking-under-lock sections
# ---------------------------------------------------------------------------

#: functions whose lock-held region *intentionally* contains blocking work
#: (coarse serialisation locks): "rel::qualname" -> rationale. KTL011 skips
#: findings inside these bodies but still requires the entry to name a live
#: function — a stale entry is itself a finding. Prefer a narrower lock
#: over a new entry here.
BLOCKING_ALLOW = {
    "kart_tpu/core/odb.py::ObjectDb.bulk_pack": (
        "the bulk-pack lock IS the serialisation: one _bulk_writer slot, so "
        "concurrent pushes must block for the whole pack write (fdatasync "
        "and flusher join included) instead of interleaving objects into "
        "each other's packs"
    ),
    "kart_tpu/transport/service.py::_land_quarantined": (
        "the push critical section deliberately holds the thread+file push "
        "locks across quarantine migrate and ref CAS — releasing mid-way is "
        "exactly the torn-push window PR 2/PR 8 closed"
    ),
    "kart_tpu/transport/service.py::locked_ref_updates": (
        "the back-compat push entry point: ref validation + apply must run "
        "as one unit under the cross-process push lock, same section the "
        "quarantine path holds (docs/SERVING.md §6)"
    ),
    "kart_tpu/tiles/source.py::TileSource.envelopes": (
        "the envelope-fallback build intentionally runs its O(N) blob scan "
        "under the per-source lock: concurrent envelope callers for one "
        "commit must block on the one build rather than each paying it "
        "(docs/TILES.md §2); tile requests for other commits use other "
        "TileSource instances and other locks"
    ),
    "kart_tpu/tiles/source.py::TileSource.vertices": (
        "the vertex-fallback build is the envelope fallback's sibling: one "
        "O(N) blob extraction per revision under the per-source lock, so "
        "concurrent geom-layer requests block on the one build instead of "
        "each paying it (docs/TILES.md §6)"
    ),
}

# ---------------------------------------------------------------------------
# KTL014 — the byte-budgeted cache surface and its invalidation contract
# ---------------------------------------------------------------------------

#: every byte-budgeted cache in the serving path. Keys are the telemetry-
#: style cache names; each entry declares where the cache lives, the
#: LRU-shaped module global registering instances, the key-builder whose
#: source must reference a commit-/ref-pinning token (commit-addressed
#: keys are the invalidation-by-construction half of the contract), and
#: the drop hook `_apply_validated_updates` must call on a ref update —
#: or, when no drop is needed, a written rationale. KTL014 cross-checks
#: all of this in both directions (code <-> registry), like KTL001/KTL003.
CACHES = {
    "server.enum_cache": {
        "module": "kart_tpu/transport/service.py",
        "cls": "PackEnumCache",
        "registry_global": "_ENUM_CACHES",
        "key_fn": "_enum_cache_key",
        "key_tokens": ("refs_fingerprint",),
        "ref_drop": "invalidate",
    },
    "tiles.cache": {
        "module": "kart_tpu/tiles/cache.py",
        "cls": "TileCache",
        "registry_global": "_TILE_CACHES",
        "key_fn": "tile_key",
        "key_tokens": ("commit_oid",),
        "ref_drop": "invalidate_tile_caches",
    },
    "tiles.source": {
        "module": "kart_tpu/tiles/source.py",
        "cls": None,  # plain commit-keyed LRU, not a SingleFlightLRU
        "registry_global": "_SOURCES",
        "key_fn": "source_for",
        "key_tokens": ("commit_oid",),
        "ref_drop": None,
        "ref_drop_rationale": (
            "source keys pin (gitdir, commit oid, dataset) and a commit's "
            "blocks never change, so a ref move cannot stale them; the LRU "
            "bound alone reclaims memory (docs/TILES.md §3)"
        ),
    },
    "query.cache": {
        "module": "kart_tpu/query/cache.py",
        "cls": "QueryCache",
        "registry_global": "_QUERY_CACHES",
        "key_fn": "query_request_key",
        "key_tokens": ("commit_oid",),
        "ref_drop": "invalidate_query_caches",
    },
    "fleet.peer_cache": {
        "module": "kart_tpu/fleet/peercache.py",
        "cls": "PeerCache",
        "registry_global": "_PEER_CACHES",
        "key_fn": "peer_key",
        "key_tokens": ("commit_pinned_key",),
        "ref_drop": None,
        "ref_drop_rationale": (
            "entries are keyed by the origin cache's own commit-addressed "
            "key (tile keys embed the commit oid, fetch-pack keys the exact "
            "refs fingerprint) and a fetch is only accepted when the peer's "
            "strong validator equals the locally computed one — a ref move "
            "changes what new requests compute, never what an existing key "
            "means; the LRU bound alone reclaims memory (docs/FLEET.md §4). "
            "Replicas also never run _apply_validated_updates (writes are "
            "proxied; refs advance via the sync loop), so the hook could "
            "not fire there anyway"
        ),
    },
    "diff.device.resident": {
        "module": "kart_tpu/ops/resident.py",
        "cls": "PageStore",
        "registry_global": None,  # one store a process: resident.PAGES
        "key_fn": "page_key",
        "key_tokens": ("tree_oid",),
        "ref_drop": None,
        "ref_drop_rationale": (
            "page keys pin the feature tree's oid — the sidecar's own "
            "content address — with the column, the page number and the "
            "page's rows, and a tree oid never changes meaning, so a ref "
            "move cannot stale a page (the rationale tiles.source gives "
            "for its commit-keyed blocks); blocks that name no tree are "
            "never kept; the byte budget alone reclaims device memory "
            "(docs/DEVICE.md §5)"
        ),
    },
}

#: where every ref update funnels; the declared ``ref_drop`` hooks above
#: must be invoked inside this function's body.
REF_UPDATE_HOOK = ("kart_tpu/transport/service.py", "_apply_validated_updates")

#: the live-update emission hook (docs/EVENTS.md §3): the same ref-update
#: funnel must call this function so a landed push books its CDC event —
#: KTL014 checks the call the same way it checks the cache drop hooks (a
#: push that silently skipped emission would strand every subscriber on
#: its poll fallback).
EVENT_EMIT_HOOK = "notify_ref_updates"

#: LRU-shaped module globals (OrderedDict + popitem eviction) that are NOT
#: commit-addressed data caches and therefore owe no invalidation drop:
#: "rel::NAME" -> rationale. A stale entry is a finding.
CACHE_EXEMPT_GLOBALS = {
    "kart_tpu/transport/service.py::_MERGE_QUEUES": (
        "a registry of per-ref FIFO queues, not cached data: correctness "
        "lives with push_file_lock; eviction only unlinks idle queues"
    ),
    "kart_tpu/ops/blocks.py::_VERTEX_MEMO": (
        "content-addressed, not commit/ref-addressed: the key is the sha1 "
        "of the decoded section's own bytes, so two different byte strings "
        "can never share an entry and no ref move can stale one — the LRU "
        "bound alone reclaims memory (docs/FORMAT.md §3.4)"
    ),
    "kart_tpu/events/__init__.py::_EMITTERS": (
        "a registry of per-repo event emitters, not cached data: the "
        "announced history and tips live in the on-disk event log, and a "
        "re-created emitter reconciles from it byte-for-byte; eviction "
        "only parks an idle worker (docs/EVENTS.md §3)"
    ),
}

# ---------------------------------------------------------------------------
# KTL020/KTL021 — the device execution surface
# ---------------------------------------------------------------------------

#: the only files allowed to import jax (always lazily, inside functions —
#: KTL021 flags module-top-level jax imports even here: `import jax` costs
#: ~1.8s and the CLI's small-repo paths must never pay it). bench.py
#: deliberately drives devices directly for the --multichip sweep.
DEVICE_MODULES = frozenset(
    {
        "kart_tpu/diff/backend.py",
        "kart_tpu/diff/device_batch.py",
        "kart_tpu/ops/_lazy.py",
        "kart_tpu/ops/bbox.py",
        "kart_tpu/ops/diff_kernel.py",
        "kart_tpu/ops/merge_kernel.py",
        "kart_tpu/ops/resident.py",
        "kart_tpu/parallel/__init__.py",
        "kart_tpu/parallel/mesh.py",
        "kart_tpu/routing.py",
        "kart_tpu/runtime.py",
        "bench.py",
    }
)

#: the fallback seam: the only names non-device modules may import from a
#: device module. Every entry either routes through an internal cost model
#: with a host fallback, is a host-only helper (numpy twins, constants),
#: or is device-independent plumbing. KTL021 checks both directions: an
#: import outside this list is a finding, and so is a listed name its
#: module no longer defines.
DEVICE_SEAMS = {
    "kart_tpu/diff/backend.py": frozenset(
        {
            # project_envelopes is the pyramid exporter's batch seam: host
            # numpy by default, shard_map when the probe says devices are
            # live, host fallback mid-call — the first non-diff workload
            "select_backend",
            "warm_probe",
            "project_envelopes",
            # join_bbox_counts is the query engine's spatial-join batch
            # seam: same gating ladder as project_envelopes
            "join_bbox_counts",
            # refine_intersects is the exact-refine seam (ISSUE 20): host
            # numpy predicates by default, shard_map when the row count
            # clears the sharding floor, host fallback mid-call
            "refine_intersects",
            # classify_span opens the diff's `diff.classify` span (no
            # device work of its own)
            "classify_span",
            # merge_classify: the diff's classify twice through
            # select_backend's backend (each diff its own host fallback),
            # then the three-way rule on the host
            "merge_classify",
            # the host overlap predicate the join counts with — the refine
            # stage recomputes it to recover the exact pair set the counts
            # hold (pure numpy, no device dependency)
            "_join_overlap_np",
        }
    ),
    "kart_tpu/diff/device_batch.py": frozenset(
        {
            # the pair packer is pure numpy (gathers from the cached
            # segment table into padded slabs) — host refine evaluates
            # the very same slabs the device kernel consumes, which is
            # half of the bit-identity argument (docs/DEVICE.md §6)
            "pack_geom_pairs",
        }
    ),
    "kart_tpu/ops/bbox.py": frozenset(
        {
            # bbox_intersects asks kart_tpu.routing (row floor, jax_ready())
            # and falls back to the native/numpy host scan; *_np names are
            # the host twins
            "bbox_intersects",
            "bbox_intersects_np",
            "bbox_blocks_np",
            "classify_env_blocks_np",
            "BLOCK_ALL_IN",
            "BLOCK_ALL_OUT",
            "BLOCK_BOUNDARY",
        }
    ),
    "kart_tpu/ops/diff_kernel.py": frozenset(
        {
            # classify_blocks owns cost-model routing + host fallback;
            # changed_indices is pure numpy; the rest are class constants
            "classify_blocks",
            "changed_indices",
            "DELETE",
            "INSERT",
            "UPDATE",
        }
    ),
    "kart_tpu/ops/merge_kernel.py": frozenset(
        {
            # decision codes (the router is diff/backend.py merge_classify)
            "CONFLICT",
            "KEEP_OURS",
            "TAKE_THEIRS",
        }
    ),
    "kart_tpu/runtime.py": frozenset(
        {
            # Watchdog is device-independent timeout machinery; the probe
            # invalidation hook backs `kart --reprobe`
            "Watchdog",
            "invalidate_probe_cache",
        }
    ),
}

# ---------------------------------------------------------------------------
# KTL030-034 — the untrusted-input (taint) surface
# ---------------------------------------------------------------------------

#: every function whose inputs are attacker-controlled wire bytes or
#: wire-derived values. The dataflow engine (analysis/dataflow.py) seeds
#: taint from these declarations and tracks it to the KTL030-034 sinks.
#: Keys are "repo-relative-path::qualname"; each entry declares where the
#: taint enters:
#:
#:   "params"        parameter names carrying untrusted bytes/values
#:   "attrs"         dotted ``self.X`` attributes that are untrusted
#:                   (request handlers: headers / path / body stream)
#:   "calls"         call names whose *results* are untrusted (peer
#:                   responses fetched inside the function)
#:   "kind"          the wire surface it belongs to (docs/ANALYSIS.md §5)
#:   "error"         the declared escape type: the only exception a
#:                   crafted payload may raise out of the function (None =
#:                   the parser is tolerant and must not raise at all)
#:   "fuzz"          True = the decoder has a pure bytes->value shape and
#:                   must be covered by the registry-driven prefix-fuzz
#:                   harness (tests/test_wire_fuzz.py) — a new entry with
#:                   fuzz=True fails that test until it gets an adapter
#:   "consume_exact" True = KTL033: a registered versioned wire decoder
#:                   that must consume its payload exactly or raise (the
#:                   canonical-bytes/ETag-aliasing contract, PR 14)
#:
#: KTL030's finalize round-trips this table against the tree: an entry
#: naming no live function, or a param/attr its signature doesn't have,
#: is itself a finding (tamper-tested like KTL001/KTL003/KTL014).
TAINT_SOURCES = {
    # tile/stream payload bytes (docs/TILES.md §4-§5)
    "kart_tpu/tiles/streams.py::varint_decode": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/streams.py::bitunpack": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError",
    },
    "kart_tpu/tiles/streams.py::decode_stream": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True, "consume_exact": True,
    },
    "kart_tpu/tiles/streams.py::decode_bytes_stream": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/encode.py::decode_bin_layer": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/encode.py::decode_ktb2_layer": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/encode.py::decode_props_layer": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/encode.py::decode_mvt_layer": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    "kart_tpu/tiles/encode.py::parse_payload": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True, "consume_exact": True,
    },
    # sidecar geometry section bytes (docs/FORMAT.md §3.4)
    "kart_tpu/geom.py::decode_vertex_column": {
        "kind": "tile-payload", "params": ("data",),
        "error": "TileEncodeError", "fuzz": True,
    },
    # pack-stream reads (ROBUSTNESS.md §2)
    "kart_tpu/transport/pack.py::read_pack": {
        "kind": "pack-stream", "params": ("fileobj",),
        "error": "PackFormatError", "fuzz": True,
    },
    # HTTP request bodies / query params / headers (docs/SERVING.md)
    "kart_tpu/transport/http.py::read_framed": {
        "kind": "http-body", "params": ("fp",),
        "error": "HttpTransportError", "fuzz": True,
    },
    "kart_tpu/transport/http.py::KartRequestHandler._read_body": {
        "kind": "http-body", "attrs": ("self.headers", "self.rfile"),
        "error": None,
    },
    "kart_tpu/transport/http.py::KartRequestHandler._read_body_spooled": {
        "kind": "http-body", "attrs": ("self.headers", "self.rfile"),
        "error": None,
    },
    "kart_tpu/transport/http.py::KartRequestHandler._handle_tile": {
        "kind": "http-query", "params": ("path",),
        "attrs": ("self.headers",), "error": None,
    },
    "kart_tpu/transport/http.py::KartRequestHandler._handle_query": {
        "kind": "http-query", "attrs": ("self.path", "self.headers"),
        "error": None,
    },
    "kart_tpu/transport/protocol.py::error_attrs_from_wire": {
        "kind": "http-body", "params": ("body",), "error": None,
    },
    # stdio frame fields (ROBUSTNESS.md §1)
    "kart_tpu/transport/stdio.py::serve_stdio": {
        "kind": "stdio-frame", "params": ("in_fp",),
        "error": "StdioTransportError",
    },
    # event-log lines (docs/EVENTS.md §2: torn/corrupt lines are dropped,
    # never raised)
    "kart_tpu/events/log.py::_parse_lines": {
        "kind": "event-log", "params": ("raw",), "error": None, "fuzz": True,
    },
    # peer-cache fill responses (docs/FLEET.md §4)
    "kart_tpu/fleet/peercache.py::_fetch_validated": {
        "kind": "peer-fill", "calls": ("urlopen",), "error": None,
    },
    # query params arriving over HTTP (docs/QUERY.md §5)
    "kart_tpu/query/scan.py::parse_bbox": {
        "kind": "http-query", "params": ("text",),
        "error": "QueryError", "fuzz": True,
    },
}

#: the sanitizer surface the taint engine recognises beyond inline
#: bounds-check-then-raise guards. "ceilings" are the declared constants
#: tainted sizes must be compared against (a ceiling nothing references
#: any more is a finding); "validators" are functions whose call marks the
#: argument validated (they raise on anything malformed — a declared
#: validator nothing calls is a finding). Both legs are round-tripped by
#: KTL030/KTL034's finalize, tamper-tested like KTL001/KTL003.
SANITIZERS = {
    "ceilings": {
        "kart_tpu/tiles/encode.py::MAX_DECODE_ROWS": (
            "decompression-bomb ceiling: every decoded row/feature count "
            "a payload declares is capped here before allocation"
        ),
    },
    "validators": {
        "kart_tpu/core/refs.py::check_ref_format": (
            "git check_refname_format subset: rejects control bytes, "
            "traversal and lock/debris-shaped names before a ref touches "
            "the filesystem"
        ),
    },
}

# ---------------------------------------------------------------------------
# KTL007 — bench record keys and where they must be asserted
# ---------------------------------------------------------------------------

#: the schema guard every bench.py result key must appear in (either as a
#: NEW_KEYS literal there or as a key of the newest BENCH_r*.json record the
#: guard replays).
BENCH_SCHEMA_TEST = "tests/test_bench_schema.py"
BENCH_RECORD_GLOB = "BENCH_r*.json"
