"""DiffBackend registry — one seam where the diff engine picks its
execution layer (ISSUE 6 tentpole).

Three backends with identical observable behaviour (bit-identical classes
and counts, pinned by tests):

* ``host_native`` — the C++ streaming merge-join (numpy twin beneath it).
  Owns small blocks, CPU-only deployments and every fallback.
* ``device_jax`` — the single-device jitted kernels: one route, a pipeline
  of key-range chunks (``ops.diff_kernel.classify_blocks``).
* ``sharded_jax`` — the multi-device execution layer: KCOL blocks stream
  through :mod:`kart_tpu.diff.device_batch` as fixed-shape record batches,
  classified shard-local with ``shard_map`` over the ``features`` mesh
  axis; the spatial prefilter and the estimation's sampled count ride the
  same mesh (pmapped psum — only 3 scalars leave each device).

Selection (:func:`select_backend`) asks :mod:`kart_tpu.routing`, where the
ladder and its forcing knobs live: ``KART_DIFF_BACKEND`` when set
(``host_native`` / ``device_jax`` / ``sharded_jax``), else the cost-model
auto route: sharding when the mesh exists and the block pays for it,
single-device when profitable, host otherwise. The probe verdict these
decisions consult is the *persisted* one (kart_tpu.runtime), so a CPU
fallback is a cached choice, not a re-paid timeout.

Every device backend degrades to ``host_native`` on failure mid-call
(device OOM, a device runtime error, an injected ``diff.device_transfer``
fault): the CLI must always complete, and a failed device attempt publishes
nothing. Every such rung bumps ``diff.device.fallbacks{what=…}``
(:func:`kart_tpu.ops.diff_kernel.note_device_fallback`) — a measurement
asserts it stayed 0. Before it degrades, a rung the device refused an
allocation is made once more with the classify's resident pages let go
(:func:`kart_tpu.ops.resident.with_pages_let_go`): they are a cache, and a
cache must not cost a program the device.
"""

import functools
import os

import numpy as np

from kart_tpu import routing
from kart_tpu import telemetry as tm
from kart_tpu.ops.resident import with_pages_let_go

BACKENDS = {}


def _register(cls):
    BACKENDS[cls.name] = cls()
    return cls


class DiffBackend:
    """One diff execution layer. Subclasses override the device-capable
    entry points; the base class is the host contract every backend must
    degrade to."""

    name = None

    def keeps_pages(self, n_rows):
        """Does a classify of ``n_rows`` through this backend read pages the
        device keeps between calls (``ops/resident.py``), so that a
        revision it has seen costs no copy? Base: no — the host engine reads
        the sidecar, the mesh streams its record batches every call."""
        return False

    def classify(self, old_block, new_block):
        """-> (old_class int8 (n_old,), new_class (n_new,), counts dict),
        block-row order."""
        raise NotImplementedError

    def counts(self, old_block, new_block):
        """Count-only classify (`-o feature-count`, estimation): backends
        that can reduce on device override this to skip materialising
        classes host-side."""
        return self.classify(old_block, new_block)[2]

    def guarded_counts(self, old_block, new_block):
        """The hash-keyed count's classify and its cross-version collision
        guard -> (counts, ``engine.GuardVerdict``), under the spans
        ``diff.classify`` then ``diff.hash_guard``. Base: the classes come
        home and :func:`kart_tpu.diff.engine.host_guard` compares the UPDATE
        pairs' paths."""
        from kart_tpu.diff.engine import host_guard

        with classify_span(self, old_block, new_block):
            old_class, new_class, counts = self.classify(old_block, new_block)
        return counts, host_guard(old_block, new_block, old_class, new_class)

    def sampled_counts(self, old_sub, new_sub):
        """Counts of an estimation subsample (small blocks, called once)."""
        return self.counts(old_sub, new_sub)

    def merc_envelopes(self, env):
        """(M, 4) f64 wsen envelope degrees -> (mx0, my0, mx1, my1) f64
        normalized-mercator columns (x from lon, y from lat with the
        north edge first — the tile quantizer's input shape). The first
        *non-diff* workload behind this seam (ISSUE 15): whole-pyramid
        tile export projects its encode batches here. Base: the host
        numpy transform (`tiles.grid.merc_xy_cols` — the serving path's
        exact ops, so host batches are bit-identical to per-tile
        serving)."""
        from kart_tpu.tiles.grid import merc_xy_cols

        e = np.asarray(env, dtype=np.float64)
        mx0, my0 = merc_xy_cols(e[:, 0], e[:, 3])
        mx1, my1 = merc_xy_cols(e[:, 2], e[:, 1])
        return mx0, my0, mx1, my1

    def envelope_hits(self, block, query):
        """bool (count,) envelope-vs-query intersections for one sidecar
        block — the spatial prefilter's scan. Base: the host path
        (block-pruned native scan; KART_BLOCK_PRUNE=0 forces the full
        branchless scan — bit-identical either way, fuzz-tested)."""
        if block.count == 0:
            return np.zeros(0, dtype=bool)
        if (
            block.env_blocks is not None
            and os.environ.get("KART_BLOCK_PRUNE", "1") != "0"
        ):
            from kart_tpu.native import bbox_blocks_f32

            agg, flags, block_rows = block.env_blocks
            return bbox_blocks_f32(
                block.envelopes, agg, flags, block_rows, query
            )
        from kart_tpu.native import bbox_intersects_f32

        return bbox_intersects_f32(block.envelopes, query)

    def envelope_census(self, block, query):
        """(aggregate blocks, blocks on the query's boundary) of one sidecar
        block: the boundary blocks are the ones the block-pruned scan reads
        row by row (all-in and all-out blocks are decided from their
        aggregates alone). (0, 0) for a sidecar without aggregates."""
        if block.env_blocks is None:
            return 0, 0
        from kart_tpu.ops.bbox import BLOCK_BOUNDARY, classify_env_blocks_np

        agg, flags, _ = block.env_blocks
        cls = classify_env_blocks_np(agg, flags, np.asarray(query, dtype=np.float64))
        return len(cls), int(np.count_nonzero(cls == BLOCK_BOUNDARY))

    def join_counts(self, build_env, probe_env):
        """Spatial-join batch kernel (ISSUE 16): (T, 4) f32 build-tile
        envelopes x (B, 4) f32 probe-batch envelopes -> (per-probe match
        counts int64 (B,), total pairs int). The overlap predicate is
        comparison-only f32 (no arithmetic), so every backend is
        bit-identical by construction; NaN (padding / NULL-geometry) rows
        never match on either side. Base: the chunked numpy broadcast."""
        return _host_join_counts(build_env, probe_env)

    def refine_pairs(self, col_a, ia, col_b, ib):
        """Exact-refine batch kernel (ISSUE 20): candidate pair index
        arrays over two vertex columns -> bool (P,) exact intersection
        verdicts. Predicates are exact int64 arithmetic on quantized
        coordinates (kart_tpu.geom), so every backend is bit-identical by
        construction. Base: the memoized numpy twin."""
        from kart_tpu.geom import refine_pairs_host

        return refine_pairs_host(col_a, ia, col_b, ib)


@_register
class HostNativeBackend(DiffBackend):
    name = "host_native"

    def classify(self, old_block, new_block):
        from kart_tpu.ops.diff_kernel import classify_blocks_host

        return classify_blocks_host(old_block, new_block)


@_register
class DeviceJaxBackend(DiffBackend):
    """Single-device kernels; classify_blocks keeps its own cost-model
    routing (the chunked device route or the host) and host fallback."""

    name = "device_jax"

    def keeps_pages(self, n_rows):
        """Where the one-device route takes the call (the ladder's answer
        :func:`classify_blocks` asks too): its pages stay resident."""
        return routing.device_open(n_rows)

    def classify(self, old_block, new_block):
        from kart_tpu.ops.diff_kernel import classify_blocks

        return classify_blocks(old_block, new_block)

    def guarded_counts(self, old_block, new_block):
        """Where both path columns have one stride the guard runs on the
        device beside the classify (``classify_blocks_guarded``): the class
        arrays never come home, and the ``diff.hash_guard`` span is the wait
        for the summed verdict and its one read. Else the base's host guard
        over the classes the classify fetched. A device that fails at that
        read is a fallback rung (``what=hash_guard``): the host classifies
        again and its guard answers."""
        from kart_tpu.diff.engine import guard_verdict, host_guard
        from kart_tpu.ops.diff_kernel import (
            classify_blocks_guarded,
            classify_blocks_host,
            note_device_fallback,
        )

        with classify_span(self, old_block, new_block) as sp:
            old_class, new_class, counts, verdict = classify_blocks_guarded(
                old_block, new_block
            )
            sp.set(counts_only=verdict is not None)
        if verdict is None:
            return counts, host_guard(old_block, new_block, old_class, new_class)
        try:
            return counts, guard_verdict(
                "device", lambda: tuple(int(v) for v in np.asarray(verdict))
            )
        except Exception as e:
            note_device_fallback("hash_guard", e, "host path")
            old_class, new_class, _ = classify_blocks_host(old_block, new_block)
            return counts, host_guard(old_block, new_block, old_class, new_class)


@_register
class ShardedJaxBackend(DiffBackend):
    name = "sharded_jax"

    def _fall_back(self, e, what):
        from kart_tpu.ops.diff_kernel import note_device_fallback

        note_device_fallback(what, e, "host_native")
        return BACKENDS["host_native"]

    def classify(self, old_block, new_block):
        from kart_tpu.diff.device_batch import classify_blocks_batched

        try:
            result = with_pages_let_go(
                lambda: classify_blocks_batched(old_block, new_block)
            )
        except Exception as e:
            # device OOM / runtime error / injected transfer fault: nothing
            # was published, so the host engine starts from clean state
            return self._fall_back(e, "classify").classify(old_block, new_block)
        from kart_tpu.parallel.sharded_diff import STATS

        STATS["sharded_classify_calls"] += 1
        return result

    def counts(self, old_block, new_block):
        # count-only rounds: the per-row class arrays stay on the devices,
        # only the psum'd 3-vector comes home (`-o feature-count` at 100M
        # would otherwise download + scatter ~200MB it immediately drops)
        from kart_tpu.diff.device_batch import classify_blocks_batched

        try:
            _, _, counts = with_pages_let_go(
                lambda: classify_blocks_batched(
                    old_block, new_block, counts_only=True
                )
            )
        except Exception as e:
            return self._fall_back(e, "counts").counts(old_block, new_block)
        from kart_tpu.parallel.sharded_diff import STATS

        STATS["sharded_classify_calls"] += 1
        return counts

    def sampled_counts(self, old_sub, new_sub):
        try:
            counts = with_pages_let_go(
                lambda: sampled_counts_pmapped(old_sub, new_sub)
            )
        except Exception as e:
            return self._fall_back(e, "sampled_counts").counts(old_sub, new_sub)
        from kart_tpu.parallel.sharded_diff import STATS

        STATS["sharded_classify_calls"] += 1
        return counts

    def envelope_hits(self, block, query):
        q = np.asarray(query, dtype=np.float64)
        if (
            block.envelopes is None
            or q[2] < q[0]  # wrapping query rect: host path owns the cyclic math
            or not routing.runtime_ready(block.count, routing.DEVICE_MIN_ENVELOPES)
        ):
            return super().envelope_hits(block, query)
        try:
            return with_pages_let_go(
                lambda: sharded_envelope_hits(block.envelopes, block.count, q)
            )
        except Exception as e:
            return self._fall_back(e, "envelope_hits").envelope_hits(block, query)

    def merc_envelopes(self, env):
        e = np.asarray(env, dtype=np.float64)
        if not routing.runtime_ready(len(e), routing.DEVICE_MIN_ENVELOPES):
            return super().merc_envelopes(e)
        try:
            return with_pages_let_go(lambda: sharded_merc_envelopes(e))
        except Exception as exc:
            return self._fall_back(exc, "merc_envelopes").merc_envelopes(e)

    def join_counts(self, build_env, probe_env):
        try:
            return with_pages_let_go(
                lambda: sharded_join_counts(build_env, probe_env)
            )
        except Exception as e:
            # device OOM / runtime error mid-batch: nothing was published
            # (the query layer accumulates only returned batches), so the
            # host twin recomputes this batch from clean state
            return self._fall_back(e, "join").join_counts(build_env, probe_env)

    def refine_pairs(self, col_a, ia, col_b, ib):
        try:
            return with_pages_let_go(
                lambda: sharded_refine_pairs(col_a, ia, col_b, ib)
            )
        except Exception as e:
            # nothing published mid-batch (the refine stage only applies
            # returned verdict arrays), so the host twin restarts clean
            return self._fall_back(e, "refine").refine_pairs(
                col_a, ia, col_b, ib
            )


def classify_span(backend, old_block, new_block, counts_only=False, **attrs):
    """The ``diff.classify`` span a diff's classify runs under (a merge's
    two diffs add ``side``)."""
    return tm.span(
        "diff.classify",
        rows=max(old_block.count, new_block.count),
        backend=backend.name,
        counts_only=counts_only,
        **attrs,
    )


def select_backend(n_rows):
    """The backend the production diff path runs ``n_rows`` through:
    :func:`kart_tpu.routing.select_engine`'s answer as a backend object."""
    return BACKENDS[routing.select_engine(n_rows)]


def warm_probe(n_rows):
    """Kick the async backend probe as soon as a diff *might* route to a
    device — init overlaps the remaining sidecar loads / prefilter instead
    of serialising after them. Row-gated so small diffs never pay the
    background jax import, and knob-gated exactly like the routing it warms
    for (:func:`kart_tpu.routing.any_device_route`)."""
    if routing.any_device_route(n_rows):
        from kart_tpu.runtime import probe_backend_async

        probe_backend_async()


def _mesh_or_host(n_rows, allow_device):
    """The backend of a batch workload with a mesh kernel and a host twin
    (projection, join, refine): the routing ladder's answer for ``n_rows``
    — knobs, row floor, ``jax_ready()`` (a stuck runtime can't hang the
    first device_put), no virtual CPU mesh as a production engine, two
    devices or more: on one chip these never route to the device
    (docs/DEVICE.md "what runs where")."""
    return BACKENDS[
        routing.mesh_or_host(n_rows) if allow_device else "host_native"
    ]


# --- 3-way merge classify: two diffs through the diff's backend -------------

def merge_classify(ancestor_block, ours_block, theirs_block):
    """FeatureBlock x3 -> (union_keys (U,) int64 np, decision (U,) int8 np,
    presence (U,) int8 np with bits a=1/o=2/t=4, stats dict), the same on
    every engine: the diffs ancestor -> ours and ancestor -> theirs through
    the backend :func:`select_backend` picks for the largest revision, each
    under its own ``diff.classify`` span (``side``), then the three-way rule
    over what they report changed
    (:func:`kart_tpu.ops.merge_kernel.merge_classify_two_diffs`). A device
    that fails either diff is that diff's own fallback rung. The
    ``diff.merge_classify`` span names the backend (``backend=``, as
    ``diff.classify`` does) and carries the call's census:
    ``rows_ancestor``, ``rows_ours``, ``rows_theirs``, ``union``,
    ``conflicts``, ``take_theirs``."""
    from kart_tpu.ops.merge_kernel import merge_classify_two_diffs

    blocks = (ancestor_block, ours_block, theirs_block)
    n_max = max(b.count for b in blocks)
    with tm.span(
        "diff.merge_classify",
        rows=n_max,
        rows_ancestor=ancestor_block.count,
        rows_ours=ours_block.count,
        rows_theirs=theirs_block.count,
    ) as span:
        backend = select_backend(n_max)

        def classify(side, old_block, new_block):
            with classify_span(backend, old_block, new_block, side=side):
                if not max(old_block.count, new_block.count):
                    # the dataset is in neither revision
                    return np.zeros(0, dtype=np.int8), np.zeros(0, dtype=np.int8)
                return backend.classify(old_block, new_block)[:2]

        result = merge_classify_two_diffs(*blocks, classify)
        span.set(backend=backend.name, union=len(result[0]), **result[3])
    return result


# --- sharded bbox prefilter kernel ------------------------------------------

def _query_f32_thresholds(query_f64):
    """Exact f64-equivalent f32 thresholds, mirroring the native scan
    (native/spatial_filter.cpp make_query_f32): comparing a float x against
    a double bound b satisfies (double)x <= b <=> x <= largest_float_le(b),
    and symmetrically for >=. Keeps the device scan bit-identical to the
    host engine's branchless f32 pass."""
    q = np.asarray(query_f64, dtype=np.float64)
    f = q.astype(np.float32)
    back = f.astype(np.float64)
    ge = np.where(back < q, np.nextafter(f, np.float32(np.inf)), f)
    le = np.where(back > q, np.nextafter(f, np.float32(-np.inf)), f)
    # (qw_ge, qs_ge, qe_le, qn_le)
    return np.asarray([ge[0], ge[1], le[2], le[3]], dtype=np.float32)


def _bbox_hits_f32_step(w, s, e, n, q):
    """Branchless f32 envelope scan (non-wrapping query), the shard-local
    body: same predicate as native scan_rows_f32."""
    lat = (s <= q[3]) & (q[1] <= n)
    a = w <= q[2]
    b = q[0] <= e
    wrap = e < w
    return lat & ((a & b) | (wrap & (a | b)))


@functools.lru_cache(maxsize=8)
def _make_sharded_bbox(mesh):
    import jax

    from jax.sharding import PartitionSpec as P

    from kart_tpu.parallel.mesh import FEATURES_AXIS

    def _step(w, s, e, n, q):
        return _bbox_hits_f32_step(w[0], s[0], e[0], n[0], q)[None]

    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _step, mesh=mesh, in_specs=(spec,) * 4 + (P(),), out_specs=spec
    )
    return jax.jit(fn)


def sharded_envelope_hits(envelopes, count, query_f64):
    """(count, 4) f32 envelopes + non-wrapping f64 query rect -> bool
    (count,) hits, computed shard-local over the feature axis (no
    cross-device traffic at all — the out spec keeps hits sharded and the
    host reassembles). Padding rows scan at latitude 91: never a hit."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.ops.blocks import bucket_size
    from kart_tpu.parallel.mesh import FEATURES_AXIS, make_mesh

    mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    per = bucket_size(max(-(-count // n_shards), 1))
    cols = np.full((4, n_shards * per), 91.0, dtype=np.float32)
    if count:
        cols[:, :count] = np.asarray(envelopes[:count], dtype=np.float32).T
    q = _query_f32_thresholds(query_f64)
    fn = _make_sharded_bbox(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    with tm.span("diff.device.transfer", rows=int(count)):
        args = [
            jax.device_put(c.reshape(n_shards, per), sharding) for c in cols
        ]
    hits = fn(*args, jax.device_put(q))
    return np.asarray(hits).reshape(-1)[:count]


# --- sharded mercator projection (the tile exporter's batch workload) -------

def project_envelopes(env, allow_device=True):
    """(M, 4) f64 wsen degrees -> (mx0, my0, mx1, my1) normalized-mercator
    f64 columns, routed through the backend registry — the pyramid
    exporter's per-batch entry point (the first non-diff workload on the
    PR 6 seam). ``allow_device=False`` pins the host transform (pool
    workers: a forked child must never touch a device runtime).

    Byte-determinism note (docs/TILES.md §5.1): device transcendentals are
    *not* bit-identical to numpy's, so the tile quantizer treats device
    output as a fast approximation and re-runs the host ops on any row
    whose quantized value lands within a safety margin of a rounding
    boundary (:func:`kart_tpu.tiles.clip.quantize_from_merc`) — the
    exported integers are provably the host integers either way."""
    e = np.asarray(env, dtype=np.float64)
    return _mesh_or_host(len(e), allow_device).merc_envelopes(e)


@functools.lru_cache(maxsize=8)
def _make_sharded_merc(mesh):
    import jax

    from jax.sharding import PartitionSpec as P

    from kart_tpu.parallel.mesh import FEATURES_AXIS

    import jax.numpy as jnp

    from kart_tpu.tiles.grid import MERC_MAX_LAT

    def _merc(lon, lat):
        lat = jnp.clip(lat, -MERC_MAX_LAT, MERC_MAX_LAT)
        x = (lon + 180.0) / 360.0
        s = jnp.sin(jnp.radians(lat))
        y = 0.5 - jnp.log((1.0 + s) / (1.0 - s)) / (4.0 * jnp.pi)
        return x, y

    def _step(w, s, e, n):
        mx0, my0 = _merc(w[0], n[0])
        mx1, my1 = _merc(e[0], s[0])
        return mx0[None], my0[None], mx1[None], my1[None]

    jax.config.update("jax_enable_x64", True)  # f64 degrees in, f64 merc out
    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _step, mesh=mesh, in_specs=(spec,) * 4, out_specs=(spec,) * 4
    )
    return jax.jit(fn)


def sharded_merc_envelopes(env):
    """(M, 4) f64 degrees -> 4 merc columns, computed shard-local over the
    feature axis (pure elementwise — zero cross-device traffic; padding
    rows project to garbage and are sliced off)."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.ops.blocks import bucket_size
    from kart_tpu.parallel.mesh import FEATURES_AXIS, make_mesh

    count = len(env)
    mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    per = bucket_size(max(-(-count // n_shards), 1))
    cols = np.zeros((4, n_shards * per), dtype=np.float64)
    if count:
        cols[:, :count] = np.asarray(env, dtype=np.float64).T
    fn = _make_sharded_merc(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    with tm.span("diff.device.project", rows=int(count)):
        args = [
            jax.device_put(c.reshape(n_shards, per), sharding) for c in cols
        ]
        out = fn(*args)
    return tuple(np.asarray(o).reshape(-1)[:count] for o in out)


# --- spatial-join batch kernel (the query engine's workload, ISSUE 16) ------

def _join_overlap_np(pw, ps, pe, pn, bw, bs, be, bn):
    """Pairwise bbox-overlap matrix, probe rows (column vectors (B, 1))
    against build rows ((T,)): comparison-only f32 — no arithmetic, so the
    numpy and XLA twins are bit-identical and NaN rows (padding,
    NULL-geometry) never match. Cyclic longitude: ``e < w`` wraps; two
    wrapping ranges always overlap (both contain the anti-meridian), one
    wrapping range overlaps iff either ordinary endpoint test passes."""
    lat = (bs <= pn) & (ps <= bn)
    a = bw <= pe
    b = pw <= be
    bwrap = be < bw
    pwrap = pe < pw
    both = bwrap & pwrap
    one = bwrap ^ pwrap
    return lat & ((a & b) | both | (one & (a | b)))


def _host_join_counts(build_env, probe_env, chunk=8192):
    """Chunked numpy broadcast-probe: (T, 4) x (B, 4) f32 -> per-probe
    int64 counts + total. Probe sub-chunks bound the (chunk, T) bool
    intermediates (~32 MB at the 4096-row tile width)."""
    b = np.asarray(build_env, dtype=np.float32)
    p = np.asarray(probe_env, dtype=np.float32)
    counts = np.zeros(len(p), dtype=np.int64)
    if len(b) and len(p):
        bw, bs, be, bn = b[:, 0], b[:, 1], b[:, 2], b[:, 3]
        for lo in range(0, len(p), chunk):
            sub = p[lo : lo + chunk]
            hit = _join_overlap_np(
                sub[:, 0:1], sub[:, 1:2], sub[:, 2:3], sub[:, 3:4],
                bw[None, :], bs[None, :], be[None, :], bn[None, :],
            )
            counts[lo : lo + len(sub)] = np.count_nonzero(hit, axis=1)
    return counts, int(counts.sum())


@functools.lru_cache(maxsize=8)
def _make_sharded_join(mesh):
    import jax

    from jax.sharding import PartitionSpec as P

    import jax.numpy as jnp

    from kart_tpu.parallel.mesh import FEATURES_AXIS

    def _step(pw, ps, pe, pn, bw, bs, be, bn):
        # probe cols (1, B) per-device slices; build cols (T,) replicated.
        # Same comparison-only predicate as the numpy twin: bit-identical.
        hit = _join_overlap_np(
            pw[0][:, None], ps[0][:, None], pe[0][:, None], pn[0][:, None],
            bw[None, :], bs[None, :], be[None, :], bn[None, :],
        )
        counts = jnp.sum(hit, axis=1, dtype=jnp.int32)
        total = jax.lax.psum(jnp.sum(counts, dtype=jnp.int64), FEATURES_AXIS)
        return counts[None], total

    jax.config.update("jax_enable_x64", True)  # int64 pair totals
    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(spec,) * 4 + (P(),) * 4,
        out_specs=(spec, P()),
    )
    return jax.jit(fn)


def sharded_join_counts(build_env, probe_env):
    """(T, 4) x (B, 4) f32 -> (per-probe counts int64 (B,), psum'd total):
    probe columns sharded over the feature axis, the build tile replicated
    on every device, the (B_shard, T) overlap matrix reduced on-device —
    per-probe counts come home sharded, the pair total crosses the mesh as
    one psum'd scalar. Padding rows are NaN on both sides: never a match,
    so padded results equal unpadded ones exactly."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.diff.device_batch import pack_env_round
    from kart_tpu.ops.blocks import bucket_size
    from kart_tpu.parallel.mesh import FEATURES_AXIS, make_mesh

    mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    m = len(probe_env)
    per = bucket_size(max(-(-m // n_shards), 1), minimum=256)
    pcols = pack_env_round(probe_env, 0, m, n_shards, per)
    t = len(build_env)
    tcap = bucket_size(max(t, 1), minimum=256)
    bcols = np.full((4, tcap), np.nan, dtype=np.float32)
    if t:
        bcols[:, :t] = np.asarray(build_env, dtype=np.float32).T
    fn = _make_sharded_join(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    with tm.span("diff.device.transfer", rows=int(m)):
        args = [jax.device_put(c, sharding) for c in pcols]
        args += [jax.device_put(c) for c in bcols]
    counts, total = fn(*args)
    return (
        np.asarray(counts).reshape(-1)[:m].astype(np.int64),
        int(total),
    )


def join_bbox_counts(build_env, probe_env, allow_device=True, route_rows=None):
    """The query engine's per-batch entry point on this seam (docs/QUERY.md
    §4): build-tile x probe-batch envelope overlap counts, routed exactly
    like :func:`project_envelopes` — same env gates, same readiness ladder,
    same host fallback. ``route_rows`` lets the caller gate on the *whole*
    probe side rather than one batch (the join streams many fixed-size
    batches through one routing decision)."""
    b = np.asarray(build_env, dtype=np.float32)
    p = np.asarray(probe_env, dtype=np.float32)
    rows = len(p) if route_rows is None else int(route_rows)
    return _mesh_or_host(rows, allow_device).join_counts(b, p)


# --- exact-refine batch kernel (the query engine's refine stage, ISSUE 20) --

@functools.lru_cache(maxsize=8)
def _make_sharded_refine(mesh):
    import jax

    from jax.sharding import PartitionSpec as P

    import jax.numpy as jnp

    from kart_tpu.geom import ray_crossings, seg_pairs_intersect
    from kart_tpu.parallel.mesh import FEATURES_AXIS

    def _step(ax0, ay0, ax1, ay1, an, bx0, by0, bx1, by1, bn, ap, bp):
        # (1, Pp, S) int32 segment slabs per device. Cast to int64 — the
        # exactness contract (kart_tpu.geom): |coord| < 2^25, so every
        # product below fits 52 bits and equals the numpy twin bit for
        # bit. The predicate functions themselves are the *same* operator-
        # only expressions the host evaluates — shared source, not twins.
        a = [v[0].astype(jnp.int64) for v in (ax0, ay0, ax1, ay1)]
        b = [v[0].astype(jnp.int64) for v in (bx0, by0, bx1, by1)]
        am = jnp.arange(a[0].shape[1])[None, :] < an[0][:, None]
        bm = jnp.arange(b[0].shape[1])[None, :] < bn[0][:, None]
        pm = am[:, :, None] & bm[:, None, :]
        col = [v[:, :, None] for v in a]  # A segments down the matrix
        row = [v[:, None, :] for v in b]  # B segments across
        seg_any = (seg_pairs_intersect(*col, *row) & pm).any(axis=(1, 2))
        # A starts vs B rings: even-odd parity per vertex, any inside
        cnt_ab = (ray_crossings(col[0], col[1], *row) & pm).sum(axis=2)
        a_in_b = (((cnt_ab & 1) == 1) & am).any(axis=1)
        # B starts vs A rings (transposed orientation, same masks)
        cnt_ba = (
            ray_crossings(row[0], row[1], *col)
            & pm
        ).sum(axis=1)
        b_in_a = (((cnt_ba & 1) == 1) & bm).any(axis=1)
        verdict = seg_any | (bp[0] & a_in_b) | (ap[0] & b_in_a)
        return verdict[None]

    jax.config.update("jax_enable_x64", True)  # exact int64 predicates
    spec = P(FEATURES_AXIS)
    fn = jax.shard_map(
        _step, mesh=mesh, in_specs=(spec,) * 12, out_specs=spec
    )
    return jax.jit(fn)


def sharded_refine_pairs(col_a, ia, col_b, ib):
    """Candidate pairs -> bool (P,) exact verdicts, pairs sharded over the
    feature axis, each device reducing its own (Pp, SA, SB) predicate slab
    — only the verdict bits come home. Rounds are capped by
    ``KART_GEOM_BATCH_ROWS`` and shrunk further when a round's slab would
    exceed the element budget (one huge polygon must not OOM the mesh).
    Padding pair rows carry zero segment counts: their masks are empty, so
    the verdict is False and they slice off exactly."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from kart_tpu.diff.device_batch import pack_geom_pairs
    from kart_tpu.geom import geom_batch_rows
    from kart_tpu.ops.blocks import bucket_size
    from kart_tpu.parallel.mesh import FEATURES_AXIS, make_mesh

    ia = np.asarray(ia, dtype=np.int64)
    ib = np.asarray(ib, dtype=np.int64)
    total = len(ia)
    out = np.zeros(total, dtype=bool)
    if not total:
        return out
    mesh = make_mesh()
    n_shards = int(mesh.devices.size)
    fn = _make_sharded_refine(mesh)
    sharding = NamedSharding(mesh, P(FEATURES_AXIS))
    batch = geom_batch_rows()
    for lo in range(0, total, batch):
        hi = min(lo + batch, total)
        pack = pack_geom_pairs(col_a, ia[lo:hi], col_b, ib[lo:hi])
        sa = pack["a"][0].shape[1]
        sb = pack["b"][0].shape[1]
        # keep each device's (Pp, SA, SB) slab under ~2^24 elements
        rows = max(min(hi - lo, (1 << 24) * n_shards // max(sa * sb, 1)), 1)
        for r0 in range(0, hi - lo, rows):
            r1 = min(r0 + rows, hi - lo)
            m = r1 - r0
            per = bucket_size(max(-(-m // n_shards), 1), minimum=64)
            def _pad(arr, fill=0):
                cols = arr.shape[1:]
                padded = np.zeros((n_shards * per,) + cols, dtype=arr.dtype)
                padded[:m] = arr[r0:r1]
                return padded.reshape((n_shards, per) + cols)
            with tm.span("diff.device.transfer", rows=int(m)):
                args = [
                    jax.device_put(_pad(c), sharding)
                    for c in pack["a"] + [pack["a_n"]] + pack["b"] + [pack["b_n"]]
                ]
                args += [
                    jax.device_put(_pad(pack[k]), sharding)
                    for k in ("a_poly", "b_poly")
                ]
            verdict = fn(*args)
            out[lo + r0 : lo + r1] = np.asarray(verdict).reshape(-1)[:m]
    return out


def refine_intersects(col_a, ia, col_b, ib, allow_device=True, route_rows=None):
    """The query engine's exact-refine entry point on this seam
    (docs/QUERY.md §4b): candidate pair indices over two vertex columns ->
    bool exact-intersection verdicts, routed exactly like
    :func:`join_bbox_counts` — same env gates, same readiness ladder, same
    host fallback. ``route_rows`` gates on the whole candidate set when
    the caller streams many batches through one routing decision. Callers
    only hand over pairs whose both sides have usable geometry (kind != 0);
    everything else keeps its envelope verdict — the fail-open rule that
    makes exact matches a structural subset of bbox matches."""
    rows = len(ia) if route_rows is None else int(route_rows)
    return _mesh_or_host(rows, allow_device).refine_pairs(col_a, ia, col_b, ib)


# --- pmapped sampled-count reduction ----------------------------------------

@functools.lru_cache(maxsize=8)
def _make_pmapped_counts(n_dev):
    import jax

    from kart_tpu.ops.diff_kernel import _classify_mergesort_core

    def _pmapped_counts(ok, oo, nk, no, oc, nc):
        _, _, _, counts = _classify_mergesort_core(ok, oo, nk, no, oc, nc)
        return jax.lax.psum(counts, "devices")

    jax.config.update("jax_enable_x64", True)  # int64 keys / PAD_KEY
    return jax.pmap(_pmapped_counts, axis_name="devices")


def sampled_counts_pmapped(old_block, new_block):
    """Estimation's sampled count as a pmapped reduction: each device
    classifies its contiguous key-range slice of the subsample and only the
    psum'd 3-vector comes home — the SURVEY §2.3 slot, now on the real
    mesh. -> counts dict, identical to the host classify (the slices are
    key-aligned, so shard-local joins equal the global join)."""
    import jax

    from kart_tpu.diff.device_batch import batch_splits, pack_round
    from kart_tpu.ops.blocks import bucket_size

    n_dev = jax.local_device_count()
    n_old, n_new = old_block.count, new_block.count
    old_keys = np.asarray(old_block.keys[:n_old])
    new_keys = np.asarray(new_block.keys[:n_new])
    # capacity that yields <= n_dev key-aligned chunks (grow until it fits;
    # terminates because one chunk always suffices at max side length)
    cap = max(-(-max(n_old, n_new, 1) // n_dev), 1)
    while True:
        (old_splits, new_splits), n_chunks = batch_splits(
            (old_keys, new_keys), cap
        )
        if n_chunks <= n_dev:
            break
        cap *= 2
    bucket = bucket_size(cap)
    ok, oo, oc, _ = pack_round(old_keys, old_block.oids, old_splits, 0, n_dev, bucket)
    nk, no, nc, _ = pack_round(new_keys, new_block.oids, new_splits, 0, n_dev, bucket)
    fn = _make_pmapped_counts(n_dev)
    with tm.span("diff.device.classify", rows=int(max(n_old, n_new)), shards=n_dev):
        counts = np.asarray(fn(ok, oo, nk, no, oc, nc))[0]
    return {
        "inserts": int(counts[0]),
        "updates": int(counts[1]),
        "deletes": int(counts[2]),
    }
