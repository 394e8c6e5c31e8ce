"""Spatial filtering: work with just the features inside an area of interest.

Client side (reference: kart/spatial_filter/__init__.py): a filter spec —
``<crs>;<geometry>`` — from CLI / config / file; per-dataset
:class:`SpatialFilter` objects test feature envelopes against the filter,
with the filter transformed into each dataset's CRS once up front (reference
transforms per-dataset the same way, spatial_filter/__init__.py:611-694).

Server side (reference: vendor/spatial-filter/spatial_filter.cpp): during a
filtered partial clone, :func:`blob_filter_for_spec` vetoes feature blobs
whose envelope misses the filter, consulting the bit-packed envelope index
(:mod:`kart_tpu.spatial_filter.index`) when built — with on-the-fly envelope
decoding as fallback (the reference hard-requires the index; we degrade
gracefully).  The native fast path lives in the C++ extension
(:mod:`kart_tpu.native`); the vectorized TPU path is
:func:`kart_tpu.ops.bbox.bbox_intersects`.

Match results are tri-state (MATCHED / NOT_MATCHED / PROMISED — reference
MatchResult, spatial_filter/__init__.py:413-432): a feature whose geometry
is itself a promised blob can't be tested locally.
"""

import logging
import os
from enum import Enum

import numpy as np

from kart_tpu.core.odb import ObjectPromised
from kart_tpu.crs import CRS, Transform, make_crs
from kart_tpu.geometry import MULTIPOLYGON, POLYGON, Geometry

L = logging.getLogger("kart_tpu.spatial_filter")


def _transform_ring(t, ring):
    rx, ry = t.transform(ring[:, 0], ring[:, 1])
    return np.stack([rx, ry], axis=1)

EPSG_4326_WKT = """GEOGCS["WGS 84",DATUM["WGS_1984",SPHEROID["WGS 84",6378137,298.257223563,AUTHORITY["EPSG","7030"]],AUTHORITY["EPSG","6326"]],PRIMEM["Greenwich",0],UNIT["degree",0.0174532925199433],AUTHORITY["EPSG","4326"]]"""


class SpatialFilterError(ValueError):
    pass


class MatchResult(Enum):
    MATCHED = "matched"
    NOT_MATCHED = "not-matched"
    PROMISED = "promised"  # can't tell: geometry blob not present locally


def _rect_overlaps(env, rect):
    """(min-x, max-x, min-y, max-y) vs (w, e, s, n) rect, anti-meridian aware
    on the x axis (reference: bbox_intersects_fast,
    spatial_filter/__init__.py:709-734)."""
    x0, x1, y0, y1 = env
    w, e, s, n = rect
    if y1 < s or y0 > n:
        return False
    if e >= w:  # normal range
        if x1 >= x0:
            return x0 <= e and w <= x1
        # env crosses the anti-meridian
        return x0 <= e or w <= x1
    # rect crosses the anti-meridian
    if x1 >= x0:
        return x0 <= e or w <= x1
    return True  # both cross: they share the anti-meridian


class ResolvedSpatialFilterSpec:
    """A parsed, usable filter: CRS + geometry
    (reference: ResolvedSpatialFilterSpec, spatial_filter/__init__.py)."""

    def __init__(self, crs_spec, geometry, *, match_all=False):
        self.match_all = match_all
        if match_all:
            self.crs_spec = self.geometry = self.crs = None
            return
        self.crs_spec = crs_spec
        self.crs = make_crs(crs_spec)
        if isinstance(geometry, Geometry):
            self.geometry = geometry
        else:
            self.geometry = Geometry.from_string(
                geometry,
                allowed_types=(POLYGON, MULTIPOLYGON),
            )

    @classmethod
    def from_spec_string(cls, text):
        """``<crs>;<geometry>`` where geometry is WKT or hex WKB, or the
        contents of a file via ``@filename``
        (reference: spatial_filter/__init__.py:170-270)."""
        if text in (None, "", "none"):
            return cls(None, None, match_all=True)
        if text.startswith("@"):
            path = text[1:]
            if not os.path.exists(path):
                raise SpatialFilterError(f"No such file: {path}")
            with open(path) as f:
                text = f.read().strip()
        crs_spec, sep, geom_text = text.partition(";")
        if not sep:
            raise SpatialFilterError(
                "Spatial filter must be in the form <crs>;<geometry> "
                "(e.g. 'EPSG:4326;POLYGON((...))')"
            )
        return cls(crs_spec.strip(), geom_text.strip())

    @classmethod
    def from_repo_config(cls, repo):
        from kart_tpu.core.repo import KartConfigKeys

        geom = repo.config.get(KartConfigKeys.KART_SPATIALFILTER_GEOMETRY)
        crs = repo.config.get(KartConfigKeys.KART_SPATIALFILTER_CRS)
        if not geom or not crs:
            return cls(None, None, match_all=True)
        return cls(crs, geom)

    # -- envelopes -----------------------------------------------------------

    @property
    def envelope_native(self):
        """(min-x, max-x, min-y, max-y) in the filter's own CRS."""
        return self.geometry.envelope()

    @property
    def envelope_wsen_4326(self):
        """(w, s, e, n) in EPSG:4326 — the form the envelope index and the
        wire filter argument use."""
        env = self.envelope_native
        if not self.crs.is_geographic:
            t = Transform(self.crs, make_crs(EPSG_4326_WKT))
            env = t.transform_envelope(env)
        x0, x1, y0, y1 = env
        return (x0, y0, x1, y1)

    @property
    def filter_arg(self):
        """The ``extension:spatial=`` argument: ``w,s,e,n`` in EPSG:4326
        (reference: kart/repo.py:288-302)."""
        return ",".join(f"{v:.7f}" for v in self.envelope_wsen_4326)

    def config_items(self):
        from kart_tpu.core.repo import KartConfigKeys

        return {
            KartConfigKeys.KART_SPATIALFILTER_GEOMETRY: self.geometry.to_wkt(),
            KartConfigKeys.KART_SPATIALFILTER_CRS: self.crs_spec,
        }

    def resolve_for_dataset(self, dataset):
        """-> SpatialFilter in the dataset's CRS."""
        if self.match_all:
            return SpatialFilter.MATCH_ALL
        return SpatialFilter.for_dataset(self, dataset)


class SpatialFilter:
    """A filter ready to test features of one dataset: the filter envelope
    and full polygon geometry (all parts, all holes), pre-transformed into
    the dataset's CRS. Matching is the reference's two stages
    (spatial_filter/__init__.py:534-590): envelope fast-path, then GEOS
    Intersects semantics on the actual feature geometry for the residue."""

    MATCH_ALL = None  # set below

    def __init__(self, rect_wesn=None, geom_column_name=None, polygon_parts=None,
                 reprojected=False):
        self.match_all = rect_wesn is None
        self.rect = rect_wesn  # (w, e, s, n) in dataset CRS
        self.geom_column_name = geom_column_name
        self.polygon_parts = polygon_parts  # [(outer, [holes]), ...] dataset CRS
        # True when the filter was transformed out of its own CRS: its
        # polygon then no longer lies in the CRS of the filter's envelope
        self.reprojected = reprojected
        self._rect_parts = None  # lazy: the rect as a polygon part

    @classmethod
    def for_dataset(cls, spec, dataset):
        geom_col = dataset.geom_column_name
        if geom_col is None:
            return cls.MATCH_ALL  # non-spatial dataset: everything matches
        x0, x1, y0, y1 = spec.envelope_native
        parts = _polygon_parts(spec.geometry)
        ds_crs_wkt = None
        reprojected = False
        try:
            ids = dataset.crs_identifiers()
            if ids:
                ds_crs_wkt = dataset.get_crs_definition(ids[0])
        except Exception:
            ds_crs_wkt = None
        if ds_crs_wkt:
            ds_crs = CRS(ds_crs_wkt)
            if ds_crs != spec.crs:
                try:
                    t = Transform(spec.crs, ds_crs)
                    reprojected = True
                    x0, x1, y0, y1 = t.transform_envelope((x0, x1, y0, y1))
                    if parts is not None:
                        parts = [
                            (
                                _transform_ring(t, outer),
                                [_transform_ring(t, h) for h in holes],
                            )
                            for outer, holes in parts
                        ]
                except Exception as e:
                    # unknown projection: fail open rather than dropping
                    # features — but never silently
                    L.warning(
                        "Spatial filter cannot be transformed into the CRS of "
                        "dataset %r (%s); the filter will not be applied to "
                        "this dataset.",
                        dataset.path,
                        e,
                    )
                    return cls.MATCH_ALL
        return cls((x0, x1, y0, y1), geom_col, parts, reprojected)

    def matches(self, feature):
        result = self.match_result(feature)
        if result is MatchResult.PROMISED:
            raise ObjectPromised("<feature geometry>")
        return result is MatchResult.MATCHED

    def match_result(self, feature) -> MatchResult:
        if self.match_all:
            return MatchResult.MATCHED
        try:
            geom = feature.get(self.geom_column_name)
        except ObjectPromised:
            return MatchResult.PROMISED
        return self.match_geometry(geom)

    def match_geometry(self, geom) -> MatchResult:
        """Staged exactly like the reference (envelope fast-path, then a
        real-geometry intersection for the residue — GEOS Intersects
        semantics, kart/spatial_filter/__init__.py:556-590): a feature whose
        *envelope* clips the filter but whose geometry doesn't must be
        NOT_MATCHED."""
        if geom is None:
            return MatchResult.MATCHED  # NULL geometry always matches (ref.)
        env = Geometry.of(geom).envelope()
        if env is None:
            return MatchResult.MATCHED  # empty geometry
        if not _rect_overlaps(env, self.rect):
            return MatchResult.NOT_MATCHED

        filter_parts = self.polygon_parts
        if filter_parts is None:
            # rectangular filter: envelope fully inside => geometry inside
            x0, x1, y0, y1 = env
            w, e, s, n = self.rect
            if w <= x0 and x1 <= e and s <= y0 and y1 <= n:
                return MatchResult.MATCHED
            filter_parts = self._rect_as_parts()
        else:
            rel = _polygon_set_env_relation(filter_parts, env)
            if rel == "disjoint":
                return MatchResult.NOT_MATCHED
            if rel == "contains":
                return MatchResult.MATCHED  # whole envelope inside the filter
        # residue: the filter polygon only partially covers the envelope —
        # decide on the actual feature geometry
        feat = _feature_geom_parts(geom)
        if feat is None:
            return MatchResult.MATCHED  # unparseable: fail open (ref. does)
        if _geom_intersects_polygon_set(feat, filter_parts):
            return MatchResult.MATCHED
        return MatchResult.NOT_MATCHED

    def filter_parts(self):
        """The filter as polygon parts [(outer, [holes]), ...] in the
        dataset's CRS: its polygon's, or its rectangle's."""
        return self.polygon_parts or self._rect_as_parts()

    def _rect_as_parts(self):
        """The rect filter as a polygon part, for the exact residue test."""
        if self._rect_parts is None:
            w, e, s, n = self.rect
            ring = np.array(
                [(w, s), (e, s), (e, n), (w, n), (w, s)], dtype=np.float64
            )
            self._rect_parts = [(ring, [])]
        return self._rect_parts

    def matches_envelope(self, env):
        if self.match_all:
            return True
        return _rect_overlaps(env, self.rect)

    def __bool__(self):
        return not self.match_all


SpatialFilter.MATCH_ALL = SpatialFilter()


def _polygon_parts(geometry):
    """Polygon/MultiPolygon -> list of (outer_ring, [hole_rings]) with each
    ring an (N,2) float64 array, or None when the geometry isn't a polygon.
    Every part and every interior ring is kept — the intersection test is
    exact, not first-outer-ring-only."""
    from kart_tpu.geometry import parse_wkb

    try:
        value = parse_wkb(Geometry.of(geometry).to_wkb())
    except Exception:
        return None
    name = value[0]
    if name == "Polygon":
        polys = [value]
    elif name == "MultiPolygon":
        polys = value.payload or []
    else:
        return None
    parts = []
    for poly in polys:
        rings = [
            np.asarray(ring, dtype=np.float64)[:, :2]
            for ring in (poly.payload or [])
            if len(ring) >= 3
        ]
        if rings:
            parts.append((rings[0], rings[1:]))
    return parts or None


def _polygon_set_env_relation(parts, env):
    """Filter polygon set vs feature envelope: "disjoint" (no part meets the
    rect), "contains" (one part's region covers the whole rect — geometry
    inside guaranteed), or "partial" (needs the exact residue test)."""
    x0, x1, y0, y1 = env
    any_hit = False
    for outer, holes in parts:
        crossing = False
        for ring in (outer, *holes):
            xs, ys = ring[:, 0], ring[:, 1]
            if np.any(
                _segment_hits_rect(
                    xs, ys, np.roll(xs, -1), np.roll(ys, -1), x0, x1, y0, y1
                )
            ):
                crossing = True
                break
        if crossing:
            any_hit = True
            continue  # boundary passes through the rect: partial by this part
        if _point_in_ring(outer, x0, y0) and not any(
            _point_in_ring(hole, x0, y0) for hole in holes
        ):
            # no boundary inside the rect + one corner interior => the whole
            # rect is interior to this part
            return "contains"
    if not any_hit:
        return "disjoint"
    return "partial"


#: verdicts of :func:`polygon_set_env_relations`, one a row
ENV_DISJOINT, ENV_CONTAINS, ENV_PARTIAL = 0, 1, 2


def polygon_set_env_relations(parts, x0, x1, y0, y1):
    """Batched :func:`_polygon_set_env_relation`: the filter polygon set
    against m envelopes at once (float64 (m,) columns min-x, max-x, min-y,
    max-y) -> uint8 (m,) of ENV_DISJOINT / ENV_CONTAINS / ENV_PARTIAL, the
    verdict the scalar form gives row by row. An envelope that wraps the
    anti-meridian (max-x < min-x) or is not finite is ENV_PARTIAL: only the
    geometry itself can say.

    The envelopes are sorted by min-y once, so the envelopes a segment can
    touch — those whose corner its y-range straddles (the even-odd ray),
    those whose y-range it meets (the clip) — are one slice of that order,
    found by bisection: the cost is a few vector operations a segment over
    the envelopes near it, not a Python call a feature."""
    x0, x1, y0, y1 = (np.asarray(v, dtype=np.float64) for v in (x0, x1, y0, y1))
    out = np.full(len(x0), ENV_PARTIAL, dtype=np.uint8)
    decidable = np.flatnonzero((x1 >= x0) & np.isfinite(x0 + x1 + y0 + y1))
    decidable = decidable[np.argsort(y0[decidable], kind="stable")]
    out[decidable] = _sorted_env_relations(
        parts, x0[decidable], x1[decidable], y0[decidable], y1[decidable]
    )
    return out


def _sorted_env_relations(parts, x0, x1, y0, y1):
    """:func:`polygon_set_env_relations` for finite, non-wrapping envelopes
    in ascending order of y0."""
    m = len(x0)
    tallest = float(np.max(y1 - y0)) if m else 0.0
    contains = np.zeros(m, dtype=bool)
    any_hit = np.zeros(m, dtype=bool)
    for outer, holes in parts:
        crossing = np.zeros(m, dtype=bool)
        in_part = None  # the corner (x0, y0): in the outer ring, in no hole
        for ring in (outer, *holes):
            ax, ay = ring[:, 0], ring[:, 1]
            bx, by = np.roll(ax, -1), np.roll(ay, -1)
            lo_x, hi_x = np.minimum(ax, bx), np.maximum(ax, bx)
            lo_y, hi_y = np.minimum(ay, by), np.maximum(ay, by)
            # the ray from the corner crosses a segment only if
            # lo_y <= y0 < hi_y; the segment clips the envelope only if
            # lo_y - tallest <= y0 <= hi_y (and then y1, x0, x1 decide)
            ray = np.searchsorted(y0, [lo_y, hi_y], side="left")
            near = (
                np.searchsorted(y0, lo_y - tallest, side="left"),
                np.searchsorted(y0, hi_y, side="right"),
            )
            parity = np.zeros(m, dtype=np.uint8)
            rows, segs = [], []
            for k in range(len(ax)):
                sl = slice(ray[0][k], ray[1][k])
                if sl.start < sl.stop:
                    # even-odd, as _point_in_ring has it (segment b -> a)
                    py = y0[sl]
                    parity[sl] += x0[sl] < (ax[k] - bx[k]) * (py - by[k]) / (
                        ay[k] - by[k]
                    ) + bx[k]
                sl = slice(near[0][k], near[1][k])
                met = np.flatnonzero(
                    (y1[sl] >= lo_y[k]) & (x0[sl] <= hi_x[k]) & (x1[sl] >= lo_x[k])
                )
                if len(met):
                    rows.append(met + sl.start)
                    segs.append(np.full(len(met), k))
            if rows:
                row, seg = np.concatenate(rows), np.concatenate(segs)
                hits = _segment_hits_rect(
                    ax[seg], ay[seg], bx[seg], by[seg],
                    x0[row], x1[row], y0[row], y1[row],
                )
                crossing[row[hits]] = True
            inside = parity % 2 == 1
            in_part = inside if in_part is None else in_part & ~inside
        any_hit |= crossing
        contains |= ~crossing & in_part
    return np.where(
        contains, ENV_CONTAINS, np.where(any_hit, ENV_PARTIAL, ENV_DISJOINT)
    ).astype(np.uint8)


def _point_in_polygon_set(parts, px, py):
    """GEOS-style containment in a (multi)polygon with holes."""
    for outer, holes in parts:
        if _point_in_ring(outer, px, py) and not any(
            _point_in_ring(h, px, py) for h in holes
        ):
            return True
    return False


def _feature_geom_parts(geom):
    """Feature geometry -> {"points": (p,2) array, "lines": [(n,2)],
    "polys": [(outer, [holes])]} over every part of any WKB type, or None
    when unparseable."""
    from kart_tpu.geometry import parse_wkb

    try:
        value = parse_wkb(Geometry.of(geom).to_wkb())
    except Exception:
        return None

    points, lines, polys = [], [], []

    def walk(v):
        name, payload = v[0], v.payload
        if payload is None:
            return
        if name == "Point":
            points.append(payload[:2])
        elif name == "MultiPoint":
            for child in payload:
                walk(child)
        elif name == "LineString":
            if len(payload) >= 2:
                lines.append(np.asarray(payload, dtype=np.float64)[:, :2])
        elif name == "MultiLineString":
            for child in payload:
                walk(child)
        elif name == "Polygon":
            rings = [
                np.asarray(r, dtype=np.float64)[:, :2]
                for r in payload
                if len(r) >= 3
            ]
            if rings:
                polys.append((rings[0], rings[1:]))
        elif name in ("MultiPolygon", "GeometryCollection"):
            for child in payload:
                walk(child)

    walk(value)
    return {
        "points": np.asarray(points, dtype=np.float64).reshape(-1, 2),
        "lines": lines,
        "polys": polys,
    }


def _ring_segments(ring):
    a = ring
    b = np.roll(ring, -1, axis=0)
    return a, b


def _segments_cross(a0, a1, b0, b1, chunk=1024):
    """Any segment of set A touches/crosses any of set B (GEOS Intersects
    counts touching). a0/a1: (na,2); b0/b1: (nb,2). Pairwise orientation
    test, chunked over A to bound the (na, nb) broadcast."""

    def cross(ox, oy, ax, ay, bx, by):
        return (ax - ox) * (by - oy) - (ay - oy) * (bx - ox)

    na = len(a0)
    for lo in range(0, na, chunk):
        p0 = a0[lo : lo + chunk][:, None, :]  # (ca,1,2)
        p1 = a1[lo : lo + chunk][:, None, :]
        q0 = b0[None, :, :]  # (1,nb,2)
        q1 = b1[None, :, :]
        d1 = cross(p0[..., 0], p0[..., 1], p1[..., 0], p1[..., 1], q0[..., 0], q0[..., 1])
        d2 = cross(p0[..., 0], p0[..., 1], p1[..., 0], p1[..., 1], q1[..., 0], q1[..., 1])
        d3 = cross(q0[..., 0], q0[..., 1], q1[..., 0], q1[..., 1], p0[..., 0], p0[..., 1])
        d4 = cross(q0[..., 0], q0[..., 1], q1[..., 0], q1[..., 1], p1[..., 0], p1[..., 1])
        proper = ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0))
        if np.any(proper):
            return True
        # touching / collinear-overlap: an endpoint of one lies on the other
        if np.any(
            (d1 == 0) & _on_segment(p0, p1, q0)
            | (d2 == 0) & _on_segment(p0, p1, q1)
            | (d3 == 0) & _on_segment(q0, q1, p0)
            | (d4 == 0) & _on_segment(q0, q1, p1)
        ):
            return True
    return False


def _on_segment(s0, s1, p):
    """p collinear with segment (s0, s1): is it within the segment's bbox?"""
    return (
        (p[..., 0] >= np.minimum(s0[..., 0], s1[..., 0]))
        & (p[..., 0] <= np.maximum(s0[..., 0], s1[..., 0]))
        & (p[..., 1] >= np.minimum(s0[..., 1], s1[..., 1]))
        & (p[..., 1] <= np.maximum(s0[..., 1], s1[..., 1]))
    )


def _filter_ring_segs(parts):
    rings = []
    for outer, holes in parts:
        rings.append(outer)
        rings.extend(holes)
    a = np.concatenate([r for r in rings])
    b = np.concatenate([np.roll(r, -1, axis=0) for r in rings])
    return a, b


def _geom_intersects_polygon_set(feat, parts):
    """GEOS Intersects(filter polygon set, feature geometry) over the parsed
    feature parts (points/lines/polygons)."""
    pts = feat["points"]
    for i in range(len(pts)):
        if _point_in_polygon_set(parts, pts[i, 0], pts[i, 1]):
            return True
    if len(pts):
        # boundary touch — a point exactly on a filter edge counts as
        # Intersects. Tested for every feature's points, not only
        # points-only features: a GeometryCollection whose point touches
        # the boundary matches even when its lines/polys are disjoint.
        fa, fb = _filter_ring_segs(parts)
        p = pts[:, None, :]
        d = (fb[None, :, 0] - fa[None, :, 0]) * (p[..., 1] - fa[None, :, 1]) - (
            fb[None, :, 1] - fa[None, :, 1]
        ) * (p[..., 0] - fa[None, :, 0])
        if np.any((d == 0) & _on_segment(fa[None, :, :], fb[None, :, :], p)):
            return True
    if not feat["lines"] and not feat["polys"]:
        return False

    fa, fb = _filter_ring_segs(parts)
    for line in feat["lines"]:
        a0, a1 = line[:-1], line[1:]
        if len(a0) and _segments_cross(a0, a1, fa, fb):
            return True
        # no boundary crossing: the line is wholly inside or outside
        if _point_in_polygon_set(parts, line[0, 0], line[0, 1]):
            return True
    for outer, holes in feat["polys"]:
        for ring in (outer, *holes):
            r0, r1 = _ring_segments(ring)
            if _segments_cross(r0, r1, fa, fb):
                return True
        # no boundary crossing: disjoint, feature inside filter, or filter
        # inside feature (possibly inside a feature hole)
        if _point_in_polygon_set(parts, outer[0, 0], outer[0, 1]):
            return True
        for fouter, _fholes in parts:
            fx, fy = fouter[0, 0], fouter[0, 1]
            if _point_in_ring(outer, fx, fy) and not any(
                _point_in_ring(h, fx, fy) for h in holes
            ):
                return True
    return False


def _point_in_ring(ring, px, py):
    xs, ys = ring[:, 0], ring[:, 1]
    xj, yj = np.roll(xs, 1), np.roll(ys, 1)
    crossing = ((ys > py) != (yj > py)) & (
        px < (xj - xs) * (py - ys) / np.where(yj == ys, np.inf, yj - ys) + xs
    )
    return bool(np.sum(crossing) % 2)


def _segment_hits_rect(ax, ay, bx, by, x0, x1, y0, y1):
    """Vectorized Liang–Barsky clip: exact segment-vs-rect intersection."""
    dx, dy = bx - ax, by - ay
    t0 = np.zeros_like(ax, dtype=np.float64)
    t1 = np.ones_like(ax, dtype=np.float64)
    hit = np.ones_like(ax, dtype=bool)
    for p, q in (
        (-dx, ax - x0),
        (dx, x1 - ax),
        (-dy, ay - y0),
        (dy, y1 - ay),
    ):
        parallel_out = (p == 0) & (q < 0)
        with np.errstate(divide="ignore", invalid="ignore"):
            t = np.where(p != 0, q / np.where(p == 0, 1.0, p), 0.0)
        t0 = np.where(p < 0, np.maximum(t0, t), t0)
        t1 = np.where(p > 0, np.minimum(t1, t), t1)
        hit &= ~parallel_out
    return hit & (t0 <= t1)


# -- server side: blob filter for partial clone -----------------------------


def blob_filter_for_spec(src_repo, wsen_arg):
    """-> callable(path, oid) -> bool for ObjectEnumerator.blob_filter.

    wsen_arg: "w,s,e,n" string or a 4-tuple, EPSG:4326. Feature blobs whose
    envelope misses the rect are vetoed (= left promised on the client);
    everything else ships (reference: spatial_filter.cpp:212-260 — also
    fails open: blobs with no envelope record are shipped)."""
    if isinstance(wsen_arg, str):
        parts = [float(p) for p in wsen_arg.split(",")]
        if len(parts) != 4:
            raise SpatialFilterError(f"Bad spatial filter rect: {wsen_arg!r}")
        w, s, e, n = parts
    else:
        w, s, e, n = wsen_arg

    from kart_tpu.spatial_filter.index import EnvelopeIndexReader

    reader = EnvelopeIndexReader.open(src_repo)  # None if no index built
    transforms = _DatasetEnvelopeDecoder(src_repo)

    # batch pre-pass over the whole envelope table: one vectorized
    # bbox-intersect call (native C++ / numpy) instead of a sqlite lookup
    # per blob — the TPU-era answer to spatial_filter.cpp's per-OID loop
    matched_oids = rejected_oids = None
    if reader is not None:
        import os as _os

        from kart_tpu.ops.bbox import bbox_intersects
        from kart_tpu.spatial_filter.index import db_path

        oids, wsen = reader.all_envelopes()
        if len(oids):
            # cache key = (index path, mtime): a long-running server keeps
            # the envelope columns device-resident across filtered fetches
            idx_path = db_path(src_repo)
            try:
                key = ("envidx", idx_path, _os.stat(idx_path).st_mtime_ns)
            except OSError:
                key = None
            # the veto must stay conservative under the device kernel's
            # float32 rounding: widen the query by more than f32 ulp at
            # +-360 (2.2e-5 deg) but under the envelope codec's own
            # outward-rounded granularity (360/2^20 = 3.4e-4 deg) — a
            # borderline feature ships (fail open) instead of being
            # wrongly withheld from the clone
            pad = 1e-4
            hits = bbox_intersects(
                wsen, (w - pad, s - pad, e + pad, n + pad), cache_key=key
            )
            matched_oids = {o for o, h in zip(oids, hits) if h}
            rejected_oids = {o for o, h in zip(oids, hits) if not h}

    def blob_filter(path, oid):
        ds_feature = _split_feature_path(path)
        if ds_feature is None:
            return True  # meta / non-feature blob: always ship
        if matched_oids is not None:
            if oid in matched_oids:
                return True
            if oid in rejected_oids:
                return False
            # not indexed: fall through to on-the-fly decode
        env_4326 = transforms.envelope_4326(ds_feature[0], oid)
        if env_4326 is None:
            return True  # no geometry / undecodable: fail open
        x0, x1, y0, y1 = env_4326
        return _rect_overlaps((x0, x1, y0, y1), (w, e, s, n))

    return blob_filter


def _split_feature_path(path):
    """'<ds>/.table-dataset/feature/ab/cd' -> (ds_path, rel) or None."""
    for dirname in (".table-dataset", ".sno-dataset"):
        marker = f"/{dirname}/feature/"
        idx = path.find(marker)
        if idx >= 0:
            return path[:idx], path[idx + len(marker) :]
    return None


class _DatasetEnvelopeDecoder:
    """On-the-fly feature envelope decode + transform to EPSG:4326, cached
    per dataset (fallback when the envelope index isn't built)."""

    def __init__(self, repo):
        self.repo = repo
        self._cache = {}

    def _dataset_transform(self, ds_path):
        if ds_path in self._cache:
            return self._cache[ds_path]
        transform = None
        try:
            ds = self.repo.datasets("HEAD").get(ds_path)
            if ds is not None and ds.geom_column_name is not None:
                ids = ds.crs_identifiers()
                crs_wkt = ds.get_crs_definition(ids[0]) if ids else None
                if crs_wkt:
                    ds_crs = CRS(crs_wkt)
                    if not ds_crs.is_geographic:
                        transform = Transform(ds_crs, make_crs(EPSG_4326_WKT))
                    else:
                        transform = "identity"
                else:
                    transform = "identity"
        except Exception:
            transform = None
        self._cache[ds_path] = transform
        return transform

    def envelope_4326(self, ds_path, oid):
        transform = self._dataset_transform(ds_path)
        if transform is None:
            return None
        try:
            from kart_tpu.core.serialise import msg_unpack

            data = self.repo.odb.read_blob(oid)
            _, values = msg_unpack(data)
            geom = next((v for v in values if isinstance(v, Geometry)), None)
            if geom is None:
                return None
            env = geom.envelope()
            if env is None:
                return None
            if transform == "identity":
                return env
            from kart_tpu.spatial_filter.index import wrap_lon

            x0, x1, y0, y1 = transform.transform_envelope(env)
            # same anti-meridian semantics as the built index: out-of-range
            # lons wrap, possibly producing a cyclic (x0 > x1) envelope
            # that _rect_overlaps evaluates cyclically
            return (float(wrap_lon(x0)), float(wrap_lon(x1)), y0, y1)
        except Exception:
            return None
