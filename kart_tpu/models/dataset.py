"""Datasets V3/V2 table model (reference: kart/dataset3.py, kart/base_dataset.py).

A dataset is an immutable view of a git tree:

    <ds-path>/.table-dataset/          (V2: .sno-dataset)
        meta/
            schema.json                ordered column dicts
            legend/<hexhash>           msgpack (pk-col-ids, non-pk-col-ids)
            title, description         text
            crs/<identifier>.wkt       CRS definitions
            path-structure.json        PathEncoder spec
            capabilities.json          forward-compat refusal marker
        feature/<encoded-path>         msgpack [legend-hash, [non-pk values]]
    <ds-path>/metadata.xml             "attachment" meta item (outer tree)

Datasets never write trees directly — mutating methods *return* things to
write (path, blob) and the caller batches them through a TreeBuilder into a
commit (same discipline as the reference, dataset3.py:55-61).

The per-feature read path here is the *scalar* reference path; bulk access
goes through :meth:`feature_index` / :meth:`feature_blob_batch`, which feed
numpy/JAX columnar blocks (kart_tpu/ops) instead of per-feature Python dicts.
"""

import functools
import logging

import numpy as np

from kart_tpu.core.odb import ObjectMissing, ObjectPromised, TreeView
from kart_tpu.core.serialise import (
    b64decode_str,
    ensure_bytes,
    ensure_text,
    json_pack,
    json_unpack,
    msg_pack,
    msg_unpack,
    msg_unpack_ext_raw,
)
from kart_tpu.models.paths import PathEncoder, encoder_for_schema
from kart_tpu.models.schema import Legend, Schema

L = logging.getLogger("kart_tpu.dataset")

META_ITEM_NAMES = ("title", "description", "schema.json", "metadata.xml")
ATTACHMENT_META_ITEMS = ("metadata.xml",)


class IntegrityError(ValueError):
    pass


class NotYetImplemented(RuntimeError):
    pass


class FeatureOidPromise:
    """Zero-arg callable resolving a feature dict from its blob oid.

    Unlike an opaque closure, the oid/dataset are open attributes so delta
    consumers (diff writers) can batch-prefetch many promises' blob data in
    one native batch pack inflate (``odb.read_blobs_batch``) and stash it on
    ``data`` — the per-feature pack bisect + single-shot inflate was ~55us
    of the ~80us/feature materialisation cost at 10M-polygon scale
    (reference's equivalent loop: kart/base_diff_writer.py:279-341).
    Tri-state semantics are unchanged: an unprefetched promised blob raises
    ObjectPromised from the per-object read exactly as before."""

    __slots__ = ("ds", "pk_values", "oid_hex", "data")

    def __init__(self, ds, pk_values, oid_hex):
        self.ds = ds
        self.pk_values = pk_values
        self.oid_hex = oid_hex
        self.data = None

    def __call__(self):
        data = self.data
        if data is None:
            data = self.ds._feature_odb().read_blob(self.oid_hex)
        else:
            self.data = None  # one-shot: free the blob bytes after decode
        return self.ds.get_feature(self.pk_values, data=data)


def _json_value_str(v, _float_repr=float.__repr__):
    """One scalar -> its JSON text, byte-identical to the stdlib encoder
    with ``separators=(",", ":"), ensure_ascii=True``. Exact-type checks:
    bool is an int subclass and must not take the int branch. (The common
    int/str/float cases are inlined in feature_json_str_from_data; this
    covers the rest plus subclass oddities.)"""
    t = v.__class__
    if t is int:
        return str(v)
    if t is str:
        from json.encoder import encode_basestring_ascii

        return encode_basestring_ascii(v)
    if t is float:
        # json emits float.__repr__ for finite floats, names otherwise
        if v == v and v not in (float("inf"), float("-inf")):
            return _float_repr(v)
        return "NaN" if v != v else ("Infinity" if v > 0 else "-Infinity")
    if t is bool:
        return "true" if v else "false"
    if t is bytes:
        return '"' + v.hex() + '"'
    import json as _json

    return _json.dumps(v, separators=(",", ":"), ensure_ascii=True)


def compiled_blob_encoder(schema):
    """Per-legend *compiled* feature-blob serialiser ``fn(feature_dict) ->
    (pk_values, blob_bytes)`` — the blob-encode twin of the compiled JSON
    serialisers below (:meth:`Dataset3._jsonl_serializer`): the column
    resolution ``Schema.feature_to_raw_dict`` + ``Legend.to_value_tuples``
    performs per feature is unrolled into straight-line code feeding one
    reused msgpack Packer, so the import/apply hot loop pays no raw dict, no
    value-tuple list and no per-feature Packer construction. Bit-identical
    to ``schema.encode_feature_blob`` (tested): the Packer carries the same
    ``strict_types``/``use_bin_type``/default-hook configuration as
    ``core.serialise.msg_pack``, so any value the generic path accepts (or
    rejects) behaves identically here — geometry just skips the subclass
    hook dispatch via an inlined ``pack_ext_type``. Every embedded literal
    goes through repr(), keeping arbitrary column names inert string
    constants in the generated source.

    NOT thread-safe: the packer buffer is reused across calls, so each
    thread needs its own encoder (the import pipeline's encode stage owns
    exactly one)."""
    import msgpack

    from kart_tpu.core.serialise import GEOMETRY_EXT_CODE, _pack_hook
    from kart_tpu.geometry import Geometry as _Geom

    cols = {c.id: c for c in schema.columns}
    legend = schema.legend
    pk_names = [cols[cid].name for cid in legend.pk_columns]
    lines = [
        "def _enc(f, _p=_p, _lh=_lh, _G=_G, _Geom=_Geom, _bytes=bytes):",
        " _p.pack_array_header(2)",
        " _p.pack(_lh)",
        f" _p.pack_array_header({len(legend.non_pk_columns)})",
    ]
    for cid in legend.non_pk_columns:
        c = cols[cid]
        if c.data_type == "geometry":
            lines.append(f" v = f[{c.name!r}]")
            lines.append(" if v is None: _p.pack(None)")
            # ext-encode only Geometry instances — the generic hook packs a
            # plain-bytes geometry value as bin, and the blobs must match
            lines.append(" elif isinstance(v, _Geom): _p.pack_ext_type(_G, _bytes(v))")
            lines.append(" else: _p.pack(v)")
        else:
            lines.append(f" _p.pack(f[{c.name!r}])")
    pk_expr = ", ".join(f"f[{n!r}]" for n in pk_names)
    trailing = "," if len(pk_names) == 1 else ""
    lines.append(f" pk = ({pk_expr}{trailing})")
    lines.append(" out = _p.bytes()")
    lines.append(" _p.reset()")
    lines.append(" return pk, out")
    namespace = {
        # autoreset=False: the blob is composed incrementally (array header,
        # hash, values) — with autoreset every pack() would flush mid-record
        "_p": msgpack.Packer(
            use_bin_type=True,
            strict_types=True,
            default=_pack_hook,
            autoreset=False,
        ),
        "_lh": schema.legend_hash,
        "_G": GEOMETRY_EXT_CODE,
        "_Geom": _Geom,
    }
    exec("\n".join(lines), namespace)
    return namespace["_enc"]


class DatasetCapabilityError(RuntimeError):
    """Dataset requires capabilities this version doesn't support
    (reference: dataset3.py:109-124)."""


class Dataset3:
    """V3 dataset bound to a tree. ``tree`` is the outer dataset tree (the
    one at ``path``); pass ``tree=None`` for a dataset that doesn't exist yet
    (import target)."""

    VERSION = 3
    DATASET_DIRNAME = ".table-dataset"

    FEATURE_PATH = "feature/"
    META_PATH = "meta/"
    LEGEND_PATH = "meta/legend/"
    SCHEMA_PATH = "meta/schema.json"
    TITLE_PATH = "meta/title"
    DESCRIPTION_PATH = "meta/description"
    CRS_PATH = "meta/crs/"
    PATH_STRUCTURE_PATH = "meta/path-structure.json"
    CAPABILITIES_PATH = "meta/capabilities.json"

    def __init__(self, tree, path, repo=None):
        self.tree = tree
        self.path = path.strip("/")
        self.repo = repo
        self._meta_cache = {}
        if self.inner_tree is not None:
            self._refuse_unknown_capabilities()

    # -- identity ----------------------------------------------------------

    @classmethod
    def is_dataset_tree(cls, tree):
        if tree is None:
            return False
        try:
            entry = tree.entry(cls.DATASET_DIRNAME)
        except KeyError:
            return False
        return entry.is_tree

    @property
    def inner_tree(self):
        if self.tree is None:
            return None
        try:
            node = self.tree.get(self.DATASET_DIRNAME)
        except KeyError:
            return None
        return node if isinstance(node, TreeView) else None

    @property
    def inner_path(self):
        return f"{self.path}/{self.DATASET_DIRNAME}"

    @property
    def feature_tree(self):
        inner = self.inner_tree
        if inner is None:
            return None
        return inner.get_or_none("feature")

    def _refuse_unknown_capabilities(self):
        caps = self.get_meta_item("capabilities.json", missing_ok=True)
        if caps:
            raise DatasetCapabilityError(
                f"Dataset {self.path} requires unsupported capabilities: {caps}"
            )

    # -- meta items ----------------------------------------------------------

    def get_data_at(self, rel_path, missing_ok=False):
        """Raw bytes at path relative to the inner tree."""
        inner = self.inner_tree
        node = inner.get_or_none(rel_path) if inner is not None else None
        if node is None or isinstance(node, TreeView):
            if missing_ok:
                return None
            raise KeyError(f"{self.inner_path}/{rel_path}")
        return node.data

    def get_meta_item(self, name, missing_ok=True):
        """Decoded meta item: JSON names -> parsed, .wkt/text -> str,
        unknown extensions -> bytes (reference: base_dataset.py:324-364)."""
        if name in self._meta_cache:
            return self._meta_cache[name]
        if name in ATTACHMENT_META_ITEMS:
            data = None
            if self.tree is not None:
                node = self.tree.get_or_none(name)
                data = node.data if node is not None and not isinstance(node, TreeView) else None
        else:
            data = self.get_data_at(self.META_PATH + name, missing_ok=True)
            if data is None and not name.startswith("crs/"):
                # names like "crs/EPSG:4326.wkt" are already qualified
                data = self.get_data_at(name, missing_ok=True)
        if data is None:
            if missing_ok:
                result = None
            else:
                raise KeyError(f"No meta item: {name}")
        elif name.endswith(".json"):
            result = json_unpack(data)
        elif name.endswith(".wkt") or name in ("title", "description"):
            result = ensure_text(data)
        elif name == "metadata.xml":
            result = ensure_text(data)
        else:
            result = data
        self._meta_cache[name] = result
        return result

    def meta_items(self, only_standard_items=True):
        """dict of all present meta items."""
        out = {}
        for name in ("title", "description", "schema.json"):
            value = self.get_meta_item(name)
            if value is not None:
                out[name] = value
        for name in self.crs_identifiers():
            out[f"crs/{name}.wkt"] = self.get_meta_item(f"crs/{name}.wkt")
        value = self.get_meta_item("metadata.xml")
        if value is not None:
            out["metadata.xml"] = value
        if not only_standard_items:
            inner = self.inner_tree
            meta = inner.get_or_none("meta") if inner is not None else None
            if meta is not None:
                for path, entry in meta.walk_blobs():
                    if path.startswith("legend/"):
                        continue
                    name = path
                    if name not in out and name not in (
                        "path-structure.json",
                        "capabilities.json",
                    ):
                        out[name] = self.get_meta_item(name)
        return out

    def crs_identifiers(self):
        inner = self.inner_tree
        if inner is None:
            return []
        crs_tree = inner.get_or_none("meta/crs")
        if crs_tree is None:
            return []
        return [
            e.name[: -len(".wkt")]
            for e in crs_tree.entries()
            if e.name.endswith(".wkt")
        ]

    def get_crs_definition(self, identifier=None):
        ids = self.crs_identifiers()
        if identifier is None:
            if len(ids) != 1:
                raise ValueError(
                    f"Dataset {self.path} has {len(ids)} CRS definitions; specify one of {ids}"
                )
            identifier = ids[0]
        if identifier.startswith("crs/"):
            identifier = identifier[4:-4] if identifier.endswith(".wkt") else identifier[4:]
        return self.get_meta_item(f"crs/{identifier}.wkt")

    @property
    def schema(self) -> Schema:
        if "__schema__" not in self._meta_cache:
            cols = self.get_meta_item("schema.json", missing_ok=False)
            self._meta_cache["__schema__"] = Schema.from_column_dicts(cols)
        return self._meta_cache["__schema__"]

    @property
    def has_geometry(self):
        return self.schema.has_geometry

    @property
    def geom_column_name(self):
        col = self.schema.first_geometry_column
        return col.name if col else None

    def get_legend(self, legend_hash) -> Legend:
        key = f"__legend__{legend_hash}"
        if key not in self._meta_cache:
            data = self.get_data_at(self.LEGEND_PATH + legend_hash)
            self._meta_cache[key] = Legend.loads(data)
        return self._meta_cache[key]

    @property
    def path_encoder(self) -> PathEncoder:
        if "__encoder__" not in self._meta_cache:
            spec = self.get_meta_item("path-structure.json")
            if spec is not None:
                enc = PathEncoder.get(**spec)
            else:
                enc = PathEncoder.LEGACY_ENCODER
            self._meta_cache["__encoder__"] = enc
        return self._meta_cache["__encoder__"]

    # -- feature reads -------------------------------------------------------

    def decode_path_to_pks(self, path):
        """feature blob path (or bare filename) -> pk value tuple."""
        return PathEncoder.decode_filename(path.rsplit("/", 1)[-1])

    def decode_path_to_1pk(self, path):
        pks = self.decode_path_to_pks(path)
        if len(pks) != 1:
            raise ValueError(f"Dataset has composite pk: {pks}")
        return pks[0]

    def encode_1pk_to_path(self, pk, relative=False):
        return self.encode_pks_to_path((pk,), relative=relative)

    def encode_pks_to_path(self, pk_values, relative=False):
        rel = self.FEATURE_PATH + self.path_encoder.encode_pks_to_path(pk_values)
        return rel if relative else f"{self.inner_path}/{rel}"

    def get_feature(self, pk_values=None, *, path=None, data=None):
        """-> feature dict keyed by column name. Give pk values, a blob path
        (relative to the feature tree), or raw blob data."""
        if data is None:
            if path is not None:
                pk_values = self.decode_path_to_pks(path)
            else:
                pk_values = self.schema.sanitise_pks(pk_values)
            rel = self.path_encoder.encode_pks_to_path(tuple(pk_values))
            data = self.get_data_at(self.FEATURE_PATH + rel)
        elif pk_values is None and path is not None:
            pk_values = self.decode_path_to_pks(path)
        legend_hash, non_pk_values = msg_unpack(data)
        legend = self.get_legend(legend_hash)
        raw = legend.to_raw_dict(tuple(pk_values), tuple(non_pk_values))
        return self.schema.feature_from_raw_dict(raw)

    def get_feature_promise(self, pk_values, path=None):
        """-> zero-arg callable that reads the feature lazily."""
        return functools.partial(self.get_feature, pk_values, path=path)

    def _json_plan(self, legend_hash):
        """Per-legend decode plan for :meth:`feature_json_from_data`:
        [(column name, (is_pk, value index) | None, is_geometry)] in schema
        order — the same column resolution get_feature performs through
        Legend.to_raw_dict + Schema.feature_from_raw_dict, precomputed."""
        plans = self.__dict__.setdefault("_json_plans", {})
        plan = plans.get(legend_hash)
        if plan is None:
            legend = self.get_legend(legend_hash)
            pk_pos = {cid: i for i, cid in enumerate(legend.pk_columns)}
            nonpk_pos = {cid: i for i, cid in enumerate(legend.non_pk_columns)}
            plan = []
            for c in self.schema.columns:
                if c.id in pk_pos:
                    src = (True, pk_pos[c.id])
                elif c.id in nonpk_pos:
                    src = (False, nonpk_pos[c.id])
                else:
                    src = None  # column added since this legend: None value
                plan.append((c.name, src, c.data_type == "geometry"))
            plans[legend_hash] = plan
        return plan

    def feature_json_from_data(self, pk_values, data):
        """Feature blob bytes -> JSON-ready dict (geometry as upper-hex WKB,
        bytes as hex), bit-identical to
        ``feature_as_json(self.get_feature(pk_values, data=data))`` but in
        one dict build with no Geometry construction — the hot
        materialisation path of `diff -o json/json-lines` (the reference's
        per-feature loop: kart/dataset3.py:185-223 + feature_output.py:34)."""
        from kart_tpu.geometry import gpkg_hex_wkb

        legend_hash, non_pk_values = msg_unpack_ext_raw(data)
        out = {}
        for name, src, is_geom in self._json_plan(legend_hash):
            v = None
            if src is not None:
                is_pk, i = src
                seq = pk_values if is_pk else non_pk_values
                if i < len(seq):
                    v = seq[i]
            if v is not None:
                if is_geom:
                    v = gpkg_hex_wkb(v)
                elif isinstance(v, bytes):
                    v = v.hex()
            out[name] = v
        return out

    def _jsonl_plan(self, legend_hash):
        """Per-legend *serialise* plan for :meth:`feature_json_str_from_data`:
        [(json member prefix '"name":' (',' -joined), source, is_geometry)].
        Same column resolution as :meth:`_json_plan`, with the member names
        pre-escaped so the hot loop only serialises values."""
        from json.encoder import encode_basestring_ascii

        plans = self.__dict__.setdefault("_jsonl_plans", {})
        plan = plans.get(legend_hash)
        if plan is None:
            plan = []
            for i, (name, src, is_geom) in enumerate(self._json_plan(legend_hash)):
                prefix = ("" if i == 0 else ",") + encode_basestring_ascii(name) + ":"
                plan.append((prefix, src, is_geom))
            plans[legend_hash] = plan
        return plan

    def jsonl_native_plans(self):
        """The :meth:`_jsonl_plan` of every legend under ``meta/legend/``,
        packed for ``native.jsonl_chunk``: what the native materialiser
        serialises a blob by. A blob whose legend is not among them comes
        back declined and takes :meth:`feature_json_str_from_data`."""
        from kart_tpu import native

        inner = self.inner_tree
        legends = None
        if inner is not None:
            legends = inner.get_or_none(self.LEGEND_PATH.rstrip("/"))
        names = [e.name for e in legends.entries()] if legends is not None else []
        return native.pack_jsonl_plans({h: self._jsonl_plan(h) for h in names})

    def _jsonl_serializer(self, legend_hash):
        """Per-legend *compiled* serialiser ``fn(pk_values, non_pk_values)
        -> json object text``: the column plan unrolled into straight-line
        code (no plan loop, no per-column tuple unpacks — ~30% of the
        serialise wall at 1M-changed scale). Every embedded literal goes
        through repr(), so arbitrary column names stay inert string
        constants in the generated source."""
        fns = self.__dict__.setdefault("_jsonl_fns", {})
        fn = fns.get(legend_hash)
        if fn is not None:
            return fn
        from json.encoder import encode_basestring_ascii

        from kart_tpu.geometry import gpkg_hex_wkb

        lines = [
            "def _ser(pk, vals, _str=str, _esc=_esc, _fr=_fr, _hex=_hex, _jvs=_jvs):",
            " np_ = len(pk)",
            " nv_ = len(vals)",
        ]
        parts = []
        for k, (prefix, src, is_geom) in enumerate(self._jsonl_plan(legend_hash)):
            if src is None:
                parts.append(repr(prefix + "null"))
                continue
            is_pk, i = src
            seq, bound = ("pk", "np_") if is_pk else ("vals", "nv_")
            lines.append(f" v{k} = {seq}[{i}] if {i} < {bound} else None")
            if is_geom:
                parts.append(
                    f"({prefix!r} + ('null' if v{k} is None else"
                    f" '\"' + _hex(v{k}) + '\"'))"
                )
            else:
                parts.append(
                    f"({prefix!r} + ('null' if v{k} is None else"
                    f" _str(v{k}) if v{k}.__class__ is int else"
                    f" _esc(v{k}) if v{k}.__class__ is str else"
                    f" _fr(v{k}) if v{k}.__class__ is float"
                    f" and v{k} == v{k} and -1e400 < v{k} < 1e400 else"
                    f" _jvs(v{k})))"
                )
            # exact-type dispatch mirrors _json_value_str: bool (an int
            # subclass), non-finite floats and exotic types all defer there
        body = " + ".join(parts) if parts else "''"
        lines.append(f" return '{{' + {body} + '}}'")
        namespace = {
            "_esc": encode_basestring_ascii,
            "_fr": float.__repr__,
            "_hex": gpkg_hex_wkb,
            "_jvs": _json_value_str,
        }
        exec("\n".join(lines), namespace)
        fn = namespace["_ser"]
        fns[legend_hash] = fn
        return fn

    def feature_json_str_from_data(self, pk_values, data):
        """Feature blob bytes -> the feature's compact-JSON object text,
        byte-identical to JSON-encoding :meth:`feature_json_from_data`'s
        dict with ``separators=(",", ":"), ensure_ascii=True`` (tested) —
        but fused: one msgpack decode feeding the legend's compiled
        serialiser directly, with no intermediate dict and no generic
        encoder walk over it. This is the hot tail of full-output `diff -o
        json-lines` (the per-feature dict round-trip was ~40% of the 49.6k
        features/s materialisation wall at 10M-polygon scale)."""
        legend_hash, non_pk_values = msg_unpack_ext_raw(data)
        fns = self.__dict__.get("_jsonl_fns")
        fn = fns.get(legend_hash) if fns is not None else None
        if fn is None:
            fn = self._jsonl_serializer(legend_hash)
        return fn(pk_values, non_pk_values)

    def get_feature_from_oid(self, pk_values, oid_hex):
        """Feature dict resolved straight from its blob oid. The diff
        engines already know each changed feature's oid (tree-diff entries /
        sidecar columns), so the per-feature path->tree walk — a parse_tree
        per directory level, measured ~500us per materialised feature at
        10M-polygon scale — is skipped entirely. Tri-state semantics are
        unchanged: a promised blob raises ObjectPromised from the odb read
        exactly as the path walk would."""
        return self.get_feature(
            pk_values, data=self._feature_odb().read_blob(oid_hex)
        )

    def get_feature_promise_from_oid(self, pk_values, oid_hex):
        """-> zero-arg callable; like get_feature_promise but resolves via
        the known blob oid instead of the feature path. The promise carries
        its oid openly (:class:`FeatureOidPromise`) so delta consumers can
        batch-prefetch blob data through the native batch pack reader."""
        return FeatureOidPromise(self, pk_values, oid_hex)

    def _feature_odb(self):
        """Object store feature blobs resolve from (cached: the tree walk
        behind :attr:`feature_tree` costs ~13us and the materialisation path
        used to pay it once per feature)."""
        odb = self.__dict__.get("_feature_odb_cache")
        if odb is None:
            tree = self.feature_tree
            odb = tree.odb if tree is not None else self.repo.odb
            self.__dict__["_feature_odb_cache"] = odb
        return odb

    def features(self, spatial_filter=None, log_progress=False, skip_promised=False):
        """Stream all features (schema order). Bulk columnar access should
        prefer feature_index + feature_blob_batch.

        skip_promised: features whose blobs are promised (partial clone) are
        skipped instead of raising — during checkout of a spatially-filtered
        clone a promised blob *is* the out-of-filter signal (reference:
        working copies contain only in-filter features, kart/checkout.py)."""
        feature_tree = self.feature_tree
        if feature_tree is None:
            return
        odb = feature_tree.odb
        n_promised = 0
        from kart_tpu.utils import chunked

        for chunk in chunked(feature_tree.walk_blobs(), 10000):
            # bulk read: one native batch inflate per chunk; the per-object
            # path covers whatever the batch can't (loose/delta/promised)
            batch = odb.read_blobs_batch([entry.oid for _, entry in chunk])
            for path, entry in chunk:
                pk_values = self.decode_path_to_pks(path)
                data = batch.get(entry.oid)
                try:
                    if data is None:
                        data = odb.read_blob(entry.oid)
                    feature = self.get_feature(pk_values, data=data)
                except ObjectPromised:
                    if skip_promised:
                        n_promised += 1
                        continue
                    raise
                if spatial_filter is not None and not spatial_filter.matches(
                    feature
                ):
                    continue
                yield feature
        if n_promised:
            L.debug(
                "%s: skipped %d promised (out-of-filter) features",
                self.path,
                n_promised,
            )

    @property
    def feature_count(self):
        feature_tree = self.feature_tree
        if feature_tree is None:
            return 0
        return sum(1 for _ in feature_tree.walk_blobs())

    # -- columnar bulk access ------------------------------------------------

    def feature_index(self):
        """-> (paths list[str], pk int64 array | None, oid bytes array (N,20)).

        The bridge from blob-world to array-world: one host walk of the
        feature tree produces the (pk, oid) arrays the TPU diff engine
        consumes. pk array is None for datasets without a single int pk
        (their identity array is the filename hash instead).
        """
        feature_tree = self.feature_tree
        if feature_tree is None:
            return [], None, np.zeros((0, 20), dtype=np.uint8)
        paths = []
        oids = []
        for path, entry in feature_tree.walk_blobs():
            paths.append(path)
            oids.append(entry.oid)
        oid_arr = (
            np.frombuffer(
                bytes.fromhex("".join(oids)), dtype=np.uint8
            ).reshape(-1, 20)
            if oids
            else np.zeros((0, 20), dtype=np.uint8)
        )
        enc = self.path_encoder
        pk_arr = None
        if isinstance(enc, type(PathEncoder.INT_PK_ENCODER)) and enc.scheme == "int":
            pk_arr = enc.decode_paths_batch(paths)
        return paths, pk_arr, oid_arr

    def feature_blob_batch(self, paths):
        """Fetch many feature blobs -> list[bytes] (absent -> None)."""
        odb = self.tree.odb
        feature_tree = self.feature_tree
        out = []
        for p in paths:
            node = feature_tree.get_or_none(p) if feature_tree is not None else None
            out.append(odb.read_blob(node.oid) if node is not None else None)
        return out

    # -- writing (returns things to write) -----------------------------------

    @classmethod
    def new_dataset_meta_blobs(cls, path, schema, *, title=None, description=None,
                               crs_defs=None, path_encoder=None):
        """-> [(full_path, blob_bytes)] for a brand-new dataset's meta tree."""
        inner = f"{path.strip('/')}/{cls.DATASET_DIRNAME}"
        enc = path_encoder or encoder_for_schema(schema)
        blobs = [
            (f"{inner}/{cls.SCHEMA_PATH}", schema.dumps()),
            (
                f"{inner}/{cls.LEGEND_PATH}{schema.legend_hash}",
                schema.legend.dumps(),
            ),
        ]
        if enc is not PathEncoder.LEGACY_ENCODER:
            blobs.append(
                (f"{inner}/{cls.PATH_STRUCTURE_PATH}", json_pack(enc.to_dict()))
            )
        if title:
            blobs.append((f"{inner}/{cls.TITLE_PATH}", ensure_bytes(title)))
        if description:
            blobs.append(
                (f"{inner}/{cls.DESCRIPTION_PATH}", ensure_bytes(description))
            )
        for ident, wkt in (crs_defs or {}).items():
            blobs.append((f"{inner}/{cls.CRS_PATH}{ident}.wkt", ensure_bytes(wkt)))
        return blobs

    def encode_feature(self, feature, schema=None, *, relative=False):
        """feature dict -> (path, blob_bytes)."""
        schema = schema or self.schema
        pk_values, blob = schema.encode_feature_blob(feature)
        rel = self.FEATURE_PATH + self.path_encoder.encode_pks_to_path(pk_values)
        return (rel if relative else f"{self.inner_path}/{rel}", blob)

    def encode_meta_item(self, name, value):
        """meta item name/value -> (full_path, blob_bytes or None-to-delete)."""
        if value is None:
            data = None
        elif name.endswith(".json"):
            data = json_pack(value)
        else:
            data = ensure_bytes(value)
        if name in ATTACHMENT_META_ITEMS:
            return (f"{self.path}/{name}", data)
        return (f"{self.inner_path}/{self.META_PATH}{name}", data)

    def import_iter_feature_blobs(self, features, schema=None):
        """Generator of (full_path, blob_bytes) over a feature iterable —
        the import hot loop (reference: dataset3.py:302-346). Encodes
        through the legend's compiled blob serialiser
        (:func:`compiled_blob_encoder`, bit-identical to
        ``schema.encode_feature_blob``)."""
        schema = schema or self.schema
        enc = self.path_encoder
        prefix = f"{self.inner_path}/{self.FEATURE_PATH}"
        encode = compiled_blob_encoder(schema)
        for feature in features:
            if isinstance(feature, dict):
                pk_values, blob = encode(feature)
            else:
                # schema-ordered sequences (the other shape
                # feature_to_raw_dict accepts) take the generic path —
                # the compiled encoder indexes by column name
                pk_values, blob = schema.encode_feature_blob(feature)
            yield prefix + enc.encode_pks_to_path(pk_values), blob

    # -- applying diffs ------------------------------------------------------

    def apply_diff(self, ds_diff, tree_builder, *, allow_missing_old=False):
        """Apply one dataset's DatasetDiff through the tree builder, with
        conflict detection (reference: rich_base_dataset.py:302-501)."""
        schema = self.apply_meta_diff(
            ds_diff.get("meta"), tree_builder, allow_missing_old=allow_missing_old
        )
        self.apply_feature_diff(
            ds_diff.get("feature"),
            tree_builder,
            schema=schema,
            allow_missing_old=allow_missing_old,
        )

    def apply_meta_diff(self, meta_diff, tree_builder, *, allow_missing_old=False):
        """-> the schema features should be encoded against after this diff."""
        from kart_tpu.core.structure import PatchApplyError

        schema = None if self.inner_tree is None else self.schema
        if not meta_diff:
            return schema

        for name, delta in meta_diff.items():
            if not allow_missing_old:
                current = self.get_meta_item(name) if self.inner_tree is not None else None
                old = delta.old_value
                if current != old:
                    raise PatchApplyError(
                        f"Conflict at {self.path}:meta:{name} — "
                        f"value does not match the patch's old value"
                    )
            if name == "schema.json":
                if delta.new is None:
                    raise PatchApplyError(
                        f"Cannot delete schema of {self.path}; delete the dataset instead"
                    )
                new_schema = Schema.from_column_dicts(delta.new_value)
                if (
                    schema is not None
                    and not schema.is_pk_compatible(new_schema)
                    and self.feature_count
                ):
                    raise NotYetImplemented(
                        "Schema changes that alter the primary key are not yet "
                        "supported on non-empty datasets"
                    )
                path, data = self.encode_meta_item(name, delta.new_value)
                tree_builder.insert(path, tree_builder.odb.write_blob(data))
                tree_builder.insert(
                    f"{self.inner_path}/{self.LEGEND_PATH}{new_schema.legend_hash}",
                    tree_builder.odb.write_blob(new_schema.legend.dumps()),
                )
                from kart_tpu.models.paths import encoder_for_schema
                from kart_tpu.core.serialise import json_pack as _jp

                if schema is None:
                    enc = encoder_for_schema(new_schema)
                    if enc is not PathEncoder.LEGACY_ENCODER:
                        tree_builder.insert(
                            f"{self.inner_path}/{self.PATH_STRUCTURE_PATH}",
                            tree_builder.odb.write_blob(_jp(enc.to_dict())),
                        )
                    self._meta_cache["__encoder__"] = enc
                schema = new_schema
                continue
            path, data = self.encode_meta_item(name, delta.new_value)
            if data is None:
                tree_builder.remove(path)
            else:
                tree_builder.insert(path, tree_builder.odb.write_blob(data))
        return schema

    def apply_feature_diff(
        self, feature_diff, tree_builder, *, schema=None, allow_missing_old=False
    ):
        from kart_tpu.core.structure import PatchApplyError

        if not feature_diff:
            return
        schema = schema or self.schema
        odb = tree_builder.odb
        has_tree = self.feature_tree is not None
        for delta in feature_diff.values():
            old_path = (
                self.encode_pks_to_path(
                    schema.sanitise_pks(
                        delta.old_key if isinstance(delta.old_key, (list, tuple)) else [delta.old_key]
                    )
                )
                if delta.old is not None
                else None
            )
            if not allow_missing_old and delta.old is not None:
                try:
                    current = self.get_feature(
                        schema.sanitise_pks(
                            delta.old_key
                            if isinstance(delta.old_key, (list, tuple))
                            else [delta.old_key]
                        )
                    ) if has_tree else None
                except (KeyError, ObjectMissing):
                    current = None
                if current != delta.old_value:
                    raise PatchApplyError(
                        f"Conflict at {self.path}:feature:{delta.old_key} — "
                        f"feature does not match the patch's old value"
                    )
            if delta.new is None:
                tree_builder.remove(old_path)
                continue
            new_feature = delta.new_value
            pk_values, blob = schema.encode_feature_blob(new_feature)
            new_path = (
                f"{self.inner_path}/{self.FEATURE_PATH}"
                + self.path_encoder.encode_pks_to_path(pk_values)
            )
            if delta.old is None and not allow_missing_old and has_tree:
                probe = self.path_encoder.encode_pks_to_path(pk_values)
                if self.get_data_at(self.FEATURE_PATH + probe, missing_ok=True) is not None:
                    raise PatchApplyError(
                        f"Conflict at {self.path}:feature:{delta.new_key} — "
                        f"inserted feature already exists"
                    )
            if old_path is not None and old_path != new_path:
                tree_builder.remove(old_path)
            tree_builder.insert(new_path, odb.write_blob(blob))

    def all_features_diff(self, as_delete=False):
        """Whole-dataset insert (or delete) diff — lazy values
        (reference: rich_base_dataset.py:503-519)."""
        from kart_tpu.diff.structs import Delta, DeltaDiff, DatasetDiff, KeyValue

        feature_diff = DeltaDiff()
        feature_tree = self.feature_tree
        if feature_tree is not None:
            for path, entry in feature_tree.walk_blobs():
                pks = self.decode_path_to_pks(path)
                key = pks[0] if len(pks) == 1 else pks
                kv = KeyValue((key, self.get_feature_promise(pks)))
                feature_diff.add_delta(
                    Delta.delete(kv) if as_delete else Delta.insert(kv)
                )
        meta_diff = DeltaDiff()
        for name, value in self.meta_items().items():
            kv = KeyValue((name, value))
            meta_diff.add_delta(Delta.delete(kv) if as_delete else Delta.insert(kv))
        result = DatasetDiff()
        result["meta"] = meta_diff
        result["feature"] = feature_diff
        return result

    def __repr__(self):
        return f"{type(self).__name__}({self.path!r})"


class Dataset2(Dataset3):
    """Legacy V2 storage: different dirname, hash-distributed 256^2 paths
    (reference: kart/dataset2.py)."""

    VERSION = 2
    DATASET_DIRNAME = ".sno-dataset"


def dataset_class_for_version(version):
    if version == 3:
        return Dataset3
    if version == 2:
        return Dataset2
    raise NotYetImplemented(
        f"Repo structure version {version} is not supported (supported: 2, 3)"
    )
