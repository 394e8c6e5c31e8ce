"""Diff writers: text / json / geojson / json-lines / quiet / feature-count /
html (reference: kart/base_diff_writer.py + per-format writer modules).

A writer is constructed from a commit spec (``A``, ``A..B``, ``A...B`` or
nothing = HEAD vs working copy), streams the diff through the chosen format,
and reports ``has_changes`` for the exit code. Values stay lazy until each
delta is written.
"""

import itertools
import json
import logging
import re
import sys
from datetime import datetime, timedelta, timezone

import click

from kart_tpu import telemetry as tm
from kart_tpu.core.repo import InvalidOperation, NotFound
from kart_tpu.crs import Transform
from kart_tpu.diff.engine import get_dataset_diff, get_repo_diff
from kart_tpu.diff.key_filters import RepoKeyFilter
from kart_tpu.diff.output import (
    dump_json_output,
    feature_as_geojson,
    feature_as_json,
    feature_as_text,
    feature_field_as_text,
    format_wkt_for_output,
    resolve_output_path,
)
from kart_tpu.diff.structs import RepoDiff
from kart_tpu.models.dataset import FeatureOidPromise
from kart_tpu.models.schema import Schema
from kart_tpu.utils import map_in_order, pool_workers

_NULL = object()


L = logging.getLogger("kart_tpu.diff")


def _promised_value_oids(delta):
    """Force both sides of a delta; -> oids of any promised blobs. Forcing
    is free here: every writer that iterates deltas prints the values."""
    from kart_tpu.core.odb import ObjectPromised

    oids = []
    for kv in (delta.old, delta.new):
        if kv is None:
            continue
        try:
            kv.get_lazy_value()
        except ObjectPromised as e:
            oids.append(e.oid)
    return oids


class BaseDiffWriter:
    @classmethod
    def get_diff_writer_class(cls, output_format):
        writers = {
            "text": TextDiffWriter,
            "json": JsonDiffWriter,
            "json-lines": JsonLinesDiffWriter,
            "geojson": GeojsonDiffWriter,
            "quiet": QuietDiffWriter,
            "feature-count": FeatureCountDiffWriter,
            "html": HtmlDiffWriter,
        }
        try:
            return writers[output_format]
        except KeyError:
            raise click.UsageError(
                f"Unknown output format: {output_format!r} (expected one of "
                f"{', '.join(writers)})"
            )

    def __init__(
        self,
        repo,
        commit_spec="HEAD",
        user_key_filters=(),
        output_path="-",
        *,
        json_style="pretty",
        target_crs=None,
        diff_estimate_accuracy=None,
        commit=None,
        patch_type="full",
        include_patch_header=False,
    ):
        self.repo = repo
        self.commit_spec = commit_spec
        self.output_path = output_path
        self.json_style = json_style
        self.target_crs = target_crs
        self.patch_type = patch_type
        self.include_patch_header = include_patch_header
        self.commit = commit  # set for `kart show`
        self.repo_key_filter = RepoKeyFilter.build_from_user_patterns(user_key_filters)
        self.base_rs, self.target_rs, self.working_copy = self.parse_diff_commit_spec(
            repo, commit_spec
        )
        self.has_changes = False
        self.spatial_filter_pk_conflicts = {}
        # the repo's spatial filter (set by a filtered clone / config):
        # diffs only show matching deltas (reference:
        # base_diff_writer.py:279-341). Engine prefilters envelope-carrying
        # sidecar blocks; iter_deltas applies the exact per-value residue.
        from kart_tpu.spatial_filter import ResolvedSpatialFilterSpec

        self.spatial_filter_spec = ResolvedSpatialFilterSpec.from_repo_config(repo)
        if self.spatial_filter_spec.match_all:
            self.spatial_filter_spec = None
        self._ds_sf_cache = {}

    # -- commit spec --------------------------------------------------------

    @classmethod
    def parse_diff_commit_spec(cls, repo, commit_spec):
        """'A', 'A..B', 'A...B' or '' -> (base_rs, target_rs, working_copy)
        (reference: base_diff_writer.py:139-179)."""
        commit_spec = commit_spec or "HEAD"
        parts = re.split(r"(\.{2,3})", commit_spec)
        if len(parts) == 3:
            base_rs = repo.structure(parts[0] or "HEAD")
            target_rs = repo.structure(parts[2] or "HEAD")
            if parts[1] == "..":
                # A..B means merge-base(A,B) <> B (git log semantics)
                ancestor = repo.merge_base(base_rs.commit_oid, target_rs.commit_oid)
                if ancestor is None:
                    raise InvalidOperation(
                        "No common ancestor found — try the ... operator"
                    )
                base_rs = repo.structure(ancestor)
            return base_rs, target_rs, None
        base_rs = repo.structure(parts[0] if parts[0] else "HEAD")
        target_rs = repo.structure("HEAD")
        working_copy = repo.working_copy
        if working_copy is None:
            raise NotFound(
                "No working copy — diff between commits requires two revisions "
                "(eg HEAD^...HEAD)"
            )
        working_copy.assert_db_tree_match(target_rs.tree_oid)
        return base_rs, target_rs, working_copy

    # -- diff access --------------------------------------------------------

    @property
    def all_ds_paths(self):
        base_paths = set(self.base_rs.datasets.paths()) if self.base_rs else set()
        target_paths = set(self.target_rs.datasets.paths()) if self.target_rs else set()
        paths = base_paths | target_paths
        if not self.repo_key_filter.match_all:
            paths &= set(self.repo_key_filter.ds_paths())
        return sorted(paths)

    def get_repo_diff(self):
        return get_repo_diff(
            self.base_rs,
            self.target_rs,
            repo_key_filter=self.repo_key_filter,
            include_wc_diff=self.working_copy is not None,
            working_copy=self.working_copy,
            spatial_filter_spec=self.spatial_filter_spec,
        )

    def get_ds_diff(self, ds_path):
        return get_dataset_diff(
            self.base_rs,
            self.target_rs,
            ds_path,
            ds_filter=self.repo_key_filter[ds_path],
            include_wc_diff=self.working_copy is not None,
            working_copy=self.working_copy,
            spatial_filter_spec=self.spatial_filter_spec,
        )

    def _ds_spatial_filter(self, ds_path):
        """Per-dataset SpatialFilter (filter polygon transformed into the
        dataset's CRS), or None when no filter is active / the dataset is
        non-spatial."""
        if self.spatial_filter_spec is None or ds_path is None:
            return None
        if ds_path not in self._ds_sf_cache:
            ds = None
            for rs in (self.target_rs, self.base_rs):
                if rs is not None:
                    ds = rs.datasets.get(ds_path)
                    if ds is not None:
                        break
            sf = (
                self.spatial_filter_spec.resolve_for_dataset(ds)
                if ds is not None
                else None
            )
            from kart_tpu.spatial_filter import SpatialFilter

            self._ds_sf_cache[ds_path] = None if sf is SpatialFilter.MATCH_ALL else sf
        return self._ds_sf_cache[ds_path]

    @staticmethod
    def _delta_matches_filter(delta, sf):
        """True when either side of the delta matches the spatial filter
        (reference semantics: base_diff_writer's matches_delta_values).
        A side whose value is a promised blob can't be tested — fail open
        (a filtered clone only promises out-of-filter features, and the
        engine's envelope prefilter has already screened those out)."""
        from kart_tpu.core.odb import ObjectMissing, ObjectPromised
        from kart_tpu.spatial_filter import MatchResult

        for kv in (delta.old, delta.new):
            if kv is None:
                continue
            try:
                feature = kv.get_lazy_value()
            except (ObjectPromised, ObjectMissing):
                return True
            if sf.match_result(feature) is MatchResult.MATCHED:
                return True
        return False

    #: rows per batch blob prefetch in iter_deltas: large enough to amortise
    #: the native batch inflate setup, small enough that prefetched blob
    #: bytes for one chunk stay a few MB
    PREFETCH_CHUNK = 8192

    def iter_deltas(self, ds_diff, ds_path=None):
        """Stream (key, delta). Deltas whose values are oid-promises get
        their blob data prefetched chunk-wise through the native batch pack
        reader (one reused z_stream over offset-sorted records) instead of
        a per-feature pack bisect + inflate. With an active repo spatial
        filter (pass ds_path), only matching deltas stream. On a partial
        clone, deltas whose values are promised blobs are buffered while
        the rest stream, then backfilled from the promisor remote in one
        batch fetch and re-yielded (reference: DeltaFetcher,
        kart/base_diff_writer.py:467-534)."""
        feature_diff = ds_diff.get("feature")
        if not feature_diff:
            return
        sf = self._ds_spatial_filter(ds_path)
        if not self.repo.has_promisor_remote():
            for key, delta in self._iter_prefetched(feature_diff.sorted_items()):
                if sf is None or self._delta_matches_filter(delta, sf):
                    self.has_changes = True
                    yield key, delta
            return
        buffered = []
        missing = []
        for key, delta in self._iter_prefetched(feature_diff.sorted_items()):
            oids = _promised_value_oids(delta)
            if oids:
                buffered.append((key, delta))
                missing.extend(oids)
                continue
            if sf is None or self._delta_matches_filter(delta, sf):
                self.has_changes = True
                yield key, delta
        if buffered:
            from kart_tpu.transport.remote import fetch_promised_blobs

            L.info(
                "Fetching %d promised objects to complete the diff ...",
                len(missing),
            )
            fetch_promised_blobs(self.repo, missing)
            for key, delta in buffered:
                if sf is None or self._delta_matches_filter(delta, sf):
                    self.has_changes = True
                    yield key, delta

    def _iter_prefetched(self, items):
        """Chunk the (key, delta) stream and batch-read the blob data of
        every unforced oid-promise in the chunk. Promises whose blobs the
        batch can't serve (loose objects, deltified records, promised) keep
        their per-object fallback — semantics are identical either way."""
        from kart_tpu.models.dataset import FeatureOidPromise
        from kart_tpu.utils import chunked

        odb_of_ds = {}
        for chunk in chunked(items, self.PREFETCH_CHUNK):
            by_odb = {}
            for _key, delta in chunk:
                for kv in (delta.old, delta.new):
                    if kv is None or not kv.value_is_lazy:
                        continue
                    promise = kv[1]
                    if (
                        isinstance(promise, FeatureOidPromise)
                        and promise.data is None
                    ):
                        odb = odb_of_ds.get(id(promise.ds))
                        if odb is None:
                            odb = promise.ds._feature_odb()
                            odb_of_ds[id(promise.ds)] = odb
                        by_odb.setdefault(id(odb), (odb, []))[1].append(promise)
            for odb, promises in by_odb.values():
                got = odb.read_blobs_batch([p.oid_hex for p in promises])
                for p in promises:
                    p.data = got.get(p.oid_hex)
            yield from chunk

    @staticmethod
    def _feature_json_fast(kv, tx):
        """JSON-ready dict for one delta side. When the value is an unforced
        oid-promise with prefetched blob data and no --crs reprojection, the
        fused blob->JSON decode runs (one dict build, no Geometry objects);
        otherwise the generic force-then-convert path. Output is identical."""
        if tx is None:
            v = kv[1]
            if (
                isinstance(v, FeatureOidPromise)
                and v.data is not None
                and kv.value_is_lazy
            ):
                data, v.data = v.data, None
                return v.ds.feature_json_from_data(v.pk_values, data)
        return feature_as_json(kv.get_lazy_value(), kv.key, tx)

    def get_geometry_transforms(self, ds_path, ds_diff):
        """-> (old_transform, new_transform) to the --crs target, or (None,
        None)."""
        if self.target_crs is None:
            return None, None

        from kart_tpu.diff.output import geometry_transform_for_dataset

        def transform_for(rs):
            ds = rs.datasets.get(ds_path) if rs is not None else None
            return geometry_transform_for_dataset(ds, self.target_crs)

        return transform_for(self.base_rs), transform_for(self.target_rs)

    # -- common output pieces -----------------------------------------------

    def commit_header_json(self):
        commit = self.commit
        oid = getattr(commit, "oid", None)
        if commit is None:
            return None
        author = commit.author
        tz = timezone(timedelta(minutes=author.offset))
        when = datetime.fromtimestamp(author.time, timezone.utc).astimezone(tz)
        return {
            "commit": oid,
            "abbrevCommit": oid[:7] if oid else None,
            "message": commit.message,
            "authorName": author.name,
            "authorEmail": author.email,
            "authorTime": when.strftime("%Y-%m-%dT%H:%M:%SZ")
            if author.offset == 0
            else when.isoformat(),
            "authorTimeOffset": f"{'+' if author.offset >= 0 else '-'}{abs(author.offset) // 60:02d}:{abs(author.offset) % 60:02d}",
        }

    def write_warnings_footer(self):
        # WC diffs record pk collisions with out-of-filter features on the
        # working-copy instance as they stream; fold them in here so every
        # writer subclass (text/json/geojson/...) surfaces them
        if self.working_copy is not None:
            for ds_path, pks in self.working_copy.spatial_filter_pk_conflicts.items():
                if pks:
                    existing = self.spatial_filter_pk_conflicts.setdefault(ds_path, [])
                    existing.extend(pk for pk in pks if pk not in existing)
        conflicts = self.spatial_filter_pk_conflicts
        if conflicts and any(conflicts.values()):
            click.secho(
                "Warning: Some primary keys of newly-inserted features in the "
                "working copy conflict with features outside the spatial filter "
                "- if committed, they would overwrite those features.",
                bold=True,
                err=True,
            )
            for ds_path, pks in conflicts.items():
                if pks:
                    shown = ", ".join(str(pk) for pk in pks[:50])
                    more = f", (... {len(pks) - 50} more)" if len(pks) > 50 else ""
                    click.echo(
                        f"  In dataset {ds_path} the conflicting primary key values are: {shown}{more}",
                        err=True,
                    )

    def _mark_ds_changes(self, ds_diff):
        """has_changes bookkeeping per dataset. With an active spatial
        filter, feature changes only count when a delta actually streams
        (iter_deltas marks that) — the exit code must agree with the
        output, not with the unfiltered diff."""
        if self.spatial_filter_spec is None:
            if ds_diff:
                self.has_changes = True
        elif ds_diff.get("meta"):
            self.has_changes = True

    def write_diff(self):
        self.write_header()
        for ds_path in self.all_ds_paths:
            ds_diff = self.get_ds_diff(ds_path)
            if ds_diff:
                self._mark_ds_changes(ds_diff)
                self.write_ds_diff(ds_path, ds_diff)
        self.write_warnings_footer()
        return self.has_changes

    def write_header(self):
        pass

    def write_ds_diff(self, ds_path, ds_diff):
        raise NotImplementedError


class TextDiffWriter(BaseDiffWriter):
    """Human-readable (lossy for geometry) (reference: text_diff_writer.py)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.fp = resolve_output_path(self.output_path)
        self.pecho = {"file": self.fp, "color": getattr(self.fp, "isatty", lambda: False)()}

    def write_header(self):
        commit = self.commit
        if commit is None:
            return
        author = commit.author
        tz = timezone(timedelta(minutes=author.offset))
        when = datetime.fromtimestamp(author.time, timezone.utc).astimezone(tz)
        click.secho(f"commit {getattr(commit, 'oid', '')}", fg="yellow", **self.pecho)
        click.secho(f"Author: {author.name} <{author.email}>", **self.pecho)
        click.secho(f"Date:   {when.strftime('%c %z')}", **self.pecho)
        click.secho(**self.pecho)
        for line in commit.message.splitlines():
            click.secho(f"    {line}", **self.pecho)
        click.secho(**self.pecho)

    def write_ds_diff(self, ds_path, ds_diff):
        if "meta" in ds_diff:
            for key, delta in ds_diff["meta"].sorted_items():
                self.write_meta_delta(ds_path, key, delta)
        for key, delta in self.iter_deltas(ds_diff, ds_path):
            self.write_feature_delta(ds_path, key, delta)

    def write_meta_delta(self, ds_path, key, delta):
        if delta.old:
            click.secho(f"--- {ds_path}:meta:{delta.old_key}", bold=True, **self.pecho)
        if delta.new:
            click.secho(f"+++ {ds_path}:meta:{delta.new_key}", bold=True, **self.pecho)
        if key == "schema.json" and delta.old and delta.new:
            click.echo(
                self._schema_diff_as_text(
                    Schema.from_column_dicts(delta.old_value),
                    Schema.from_column_dicts(delta.new_value),
                ),
                **self.pecho,
            )
            return
        if delta.old:
            click.secho(
                self._prefix_meta_item(delta.old_value, delta.old_key, "- "),
                fg="red",
                **self.pecho,
            )
        if delta.new:
            click.secho(
                self._prefix_meta_item(delta.new_value, delta.new_key, "+ "),
                fg="green",
                **self.pecho,
            )

    @classmethod
    def _prefix_meta_item(cls, value, name, prefix):
        if name.endswith(".wkt"):
            text = format_wkt_for_output(value)
        elif name.endswith(".json"):
            text = json.dumps(value, indent=2)
        else:
            text = str(value)
        return re.sub("^", prefix, text, flags=re.MULTILINE)

    @classmethod
    def _schema_diff_as_text(cls, old_schema, new_schema):
        old_by_id = {c.id: c for c in old_schema}
        new_by_id = {c.id: c for c in new_schema}
        lines = ["["]
        for col in old_schema:
            if col.id not in new_by_id:
                lines.append(
                    click.style(
                        re.sub("^", "-   ", json.dumps(col.to_dict(), indent=2), flags=re.MULTILINE) + ",",
                        fg="red",
                    )
                )
        for col in new_schema:
            old_col = old_by_id.get(col.id)
            text = json.dumps(col.to_dict(), indent=2)
            if old_col is None:
                lines.append(
                    click.style(re.sub("^", "+   ", text, flags=re.MULTILINE) + ",", fg="green")
                )
            elif old_col == col:
                lines.append(re.sub("^", "    ", text, flags=re.MULTILINE) + ",")
            else:
                old_text = json.dumps(old_col.to_dict(), indent=2)
                lines.append(
                    click.style(re.sub("^", "-   ", old_text, flags=re.MULTILINE) + ",", fg="red")
                )
                lines.append(
                    click.style(re.sub("^", "+   ", text, flags=re.MULTILINE) + ",", fg="green")
                )
        lines.append("]")
        return "\n".join(lines)

    def write_feature_delta(self, ds_path, key, delta):
        if delta.type == "insert":
            click.secho(f"+++ {ds_path}:feature:{delta.new_key}", bold=True, **self.pecho)
            click.secho(feature_as_text(delta.new_value, prefix="+ "), fg="green", **self.pecho)
            return
        if delta.type == "delete":
            click.secho(f"--- {ds_path}:feature:{delta.old_key}", bold=True, **self.pecho)
            click.secho(feature_as_text(delta.old_value, prefix="- "), fg="red", **self.pecho)
            return
        click.secho(
            f"--- {ds_path}:feature:{delta.old_key}\n+++ {ds_path}:feature:{delta.new_key}",
            bold=True,
            **self.pecho,
        )
        old_f, new_f = delta.old_value, delta.new_value
        for k in itertools.chain(
            old_f.keys(), (k for k in new_f.keys() if k not in old_f)
        ):
            if k.startswith("__") or old_f.get(k, _NULL) == new_f.get(k, _NULL):
                continue
            if k in old_f:
                click.secho(feature_field_as_text(old_f, k, "- "), fg="red", **self.pecho)
            if k in new_f:
                click.secho(feature_field_as_text(new_f, k, "+ "), fg="green", **self.pecho)


class JsonDiffWriter(BaseDiffWriter):
    """Complete diff as one JSON document: ``kart.diff/v1+hexwkb``
    (reference: json_diff_writers.py:18)."""

    def write_diff(self):
        repo_diff = self.get_repo_diff()
        if self.spatial_filter_spec is None:
            self.has_changes = bool(repo_diff)
        else:
            for _p, _d in repo_diff.items():
                self._mark_ds_changes(_d)
        output = {}
        header = self.commit_header_json()
        if header is not None:
            output["kart.show/v1"] = header
        output["kart.diff/v1+hexwkb"] = {
            ds_path: self.ds_diff_as_json(ds_path, ds_diff)
            for ds_path, ds_diff in repo_diff.items()
        }
        if self.include_patch_header:
            output["kart.patch/v1"] = self.patch_header()
        dump_json_output(output, self.output_path, json_style=self.json_style)
        self.write_warnings_footer()
        return self.has_changes

    def patch_header(self):
        header = self.commit_header_json() or {}
        base = self.base_rs.commit_oid if self.base_rs else None
        return {
            "authorEmail": header.get("authorEmail"),
            "authorName": header.get("authorName"),
            "authorTime": header.get("authorTime"),
            "authorTimeOffset": header.get("authorTimeOffset"),
            "base": base,
            "message": header.get("message"),
        }

    def ds_diff_as_json(self, ds_path, ds_diff):
        result = {}
        if "meta" in ds_diff:
            result["meta"] = {
                key: self.meta_delta_as_json(delta)
                for key, delta in ds_diff["meta"].sorted_items()
            }
        if "feature" in ds_diff:
            old_tx, new_tx = self.get_geometry_transforms(ds_path, ds_diff)
            features = []
            for key, delta in self.iter_deltas(ds_diff, ds_path):
                item = {}
                if delta.old and (self.patch_type == "full" or not delta.new):
                    item["-"] = self._feature_json_fast(delta.old, old_tx)
                if delta.new:
                    out_key = "+"
                    if delta.old and self.patch_type == "minimal":
                        out_key = "*"
                    item[out_key] = self._feature_json_fast(delta.new, new_tx)
                features.append(item)
            result["feature"] = features
        return result

    def meta_delta_as_json(self, delta):
        out = {}
        if delta.old is not None:
            out["-"] = delta.old_value
        if delta.new is not None:
            out["+"] = delta.new_value
        if self.patch_type == "minimal" and "-" in out and "+" in out:
            out.pop("-")
            out["*"] = out.pop("+")
        return out


def _ascii_sink(fp):
    """``write(buffer)`` for pure-ASCII bytes bound for the text file
    ``fp``: straight to the binary file under it where it has one whose
    encoding and newlines leave ASCII as it is, decoded into ``fp.write``
    otherwise. Text written to ``fp`` before is flushed down here, so order
    holds as long as ``fp`` itself is not written to between these writes."""
    import codecs
    import os

    raw = getattr(fp, "buffer", None)
    try:
        plain = (
            raw is not None
            and os.linesep == "\n"
            and codecs.lookup(fp.encoding).name in ("utf-8", "ascii", "iso8859-1")
        )
    except (LookupError, TypeError):
        plain = False
    if not plain:
        return lambda piece: fp.write(bytes(piece).decode("ascii"))
    fp.flush()
    return raw.write


class JsonLinesDiffWriter(BaseDiffWriter):
    """Streaming: one JSON object per line (reference: json_diff_writers.py:279)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.fp = resolve_output_path(self.output_path)
        # one reused encoder: json.dump() builds a fresh encoder + iterencode
        # closure per call and feeds the file ~50 tiny writes per line
        # (measured ~30% of a 200k-line materialisation); encode() emits one
        # string per line instead
        self._encode = json.JSONEncoder(
            separators=(",", ":"), ensure_ascii=True
        ).encode

    def _writeln(self, obj):
        self.fp.write(self._encode(obj))
        self.fp.write("\n")

    def write_header(self):
        self._writeln(
            {"type": "version", "version": "kart.diff/v2", "outputFormat": "JSONL+hexwkb"}
        )
        header = self.commit_header_json()
        if header:
            self._writeln({"type": "commit", "value": header})

    def write_diff(self):
        """Like the base write_diff, but commit<>commit full-output diffs of
        int-pk datasets stream through the fused columnar row plan
        (engine.get_feature_diff_rows) instead of building a Delta per
        feature — identical bytes, ~3x the materialisation rate at
        1M-changed scale (tested byte-equal)."""
        self.write_header()
        for ds_path in self.all_ds_paths:
            if self._write_ds_fast(ds_path):
                continue
            ds_diff = self.get_ds_diff(ds_path)
            if ds_diff:
                self._mark_ds_changes(ds_diff)
                self.write_ds_diff(ds_path, ds_diff)
        self.write_warnings_footer()
        return self.has_changes

    def _write_ds_fast(self, ds_path):
        """Fused columnar materialisation for one dataset; True when this
        path handled it. Only the plain commit<>commit full-output case is
        eligible — working-copy diffs, spatial filters, key filters, --crs
        reprojection and promisor backfill keep the delta path."""
        import os

        if (
            os.environ.get("KART_FUSED_JSONL", "1") == "0"
            or self.working_copy is not None
            or self.spatial_filter_spec is not None
            or not self.repo_key_filter.match_all
            or self.target_crs is not None
            or self.repo.has_promisor_remote()
        ):
            return False
        from kart_tpu.diff.engine import get_feature_diff_rows, get_meta_diff

        rows = get_feature_diff_rows(self.base_rs, self.target_rs, ds_path)
        if rows is None:
            return False
        base_ds = self.base_rs.datasets.get(ds_path)
        target_ds = self.target_rs.datasets.get(ds_path)
        meta_diff = get_meta_diff(base_ds, target_ds)
        self._write_meta_infos(ds_path, meta_diff)
        if meta_diff:
            self.has_changes = True
        m = rows["count"]
        if not m:
            return True
        self.has_changes = True
        with tm.span("serialise.features", dataset=ds_path, rows=int(m)):
            self._materialise_rows(
                rows, base_ds, target_ds, self._feature_head(ds_path)
            )
        tm.incr("serialise.features_materialised", int(m))
        return True

    def _feature_head(self, ds_path):
        """The constant line prefix of every feature line of one dataset."""
        return '{"type":"feature","dataset":' + self._encode(ds_path) + ',"change":{'

    def _write_meta_infos(self, ds_path, meta_diff):
        """metaInfo lines for one dataset's meta diff (shared by the delta
        path and the fused fast path — the two must emit identical bytes)."""
        for key, delta in meta_diff.sorted_items():
            obj = {"type": "metaInfo", "dataset": ds_path, "key": key, "change": {}}
            if delta.old is not None:
                obj["change"]["-"] = delta.old_value
            if delta.new is not None:
                obj["change"]["+"] = delta.new_value
            self._writeln(obj)

    #: bytes one native chunk call may write; a chunk that outgrows it comes
    #: back in several calls (8,192 polygon rows are ~4 MB)
    CHUNK_BUFFER_BYTES = 8 << 20

    def _materialise_rows(self, rows, base_ds, target_ds, head):
        """Stream a columnar row plan to ``self.fp``: chunks of
        ``PREFETCH_CHUNK`` rows, each made into finished lines by one native
        call that holds no GIL (``native.jsonl_chunk``: pack record ->
        inflate -> msgpack walk -> JSON line), on as many pool threads as
        the host has cores, at most four; this thread writes the buffers in
        row order. At most workers + 1 chunks are in flight.

        The native walk declines what it does not cover byte-exactly (a
        delta, loose or promised record, a legend it has no plan for, a
        value or geometry outside its fast paths, invalid UTF-8):
        :meth:`python_line` makes exactly those rows, spliced in place —
        and every row where ``libkart_io`` is unavailable."""
        import numpy as np

        from kart_tpu import native
        from kart_tpu.ops.blocks import oid_rows_u8

        m = rows["count"]
        pks = rows["pks"]
        sides = [
            (rows["old_block"], rows["old_rows"], base_ds._feature_odb(),
             base_ds.feature_json_str_from_data),
            (rows["new_block"], rows["new_rows"], target_ds._feature_odb(),
             target_ds.feature_json_str_from_data),
        ]
        plans = [base_ds.jsonl_native_plans(), target_ds.jsonl_native_plans()]
        head_bytes = head.encode("ascii")
        chunk_rows = self.PREFETCH_CHUNK

        def python_line(i):
            """Row i by the per-object read and the compiled serialiser."""
            pkv = (int(pks[i]),)
            parts = []
            for key, (block, side_rows, odb, to_json) in zip('-+', sides):
                if side_rows[i] >= 0:
                    sha = oid_rows_u8(block.oids[side_rows[i]]).tobytes()
                    parts.append(f'"{key}":' + to_json(pkv, odb.read_blob(sha.hex())))
            return (head + ",".join(parts) + "}}\n").encode("ascii")

        # per side, the pack that held most of the last chunk: probed first
        first_pack = [None, None]

        def locate(lo, hi):
            """-> (pack mmaps, [old, new] x (which int32, offsets int64))
            for rows lo..hi: -1 where the row lacks the side."""
            bufs, located = [], []
            for s, (block, side_rows, odb, _) in enumerate(sides):
                sel = side_rows[lo:hi]
                has = sel >= 0
                packs, which, offsets = odb.packs.locate_blobs(
                    oid_rows_u8(block.oids[sel[has]]), first=first_pack[s]
                )
                held = np.bincount(which[which >= 0], minlength=1)
                if held.any():
                    first_pack[s] = packs[int(held.argmax())]
                which[which >= 0] += len(bufs)
                bufs += [p._mm for p in packs]
                full_which = np.full(hi - lo, -1, dtype=np.int32)
                full_offsets = np.full(hi - lo, -1, dtype=np.int64)
                full_which[has] = which
                full_offsets[has] = offsets
                located.append((full_which, full_offsets))
            return bufs, located

        def materialise_chunk(lo):
            """Rows lo..lo+chunk_rows as byte pieces, in order."""
            hi = min(lo + chunk_rows, m)
            # a pool thread has no open span: name the parent
            with tm.span(
                "serialise.chunk", rows=hi - lo, parent="serialise.features"
            ) as sp:
                bufs, ((o_which, o_off), (n_which, n_off)) = locate(lo, hi)
                pieces, pos, declined = [], lo, {}
                while pos < hi:
                    at = pos - lo
                    out = np.empty(self.CHUNK_BUFFER_BYTES, dtype=np.uint8)
                    res = native.jsonl_chunk(
                        bufs, o_which[at:], o_off[at:], n_which[at:],
                        n_off[at:], pks[pos:hi], head_bytes, plans[0],
                        plans[1], out,
                    )
                    if res is None:  # no library
                        declined["no_native"] = hi - pos
                        pieces += [python_line(i) for i in range(pos, hi)]
                        break
                    total, done, row_end, status = res
                    start = 0
                    for r in np.nonzero(status[:done])[0].tolist():
                        why = native.JSONL_WHY[status[r]]
                        declined[why] = declined.get(why, 0) + 1
                        pieces += [out[start:row_end[r]], python_line(pos + r)]
                        start = row_end[r]
                    pieces.append(out[start:total])
                    pos += done
                pieces = [p for p in pieces if len(p)]
                n_python = sum(declined.values())
                n_bytes = sum(len(p) for p in pieces)
                sp.set(native_rows=hi - lo - n_python, bytes=n_bytes)
            tm.incr("serialise.rows_native", hi - lo - n_python)
            for why, n in declined.items():
                tm.incr("serialise.rows_python", n, why=why)
            return pieces, n_bytes

        write = _ascii_sink(self.fp)
        for pieces, n_bytes in map_in_order(
            materialise_chunk, range(0, m, chunk_rows), pool_workers(), "kart-jsonl"
        ):
            with tm.span("serialise.write", bytes=n_bytes):
                for piece in pieces:
                    write(piece)

    def write_ds_diff(self, ds_path, ds_diff):
        import os

        if "meta" in ds_diff:
            self._write_meta_infos(ds_path, ds_diff["meta"])
        old_tx, new_tx = self.get_geometry_transforms(ds_path, ds_diff)
        if os.environ.get("KART_FUSED_JSONL", "1") == "0":
            for key, delta in self.iter_deltas(ds_diff, ds_path):
                change = {}
                if delta.old:
                    change["-"] = self._feature_json_fast(delta.old, old_tx)
                if delta.new:
                    change["+"] = self._feature_json_fast(delta.new, new_tx)
                self._writeln({"type": "feature", "dataset": ds_path, "change": change})
            return
        # fused streaming path: each line is composed as one string — the
        # blob->JSON tail runs via feature_json_str_from_data (no
        # per-feature dicts), the line frame is a constant prefix, and one
        # fp.write emits it. Byte-identical to the dict path above (tested);
        # KART_FUSED_JSONL=0 restores the dict path.
        head = self._feature_head(ds_path)
        write = self.fp.write
        json_str = self._feature_json_str
        with tm.span("serialise.features", dataset=ds_path):
            for key, delta in self.iter_deltas(ds_diff, ds_path):
                old, new = delta.old, delta.new
                if old is not None:
                    body = '"-":' + json_str(old, old_tx)
                    if new is not None:
                        body += ',"+":' + json_str(new, new_tx)
                else:
                    body = '"+":' + json_str(new, new_tx)
                write(head + body + "}}\n")

    def _feature_json_str(self, kv, tx):
        """JSON object text for one delta side; the fused blob->text decode
        when the value is an unforced oid-promise with prefetched data and
        no --crs reprojection, the generic convert-then-encode otherwise.
        Output is byte-identical either way."""
        if tx is None:
            v = kv[1]
            if (
                isinstance(v, FeatureOidPromise)
                and v.data is not None
                and kv.value_is_lazy
            ):
                data, v.data = v.data, None
                return v.ds.feature_json_str_from_data(v.pk_values, data)
        return self._encode(feature_as_json(kv.get_lazy_value(), kv.key, tx))


class GeojsonDiffWriter(BaseDiffWriter):
    """FeatureCollection per dataset; deltas become features with
    ids like 'U-::123' (reference: json_diff_writers.py:182)."""

    def write_diff(self):
        repo_diff = self.get_repo_diff()
        if self.spatial_filter_spec is None:
            self.has_changes = bool(repo_diff)
        else:
            for _p, _d in repo_diff.items():
                self._mark_ds_changes(_d)
        ds_paths = [p for p, d in repo_diff.items() if "feature" in d]
        multi = len(ds_paths) > 1
        for ds_path in ds_paths:
            ds_diff = repo_diff[ds_path]
            collection = {
                "type": "FeatureCollection",
                "features": list(self.features_geojson(ds_path, ds_diff)),
            }
            out = self.output_path
            if multi:
                import os

                if out in (None, "-") or hasattr(out, "write"):
                    raise click.UsageError(
                        "Need an --output directory for multi-dataset GeoJSON diffs"
                    )
                os.makedirs(out, exist_ok=True)
                out = os.path.join(out, ds_path.replace("/", "__") + ".geojson")
            dump_json_output(collection, out, json_style=self.json_style)
        self.write_warnings_footer()
        return self.has_changes

    def features_geojson(self, ds_path, ds_diff):
        old_tx, new_tx = self.get_geometry_transforms(ds_path, ds_diff)
        for key, delta in self.iter_deltas(ds_diff, ds_path):
            if delta.type == "insert":
                yield feature_as_geojson(delta.new_value, delta.new_key, "I", new_tx)
            elif delta.type == "delete":
                yield feature_as_geojson(delta.old_value, delta.old_key, "D", old_tx)
            else:
                yield feature_as_geojson(delta.old_value, delta.old_key, "U-", old_tx)
                yield feature_as_geojson(delta.new_value, delta.new_key, "U+", new_tx)


class QuietDiffWriter(BaseDiffWriter):
    """No output; has_changes drives the exit code."""

    def write_ds_diff(self, ds_path, ds_diff):
        if self._ds_spatial_filter(ds_path) is not None:
            # the filtered exit code needs a real answer: stream until the
            # first matching delta flips has_changes (meta changes were
            # already counted by _mark_ds_changes)
            if not self.has_changes:
                next(self.iter_deltas(ds_diff, ds_path), None)


class FeatureCountDiffWriter(BaseDiffWriter):
    """Prints per-dataset changed-feature counts."""

    def write_diff(self):
        from kart_tpu.diff.engine import get_dataset_feature_count_fast

        fp = resolve_output_path(self.output_path)
        for ds_path in self.all_ds_paths:
            count = None
            if self.working_copy is None and self.repo_key_filter.match_all:
                # commit<>commit, unfiltered key-space: the count comes
                # straight from the classify kernel, skipping delta
                # construction entirely; an active spatial filter rides the
                # same route when envelope sidecar columns exist (the
                # prefilter is the filter there — blob values are typically
                # promised at that scale)
                count = get_dataset_feature_count_fast(
                    self.base_rs,
                    self.target_rs,
                    ds_path,
                    spatial_filter_spec=self.spatial_filter_spec,
                )
            if count is None:
                ds_diff = self.get_ds_diff(ds_path)
                if self._ds_spatial_filter(ds_path) is not None:
                    count = sum(1 for _ in self.iter_deltas(ds_diff, ds_path))
                else:
                    count = len(ds_diff.get("feature", ()))
            if count:
                self.has_changes = True
                fp.write(f"{ds_path}:\n\t{count} features changed\n")
        self.write_warnings_footer()
        return self.has_changes


_HTML_TEMPLATE = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>kart diff</title>
<style>
 body {{ font-family: sans-serif; margin: 0; display: flex; height: 100vh; }}
 #list {{ width: 40%; overflow: auto; padding: 8px; box-sizing: border-box; }}
 #map {{ flex: 1; background: #eef; }}
 .I {{ color: #070; }} .D {{ color: #a00; }} .U- {{ color: #850; }} .U\\+ {{ color: #085; }}
 pre {{ margin: 2px 0; }}
 svg path, svg circle {{ fill-opacity: .3; stroke-width: 1; }}
</style></head><body>
<div id="list"><h3>kart diff</h3></div><svg id="map"></svg>
<script>
const DATA = {data};
const list = document.getElementById('list');
const svg = document.getElementById('map');
let minx=1e9,miny=1e9,maxx=-1e9,maxy=-1e9;
const geoms = [];
for (const [ds, fc] of Object.entries(DATA)) {{
  const h = document.createElement('h4'); h.textContent = ds; list.appendChild(h);
  for (const f of fc.features) {{
    const change = f.id.split('::')[0];
    const pre = document.createElement('pre');
    pre.className = change;
    pre.textContent = f.id + ' ' + JSON.stringify(f.properties);
    list.appendChild(pre);
    if (f.geometry) {{ geoms.push([change, f.geometry]); walk(f.geometry.coordinates); }}
  }}
}}
function walk(c) {{
  if (typeof c[0] === 'number') {{
    minx=Math.min(minx,c[0]); maxx=Math.max(maxx,c[0]);
    miny=Math.min(miny,c[1]); maxy=Math.max(maxy,c[1]);
  }} else c.forEach(walk);
}}
const W=600,H=600, dx=maxx-minx||1, dy=maxy-miny||1;
svg.setAttribute('viewBox', `0 0 ${{W}} ${{H}}`);
const X=x=>(x-minx)/dx*(W-20)+10, Y=y=>H-((y-miny)/dy*(H-20)+10);
const colors={{'I':'#070','D':'#a00','U-':'#850','U+':'#085'}};
for (const [change, g] of geoms) draw(g, colors[change]||'#333');
function draw(g, color) {{
  const el = (name)=>document.createElementNS('http://www.w3.org/2000/svg', name);
  const add=(node)=>{{node.setAttribute('stroke',color);node.setAttribute('fill',color);svg.appendChild(node);}};
  const ring=(pts)=>pts.map((p,i)=>`${{i?'L':'M'}}${{X(p[0])}} ${{Y(p[1])}}`).join('');
  if (g.type==='Point') {{ const c=el('circle'); c.setAttribute('cx',X(g.coordinates[0])); c.setAttribute('cy',Y(g.coordinates[1])); c.setAttribute('r',4); add(c); }}
  else if (g.type==='LineString') {{ const p=el('path'); p.setAttribute('d',ring(g.coordinates)); p.setAttribute('fill','none'); add(p); }}
  else if (g.type==='Polygon') {{ const p=el('path'); p.setAttribute('d',g.coordinates.map(ring).join('')+'Z'); add(p); }}
  else if (g.type.startsWith('Multi')) g.coordinates.forEach(c=>draw({{type:g.type.slice(5),coordinates:c}}, color));
}}
</script></body></html>
"""


class HtmlDiffWriter(BaseDiffWriter):
    """Self-contained HTML diff viewer: embedded GeoJSON + inline SVG map (no
    network dependencies — the reference embeds a Leaflet page instead)."""

    def write_diff(self):
        repo_diff = self.get_repo_diff()
        if self.spatial_filter_spec is None:
            self.has_changes = bool(repo_diff)
        else:
            for _p, _d in repo_diff.items():
                self._mark_ds_changes(_d)
        all_data = {}
        for ds_path, ds_diff in repo_diff.items():
            if "feature" not in ds_diff:
                continue
            helper = GeojsonDiffWriter.features_geojson
            all_data[ds_path] = {
                "type": "FeatureCollection",
                "features": list(helper(self, ds_path, ds_diff)),
            }
        fp = resolve_output_path(
            self.output_path if self.output_path not in (None, "-") else "diff.html"
        )
        fp.write(_HTML_TEMPLATE.format(data=json.dumps(all_data)))
        if hasattr(fp, "name"):
            click.echo(f"Wrote {fp.name}", err=True)
        self.write_warnings_footer()
        return self.has_changes
