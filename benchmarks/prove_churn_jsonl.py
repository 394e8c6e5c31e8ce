#!/usr/bin/env python3
"""benchmarks/prove_churn_jsonl.py — the republish deployment's one-off proof
that the split by kind is right at the timed size: no cell, one process.

    python benchmarks/prove_churn_jsonl.py --seed <n> [--rows N --cache-dir D]

``-o feature-count`` prints a total, so the two churn cells' ``correct``
cannot show that a deleted feature is named as a delete and an inserted one
as an insert. This builds ``baseline2_points_10m_churn``'s layer as run.py
does and, for each of its two commits, runs ``kart diff HEAD...<branch> -o
json-lines --output <file>`` under auto routing and again on the host twin,
then holds every line against the builder's edit sets: the sign (insert,
update, delete), the pk, the rating values (``pk / 2`` before an update and
on an inserted or deleted row, ``pk`` after an update), an update's geometry
unchanged — and the twin's bytes equal. Last line of stdout: one JSON
object, ``ok`` true only on a TPU with ``device_jax`` answering, no fallback
and every check true. ``--rows`` is for a rehearsal on the CPU.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG = "baseline2_points_10m_churn"


def check_lines(path, commit):
    """-> {check name: bool} for a json-lines diff file against one
    commit's edit sets of the builder."""
    import numpy as np

    kinds = {"inserted_pks": [], "updated_pks": [], "deleted_pks": []}
    values_ok = geometry_ok = True
    with open(path) as f:
        for line in f:
            obj = json.loads(line)
            if obj.get("type") != "feature":
                continue
            old, new = obj["change"].get("-"), obj["change"].get("+")
            if old and new:
                kinds["updated_pks"].append(new["fid"])
                values_ok = values_ok and (
                    old["fid"] == new["fid"]
                    and old["rating"] == new["fid"] / 2.0
                    and new["rating"] == float(new["fid"])
                )
                geometry_ok = geometry_ok and old["geom"] == new["geom"]
            else:
                row = new or old
                kinds["inserted_pks" if new else "deleted_pks"].append(row["fid"])
                values_ok = values_ok and row["rating"] == row["fid"] / 2.0
                geometry_ok = geometry_ok and bool(row["geom"])
    checks = {
        # sorted: each named once, and no other
        f"names_the_{kind}": np.array_equal(
            np.sort(np.asarray(pks, dtype=np.int64)), commit[kind]
        )
        for kind, pks in kinds.items()
    }
    checks["values_are_the_builders"] = values_ok
    checks["geometry_unchanged"] = geometry_ok
    return checks


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rows", type=int, default=None, help="rehearsal only")
    p.add_argument("--cache-dir", default=os.path.join(HERE, ".cache"))
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import reduce
    import run

    os.environ["KART_PROBE_CACHE"] = "0"
    import jax

    config = run.load_json(HERE, "configs", CONFIG + ".json")
    if args.rows is not None:
        config["layer"]["params"]["rows"] = args.rows
    op_kind = run.load_module("ops", "cli")
    work = tempfile.mkdtemp(prefix="kart-churn-jsonl-")
    result = {"device": run.device_report(jax), "commits": {}}
    try:
        builder, base = run.base_layer(CONFIG, config, args.cache_dir)
        repo_path, info = builder.add_edit_commit(
            base, work, config["layer"]["params"], args.seed
        )
        for branch, commit in info["commits"].items():
            traffic = {
                "argv": ["-C", "{repo}", "diff", f"HEAD...{branch}", "-o",
                         "json-lines", "--output", "{out}"],
                "fallback_counter": "diff.device.fallbacks",
            }
            op = op_kind.Op(traffic, repo_path, os.path.join(work, branch))
            os.makedirs(os.path.dirname(op.out), exist_ok=True)
            op.spans(True)
            code, _ = op.run()
            backends = reduce.span_attrs(op.take_spans(), "diff.classify", "backend")
            device_out = op.out + ".device"
            os.replace(op.out, device_out)
            twin_code, _ = op.run(env=op.HOST_TWIN_ENV)
            twin = reduce.span_attrs(op.take_spans(), "diff.classify", "backend")
            with open(device_out, "rb") as a, open(op.out, "rb") as b:
                same_bytes = a.read() == b.read()
            checks = {
                "exit_0": code == 0 and twin_code == 0,
                "backend": backends == [config["expect_backend"]["1"]],
                "twin_is_host": twin == ["host_native"],
                "equals_twin": same_bytes,
                "no_fallbacks": op.fallbacks() == 0,
                **check_lines(device_out, commit),
            }
            result["commits"][branch] = {
                "rows": commit["rows"], "n_edits": commit["n_edits"],
                "bytes": os.path.getsize(device_out), "backend": backends,
                "checks": checks,
            }
    finally:
        shutil.rmtree(work, ignore_errors=True)
    result["ok"] = (
        args.rows is None
        and result["device"]["platform"] == "tpu"
        and all(all(c["checks"].values()) for c in result["commits"].values())
    )
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
