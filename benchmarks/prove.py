#!/usr/bin/env python3
"""benchmarks/prove.py — the builder's tool: every run a cell's proof needs,
in ONE call on the chip.

    python benchmarks/prove.py --cells points10m.diff_count [--sets 2 --runs 6]

For each cell, in order: a first run (it builds the layer and compiles; its
set-up is recorded apart), one ``--trace 1`` run, then ``--sets`` sets of
``--runs`` ``--trace 0`` runs, each run of a set with another seed and the
same seeds in every set. Each run is a child process running the command of
BENCHMARK.json; this parent never imports jax, so the chip is the child's.
Every run's last stdout line goes to ``<out>/<cell>/<tag>.json`` with its
stderr beside it, and the summary (medians, quartiles, the spread each bound
is set from) to ``<out>/<cell>/summary.json`` and the end of stdout. A cell
whose first run fails is given up at once.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
START = time.perf_counter()
SEED0 = 2_147_483_700  # past 2**31, as the driver's seeds are


def one_run(manifest, cell, seed, trace, seconds, out_dir, tag, extra=()):
    cmd = manifest["command"] + [
        "--workload", cell, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace), *extra,
    ]
    t0 = time.perf_counter()
    with open(os.path.join(out_dir, tag + ".err"), "w") as err:
        proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=err)
    lines = proc.stdout.decode().strip().splitlines()
    last = lines[-1] if lines else ""
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        f.write(last + "\n")
    try:
        result = json.loads(last)
    except ValueError:
        result = None
    print(f"{cell} {tag} seed={seed} rc={proc.returncode} "
          f"{time.perf_counter() - t0:.1f}s {last[:400]}", flush=True)
    return proc.returncode, result


def summarise(sets):
    """{metric: per set median/q1/q3/spread, and the wider spread}."""
    import statistics

    out = {}
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        per_set = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            if len(values) < 2:
                continue
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            per_set.append({
                "n": len(values), "median": med, "q1": q1, "q3": q3,
                "min": min(values), "max": max(values),
                "spread": (q3 - q1) / med, "values": values,
            })
        if per_set:
            out[name] = {
                "sets": per_set,
                "widest_spread": max(s["spread"] for s in per_set),
            }
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cells", nargs="+", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=int, default=None,
                   help="default: run_seconds of BENCHMARK.json")
    p.add_argument("--deadline", type=float, default=None,
                   help="seconds from the start after which no further run "
                   "of a set is started (a chip call has a time limit)")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "prove"))
    p.add_argument("--rehearse", nargs=argparse.REMAINDER, default=[],
                   help="rehearsal on the CPU: the rest of the line goes to "
                   "run.py (--rows N --cache-dir D); exit code 1 is then "
                   "what every run gives")
    args = p.parse_args(argv)
    good = (0,) if not args.rehearse else (0, 1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    ok = True
    for cell in args.cells:
        out_dir = os.path.join(args.out, cell)
        os.makedirs(out_dir, exist_ok=True)
        rc, first = one_run(
            manifest, cell, SEED0, 0, seconds, out_dir, "first", args.rehearse
        )
        if rc not in good:
            print(f"{cell}: first run failed, cell given up", flush=True)
            ok = False
            continue
        rc, traced = one_run(
            manifest, cell, SEED0 + 1, 1, seconds, out_dir, "traced", args.rehearse
        )
        ok = ok and rc in good
        sets = []
        for s in range(args.sets):
            runs = []
            for r in range(args.runs):
                if args.deadline and time.perf_counter() - START > args.deadline:
                    print(f"{cell}: deadline passed, set {s + 1} cut at "
                          f"{r} runs", flush=True)
                    break
                rc, result = one_run(
                    manifest, cell, SEED0 + 2 + r, 0, seconds, out_dir,
                    f"set{s + 1}_run{r + 1}", args.rehearse,
                )
                ok = ok and rc in good
                if result is not None:
                    runs.append(result)
            sets.append(runs)
        summary = {
            "cell": cell, "seconds": seconds,
            "first_run_setup_s": first["metrics"].get("setup_s", {}).get("value"),
            "traced": traced, "end_to_end": summarise(sets),
        }
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        brief = {
            k: {"medians": [s["median"] for s in v["sets"]],
                "spreads": [s["spread"] for s in v["sets"]]}
            for k, v in summary["end_to_end"].items()
        }
        print(f"SUMMARY {cell} {json.dumps(brief)}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
