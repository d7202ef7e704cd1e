"""Benchmark aggregator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus a JSON dump in
artifacts/bench.json for EXPERIMENTS.md). The kernels + train suites
are additionally written to ``BENCH_kernels.json`` at the repo root so
the kernel-backend AND growth-engine perf trajectories are tracked
across PRs (see PERF.md).

``--only SUITE[,SUITE...]`` runs a subset (e.g. ``--only kernels,train``).
"""
import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    from . import (
        bench_accuracy, bench_comm, bench_kernels, bench_oob, bench_time,
        bench_train, bench_volume,
    )

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--only", default=None,
        help="comma-separated suite subset: "
             "accuracy|oob|volume|comm|time|kernels|train",
    )
    parser.add_argument(
        "--history", action="store_true",
        help="append a dated name->us_per_call row for the tracked "
             "(kernels+train) suites to BENCH_history.jsonl — the "
             "across-run perf series CI uploads as an artifact",
    )
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    all_rows = []
    suites = [
        ("accuracy", "accuracy (Figs. 8-9)", bench_accuracy.run),
        ("oob", "oob (Fig. 10/Table 5)", bench_oob.run),
        ("volume", "volume (Fig. 14)", lambda: bench_volume.run() + bench_volume.run_measured()),
        ("comm", "comm (Fig. 15)", bench_comm.run),
        ("time", "time/scaling (Figs. 11-13)", bench_time.run),
        ("kernels", "kernels", bench_kernels.run),
        ("train", "train (growth engine)", bench_train.run),
    ]
    if args.only is not None:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = set(wanted) - {s[0] for s in suites}
        if unknown:
            raise SystemExit(f"unknown suite(s) {sorted(unknown)!r}")
        suites = [s for s in suites if s[0] in wanted]

    tracked_rows = {}                    # suite key -> rows in BENCH_kernels.json
    print("name,us_per_call,derived")
    for key, title, fn in suites:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # a suite failure must not hide the others
            rows = [{"bench": title, "error": str(e)[:200], "us_per_call": 0.0}]
        for r in rows:
            name = r.get("bench", title)
            us = r.get("us_per_call", 0.0)
            derived = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in r.items() if k not in ("bench", "us_per_call")
            }
            print(f"{name},{us:.1f},{json.dumps(derived)}")
        all_rows.extend(rows)
        if key in ("kernels", "train"):
            tracked_rows[key] = rows
        print(f"# suite '{title}' done in {time.time()-t0:.1f}s", file=sys.stderr)

    # Only a full run may replace the aggregate dump EXPERIMENTS.md reads;
    # --only iterations must not clobber it with a partial row set.
    if args.only is None:
        os.makedirs("artifacts", exist_ok=True)
        with open("artifacts/bench.json", "w") as f:
            json.dump(all_rows, f, indent=2, default=str)

    # BENCH_kernels.json tracks the kernel + training-engine trajectory.
    # Only rewrite it when BOTH suites ran green, so a failed or partial
    # run (--only kernels) never wipes half the tracked series.
    if set(tracked_rows) == {"kernels", "train"} and not any(
        "error" in r for rows in tracked_rows.values() for r in rows
    ):
        import jax

        payload = {
            "jax_backend": jax.default_backend(),
            "note": "interpret-mode Pallas timings off-TPU measure "
                    "emulation, not hardware; track deltas per backend",
            "rows": tracked_rows["kernels"] + tracked_rows["train"],
        }
        with open(os.path.join(_REPO_ROOT, "BENCH_kernels.json"), "w") as f:
            json.dump(payload, f, indent=2, default=str)

        if args.history:
            # One JSON line per run: the perf series a plot can read
            # straight off the CI artifact without parsing full dumps.
            from datetime import date

            line = {
                "date": date.today().isoformat(),
                "jax_backend": payload["jax_backend"],
                "us_per_call": {
                    r["bench"]: round(float(r.get("us_per_call", 0.0)), 1)
                    for r in payload["rows"]
                },
            }
            with open(os.path.join(_REPO_ROOT, "BENCH_history.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
