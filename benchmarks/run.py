"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one line per measurement) and writes
the raw rows to results/benchmarks.json.

    PYTHONPATH=src python -m benchmarks.run [--only load_test,overhead]

Each suite runs in a process of its own, started with ``spawn``: a suite's
batch-plane workers initialize JAX (on a TPU host that process takes the
chip), and suites such as ``sharded_load`` and ``autoscale`` fork shard
processes, which must not inherit a JAX runtime.  This process stays off
JAX altogether.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import traceback
from concurrent.futures import ProcessPoolExecutor

SUITES = [
    ("load_test", "Table 1 — events/s per worker"),
    ("sharded_load", "repro.bus — events/s vs worker-shard count"),
    ("overhead", "Fig 9/10 — seq + parallel DAG overhead vs baselines"),
    ("event_sourcing", "Fig 11/12 — workflow-as-code replay overhead"),
    ("autoscaling", "Fig 8 — KEDA-style scale up/down to zero"),
    ("autoscale", "Fig 8 on the sharded runtimes — 0→N→0 thread + process shards"),
    ("fault_tolerance", "Fig 13 — worker kill + recovery"),
    ("montage", "Fig 14-16 — nested state machine, scale-to-zero"),
    ("fedlearn_bench", "Fig 17 — federated learning rounds"),
    ("roofline", "§Roofline — per (arch × shape) dry-run terms"),
    ("obs", "Observability — metrics/trace plane overhead on the noop action plane"),
    ("policy", "Failure policy — idle retry-policy overhead on the noop action plane"),
    ("replication", "Host-loss domain — segment-transport overhead on the file bus"),
    ("codec", "Event codec — v1 JSON lines vs TFB1 columnar frames"),
]


def _run_suite(suite: str):
    """One suite's rows, computed in the suite's own process."""
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
    return mod.run()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    known = [s for s, _ in SUITES]
    chosen = args.only.split(",") if args.only else known
    unknown = sorted(set(chosen) - set(known))
    if unknown:
        ap.error(f"unknown suite(s): {', '.join(unknown)}; "
                 f"choose from {', '.join(known)}")

    ctx = mp.get_context("spawn")
    all_rows = []
    failures = 0
    print("name,us_per_call,derived")
    for suite in known:
        if suite not in chosen:
            continue
        try:
            # executor workers are not daemonic, so a suite may start its
            # own shard processes
            with ProcessPoolExecutor(1, mp_context=ctx) as ex:
                rows = ex.submit(_run_suite, suite).result()
        except Exception:  # noqa: BLE001 - one failed suite must not stop the rest
            traceback.print_exc()
            print(f"{suite}.FAILED,0,see stderr")
            failures += 1
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.2f},\"{r['derived']}\"")
            row = dict(r)
            if "timeline" in row:
                # keep the Fig-8 data, bounded (the committed artifact)
                row["timeline"] = [list(t) for t in row["timeline"][-200:]]
            all_rows.append(row)
        sys.stdout.flush()
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if args.only and os.path.exists(out):
        # partial rerun: replace only the rerun suites' rows, keep every
        # other committed row (a bare --only must not clobber the file)
        with open(out) as f:
            kept = [r for r in json.load(f)
                    if r.get("name", "").split(".", 1)[0] not in chosen]
        all_rows = kept + all_rows
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
