"""Find a cell's knee: run it at each offered rate and print what it kept up
with.

    python chipbench/sweep.py --workload <cell> --rates 20000,40000 --seconds 10

One process runs the cell (untraced) at each rate in turn and prints one
JSON line per rate: the offered rate, the end-to-end metrics, the backlog
left on the bus as the window closed and how late the generator ran.  The
knee is the highest rate whose completions keep up with the offer and
whose backlog does not grow (for a batcher: whose full batches start at
once, ``queue_delay_max_s`` near 0); the traffic file then records it and
offers 0.8 of it.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from chipbench.harness import bench, device as dev  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated rates")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        cell = bench.Cell(args.workload)
    except dev.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    own = dict(cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(own, rate_per_s=rate)
        run = cell.run(args.seed, args.seconds)
        late = run["late_s"].tolist()
        print(json.dumps({
            "workload": args.workload, "offered_per_s": rate,
            "e2e": run["e2e"], "attempted": run["attempted"],
            "failed": run["failed"],
            "backlog_at_close": run.get("backlog_at_close"),
            "window_compiles": (run["cache1"] - run["cache0"]
                                if "cache0" in run else None),
            "batches": len(run.get("batches", ())),
            # how long a full batch waited to start: grows once over the knee
            "queue_delay_max_s": max((min(b["waits"]) for b in
                                      run.get("batches", ()) if b["waits"]),
                                     default=None),
            "late_median_ms": statistics.median(late) * 1e3 if late else None,
            "late_max_ms": max(late) * 1e3 if late else None,
            "checks": {c["name"]: c["value"] for c in run["checks"]}}),
            flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
