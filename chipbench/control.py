"""Read the numbers a cell's limits are set from: the program's, and the
control's, on several seeds in one process.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell as ``run.py`` does (untraced) and prints one
JSON line: for the program (``program``) and for the control (``control``)
every number compared beside its limit, and ``correct`` as ``run.py``
decides it from them.  The control is the reference put in the program's
place one step below what the configuration states: for a served bfloat16
model the float8 reference, read at the same positions; for the join, the
tally with the exactly-once guarantee broken (one event delivered twice).
The benchmark's own runs never run it.  Exits 1 where the control comes
out correct on any seed or the program does not, and non-zero without a
TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from chipbench.harness import bench, device as dev  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, each run in turn")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traffic", help="another traffic file for the cell's "
                    "configuration, in place of the cell's own")
    args = ap.parse_args(argv)
    try:
        cell = bench.Cell(args.workload, traffic=args.traffic)
    except dev.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    separated = True
    for seed in (int(s) for s in args.seeds.split(",")):
        run = cell.run(seed, args.seconds, control=True)
        line = {"workload": args.workload, "seed": seed}
        for side, checks in (("program", run["checks"]),
                             ("control", run["control_checks"])):
            line[side] = {"correct": bench.judge(checks),
                          "checks": bench.check_line(checks)}
        separated &= line["program"]["correct"] and not line["control"]["correct"]
        line.update(attempted=run["attempted"], failed=run["failed"],
                    detail=run.get("sample_detail"), e2e=run["e2e"])
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
    return 0 if separated else 1


if __name__ == "__main__":
    sys.exit(main())
