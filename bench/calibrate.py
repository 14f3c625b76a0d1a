"""The readings a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... [--seconds 12]

For each seed, one JSON line: the numbers compared for sound runs of the
program, for the control (the reference computed in fp8 in the
program's place) and, for training, for the planted faults.  Training
needs no window; serving runs a short one (``--seconds``) at the cell's
own load.  The last line sums up: per number, the largest sound reading
(the lower one) and the smallest control or fault reading (the upper).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def summary(rows: list) -> dict:
    out = {}
    for row in rows:
        for variant, nums in row.items():
            if not isinstance(nums, dict):
                continue
            for k, v in nums.items():
                key = (k, variant)
                pick = max if variant == "sound" else min
                out[key] = v if key not in out else pick(out[key], v)
    return {f"{k}.{variant}": v for (k, variant), v in sorted(out.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell, entry, traffic = harness.find_cell(bench, args.workload)
    conf = harness.load_config(entry)
    devices = harness.require_chips(cell["chips"])
    harness.use_compile_cache()
    kind = __import__(traffic["kind"])
    rows = []
    if traffic["kind"] == "serve":
        for row in kind.calibrate(conf, traffic, devices, args.seeds,
                                  args.seconds):
            rows.append({"sound": {"served_gap": row["sound"]},
                         "control": {"served_gap": row["control"]}})
            print(json.dumps(row), flush=True)
    else:
        for seed in args.seeds:
            row = kind.calibrate(conf, traffic, devices, seed)
            row["seed"] = seed
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
