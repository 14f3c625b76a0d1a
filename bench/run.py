"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` by name, and its configuration,
traffic mix, correctness limits and per-layer metric readers by the
names there.  Prints the numbers compared with their limits as the last
lines of standard error, and one JSON result as the last line of
standard output.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.env_threads()
    bench = harness.load_benchmark()
    cell, entry, traffic = harness.find_cell(bench, args.workload)
    conf = harness.load_config(entry)
    limits = harness.load_limits(args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    out = execute(bench, args.workload, conf, traffic, limits, devices,
                  seed=args.seed, seconds=args.seconds, trace=args.trace,
                  t_start=T_START)
    harness.print_result(**out)
    return 0


def execute(bench, workload, conf, traffic, limits, devices, *, seed,
            seconds, trace, t_start) -> dict:
    """Everything of a run after the look for chips: the cell's kind
    module (``bench/train.py`` or ``bench/serve.py``), its check, and its
    metrics.  Returns `harness.print_result`'s arguments."""
    kind = __import__(traffic["kind"])
    trace_dir = None
    if trace:
        trace_dir = harness.OUT_DIR / f"trace-{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = kind.run(conf, traffic, devices, seed=seed, seconds=seconds,
                     trace_dir=trace_dir, limits=limits, t_start=t_start)
    checks = rec["checks"]
    correct = all(v <= lim for _, v, lim in checks)
    device = rec["device"]
    breakdown = None
    if trace:
        import timeline
        plain = timeline.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        timeline.save(plain, harness.OUT_DIR /
                      f"timeline-{workload}-{seed}.json.gz")
        tl = timeline.Timeline(plain)
        rec["timeline"] = tl
        device = dict(device, busy_s=tl.busy_s, window_s=tl.window_s)
        breakdown = tl.breakdown()
        metrics = {}
        for m in harness.metrics_for(bench, workload, trace=True):
            value = harness.metric_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = kind.end_to_end(rec)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in harness.metrics_for(bench, workload,
                                                trace=False)}
    return {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": device,
            "checks": checks, "breakdown": breakdown}


if __name__ == "__main__":
    sys.exit(main())
