"""Find a serving mix's knee once: the highest offered rate at which
the backlog does not grow.

    python3 bench/sweep.py --config qwen3-0.6b --traffic chat --rates 0.3 0.5 0.7 1.0 --shape-seeds 0 1 2 --seconds 60

One process, one scheduler, warmed up once; each rate, lowest first,
runs the mix's lengths at that rate for ``--seconds`` on each shape
seed (each its own schedule of sizes and arrivals) and drains.  Per
rate and shape seed one JSON line: requests offered, tokens served per
second in the window, TTFT p50/p95 from the due time, the median TTFT
of the requests due in the first and in the last third of the window,
and the backlog (requests due but not yet answered with a first token)
at a third of the window and at its close.  A schedule is past the knee
where a request is left unfinished, the backlog at the close is larger
than at a third, or the last third waits more than 1.5 times as long as
the first and by more than a median scheduler step (less is where in a
step a request fell due); a rate is past it where any schedule is, and
the sweep stops there.  The last line names the knee, the highest rate below that.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def backlog(win, at: float) -> int:
    t = win.t0 + at
    return sum(1 for s in win.served
               if s.due <= t and (s.first is None or s.first > t))


def third_wait(win, lo: float, hi: float) -> float | None:
    """Median TTFT, in s, of the requests due in ``[lo, hi)`` after the
    window opened (None if none); a request never answered waits
    forever."""
    import numpy as np
    w = [(s.first - s.due) if s.first is not None else float("inf")
         for s in win.served if lo <= s.due - win.t0 < hi]
    return float(np.median(w)) if w else None


def judge(win, seconds: float) -> dict:
    """One schedule's readings at one rate, and whether it is past the
    knee."""
    import numpy as np
    ttft = [s.first - s.due for s in win.served if s.first is not None]
    unfinished = sum(1 for s in win.served if not s.req.done)
    first = third_wait(win, 0, seconds / 3)
    last = third_wait(win, 2 * seconds / 3, seconds)
    grow = (backlog(win, seconds / 3), backlog(win, seconds))
    step_s = float(np.median(np.diff([win.t0] + [t for t, _ in win.steps])))
    waits_longer = first is not None and last is not None \
        and last > 1.5 * first and last - first > step_s
    ms = lambda x: None if x is None else x * 1e3
    return {"offered": len(win.served),
            "serve_tokens_per_s": win.delivered / win.seconds,
            "ttft_p50_ms": float(np.percentile(ttft or [np.inf], 50)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft or [np.inf], 95)) * 1e3,
            "ttft_first_third_ms": ms(first),
            "ttft_last_third_ms": ms(last), "step_ms": step_s * 1e3,
            "backlog_third": grow[0], "backlog_close": grow[1],
            "drain_s": win.drain_s, "unfinished": unfinished,
            "past_knee": bool(unfinished > 0 or grow[1] > grow[0]
                              or waits_longer)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--shape-seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import serve
    import traffic_gen
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    conf = harness.load_config(entry)
    traffic = harness.load_traffic(args.traffic)
    devices = harness.require_chips(1)
    harness.use_compile_cache()
    cfg, model, template, params, sch = serve.build(
        conf, traffic, devices, harness.seed_key(args.seed))
    serve.warm_up(sch, cfg.vocab_size)
    knee = None
    for rate in sorted(args.rates):
        past = False
        for shape_seed in args.shape_seeds:
            tr = dict(traffic, shape_seed=shape_seed)
            reqs = traffic_gen.requests(tr, args.seed, args.seconds,
                                        cfg.vocab_size, rate=rate)
            win = serve.open_loop(sch, reqs, args.seconds, conf,
                                  drain_limit=traffic["drain_limit_s"])
            row = judge(win, args.seconds)
            past = past or row["past_knee"]
            print(json.dumps({"rate": rate, "shape_seed": shape_seed,
                              **row}), flush=True)
            gc.collect()
        if past:
            break
        knee = rate
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
