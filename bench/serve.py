"""Serving cells: open-loop traffic through the program's `Scheduler`.

Set-up makes the weights on the device from the seed, builds the
scheduler, and warms up every program the traffic drives: the prefill
chunk and each decode burst length.  A cell past the knee also queues
its ``backlog`` and serves it until every slot has been filled once,
so that its window opens on a full batch with a queue behind it.  The
window submits each request when it falls due, steps the scheduler, and
stamps a request's tokens when `Scheduler.step()` returns with them.
Below the knee (``drain_limit_s`` over 0) the scheduler then drains what
fell due in the window; a request that does not finish has failed.
Past it (``drain_limit_s`` 0) the run ends at the close, and requests
still in flight are neither finished nor failed.

The check: a sample of the requests finished in the window, drawn from
the seed with the longest among them, goes through the reference once
each (prompt and served tokens, teacher-forced), and the number
compared is the widest gap by which a served token's logit lies below
the reference's best.  Greedy decoding makes that gap a rounding gap.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

import flops
import harness
import model as bm
import reference as R
import traffic_gen


@dataclass
class Served:
    due: float                 # absolute, time.time()
    prompt_len: int
    max_new: int
    req: object
    seen: int = 0
    first: float | None = None
    last: float | None = None


@dataclass
class Window:
    t0: float = 0.0
    t_end: float | None = None     # the close: end of its last step
    seconds: float = 0.0
    delivered: int = 0
    served: list = field(default_factory=list)
    bursts: list = field(default_factory=list)    # (wall s, tokens)
    steps: list = field(default_factory=list)     # (end time, flops)
    trace: tuple | None = None                     # (t_a, t_b), time.time()
    drain_s: float = 0.0


def build(conf: dict, traffic: dict, devices, key):
    """(program config, model, weights, scheduler)."""
    harness.add_program_path()
    from repro.launch import serve as S
    from repro.models.transformer import Model
    cfg = bm.program_config(conf)
    family = harness.family(conf)
    # the model's settings as the serving launcher sets them
    model = Model(cfg, **harness.launcher_model_kwargs(S, S.main, []))
    template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    with jax.default_device(devices[0]):
        params = jax.jit(lambda k: bm.make_weights(family, template,
                                                   k))(key)
    sch = make_scheduler(model, params, traffic)
    return cfg, model, template, params, sch


def make_scheduler(model, params, traffic):
    from repro.serve import Scheduler
    sv = traffic["server"]
    return Scheduler(model, params, slots=sv["slots"], pages=sv["pages"],
                     page_size=sv["page_size"], max_len=sv["max_len"],
                     decode_burst=sv["decode_burst"],
                     prefill_chunk=sv["prefill_chunk"],
                     use_kernel=sv["use_kernel"])


def warm_up(sch, vocab: int) -> None:
    """Run every program the window will: the chunked prefill and the
    decode bursts 1..decode_burst (the scheduler shortens a burst to the
    earliest finish), then leave the scheduler idle."""
    from repro.serve import Request
    burst = sch.decode_burst
    lengths = [b + 1 for b in range(1, burst)] + [2 * burst]
    reqs = [Request(rid=-1 - i, prompt=[(7 * i + j) % vocab
                                        for j in range(16)], max_new=n)
            for i, n in enumerate(lengths)]
    for r in reqs:
        sch.run([r])


def _records(reqs: list, rid0: int = 0) -> list:
    from repro.serve import Request
    return [Served(0.0, len(r["prompt"]), r["max_new"],
                   Request(rid=rid0 + i, prompt=r["prompt"],
                           max_new=r["max_new"]))
            for i, r in enumerate(reqs)]


def _collect(live: list, t: float, conf: dict) -> tuple:
    """Stamp at ``t`` the tokens the step that just returned made, and
    drop finished requests from ``live``.  Returns (new tokens, their
    model FLOPs; a prompt counts with its first token)."""
    new, step_flops = 0, 0.0
    for s in list(live):
        k = len(s.req.out)
        if k > s.seen:
            if s.seen == 0:
                s.first = t
                step_flops += flops.prefill_flops(conf, s.prompt_len)
            for j in range(max(s.seen, 1), k):
                step_flops += flops.decode_flops(conf, s.prompt_len + j)
            new += k - s.seen
            s.seen = k
        if s.req.done:
            s.last = t
            live.remove(s)
    return new, step_flops


def fill(sch, reqs: list, conf: dict) -> list:
    """Set-up of a cell past the knee: queue the backlog ``reqs`` and
    step the scheduler until each of its first ``slots`` requests has
    had its prompt prefilled, so that the window opens on a full batch
    with a queue behind it.  Returns the backlog's records."""
    queued = _records(reqs, rid0=1_000_000)
    now = time.time()
    for s in queued:
        s.due = now
        sch.submit(s.req)
    head, live = queued[:len(sch.slots)], list(queued)
    while any(s.first is None for s in head):
        sch.step()
        _collect(live, time.time(), conf)
    return queued


def open_loop(sch, reqs: list, seconds: float, conf: dict, *,
              drain_limit: float, trace_dir=None, queued: list = ()):
    """Submit each request when due, step the scheduler, stamp tokens as
    ``step()`` returns them.  The window closes with the first step that
    ends ``seconds`` or more after it opened; what that step made counts.
    Then, with ``drain_limit`` over 0, the run goes on until every
    request due in the window has finished, or ``drain_limit`` seconds
    after the close; with 0 it ends at the close.  ``queued`` are the
    backlog's records from `fill`, still served in the window.  With
    ``trace_dir`` the profiler records the whole window and the drain:
    starting and stopping it take seconds in which no request would be
    served, so both lie outside them."""
    win = Window()
    served = _records(reqs)
    walls, stats = sch.stats["step_walls"], sch.stats
    live = [s for s in queued if not s.req.done]
    i, n = 0, len(served)
    gc_pauses = harness.GcPauses()
    harness.quiet_gc(gc_pauses)
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    t0 = time.time()
    for s, r in zip(served, reqs):
        s.due = t0 + r["due"]
    win.t0, win.served = t0, list(queued) + served
    t_close = t0 + seconds
    while True:
        now = time.time()
        if i < n and served[i].due <= now:
            with jax.profiler.TraceAnnotation("bench.submit"):
                while i < n and served[i].due <= now:
                    sch.submit(served[i].req)
                    live.append(served[i])
                    i += 1
        if win.t_end is None and now >= t_close:
            win.t_end = now
        if i == n and not live:
            break
        if win.t_end is not None and now >= t_close + drain_limit:
            break
        nb, ds = len(walls), stats["decode_steps"]
        with jax.profiler.TraceAnnotation("bench.step"):
            worked = sch.step()
        t = time.time()
        new, step_flops = _collect(live, t, conf)
        if win.t_end is None:
            win.delivered += new
            if t >= t_close:
                win.t_end = t
        win.steps.append((t, step_flops))
        if len(walls) > nb:
            win.bursts.append((walls[-1], stats["decode_steps"] - ds))
        if not worked and i < n:
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(served[i].due - time.time(),
                                        0.05)))
    t_last = time.time()
    if win.t_end is None:   # all that fell due finished before the close
        win.t_end = t_close
    win.seconds = win.t_end - t0
    win.drain_s = max(0.0, t_last - win.t_end)
    if trace_dir is not None:
        win.trace = (t0, t_last)
        jax.profiler.stop_trace()
    harness.loud_gc(gc_pauses)
    live_at_end = sum(1 for s in win.served if not s.req.done)
    print(f"bench: {len(queued)} queued and {n} due requests, "
          f"{len(win.steps)} scheduler steps, window {win.seconds!r} s, "
          f"{win.delivered} tokens in it, drained {win.drain_s!r} s, "
          f"{live_at_end} unfinished; {gc_pauses}", file=sys.stderr)
    return win


def sample(win: Window, seed: int, k: int) -> list:
    """Up to ``k`` requests finished in the window or its drain, drawn
    from the seed, the longest (prompt and served tokens) among them."""
    done = [s for s in win.served
            if s.req.done and s.last is not None and s.last >= win.t0]
    if not done:
        return []
    longest = max(done, key=lambda s: s.prompt_len + len(s.req.out))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def _gap_fn(conf: dict, lowp: bool):
    """gaps(params, tokens (Lmax,), positions (n,), served (n,)) -> (n,):
    the f32 reference's best logit minus its logit of the served token
    at each position.  With ``lowp`` the served token is the one the
    fp8 reference puts first (the control)."""
    family = harness.family(conf)
    rc = family.reference_config(conf)
    V = conf["vocab_size"]

    def fn(params, tokens, pos, served):
        x = family.hidden(params, tokens, rc, R.matmul(False), remat=False)
        xs = x[pos]
        ref = R.matmul(False)("sd,dv->sv", xs,
                              family.unembed(params))[:, :V]
        if lowp:
            x8 = family.hidden(params, tokens, rc, R.matmul(True),
                               remat=False)
            low = R.matmul(True)("sd,dv->sv", x8[pos],
                                 family.unembed(params))[:, :V]
            served = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - got
    return jax.jit(fn)


def served_gap(params, conf: dict, picked: list, max_len: int,
               lowp: bool = False) -> float:
    """Widest gap over the picked requests' served tokens."""
    fn = _gap_fn(conf, lowp)
    worst = 0.0
    for s in picked:
        toks = list(s.req.prompt) + list(s.req.out[:-1])
        n = len(s.req.out)
        tokens = np.zeros(max_len, np.int32)
        tokens[:len(toks)] = toks
        pos = np.zeros(max_len, np.int32)
        pos[:n] = np.arange(s.prompt_len - 1, s.prompt_len - 1 + n)
        out = np.zeros(max_len, np.int32)
        out[:n] = s.req.out
        g = np.asarray(fn(params, tokens, pos, out))[:n]
        worst = max(worst, float(np.max(g)))
    return worst


def run(conf, traffic, devices, *, seed, seconds, trace_dir, limits,
        t_start):
    """One run of a serving cell: the record the metric readers read,
    with the checks (name, number, limit)."""
    key = harness.seed_key(seed)
    cfg, model, template, params, sch = build(conf, traffic, devices, key)
    warm_up(sch, cfg.vocab_size)
    reqs = traffic_gen.requests(traffic, seed, seconds, cfg.vocab_size)
    queued = fill(sch, traffic_gen.backlog(traffic, seed, cfg.vocab_size),
                  conf)
    setup_s = time.perf_counter() - t_start
    win = open_loop(sch, reqs, seconds, conf,
                    drain_limit=traffic["drain_limit_s"],
                    trace_dir=trace_dir, queued=queued)
    device = harness.device_info(devices)
    picked = sample(win, seed, traffic["check_requests"])
    del sch
    gc.collect()
    failed = unfinished(win, traffic)
    gap = served_gap(params, conf, picked, traffic["server"]["max_len"]) \
        if picked else float("inf")
    checks = [("served_gap", gap, limits["served_gap"]),
              ("failed_requests", failed, 0),
              ("checked_tokens_short", max(0, traffic["check_tokens_min"]
                                           - sum(len(s.req.out)
                                                 for s in picked)), 0)]
    return {"kind": "serve", "conf": conf, "window": win,
            "setup_s": setup_s, "device": device, "checks": checks,
            "attempted": len(win.served), "failed": failed}


def unfinished(win: Window, traffic: dict) -> int:
    """Requests that failed: below the knee, those the drain left
    unfinished; past it none (the run does not wait for them)."""
    if traffic["drain_limit_s"] <= 0:
        return 0
    return sum(1 for s in win.served if not s.req.done)


def end_to_end(rec) -> dict:
    win = rec["window"]
    return {"serve_tokens_per_s": win.delivered / win.seconds,
            "setup_s": rec["setup_s"]}


def calibrate(conf, traffic, devices, seeds, seconds) -> list:
    """The readings that set a serving cell's limit: per seed, a short
    window at the cell's load, then the served gap of the sample and the
    control's gap (the token the fp8 reference puts first) at the same
    positions."""
    key0 = harness.seed_key(seeds[0])
    cfg, model, template, params, sch = build(conf, traffic, devices, key0)
    warm_up(sch, cfg.vocab_size)
    del sch
    family = harness.family(conf)
    out = []
    for seed in seeds:
        params = jax.jit(lambda k: bm.make_weights(family, template, k))(
            harness.seed_key(seed))
        sch = make_scheduler(model, params, traffic)
        warm_up(sch, cfg.vocab_size)
        reqs = traffic_gen.requests(traffic, seed, seconds, cfg.vocab_size)
        queued = fill(sch, traffic_gen.backlog(traffic, seed,
                                               cfg.vocab_size), conf)
        win = open_loop(sch, reqs, seconds, conf,
                        drain_limit=traffic["drain_limit_s"], queued=queued)
        picked = sample(win, seed, traffic["check_requests"])
        del sch
        gc.collect()
        ml = traffic["server"]["max_len"]
        out.append({"seed": seed,
                    "tokens": sum(len(s.req.out) for s in picked),
                    "failed": unfinished(win, traffic),
                    "sound": served_gap(params, conf, picked, ml),
                    "control": served_gap(params, conf, picked, ml,
                                          lowp=True)})
    return out
