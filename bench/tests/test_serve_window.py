"""A serving window past the knee: set-up fills every slot from the
backlog, the window opens on a full batch with a queue behind it, and
closes at the end of a scheduler step."""
import jax

import harness
import serve
import traffic_gen
from small import serve_traffic


def _scheduler(tiny, traffic):
    conf = tiny["tiny-qwen3"]
    cfg, model, template, params, sch = serve.build(
        conf, traffic, jax.devices()[:1], harness.seed_key(2**35 + 1))
    serve.warm_up(sch, cfg.vocab_size)
    return conf, cfg, sch


def test_backlog_fills_every_slot_before_the_window(tiny):
    tr = serve_traffic()
    conf, cfg, sch = _scheduler(tiny, tr)
    queued = serve.fill(sch, traffic_gen.backlog(tr, 3, cfg.vocab_size),
                        conf)
    slots = tr["server"]["slots"]
    assert len(queued) == tr["backlog"] > slots
    assert all(s.first is not None for s in queued[:slots])
    # a slot freed by the last step is taken from the queue by the next
    free = sum(r is None for r in sch.slots)
    assert sch.waiting and len(sch.waiting) >= free


def test_window_closes_at_the_end_of_a_step(tiny):
    tr = serve_traffic()
    conf, cfg, sch = _scheduler(tiny, tr)
    queued = serve.fill(sch, traffic_gen.backlog(tr, 3, cfg.vocab_size),
                        conf)
    reqs = traffic_gen.requests(tr, 3, 1.0, cfg.vocab_size)
    win = serve.open_loop(sch, reqs, 1.0, conf, drain_limit=0,
                          queued=queued)
    assert win.seconds >= 1.0
    assert win.t_end == max(t for t, _ in win.steps)
    assert win.delivered > 0
    # no drain past the knee: requests are still in flight at the close
    assert any(not s.req.done for s in win.served)
    assert serve.unfinished(win, tr) == 0
    picked = serve.sample(win, 3, tr["check_requests"])
    assert picked and all(s.last >= win.t0 for s in picked)
