"""The traffic generator repeats for a seed, and every seed offers the
same work on the same schedule."""
import harness
import traffic_gen
import train

from small import train_traffic


def test_serve_traffic_repeats_for_a_seed():
    tr = harness.load_traffic("chat")
    a = traffic_gen.requests(tr, 2**40 + 7, 30, 151936)
    b = traffic_gen.requests(tr, 2**40 + 7, 30, 151936)
    assert a == b


def test_serve_seeds_share_sizes_and_arrivals():
    tr = harness.load_traffic("chat")
    a = traffic_gen.requests(tr, 1, 30, 151936)
    b = traffic_gen.requests(tr, 2, 30, 151936)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    sizes = lambda rs: [(len(r["prompt"]), r["max_new"]) for r in rs]
    assert sizes(a) == sizes(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_serve_lengths_and_rate_follow_the_file():
    tr = harness.load_traffic("chat")
    a = traffic_gen.requests(tr, 3, 2000, 151936)
    assert all(32 <= len(r["prompt"]) <= 2048 for r in a)
    assert all(8 <= r["max_new"] <= 512 for r in a)
    assert 300 < sorted(len(r["prompt"]) for r in a)[len(a) // 2] < 480
    # the window holds about rate x seconds requests
    assert 0.85 * 2000 * tr["arrivals"]["rate_per_s"] < len(a) \
        < 1.15 * 2000 * tr["arrivals"]["rate_per_s"]


def test_gamma_arrivals_keep_the_rate():
    tr = dict(harness.load_traffic("chat"))
    tr["arrivals"] = {"process": "gamma", "rate_per_s": 5.0,
                      "burst_shape": 0.3}
    a = traffic_gen.requests(tr, 3, 200, 1000)
    assert 0.75 * 1000 < len(a) < 1.25 * 1000


def test_train_batches_repeat_and_differ(tiny):
    job = train.build(tiny["tiny-qwen3"], train_traffic(workers=2),
                      _cpu())
    a, b = train.make_batch(5, 3, job), train.make_batch(5, 3, job)
    assert (a["tokens"] == b["tokens"]).all()
    c = train.make_batch(5, 4, job)
    assert (a["tokens"] != c["tokens"]).any()
    rows = a["tokens"].reshape(-1, a["tokens"].shape[-1])
    assert len({r.tobytes() for r in rows}) == rows.shape[0]
    assert (a["labels"][..., :-1] == a["tokens"][..., 1:]).all()


def _cpu():
    import jax
    return jax.devices()[:1]


def test_a_longer_window_starts_with_the_same_requests():
    tr = harness.load_traffic("chat")
    a = traffic_gen.requests(tr, 5, 30, 151936)
    b = traffic_gen.requests(tr, 5, 300, 151936)
    strip = lambda rs: [(r["due"], len(r["prompt"]), r["max_new"])
                        for r in rs]
    assert strip(b[:len(a)]) == strip(a)


def test_backlog_repeats_for_a_seed_and_shares_sizes():
    tr = harness.load_traffic("chat-overload")
    a = traffic_gen.backlog(tr, 2**40 + 7, 151936)
    assert a == traffic_gen.backlog(tr, 2**40 + 7, 151936)
    b = traffic_gen.backlog(tr, 9, 151936)
    assert len(a) == tr["backlog"]
    sizes = lambda rs: [(len(r["prompt"]), r["max_new"]) for r in rs]
    assert sizes(a) == sizes(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert all(32 <= len(r["prompt"]) <= 2048 for r in a)


def test_models_are_built_as_the_launchers_build_them():
    from repro.launch import serve as S
    from repro.launch import train as T
    from repro.models.transformer import Model
    kw = harness.launcher_model_kwargs(S, S.main, [])
    assert S.Model is Model and kw["remat"] is False
    args = train.trainer_args(harness.load_traffic("train-w1-2x2048"))
    kw = harness.launcher_model_kwargs(T, T.run, args)
    assert T.Model is Model and kw["remat"] is True and "q_chunk" in kw
