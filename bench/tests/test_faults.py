"""Whole runs on the CPU, past the look for a chip, with the timed path
broken underneath: each fault a cell can have turns `correct` false,
and the sound run stays true."""
import time

import jax
import pytest

import harness
import run
from small import SERVE_LIMITS, TRAIN_LIMITS, serve_traffic, train_traffic


def execute(conf, traffic, limits, workload, seconds=1.0):
    return run.execute(harness.load_benchmark(), workload, conf, traffic,
                       limits, jax.devices()[:1], seed=2**33 + 11,
                       seconds=seconds, trace=0,
                       t_start=time.perf_counter())


def train_run(tiny, workers=1, extra=()):
    return execute(tiny["tiny-qwen3"], train_traffic(workers, extra),
                   TRAIN_LIMITS, "qwen3-0.6b.train.w1")


def test_sound_train_run_is_correct(tiny):
    out = train_run(tiny, workers=2, extra=("--buckets", "2"))
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_state_left_unchanged(tiny, monkeypatch):
    from repro.core.dc_s3gd import DCS3GD
    step = DCS3GD.step

    def unchanged(self, state, batch, *, loss_fn):
        _, metrics = step(self, state, batch, loss_fn=loss_fn)
        return state, metrics
    monkeypatch.setattr(DCS3GD, "step", unchanged)
    assert not train_run(tiny)["correct"]


def test_half_the_batch_left_out(tiny, monkeypatch):
    from repro.models.transformer import Model
    loss = Model.loss

    def half(self, params, batch):
        return loss(self, params, jax.tree.map(
            lambda x: x[:x.shape[0] // 2], batch))
    monkeypatch.setattr(Model, "loss", half)
    assert not train_run(tiny)["correct"]


def test_exchange_between_workers_left_out(tiny, monkeypatch):
    from repro.core.reduce import MeanAllReduce
    monkeypatch.setattr(MeanAllReduce, "__call__", lambda self, tree: tree)
    out = train_run(tiny, workers=2, extra=("--buckets", "2"))
    assert not out["correct"], out["checks"]


def serve_run(tiny, mix="chat-overload"):
    return execute(tiny["tiny-qwen3"], serve_traffic(mix), SERVE_LIMITS,
                   "qwen3-0.6b.serve." + mix, seconds=3.0)


@pytest.mark.parametrize("mix", ["chat-overload", "chat"])
def test_sound_serve_run_is_correct(tiny, mix):
    out = serve_run(tiny, mix)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    # the metrics BENCHMARK.json gives the cell (none but setup_s for a
    # mix that is no cell)
    names = {m["name"] for m in harness.metrics_for(
        harness.load_benchmark(), "qwen3-0.6b.serve." + mix, trace=False)}
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_token_altered_where_produced(tiny, monkeypatch):
    from repro.serve.scheduler import Scheduler
    finished = Scheduler._is_finished

    def altered(self, req, tok):
        req.out[-1] = (tok + 1) % self.model.cfg.vocab_size
        return finished(self, req, tok)
    monkeypatch.setattr(Scheduler, "_is_finished", altered)
    assert not serve_run(tiny)["correct"]
