"""The training step names its layers for the benchmark's trace
reduction.

`bench/scopes.py` charges each device op to the innermost of its
`LAYERS` found on the op's name stack, the ``op_name`` metadata the
compiler keeps on every HLO instruction.  Each case compiles a tiny
DC-S3GD or SSGD step (per-leaf or bucketed wire, XLA or fused Pallas
tail) on the CPU and looks for every name in that metadata, for the
backward pass under ``attn``, and for the wire casts the lint's
``wire`` scope counts.  A profile of `Engine.fit` holds its host spans.
"""
import importlib.util
import re
import sys
from pathlib import Path

import jax
import pytest

from repro.analysis import lint
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.launch.engine import Engine
from repro.models.transformer import Model

_spec = importlib.util.spec_from_file_location(
    "bench_scopes",
    Path(__file__).resolve().parents[1] / "bench" / "scopes.py")
scopes = sys.modules[_spec.name] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(scopes)


def _program(algo: str, buckets: int, use_kernels: bool) -> lint.Program:
    """The lint's view of the step, over the trainer's remat model (the
    recompute lies under ``transpose(...)``) and the given tail."""
    prog = lint.Program(lint.GridPoint(algo, "mean_allreduce", buckets,
                                       False), model="transformer")
    prog.model = Model(prog.model.cfg, remat=True, q_chunk=8, kv_chunk=8,
                       scan_chunk=8, loss_chunk=16)
    prog.alg = registry.make(algo, prog.cfg, n_workers=lint.N_WORKERS,
                             reducer="mean_allreduce", buckets=buckets,
                             use_kernels=use_kernels)
    prog.engine = Engine(prog.model, prog.alg)
    prog.state = prog.engine.init_state(jax.random.PRNGKey(0))
    return prog


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["xla_tail", "fused_tail"])
@pytest.mark.parametrize("buckets", [0, 8], ids=["b0", "b8"])
@pytest.mark.parametrize("algo", ["dc_s3gd", "ssgd"])
def test_train_step_names_its_layers(algo, buckets, use_kernels):
    prog = _program(algo, buckets, use_kernels)
    hlo = prog.lowered.compile().as_text()
    keys = {scopes.layer_key(name)
            for name in re.findall(r'op_name="([^"]*)"', hlo)}
    layers = {k.split("/")[0] for k in keys}
    assert set(scopes.LAYERS) <= layers, sorted(layers)
    assert "attn/bwd" in keys and "attn/fwd" in keys
    # the wire casts sit where the lint looks for them, all of them
    assert any(c.dst == prog.comm_mlir and "/wire" in c.scope
               for c in lint.scoped_converts(prog.stablehlo_debug))
    assert lint.WireAccountingPass().run(prog) == []
    assert lint.DtypeDriftPass().run(prog) == []


def test_fit_profiles_steps_with_host_spans(tmp_path):
    """``fit(profile_dir=...)`` records the steps after the first logged
    one, each a ``train`` step span with ``repro.*`` spans inside."""
    from jax.profiler import ProfileData
    cfg = DCS3GDConfig(learning_rate=0.05, warmup_steps=1, total_steps=6)
    alg = registry.make("dc_s3gd", cfg, n_workers=lint.N_WORKERS)
    engine = Engine(lint._ToyModel(), alg)
    batch = lint._toy_batch(lint.N_WORKERS)
    engine.fit(engine.init_state(jax.random.PRNGKey(0)), lambda it: batch,
               steps=6, log_every=2, verbose=False, profile_dir=tmp_path,
               profile_steps=3)
    traces = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(traces) == 1
    spans = [(e.name, dict(e.stats).get("step_num"))
             for plane in ProfileData.from_file(str(traces[0])).planes
             for line in plane.lines for e in line.events
             if e.name == "train" or e.name.startswith("repro.")]
    # steps 1-3: three batches made, step 2's metrics fetched
    assert sorted(s for n, s in spans if n == "train") == [1, 2, 3]
    assert [n for n, _ in spans].count("repro.batch") == 3
    assert [n for n, _ in spans].count("repro.fetch") == 1
