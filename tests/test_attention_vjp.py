"""Training attention's custom VJP (`attention.flash_attention`).

The forward and the gradients match `jax.grad` of the blocked oracle
`_blocked_attention` (autodiff through its nested scans) over the masks
and shapes the model uses; and the lowered training step of a reduced
config holds no f32 buffer stacked over key tiles and computes exactly
the live tiles, the on-or-below-diagonal ones under causal masking.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import get_config, reduced
from repro.core import registry
from repro.core.types import DCS3GDConfig
from repro.launch.engine import Engine
from repro.models.attention import (_blocked_attention, _flash,
                                    flash_attention, live_tiles, tile_rows)
from repro.models.transformer import Model

# (B, Sq, Sk, KV, G, hd, vd, causal, window, tile)
CASES = {
    "causal_gqa": (2, 48, 48, 2, 2, 16, 16, True, 0, 16),
    "encoder": (2, 48, 48, 2, 2, 16, 16, False, 0, 16),
    "cross": (2, 24, 40, 2, 2, 16, 16, False, 0, 16),
    "window": (2, 48, 48, 2, 2, 16, 16, True, 12, 8),
    "mla_vd_ne_hd": (2, 48, 48, 4, 1, 24, 16, True, 0, 16),
    "ragged_tail": (1, 40, 40, 2, 2, 16, 16, True, 0, 16),
    "one_tile": (1, 32, 32, 2, 2, 16, 16, True, 0, None),
}
# worst gap over the largest magnitude of each compared array: f32 is
# rounding, bf16 the inputs' and operands' rounding against f32 math
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _inputs(case, dtype):
    B, Sq, Sk, KV, G, hd, vd = case[:7]
    ks = random.split(random.PRNGKey(3), 4)
    return (random.normal(ks[0], (B, Sq, KV, G, hd)).astype(dtype),
            random.normal(ks[1], (B, Sk, KV, hd)).astype(dtype),
            random.normal(ks[2], (B, Sk, KV, vd)).astype(dtype),
            random.normal(ks[3], (B, Sq, KV, G, vd)).astype(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(CASES))
def test_vjp_matches_autodiff_of_the_oracle(name, dtype):
    """Output and dq, dk, dv against `jax.vjp` of `_blocked_attention`
    run in f32 on the same (rounded) inputs."""
    case = CASES[name]
    _, Sq, Sk, _, _, _, _, causal, window, tile = case
    q, k, v, d_out = _inputs(case, dtype)
    pos = jnp.arange(Sq)

    def oracle(q, k, v):
        return _blocked_attention(q, k, v, pos, jnp.arange(Sk),
                                  causal=causal, window=window,
                                  q_chunk=8, kv_chunk=8)

    def vjp(q, k, v):
        if tile is None:  # the tile the sequence gets
            return flash_attention(q, k, v, pos, causal=causal,
                                   window=window)
        return _flash(q, k, v, pos, causal, window, tile)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, d_out)]
    want, pull = jax.vjp(oracle, *f32[:3])
    want = (want,) + pull(f32[3])
    got, pull = jax.vjp(vjp, q, k, v)
    got = (got,) + pull(d_out)
    for what, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.dtype == dtype, what
        gap = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        assert gap <= TOL[dtype] * float(jnp.max(jnp.abs(w))), (what, gap)


def test_tiles_follow_the_sequence():
    """One tile up to `TILE` rows; beyond, even tiles of whole 128s."""
    assert [tile_rows(S) for S in (16, 512, 600, 2048, 2100, 32768)] == \
        [16, 512, 384, 512, 512, 512]
    causal = live_tiles(4, 4, 512, causal=True, window=0)
    assert causal.sum() == 10 and not causal[0, 1] and causal[3, 0]
    band = live_tiles(4, 4, 512, causal=True, window=600)
    assert band.tolist() == [[1, 0, 0, 0], [1, 1, 0, 0], [1, 1, 1, 0],
                             [0, 1, 1, 1]]
    assert live_tiles(2, 3, 16, causal=False, window=0).all()


def _sub_jaxprs(eqn):
    """(jaxpr, times it runs per run of ``eqn``) of each inner jaxpr."""
    times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
    for value in eqn.params.values():
        for x in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr, times
            elif isinstance(x, Jaxpr):
                yield x, times


def _score_tiles(jaxpr, t, times=1) -> int:
    """Runs of score-tile products (t query rows by t keys, over batch
    and heads) in ``jaxpr``, loops
    counted by their static trip counts; none may sit in a ``while``,
    whose count the program cannot show."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shape = eqn.outvars[0].aval.shape
            n += times * (len(shape) >= 5 and shape.count(t) == 2)
        for sub, k in _sub_jaxprs(eqn):
            inner = _score_tiles(sub, t, times * k)
            assert not (inner and eqn.primitive.name == "while"), eqn
            n += inner
    return n


def test_train_step_computes_live_tiles_only():
    """The trainer's remat model at a reduced width over 2048 positions
    (4 × 4 tiles of 512): each layer computes the 10 on-or-below-diagonal
    score tiles once in the forward, once in the remat recompute and
    twice in the backward (s and dO·Vᵀ); and every f32 buffer attention
    holds is at most one score tile or one (B, S, H, head_dim) array,
    where autodiff through the scans stacked a tile per key tile."""
    cfg = reduced(get_config("qwen3-0.6b"))
    model = Model(cfg, remat=True, scan_chunk=64, loss_chunk=256)
    engine = Engine(model, registry.make("dc_s3gd", DCS3GDConfig(),
                                         n_workers=1))
    S, t = 2048, tile_rows(2048)
    state = jax.eval_shape(engine.init_state, random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 1, S), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    traced = engine.jit_train_step(state, batch).trace(state, batch)

    n = S // t
    assert n == 4
    live = n * (n + 1) // 2
    assert _score_tiles(traced.jaxpr.jaxpr, t) == 4 * cfg.n_layers * live

    hlo = traced.lower().compile().as_text()
    bound = max(cfg.n_heads * t * t, S * cfg.n_heads * cfg.head_dim)
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for result in _attn_results(hlo)
             for dims in re.findall(r"f32\[([\d,]*)\]", result)]
    assert sizes and max(sizes) <= bound, (max(sizes), bound)


def _attn_results(hlo: str):
    """Result types (a tuple's whole) of the HLO instructions under the
    ``attn`` scope, loop-carried tuples included."""
    for line in hlo.splitlines():
        if re.search(r'op_name="[^"]*/attn/', line) and " = " in line:
            yield re.match(r"(\([^()]*\)|\S+)",
                           line.split(" = ", 1)[1]).group(1)
