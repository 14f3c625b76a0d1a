"""The configuration as run: the program's model for a configuration
file, checked against the file's published widths, and the weights the
benchmark makes for it from the seed.  What either knows of the
architecture comes from the file's family (`harness.family`)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import harness


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file, after
    checking that every published width in the file is what the program
    runs."""
    harness.add_program_path()
    from repro.configs import get_config
    cfg = get_config(conf["program"]["arch"])
    cfg = dataclasses.replace(cfg, **conf["program"]["overrides"])
    want = harness.family(conf).widths(conf)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{conf['name']}: program config differs from "
                         f"the file (program, file): {bad}")
    return cfg


def make_weights(family, template, key):
    """Weights in the layout of ``template`` (a tree of shapes, from
    ``jax.eval_shape`` of the model's init): unit norm scales, zero
    biases, normal(0, 0.02) embedding rows, normal(0, 1/sqrt(fan_in))
    matrices, with the fan-in ``family.fan_in`` gives.  Pure; call
    under ``jax.jit``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for i, (path, leaf) in enumerate(flat):
        names = [getattr(p, "key", "") for p in path]
        name = names[-1]
        shape, dtype = leaf.shape, leaf.dtype
        if name == "scale":
            out.append(jnp.ones(shape, dtype))
        elif name == "bias":
            out.append(jnp.zeros(shape, dtype))
        else:
            std = 0.02 if name == "tok" else \
                family.fan_in(name, shape) ** -0.5
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)
