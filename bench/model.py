"""The configuration as run: the program's model for a configuration
file, checked against the file's published widths, and the weights the
benchmark makes for it from the seed."""
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
    ref = conf["reference"]
    want = {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "resolved_head_dim": conf.get(
            "head_dim", conf["hidden_size"] // conf["num_attention_heads"]),
        "rope_theta": float(conf["rope_theta"]),
        "norm": ref["norm"],
        "qk_norm": ref["qk_norm"],
        "norm_eps": ref["norm_eps"],
        "compute_dtype": ref["compute_dtype"],
        "tie_embeddings": conf["tie_word_embeddings"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{conf['name']}: program config differs from "
                         f"the file (program, file): {bad}")
    return cfg


def reference_config(conf: dict) -> dict:
    """What `reference.py` needs to know of a configuration."""
    ref = conf["reference"]
    return {"norm": ref["norm"], "qk_norm": ref["qk_norm"],
            "norm_eps": float(ref["norm_eps"]),
            "rope_theta": float(conf["rope_theta"]),
            "rotary_dims": int(ref["rotary_dims"])}


def _fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


def make_weights(template, key):
    """Weights in the layout of ``template`` (a tree of shapes, from
    ``jax.eval_shape`` of the model's init): unit norm scales, zero
    biases, normal(0, 0.02) embedding rows, normal(0, 1/sqrt(fan_in))
    matrices.  Pure; call under ``jax.jit``."""
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
            std = 0.02 if name == "tok" else _fan_in(name, shape) ** -0.5
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)
