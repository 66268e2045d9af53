"""Random weights for the served model, made on the device from a seed.

The benchmark makes the weights, in bfloat16 as they are served, in
one jitted call, laid out as the system under test takes them; the
reference reads the same arrays.  Scales keep activations near unit
variance at every layer, so logits spread about as a trained model's
do and greedy picks are not near-ties:

* ``embed`` N(0, 1) (N(0, 1/hidden) when tied to the head); every
  projection N(0, 1/fan_in); ``lm_head`` N(0, 1/hidden);
* RMSNorm weights 1 + N(0, 0.1^2); biases N(0, 0.1^2).

The kind and fan-in of each leaf come from the family's ``LEAVES``
(``family.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A key from any whole number, wider than 32 bits included."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", "")))


def check_layout(shapes, leaves) -> None:
    """The reference reads the program's tree by the names of the
    family's ``leaves``; refuse a layout it does not know (``lm_head``
    is absent when the embedding is tied)."""
    names = {_leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    if not set(leaves) - {"lm_head"} <= names <= set(leaves):
        raise ValueError(f"unknown parameter layout: {sorted(names)}")


def make_params(shapes, seed: int, leaves, shardings=None):
    """``shapes``: the program's parameter ShapeDtypeStruct tree;
    ``leaves``: the family's leaf name -> (kind, fan-in dim)."""
    check_layout(shapes, leaves)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    # a tied embedding is also the LM head: scale it as the head
    tied = "lm_head" not in {_leaf_name(p) for p, _ in flat}

    def build(key):
        out = []
        for i, (path, sds) in enumerate(flat):
            kind, fan_dim = leaves[_leaf_name(path)]
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, sds.shape, jnp.float32)
            if kind == "embed":
                w = z / math.sqrt(sds.shape[-1]) if tied else z
            elif kind == "norm":
                w = 1.0 + 0.1 * z
            elif kind == "bias":
                w = 0.1 * z
            elif kind == "proj2":      # (..., H, Dh, d), fan-in H*Dh
                w = z / math.sqrt(sds.shape[-3] * sds.shape[-2])
            else:
                w = z / math.sqrt(sds.shape[fan_dim])
            out.append(w.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
