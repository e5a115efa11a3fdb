"""Random weights, made from the seed on the device in one jitted call, in
the program's parameter layout (the family's ``layout`` and ``skeleton``)
and serving dtype.

Matrices are normal with fan-in scaling; norm scales are 1 + N(0, 0.1^2),
so a norm whose scale were dropped would show; the (tied) embedding has a
standard deviation of 2/sqrt(d), which gives logits of about unit-2 spread,
so greedy tokens are decided by clear margins more often than by ties.
The program and the reference both take these weights; neither makes its
own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import plugins


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _tree(conf: dict, leaf) -> dict:
    """The family's parameter tree, each leaf ``leaf(i, shape, std)`` with
    ``i`` its rank in the sorted layout."""
    fam = plugins.family(conf)
    tree = fam.skeleton(conf)
    for i, (path, spec) in enumerate(sorted(fam.layout(conf).items())):
        node = tree
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = leaf(i, *spec)
    return tree


def make_weights(conf: dict, seed: int, dtype=jnp.bfloat16):
    """All weights from ``seed``, on the default device, in ``dtype``."""
    def init(key):
        def leaf(i, shape, std):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            return ((1.0 + 0.1 * z) if std == "norm" else z * std
                    ).astype(dtype)
        return _tree(conf, leaf)
    return jax.jit(init)(seed_key(seed))


def weight_shapes(conf: dict, dtype=jnp.bfloat16) -> dict:
    return _tree(conf, lambda i, shape, std: jax.ShapeDtypeStruct(shape,
                                                                  dtype))


def check_layout(conf: dict, model) -> None:
    """Fail unless the program's own parameter tree has this layout."""
    from repro.models import split_tree
    want = jax.eval_shape(lambda: split_tree(model.init(
        jax.random.PRNGKey(0)))[0])
    have = weight_shapes(conf, jnp.dtype(model.cfg.param_dtype))
    sw = jax.tree_util.tree_structure(want)
    sh = jax.tree_util.tree_structure(have)
    if sw != sh:
        raise RuntimeError(f"parameter tree of the program {sw} differs from "
                           f"the benchmark's {sh}")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(have)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise RuntimeError(f"parameter {a} of the program vs {b}")
