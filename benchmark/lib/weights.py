"""Seeded weights, made on the device in one jitted call.

A fresh EfficientNet init is a poor subject for a comparison: in eval mode
its logits are ~1e-14 (every score 0.5).  These weights are conditioned
instead: fan-in scaled kernels (activations keep their scale), batch-norm
scales and biases spread around 1 and 0, a classifier wide enough that the
logits move with the input, and the last batch-norm of every residual branch
scaled down (``gains``, which the configuration's plain reference names),
as trained residual networks have it: with unit branches 55 blocks double
the signal's variance block by block, gradients explode toward the stem and
rounding is amplified into chaos.  The reference and the program get the
same tree.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    lo, hi = seed % (2 ** 31 - 1), seed // (2 ** 31 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _leaf(key, path: Tuple[str, ...], shape):
    name = path[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel" and len(shape) == 4:
        fan_in = shape[0] * shape[1] * shape[2]
        return n * math.sqrt(2.0 / fan_in)
    if name == "kernel":                       # classifier
        return n * (4.0 / math.sqrt(shape[0]))
    if name == "scale":
        return 1.0 + 0.1 * n
    if name == "var":
        return 1.0 + 0.2 * jax.random.uniform(key, shape, jnp.float32)
    return 0.1 * n                             # biases, running means


def _flatten(tree, prefix=()):
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(_flatten(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), tuple(v)))
    return out


def _unflatten(items):
    root: Dict[str, Any] = {}
    for path, v in items:
        d = root
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return root


def variables_for(config: Dict[str, Any], seed: int):
    """(variables, spec) of one configuration: the shapes and the residual
    gains come from its plain reference."""
    from benchmark import reference
    R = reference.model(config)
    spec = R.model_spec(config)
    gains = R.residual_gains(spec) if hasattr(R, "residual_gains") else None
    return make_variables(seed, *R.param_shapes(spec), gains=gains,
                          leaf=getattr(R, "init_leaf", _leaf)), spec


def make_variables(seed: int, param_shapes, stat_shapes, gains=None,
                   leaf=None):
    """{"params": ..., "batch_stats": ...} of float32 device arrays.
    ``gains`` maps a parameter's path (a tuple of names) to a factor;
    ``leaf(key, path, shape)`` draws one leaf (default: ``_leaf``)."""
    leaf = leaf or _leaf
    gains = {("params",) + tuple(k): v for k, v in (gains or {}).items()}
    flat = [(("params",) + p, s) for p, s in _flatten(param_shapes)] + \
        [(("batch_stats",) + p, s) for p, s in _flatten(stat_shapes)]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return [leaf(k, p, s) * gains.get(p, 1.0)
                for k, (p, s) in zip(keys, flat)]

    leaves = make(seed_key(seed))
    return _unflatten([(p, v) for (p, _), v in zip(flat, leaves)])
