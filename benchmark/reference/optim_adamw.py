"""Plain AdamW with global-norm clipping: the optimizer reference that
``reference.optimizer.name == "adamw"`` names (the interface is in
``optim_rmsprop_tf.py``).

The gradients are scaled by ``min(1, clip / (norm + 1e-6))`` over the whole
tree, the moments are the usual ones with bias correction, and the decoupled
decay ``weight_decay * p`` joins the update of every leaf of more than one
dimension before the learning rate multiplies it:
``p -= lr * (m_hat / (sqrt(v_hat) + eps) + weight_decay * p)``.

The moments live on the host (numpy) between steps and visit the device one
leaf at a time: beside a 697M-parameter model in float32, its gradients and
a layer's activations, two more trees of that size do not fit a 16 GB chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def init(params):
    zeros = lambda p: np.zeros(p.shape, np.float32)        # noqa: E731
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params), "count": 0}


@jax.jit
def _global_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))


@functools.partial(jax.jit, static_argnames=("decay",), donate_argnums=(0,))
def _leaf(p, g, mu, nu, scale, t, lr, b1, b2, eps, weight_decay, decay):
    g = g * scale
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    upd = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + eps)
    if decay:
        upd = upd + weight_decay * p
    return p - lr * upd, g, mu, nu


def update(params, grads, opt, *, lr, b1, b2, eps, weight_decay, clip):
    """Returns (new parameters, new state, the gradients as the moments got
    them: clipped)."""
    scale = jnp.minimum(1.0, clip / (_global_norm(grads) + 1e-6)) \
        if clip else jnp.float32(1.0)
    t = opt["count"] + 1
    flat_p, tree = jax.tree.flatten(params)
    new_p, g_host, mu_host, nu_host = [], [], [], []
    for p, g, mu, nu in zip(flat_p, jax.tree.leaves(grads),
                            jax.tree.leaves(opt["mu"]),
                            jax.tree.leaves(opt["nu"])):
        p, g, mu, nu = _leaf(p, g, jnp.asarray(mu), jnp.asarray(nu), scale,
                             jnp.float32(t), lr, b1, b2, eps, weight_decay,
                             decay=p.ndim > 1)
        new_p.append(p)                 # stays on the device; the rest goes
        for out, a in ((g_host, g), (mu_host, mu), (nu_host, nu)):
            out.append(np.asarray(a))   # home before the next leaf comes
    new_p, g, mu, nu = (jax.tree.unflatten(tree, x)
                        for x in (new_p, g_host, mu_host, nu_host))
    return new_p, {"mu": mu, "nu": nu, "count": t}, g


def program_first_gradient(opt_state, *, b1, **_):
    """After one step Adam's first moment is (1 - b1) * g, with g the clipped
    gradient: g follows from the program's own state.  The state is the one
    node with ``mu`` and ``nu``, wherever the program's wrappers put it."""
    nodes = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu") and hasattr(s, "nu")]
    if len(nodes) != 1:
        raise RuntimeError("no single Adam state in the optimizer state")
    return jax.tree.map(lambda m: np.asarray(m, np.float32) / (1 - b1),
                        nodes[0].mu)
