"""Plain RMSprop with TensorFlow's semantics and coupled weight decay: the
optimizer reference that ``reference.optimizer.name == "rmsprop_tf"`` names.

An optimizer reference is a module ``benchmark/reference/optim_<name>.py``
with ``init(params)``, ``update(params, grads, opt, **kw)`` (returns the new
parameters, the new state and the gradients as the optimizer got them) and
``program_first_gradient(opt_state, **kw)``, which works the first gradient
out of the *program's* optimizer state after one step.  ``kw`` are the
configuration's ``reference.optimizer`` numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def init(params):
    return {"nu": jax.tree.map(jnp.ones_like, params),
            "buf": jax.tree.map(jnp.zeros_like, params)}


@functools.partial(jax.jit, static_argnames=("lr", "alpha", "eps", "momentum",
                                             "weight_decay"))
def update(params, grads, opt, *, lr, alpha, eps, momentum, weight_decay):
    """square_avg starts at ones, eps inside the root, lr inside the momentum
    buffer; weight decay is added to the gradient of every leaf of more than
    one dimension."""
    g = jax.tree.map(lambda g_, p: g_ + weight_decay * p if p.ndim > 1
                     else g_, grads, params)
    nu = jax.tree.map(lambda n, g_: n + (1 - alpha) * (g_ * g_ - n),
                      opt["nu"], g)
    buf = jax.tree.map(lambda b, g_, n: momentum * b + lr * g_
                       / jnp.sqrt(n + eps), opt["buf"], g, nu)
    params = jax.tree.map(lambda p, b: p - b, params, buf)
    return params, {"nu": nu, "buf": buf}, g


def program_first_gradient(opt_state, *, lr, eps, **_):
    """After one step buf = lr * g / sqrt(nu + eps), so g follows from the
    program's own state with the configuration's lr and eps: a step size that
    departs from the configuration shows as a wrong gradient.  The state is
    the one node with ``momentum_buffer`` and ``square_avg``, wherever the
    program's wrappers put it."""
    nodes = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "momentum_buffer"))
        if hasattr(s, "momentum_buffer")]
    if len(nodes) != 1:
        raise RuntimeError("no single RMSpropTF state in the optimizer state")
    return jax.tree.map(
        lambda b, n: np.asarray(b, np.float64)
        * np.sqrt(np.asarray(n, np.float64) + eps) / lr,
        nodes[0].momentum_buffer, nodes[0].square_avg)
