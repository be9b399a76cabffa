"""Train state: one pytree carrying everything the train step mutates.

The reference scatters mutable training state across the torch module
(params + BN buffers), the optimizer object, apex AMP, and a deep-copied EMA
module.  Here it is a single immutable pytree — params, batch_stats,
opt_state, EMA — threaded through the jitted step with donated buffers, so
the whole update is in-place on device and checkpointing is one
``to_state_dict``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax

__all__ = ["TrainState", "create_train_state", "set_learning_rate",
           "get_learning_rate"]


class TrainState(flax.struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any
    ema: Optional[Any] = None          # {'params':…, 'batch_stats':…} or None

    @property
    def variables(self):
        return {"params": self.params, "batch_stats": self.batch_stats}

    @property
    def ema_variables(self):
        return self.ema if self.ema is not None else self.variables


def _all_single_device(tree: Any) -> bool:
    from jax.sharding import SingleDeviceSharding
    for x in jax.tree.leaves(tree):
        s = getattr(x, "sharding", None)
        if s is not None and not isinstance(s, SingleDeviceSharding):
            return False
    return True


def create_train_state(variables: Any, tx: optax.GradientTransformation,
                       with_ema: bool = False,
                       donate: bool = True) -> TrainState:
    """Build the initial :class:`TrainState` from init/loaded ``variables``.

    By default ``variables`` is CONSUMED on the single-device path (buffers
    donated into the state — accessing them afterwards raises a
    donated-buffer error); pass ``donate=False`` to keep the input tree
    live (at the cost of one params+stats copy), e.g. for param-norm
    logging or building a second state from the same tree.  Mesh-sharded
    inputs are never donated.
    """
    from ..utils.ema import init_ema

    def build(variables: Any) -> TrainState:
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params),
            ema=init_ema({"params": params, "batch_stats": batch_stats})
            if with_ema else None)

    # Single-device inputs run as ONE jitted program: eager ``tx.init`` plus
    # the EMA clone dispatch O(param-leaves) ops, one device round trip
    # each.  ``variables`` is donated — the state takes ownership
    # of the buffers like the eager path's aliasing did; without donation a
    # full params+stats copy stays live as long as the caller's reference
    # (flagship-scale models care).  Mesh-sharded inputs
    # (tp/fsdp/multi-process) stay eager: ``zeros_like`` inherits each
    # param's sharding exactly, the invariant the checkpoint-resume
    # re-layout and the FSDP opt-state memory footprint both rely on,
    # whereas jit output sharding is GSPMD's choice (observed: replicated
    # opt_state on a (data, model) mesh).
    if _all_single_device(variables):
        return jax.jit(build, donate_argnums=0 if donate else ())(variables)
    return build(variables)


def _find_hyperparams(opt_state):
    """Locate the (path, InjectHyperparamsState) nodes holding hyperparams."""
    return [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "hyperparams"))
        if hasattr(s, "hyperparams")]


def set_learning_rate(state: TrainState, lr: float) -> TrainState:
    """Rewrite the injected learning rate (the reference's
    ``param_group['lr']`` rewrite, scheduler.py:81-85) without recompiling."""
    def rewrite(node):
        if hasattr(node, "hyperparams") and "learning_rate" in node.hyperparams:
            hp = dict(node.hyperparams)
            old = hp["learning_rate"]
            new = jnp.asarray(lr, jnp.asarray(old).dtype)
            if isinstance(old, jax.Array):
                # keep the leaf's placement: a jit signature includes the
                # mesh its arguments live on, so an unplaced scalar here
                # recompiles the whole train step on the next call
                new = jax.device_put(new, old.sharding)
            hp["learning_rate"] = new
            return node._replace(hyperparams=hp)
        return node
    opt_state = jax.tree.map(
        rewrite, state.opt_state,
        is_leaf=lambda x: hasattr(x, "hyperparams"))
    return state.replace(opt_state=opt_state)


def get_learning_rate(state: TrainState) -> Optional[float]:
    nodes = _find_hyperparams(state.opt_state)
    for n in nodes:
        if "learning_rate" in n.hyperparams:
            return float(n.hyperparams["learning_rate"])
    return None
