"""Jitted train / eval steps — ONE GSPMD program from 1 chip to a pod.

The reference's per-batch hot loop (``/root/reference/dfd/runners/train.py:
594-700``: forward → loss → accuracy → metric allreduce → backward with DDP
grad allreduce → optimizer step → full device sync → EMA update) becomes ONE
compiled function per step.  XLA fuses the whole thing; there is no per-step
host sync (the runner only blocks on the scalars it logs) and no separate
allreduce launches — gradient reduction is part of the compiled program
riding ICI.

Since ISSUE 12 the step is a plain ``jax.jit`` with ``NamedSharding``
annotations over the unified ``('batch', 'model')`` mesh
(parallel/mesh.py:make_train_mesh) — the shard_map-era dispatch is gone.
``in_shardings``/``out_shardings`` come from the sharding-rule table
(parallel/sharding.py:train_state_shardings) when the caller provides it;
``donate_argnums=(0,)`` keeps the state update in-place on device.  The
same program lowers for an abstract v5e-256 topology exactly as it does
for one chip (tools/bench_multichip.py, tests/test_mesh_aot.py).

Two BN strategies (SURVEY.md §7 hard part #2):

* ``bn_mode='global'`` — BN statistics are computed over the *global*
  batch (XLA inserts the per-layer reductions): semantically apex SyncBN
  (train.py:388-400), always on.
* ``bn_mode='local'`` (default, matches the reference default) — BN
  normalizes each contiguous batch group (one per data-parallel mesh
  slot) with that group's *own* statistics.  This used to be a bespoke
  ``shard_map`` body; it is now a ``with_sharding_constraint`` over the
  batch axis inside the BN layer itself (ops/norm.py:local_stats_scope),
  so there are still no per-layer collectives in the forward — XLA keeps
  every group's statistics local to its mesh slot — and the running stats
  are updated with the group mean (what the old per-device update + one
  ``lax.pmean`` produced).

Both modes produce bit-identical optimizer updates given the same gradients;
they differ only in BN normalization statistics (per-group vs global).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..losses import cross_entropy
from ..utils.ema import update_ema
from ..utils.metrics import accuracy
from .state import TrainState

__all__ = ["make_train_step", "make_eval_step"]


def _clip_grads(grads, clip_grad: Optional[float]):
    if not clip_grad:
        return grads
    gnorm = optax.global_norm(grads)
    scale = jnp.minimum(1.0, clip_grad / (gnorm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads)


# what a sequence model's layers may sow for the step's metrics (and, for
# ``aux_loss``, its objective): train/trainer.py drains each by this name
_SOWN = ("moe_counts", "dsa_counts", "aux_loss")


def make_train_step(model, tx: optax.GradientTransformation,
                    loss_fn: Callable = cross_entropy,
                    mesh: Optional[Mesh] = None, axis: Optional[str] = None,
                    bn_mode: str = "local", ema_decay: float = 0.0,
                    clip_grad: Optional[float] = None,
                    grad_accum: int = 1,
                    donate: bool = True,
                    nonfinite_guard: bool = False,
                    state_shardings: Optional[Any] = None) -> Callable:
    """Build ``train_step(state, x, y, rng) -> (state, metrics)``.

    ``x`` is the (globally) batch-sharded input: an NHWC batch with ``y``
    int labels or soft targets, or, for a model of the sequence task (one
    with ``sequence_task``, e.g. models/phi4flash.py), ``(batch, L)`` token
    ids with ``y`` the ids shifted by one (negative = no target).  There the
    model's ``sequence_loss`` makes the chunked next-token loss
    (losses.py:next_token_loss) and ``loss_fn`` is not called.  ``metrics``
    = {'loss', 'prec1'} global-batch scalars (replaces the per-step
    ``reduce_tensor`` calls, train.py:625-627); for the sequence task
    'prec1' is the token accuracy.  ``batch_stats`` may be an empty tree.

    ``mesh`` + ``axis`` (default: the mesh's own data axis) select the
    unified GSPMD path: the batch is constrained to ``P(axis)``, local-BN
    statistics group over the mesh's batch extent, and — when
    ``state_shardings`` (the parallel/sharding.py rule table) is given —
    the jit carries explicit ``in_shardings``/``out_shardings`` so the
    compiled executable's I/O layout is pinned, CI-assertable and
    donation-aliased.  Callers passing ``state_shardings`` must place the
    state accordingly first (``place_train_state``).

    ``grad_accum > 1`` splits the batch into that many microbatches inside
    the compiled step (a ``lax.scan``): gradients are averaged across
    microbatches before ONE optimizer update, so effective batch = what the
    reference reaches with more GPUs (no reference analog — the standard
    TPU lever for the flagship 600²×12 config on few chips).  BN stats
    thread through the scan (each microbatch updates the running stats,
    like sequential smaller steps would).

    ``nonfinite_guard`` adds a device-side all-finite check on the loss and
    the global grad-norm: a bad step SELECTS the previous state (params,
    BN stats, optimizer moments, EMA, step counter all unchanged — a skip,
    not a zero-grad update, since NaN grads would still poison Adam/RMSProp
    moments through ``tx.update``) and reports ``metrics['nonfinite']`` = 1.
    One scalar flag rides the existing metrics fetch — no extra host syncs.
    The reference *meter* dropped NaN losses while the poisoned update was
    applied anyway (the exact failure this guard closes).
    """
    assert bn_mode in ("local", "global"), bn_mode
    assert grad_accum >= 1
    sequence_task = bool(getattr(model, "sequence_task", False))

    def forward_backward_one(params, batch_stats, x, y, rng):
        """(loss, grads, new batch stats, prec1, sown).  The last holds, by
        collection, what the model's layers sowed, each summed over the
        layers: ``moe_counts`` (ops/moe.py:routing_counts), ``dsa_counts``
        (ops/sparse_attention.py's census) and ``aux_loss`` (a loss of the
        model's own, e.g. the sparse-attention indexer's, which the
        gradient minimises beside ``loss``); None for a model that sows
        none."""
        if sequence_task:
            def seq_lossf(p):
                (loss, acc), mut = model.apply(
                    {"params": p, "batch_stats": batch_stats}, x, y,
                    training=True, mutable=["batch_stats", *_SOWN],
                    rngs={"dropout": rng}, method="sequence_loss")
                sown = {}
                for name in _SOWN:
                    leaves = jax.tree.leaves(mut.get(name, {}))
                    if leaves:
                        sown[name] = sum(leaves)
                if "aux_loss" not in sown:
                    return loss, (acc, mut.get("batch_stats", batch_stats),
                                  sown or None, None)
                # the objective is the sum; the metric stays the
                # next-token loss
                return loss + sown["aux_loss"], (
                    acc, mut.get("batch_stats", batch_stats), sown, loss)
            (loss, (acc, new_stats, sown, lm_loss)), grads = \
                jax.value_and_grad(seq_lossf, has_aux=True)(params)
            return (loss if lm_loss is None else lm_loss), grads, \
                new_stats, acc, sown

        def lossf(p):
            variables = {"params": p, "batch_stats": batch_stats}
            out = model.apply(variables, x, training=True,
                              mutable=["batch_stats"], rngs={"dropout": rng})
            logits, mut = out
            return loss_fn(logits, y), (logits, mut["batch_stats"])
        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            lossf, has_aux=True)(params)
        prec1 = accuracy(logits, y)
        return loss, grads, new_stats, prec1, None

    def forward_backward(params, batch_stats, x, y, rng):
        if grad_accum == 1:
            return forward_backward_one(params, batch_stats, x, y, rng)
        b = x.shape[0]
        assert b % grad_accum == 0, (b, grad_accum)
        # strided split (row j of microbatch i = global row j*A + i): under
        # a data-sharded batch each device keeps 1/A of ITS OWN rows per
        # microbatch, so no per-iteration cross-device reshuffle is needed
        # (a contiguous split would put microbatch 0 on the first dp/A
        # devices only); gradient averaging is partition-invariant
        xm = jnp.moveaxis(
            x.reshape((b // grad_accum, grad_accum) + x.shape[1:]), 1, 0)
        ym = jnp.moveaxis(
            y.reshape((b // grad_accum, grad_accum) + y.shape[1:]), 1, 0)

        def micro(carry, inp):
            stats, gsum, lsum, psum_ = carry
            xi, yi, i = inp
            loss, grads, stats, prec1, counts = forward_backward_one(
                params, stats, xi, yi, jax.random.fold_in(rng, i))
            gsum = jax.tree.map(jnp.add, gsum, grads)
            return (stats, gsum, lsum + loss, psum_ + prec1), counts

        g0 = jax.tree.map(jnp.zeros_like, params)
        z = jnp.zeros((), jnp.float32)
        (new_stats, gsum, lsum, psum_), counts = jax.lax.scan(
            micro, (batch_stats, g0, z, z), (xm, ym, jnp.arange(grad_accum)))
        inv = 1.0 / grad_accum
        grads = jax.tree.map(lambda g: g * inv, gsum)
        if counts is not None:         # counts add over the microbatches,
            counts = {k: jnp.sum(v, axis=0) for k, v in counts.items()}
            if "aux_loss" in counts:       # ... and a loss is averaged
                counts["aux_loss"] = counts["aux_loss"] * inv
        return lsum * inv, grads, new_stats, psum_ * inv, counts

    def apply_updates(state: TrainState, grads, new_stats, loss, prec1,
                      counts=None):
        grads = _clip_grads(grads, clip_grad)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        ema = state.ema
        if ema is not None:
            ema = update_ema(ema, {"params": params,
                                   "batch_stats": new_stats}, ema_decay)
        new_state = state.replace(step=state.step + 1, params=params,
                                  batch_stats=new_stats, opt_state=opt_state,
                                  ema=ema)
        metrics = {"loss": loss, "prec1": prec1}
        if counts is not None:
            # made on the device, fetched with the loss at the drain
            metrics.update(counts)
        if nonfinite_guard:
            # the clipped-grad norm: clipping rescales by a finite factor
            # (or NaN-propagates), so finiteness is unchanged vs raw grads
            # and the norm is reused-shape-wise from the clip when present
            gnorm = optax.global_norm(grads)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            # scalar-pred select per leaf: cheap (one fused select each)
            # and total — moments, EMA, BN stats and the step counter all
            # roll back together, leaving the state exactly pre-step
            new_state = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_state, state)
            metrics["nonfinite"] = (~ok).astype(jnp.float32)
            metrics["gnorm"] = gnorm
        return new_state, metrics

    if mesh is None:
        def step(state: TrainState, x, y, rng):
            loss, grads, new_stats, prec1, counts = forward_backward(
                state.params, state.batch_stats, x, y, rng)
            return apply_updates(state, grads, new_stats, loss, prec1,
                                 counts)
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    # ---- unified GSPMD path: plain jit over the mesh -------------------
    from ..parallel.mesh import data_axis_name
    axis = axis or data_axis_name(mesh)
    dp = int(mesh.shape[axis])
    batch_sh = NamedSharding(mesh, P(axis))
    if bn_mode == "local" and dp > 1:
        from ..ops.norm import local_stats_scope

        def bn_scope():
            return local_stats_scope(dp, batch_sh)
    else:
        bn_scope = contextlib.nullcontext
    # how many devices compile this step: a depthwise stage asks it when it
    # decides who computes its filter gradient (ops/conv.py:dw_grad_impl)
    from ..ops.conv import dw_grad_scope
    n_dev = int(mesh.size)

    def step(state: TrainState, x, y, rng):
        # pin the batch to the batch axis: with inferred in_shardings this
        # is what keeps GSPMD from gathering the batch onto one device; the
        # BN grouping constraint inside the scope does the rest of the
        # local-stats layout
        x = lax.with_sharding_constraint(x, batch_sh)
        y = lax.with_sharding_constraint(y, batch_sh)
        # both entered at TRACE time (ops/norm.py's idiom)
        with bn_scope(), dw_grad_scope(n_dev):
            loss, grads, new_stats, prec1, counts = forward_backward(
                state.params, state.batch_stats, x, y, rng)
        return apply_updates(state, grads, new_stats, loss, prec1, counts)

    jit_kwargs: Dict[str, Any] = {}
    if state_shardings is not None:
        rep = NamedSharding(mesh, P())
        jit_kwargs["in_shardings"] = (state_shardings, batch_sh, batch_sh,
                                      rep)
        # metrics is a dict of global scalars — a single replicated
        # sharding is a valid prefix pytree for it
        jit_kwargs["out_shardings"] = (state_shardings, rep)
    return jax.jit(step, donate_argnums=(0,) if donate else (),
                   **jit_kwargs)


def make_eval_step(model, loss_fn: Callable = cross_entropy,
                   use_ema: bool = False) -> Callable:
    """Build ``eval_step(state, x, y, valid) -> metrics``.

    ``valid`` masks padded duplicates from the ordered sharded sampler so
    validation is exact (the reference accepted the duplicate error,
    loader.py:794-796).  Returns {'loss', 'prec1', 'count'} where loss/prec1
    are means over valid samples in this batch (reference validate,
    train.py:703-767).  A model of the sequence task reports its
    next-token loss and token accuracy and no logits.
    """

    @jax.jit
    def step(state: TrainState, x, y,
             valid: Optional[jnp.ndarray] = None) -> Dict[str, jnp.ndarray]:
        variables = state.ema_variables if use_ema else state.variables
        if getattr(model, "sequence_task", False):
            loss, acc = model.apply(variables, x, y, training=False,
                                    weight=valid, method="sequence_loss")
            return {"loss": loss, "prec1": acc,
                    "count": (valid.sum() if valid is not None
                              else jnp.asarray(x.shape[0]))}
        logits = model.apply(variables, x, training=False)
        loss = loss_fn(logits, y, weight=valid)
        prec1 = accuracy(logits, y, weight=valid)
        count = (valid.sum() if valid is not None
                 else jnp.asarray(x.shape[0]))
        return {"loss": loss, "prec1": prec1, "count": count,
                "logits": logits}

    return step
