"""Pipeline parallelism (GPipe schedule) over a ``stage`` mesh axis.

The reference has no PP (SURVEY.md §2.7).  This is the TPU-native
formulation: no scheduler process, no send/recv framework — the schedule is
a ``lax.scan`` whose body every stage executes simultaneously (SPMD), with
activations hopping stage→stage+1 through ``lax.ppermute`` over ICI.  The
*backward* pipeline is not written at all: ``ppermute`` is linear and its
autodiff transpose is the reverse permute, so differentiating the scan
yields the reverse-order pipeline schedule automatically.

Layout: a depth-``D`` tower of homogeneous blocks is split into ``S``
stages of ``D/S`` blocks.  Per-block param trees are stacked on a leading
dim and sharded ``P('stage')`` — each device materialises only its own
stage's blocks (1/S of the tower's params), applying them with an inner
``lax.scan``.

Schedule (M microbatches, steps t = 0..S+M-2): at step t stage ``s`` works
on microbatch ``t - s`` when that index is valid.  SPMD executes every
stage every step (the classic (S-1)/(S-1+M) bubble shows up as wasted
FLOPs, amortised away by larger M); validity is a ``jnp.where`` select so
the program stays uniform across devices.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["gpipe_apply", "gpipe_transformer_tower",
           "pipeline_sharding", "stack_block_params"]


def stack_block_params(block_params: list) -> Any:
    """Stack per-block param trees (blocks_0..blocks_{D-1}) on a leading
    dim: list of D trees → one tree with (D, ...) leaves."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *block_params)


def pipeline_sharding(stacked: Any, mesh: Mesh, axis: str = "stage") -> Any:
    """NamedShardings putting the leading (stage-major) dim on ``axis``."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda _: sh, stacked)


def gpipe_apply(block_apply: Callable, stacked_params: Any, x: jnp.ndarray,
                axis_name: str, num_microbatches: int) -> jnp.ndarray:
    """Run the pipelined tower over ``x``.  Call inside ``shard_map``.

    ``block_apply(params_i, x) -> x`` applies ONE block.  ``stacked_params``
    is the local stage's slice: (D/S, ...) leaves.  ``x`` is the full local
    batch (B, ...); it is split into ``num_microbatches`` equal chunks.
    Output is valid on every stage (the last stage's results are summed
    across the axis — all other stages contribute zeros).
    """
    s_count = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_count = num_microbatches
    b = x.shape[0]
    assert b % m_count == 0, f"batch {b} % microbatches {m_count} != 0"
    mb = b // m_count
    micro = x.reshape((m_count, mb) + x.shape[1:])

    def apply_stage(params, h):
        def body(h, p_i):
            return block_apply(p_i, h), None
        h, _ = lax.scan(body, h, params)
        return h

    fwd_perm = [(i, i + 1) for i in range(s_count - 1)]

    def step(carry, t):
        buf, outs = carry
        m = t - idx                       # microbatch this stage works on
        valid = jnp.logical_and(m >= 0, m < m_count)
        y = apply_stage(stacked_params, buf)
        y = jnp.where(valid, y, buf)
        # last stage banks its finished microbatch (select keeps the
        # program uniform across stages — no divergent control flow)
        outs_new = lax.dynamic_update_index_in_dim(
            outs, y, jnp.clip(m, 0, m_count - 1), 0)
        take = jnp.logical_and(valid, idx == s_count - 1)
        outs = jnp.where(take, outs_new, outs)
        # hop forward; stage 0 receives zeros from the (absent) source
        nxt = lax.ppermute(y, axis_name, fwd_perm)
        # stage 0 injects the next microbatch instead
        inj = lax.dynamic_index_in_dim(
            micro, jnp.clip(t + 1, 0, m_count - 1), 0, keepdims=False)
        buf = jnp.where(idx == 0, inj, nxt)
        return (buf, outs), None

    # stage 0 starts on microbatch 0; other stages start on zeros (the
    # where() against the varying stage index already marks buf varying);
    # outs starts as plain zeros and must be marked varying for the scan
    # carry type to be stable
    buf0 = jnp.where(idx == 0, micro[0], jnp.zeros_like(micro[0]))
    outs0 = lax.pcast(jnp.zeros_like(micro), axis_name, to="varying")
    (_, outs), _ = lax.scan(step, (buf0, outs0),
                            jnp.arange(s_count + m_count - 1))
    # only the last stage holds real outputs; psum broadcasts them
    outs = lax.psum(jnp.where(idx == s_count - 1, outs, 0.0), axis_name)
    return outs.reshape((b,) + x.shape[1:])


def gpipe_transformer_tower(mesh: Mesh, block_apply: Callable,
                            stacked_params: Any, x: jnp.ndarray,
                            num_microbatches: int,
                            axis: str = "stage") -> jnp.ndarray:
    """shard_map wrapper: ``stacked_params`` leaves are (D, ...) global
    arrays sharded over ``axis``; ``x`` replicated."""
    fn = functools.partial(gpipe_apply, block_apply,
                           axis_name=axis, num_microbatches=num_microbatches)
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stacked_params), P()),
        out_specs=P())(stacked_params, x)
