"""Fused ring attention (Pallas blocks over a ppermute ring) vs dense.

Runs on the 8-virtual-device CPU mesh; the Pallas kernels execute under the
interpreter, the ring schedule (ppermute of K/V forward, of dK/dV backward)
is the real compiled collective program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepfake_detection_tpu.ops.flash_attention import flash_attention
from deepfake_detection_tpu.parallel.ring_attention import (
    full_attention, ring_flash_attention, ring_self_attention)


def _qkv(b, l, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, l, h, d)) for k in ks)


@pytest.fixture()
def sp_mesh(devices):
    return Mesh(np.asarray(devices[:4]), ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(sp_mesh, causal):
    # L_local = 96: exercises both seq padding (96→128) and D padding
    q, k, v = _qkv(2, 4 * 96, 2, 32)
    out = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, sp_mesh, seq_axis="sp", causal=causal,
        impl="ring_flash"))(q, k, v)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_grads_match_dense(sp_mesh, causal):
    q, k, v = _qkv(1, 4 * 64, 2, 32, seed=1)

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(
            q, k, v, sp_mesh, seq_axis="sp", causal=causal,
            impl="ring_flash") ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd, name in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_ring_flash_agrees_with_xla_ring(sp_mesh):
    # the two ring implementations are independent programs; agreement is a
    # strong cross-check of both
    q, k, v = _qkv(2, 4 * 128, 2, 64, seed=2)
    o1 = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, sp_mesh, seq_axis="sp", impl="ring"))(q, k, v)
    o2 = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, sp_mesh, seq_axis="sp", impl="ring_flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=2e-5)


def _ring_flash(mesh, **kw):
    """ring_flash_attention over ``mesh``'s "sp" axis with the block sizes
    ``kw`` (the shard_map wrapper passes none)."""
    spec = P(None, "sp", None, None)
    return jax.jit(shard_map(
        functools.partial(ring_flash_attention, axis_name="sp", **kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))


@pytest.mark.parametrize("shards", [1, 4])
def test_ring_flash_at_key_tiles_of_256(devices, shards):
    """The ring path's forward kernel takes traced offsets; at key tiles of
    256 its lane-replicated row statistics are repeated across the tile.
    Over four shards it matches dense; on one, where the offsets are 0 and
    the merge of one block is exact, it is the standalone op's bits (its
    ``lse`` against the standalone call's: tests/test_flash_tiles.py,
    traced against Python-int offsets)."""
    mesh = Mesh(np.asarray(devices[:shards]), ("sp",))
    q, k, v = _qkv(1, shards * 300, 2, 32, seed=3)
    out = _ring_flash(mesh, causal=True, block_k=256)(q, k, v)
    if shards == 1:
        want = flash_attention(q, k, v, causal=True, block_k=256)
        assert np.array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        full_attention(q, k, v, causal=True)), atol=2e-5, rtol=2e-5)
