"""ops/flash_attention.py's cells by class: ``tile_visible`` against a
brute-force mask, ``tile_census``, the clamped index maps (an outside cell
names the block of its row's nearest visited cell; a visited cell the block
it named at the parent commit; the kernels under them give the plain maps'
bits), shapes of several tiles a side bit for bit equal to the parent
commit's kernels (tests/fixtures/flash_attention_tiles_parent.npz, written
by this file run as a script against a checkout of the parent), and the
fused backward against the dK/dV and dQ pair: where ``fused_bwd`` sends a
shape, and the same bits either way."""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfake_detection_tpu.ops.flash_attention import flash_attention

FA = importlib.import_module("deepfake_detection_tpu.ops.flash_attention")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flash_attention_tiles_parent.npz")


# ---- the class of a cell ----------------------------------------------------

def _brute(i, j, bq, bk, seq_len, causal, window, q_off, kv_off):
    """Is any pair of tile (i, j) visible?  Pair by pair."""
    t = q_off + i * bq + np.arange(bq)[:, None]
    key = j * bk + np.arange(bk)[None, :]
    s = kv_off + key
    seen = np.broadcast_to(key < seq_len, (bq, bk)).copy()
    if causal:
        seen &= s <= t
        if window is not None:
            seen &= t - s < window
    return bool(seen.any())


# (L, block_q, block_k, causal, window, q_off, kv_off)
CLASS_CASES = [
    pytest.param(512, 128, 128, True, None, 0, 0, id="causal"),
    pytest.param(300, 128, 128, True, None, 0, 0, id="causal-L-ragged"),
    pytest.param(600, 256, 128, True, None, 0, 0, id="causal-bq>bk"),
    pytest.param(600, 128, 256, True, None, 0, 0, id="causal-bq<bk"),
    pytest.param(300, 128, 128, False, None, 0, 0, id="plain-L-ragged"),
    pytest.param(530, 128, 128, True, 300, 0, 0, id="window>2blocks"),
    pytest.param(530, 128, 128, True, 40, 0, 0, id="window<block"),
    pytest.param(530, 128, 128, True, 1, 0, 0, id="window-of-one"),
    pytest.param(600, 128, 256, True, 10, 0, 0, id="window<block-bq<bk"),
    pytest.param(600, 256, 128, True, 200, 0, 0, id="window-bq>bk"),
    pytest.param(1024, 256, 256, True, 512, 0, 0, id="window=2blocks"),
    pytest.param(256, 128, 128, True, None, 512, 256, id="ring-q-after-kv"),
    pytest.param(256, 128, 128, True, None, 256, 256, id="ring-diagonal"),
    pytest.param(256, 128, 128, True, None, 0, 256, id="ring-q-before-kv"),
    pytest.param(200, 128, 128, True, None, 192, 96, id="ring-ragged-shards"),
    pytest.param(200, 128, 128, True, 150, 192, 96, id="ring-window"),
]


@pytest.mark.parametrize("l,bq,bk,causal,window,q_off,kv_off", CLASS_CASES)
def test_class_of_every_tile_equals_the_brute_force_mask(
        l, bq, bk, causal, window, q_off, kv_off):
    """outside <=> no visible pair, on Python ints and, vectorised, on
    arrays (the kernels pass traced scalars)."""
    nq, nk = -(-l // bq) + 1, -(-l // bk) + 1      # one tile past the end
    args = (bq, bk, l, causal, window, q_off, kv_off)
    want = np.array([[_brute(i, j, *args) for j in range(nk)]
                     for i in range(nq)])
    got = np.array([[FA.tile_visible(i, j, *args) for j in range(nk)]
                    for i in range(nq)])
    assert (got == want).all()
    i, j = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    vis = FA.tile_visible(jnp.asarray(i), jnp.asarray(j), *args)
    assert (np.asarray(vis) == want).all()


def test_query_tiles_past_the_last_are_outside():
    assert FA.tile_visible(3, 2, 128, 128, 300, True, 200)
    assert not FA.tile_visible(3, 2, 128, 128, 300, True, 200, q_tiles=3)


# ---- the census -------------------------------------------------------------

CENSUS_CASES = [
    # the cell's full and cross layers and its window layer, at the blocks
    # they had at the parent and at the ones they have now
    pytest.param((16384, 512, 512, True, None), (1024, 496),
                 id="16k-causal-512"),
    pytest.param((16384, 1024, 1024, True, None), (256, 120),
                 id="16k-causal-1024"),
    pytest.param((16384, 256, 256, True, 512), (256, 67),
                 id="16k-window512-256"),
    pytest.param((16384, 512, 512, True, 512), (96, 33),
                 id="16k-window512-512"),
    pytest.param((197, 128, 128, False, None), (4, 0), id="vit-197"),
    pytest.param((150, 512, 512, True, None), (1, 0), id="one-tile"),
]


@pytest.mark.parametrize("args,want", CENSUS_CASES)
def test_census_counts_the_classes_of_each_kernels_grid(args, want):
    census = FA.tile_census(*args)
    assert sorted(census) == ["bwd", "dkv", "dq", "fwd"]
    for kernel, c in census.items():
        assert c["outside"] + c["visited"] == c["cells"], kernel
        assert (c["cells"], c["outside"]) == want, kernel


def test_census_of_unequal_blocks_gives_each_grid_its_own_count():
    c = FA.tile_census(600, 128, 256, True, 10)
    assert c["fwd"] == c["dq"] == {"cells": 10, "outside": 3, "visited": 7}
    assert c["dkv"] == c["bwd"] == {"cells": 12, "outside": 5, "visited": 7}


@pytest.mark.parametrize("args", [
    (16384, 64, 1024, 1024, True, None), (16384, 64, 512, 512, True, 512),
    (600, 16, 128, 256, True, 10), (197, 64, 128, 128, False, None)],
    ids=["16k-causal", "16k-window", "unequal-blocks", "vit-197"])
def test_a_train_steps_tiles_are_the_forwards_and_one_backward_grids(
        args, monkeypatch):
    """Where the backward is fused a step visits the forward's cells and the
    dK/dV grid's once; where the predicate refuses the row, the pair's."""
    l, head_dim, *rest = args
    c = FA.tile_census(l, *rest)
    assert FA.train_tiles_visited(*args) == \
        c["fwd"]["visited"] + c["bwd"]["visited"]
    monkeypatch.setattr(FA, "_DQ_ROW_BYTES", 128 * 128 * 4 - 1)
    assert FA.train_tiles_visited(*args) == sum(
        c[kernel]["visited"] for kernel in ("fwd", "dkv", "dq"))


# ---- the index maps ---------------------------------------------------------

# (L, block_q, block_k, window, q_off - kv_off)
MAP_CASES = [
    pytest.param(2048, 128, 128, None, 0, id="causal"),
    pytest.param(300, 128, 128, None, 0, id="causal-L-ragged"),
    pytest.param(1100, 256, 128, None, 0, id="causal-bq>bk"),
    pytest.param(1100, 128, 256, None, 0, id="causal-bq<bk"),
    pytest.param(2048, 256, 256, 512, 0, id="window=2blocks"),
    pytest.param(1100, 128, 128, 300, 0, id="window>2blocks"),
    pytest.param(1100, 128, 256, 10, 0, id="window<block-bq<bk"),
    pytest.param(1100, 256, 128, 200, 0, id="window-bq>bk"),
    pytest.param(530, 128, 128, 1, 0, id="window-of-one"),
    pytest.param(512, 128, 128, None, 256, id="static-q-after-kv"),
    pytest.param(512, 128, 128, None, -200, id="static-q-before-kv"),
]


def _nearest_visited(named, visible):
    """For every row of a grid, each cell's block as its nearest visited
    cell names it (rows with no visited cell: as the cell itself does)."""
    want = named.copy()
    for r in range(named.shape[0]):
        seen = np.flatnonzero(visible[r])
        if seen.size:
            nearest = seen[np.abs(seen[None, :] - np.arange(
                named.shape[1])[:, None]).argmin(axis=1)]
            want[r] = named[r, nearest]
    return want


@pytest.mark.parametrize("l,bq,bk,window,off", MAP_CASES)
def test_maps_repeat_a_block_outside_and_keep_the_parents_block_inside(
        l, bq, bk, window, off):
    """No copy is issued for an outside cell (Pallas copies only when the
    block index changes), and a visited cell reads what it read before."""
    bq, bk, lpq, lpk = FA._blocks(l, bq, bk)
    nq, nk = lpq // bq, lpk // bk
    # forward / dQ: (q block, k tile)
    nkt, tile = FA._k_tile_map(bq, bk, window, nk, True, l, off)
    assert nkt == FA._k_tile_map(bq, bk, window, nk)[0]
    i, j = np.meshgrid(np.arange(nq), np.arange(nkt), indexing="ij")
    jt = np.asarray(FA._k_tile(i, j, bq, bk, window))
    visible = np.asarray(FA.tile_visible(i, jt, bq, bk, l, True, window,
                                         off, 0))
    assert visible.any()
    named = np.asarray(tile(i, j))
    parent = jt if window is None else np.minimum(jt, nk - 1)
    assert (named[visible] == parent[visible]).all()
    assert (named == _nearest_visited(named, visible)).all()
    assert ((0 <= named) & (named < nk)).all()
    # dK/dV: (k block, q tile)
    nqt, tile = FA._q_tile_map(bq, bk, window, nq, True, l, off)
    assert nqt == FA._q_tile_map(bq, bk, window, nq)[0]
    j, i = np.meshgrid(np.arange(nk), np.arange(nqt), indexing="ij")
    it = np.asarray(FA._q_tile(j, i, bq, bk, window))
    visible = np.asarray(FA.tile_visible(it, j, bq, bk, l, True, window,
                                         off, 0, q_tiles=nq))
    named = np.asarray(tile(j, i))
    parent = it if window is None else np.minimum(it, nq - 1)
    assert (named[visible] == parent[visible]).all()
    assert (named == _nearest_visited(named, visible)).all()
    assert ((0 <= named) & (named < nq)).all()


def test_only_static_offsets_clamp_and_only_causal_grids():
    """Ring attention's offsets are traced: an index map cannot read them,
    so its maps stay the plain ones; a bidirectional grid has no outside
    cell to clamp."""
    assert FA._static_off(0, 0) == 0 and FA._static_off(384, 128) == 256
    assert FA._static_off(jnp.int32(0), 0) is None
    assert FA._k_tile_map(128, 128, None, 8, True, 1024, None) == (8, None)
    assert FA._q_tile_map(128, 128, None, 8, True, 1024, None) == (8, None)
    assert FA._k_tile_map(128, 128, None, 8, False, 1024, 0) == (8, None)
    assert FA._q_tile_map(128, 128, None, 8, False, 1024, 0) == (8, None)


def _kernel_operands(l, dtype=jnp.float32, d=128):
    ks = jax.random.split(jax.random.PRNGKey(l), 4)
    q, k, v, do = (jax.random.normal(key, (2, l, d), dtype) for key in ks)
    return q, k, v, do


@pytest.mark.parametrize("bk", [128, 256])
@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
@pytest.mark.parametrize("q_off,kv_off", [(0, 0), (256, 0), (0, 128)],
                         ids=["aligned", "q-after-kv", "q-before-kv"])
def test_kernels_under_clamped_maps_give_the_plain_maps_bits(
        kernel, q_off, kv_off, bk):
    """Python-int offsets clamp the causal maps, the same offsets as traced
    scalars (ring attention's) leave them plain: every visited cell reads
    the same block either way, so every bit is the same (at key tiles of
    256 the forward's row statistics are repeated across the tile)."""
    l, blk = 512, 128
    q, k, v, do = _kernel_operands(l)
    args = (128 ** -0.5, blk, bk, True, l - 20, True)

    def run(q_off, kv_off):
        out, lse = FA._fwd(q, k, v, *args, q_off, kv_off)
        if kernel == "fwd":
            return out, lse
        bwd = FA._bwd_dkv if kernel == "dkv" else FA._bwd_dq
        return bwd(q, k, v, do, lse, FA._delta(do, out), *args, q_off,
                   kv_off)

    assert FA._static_off(q_off, kv_off) is not None
    static = run(q_off, kv_off)
    traced = run(jnp.int32(q_off), jnp.int32(kv_off))
    for a, b in zip(jax.tree.leaves(static), jax.tree.leaves(traced)):
        assert np.isfinite(np.asarray(a)).all()
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
@pytest.mark.parametrize("dtype,dot_dtype", [
    (jnp.float32, None), (jnp.bfloat16, jnp.bfloat16)], ids=["f32", "bf16"])
def test_a_power_of_two_scale_folded_into_q_changes_no_bit(
        kernel, dtype, dot_dtype):
    """What flash_attention does to a power-of-two scale (q * scale before
    the kernels, which then get 1.0; dQ's factor comes back outside) against
    the kernels with the scale inside: the same bits."""
    l, blk, scale = 384, 128, 0.125
    q, k, v, do = _kernel_operands(l, dtype)
    kw = {} if dot_dtype is None else {"dot_dtype": dot_dtype}

    def run(q, scale):
        args = (scale, blk, blk, True, l - 20, True)
        out, lse = FA._fwd(q, k, v, *args, **kw)
        if kernel == "fwd":
            return out, lse
        bwd = FA._bwd_dkv if kernel == "dkv" else FA._bwd_dq
        return bwd(q, k, v, do, lse, FA._delta(do, out), *args, **kw)

    inside = run(q, scale)
    folded = run(q * scale, 1.0)
    if kernel == "dq":
        folded = folded.astype(dtype) * scale
        inside = inside.astype(dtype)
    for a, b in zip(jax.tree.leaves(inside), jax.tree.leaves(folded)):
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("scale,folds", [
    (0.125, True), (0.25, True), (1.0, True), (24 ** -0.5, False),
    (0.3, False)])
def test_only_a_power_of_two_scale_is_folded(scale, folds, monkeypatch):
    """The kernels of the public op get 1.0 for a power of two and the scale
    itself otherwise."""
    seen = []
    fwd = FA._fwd
    monkeypatch.setattr(FA, "_fwd", lambda q, k, v, scale, *a, **kw: (
        seen.append(scale), fwd(q, k, v, scale, *a, **kw))[1])
    x = jnp.ones((1, 130, 1, 16), jnp.float32)
    flash_attention(x, x, x, causal=True, scale=scale)
    assert seen == [1.0 if folds else scale]


# ---- bit for bit the parent's kernels, on shapes of several tiles a side ----

# name: (L, q heads, k heads, v heads, d, dv, dtype, flash_attention kwargs);
# the fixture holds PR 26's kernels' bits (commit 3bb8954) of the first
# group and PR 34's (765baa1) of the second: key tiles of 256 and 512 and
# values of 256 lanes, where the forward's lane-replicated row statistics
# are repeated across the tile (two, four copies) and across the values,
# and key tiles of 64, where they are cut
_PR26_CASES = {
    "causal_f32": (530, 1, 1, 1, 16, 16, "float32", dict(causal=True)),
    "plain_f32": (530, 1, 1, 1, 16, 16, "float32", dict()),
    "causal_bq256_bk128_f32": (600, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, block_q=256, block_k=128)),
    "window_f32": (530, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, window=300)),
    "causal_bf16_grouped_dv": (530, 4, 2, 1, 16, 32, "bfloat16", dict(
        causal=True, dot_dtype=jnp.bfloat16)),
    "window_bf16_grouped_dv": (530, 4, 2, 1, 16, 32, "bfloat16", dict(
        causal=True, window=300, dot_dtype=jnp.bfloat16)),
    "causal_bf16_scale_not_pow2": (530, 2, 1, 1, 24, 24, "bfloat16", dict(
        causal=True, dot_dtype=jnp.bfloat16)),
}
_PR34_CASES = {
    "causal_bk256_f32": (600, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, block_k=256)),
    "causal_bk512_f32": (1100, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, block_k=512)),
    "window_bk256_f32": (1100, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, window=300, block_k=256)),
    "window_bk512_f32": (1100, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, window=300, block_k=512)),
    "causal_bk256_bf16_grouped_dv64": (600, 4, 2, 1, 16, 64, "bfloat16",
                                       dict(causal=True, block_k=256,
                                            dot_dtype=jnp.bfloat16)),
    "causal_bk512_bf16_grouped_dv128": (1100, 2, 1, 1, 16, 128, "bfloat16",
                                        dict(causal=True, block_k=512,
                                             dot_dtype=jnp.bfloat16)),
    "causal_bf16_dv256": (530, 1, 1, 1, 16, 256, "bfloat16", dict(
        causal=True, dot_dtype=jnp.bfloat16)),
    "causal_bk64_f32": (530, 1, 1, 1, 16, 16, "float32", dict(
        causal=True, block_k=64)),
}
PARENT_CASES = {**_PR26_CASES, **_PR34_CASES}


def _run_case(name):
    l, h, hk, hv, d, dv, dtype, kw = PARENT_CASES[name]
    seeds = sorted(_PR26_CASES) + sorted(_PR34_CASES)
    ks = jax.random.split(jax.random.PRNGKey(27 + seeds.index(name)), 4)
    q = jax.random.normal(ks[0], (1, l, h, d), dtype)
    k = jax.random.normal(ks[1], (1, l, hk, d), dtype)
    v = jax.random.normal(ks[2], (1, l, hv, dv), dtype)
    w = jax.random.normal(ks[3], (1, l, h, dv), jnp.float32)
    out = flash_attention(q, k, v, **kw)
    grads = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, **kw).astype(jnp.float32) * w), (0, 1, 2))(
            q, k, v)
    return {f"{name}_{n}": _bits(a)
            for n, a in zip(("out", "dq", "dk", "dv"), (out, *grads))}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a


@pytest.mark.parametrize("name", sorted(PARENT_CASES))
def test_shapes_of_several_tiles_equal_the_parents_kernels_bit_for_bit(name):
    """The clamped maps and the one predicate change what is fetched and
    skipped, never what is computed: outputs and all three gradients to the
    last bit, whatever the operands' dtype and the scale."""
    l, *_, kw = PARENT_CASES[name]
    census = FA.tile_census(l, kw.get("block_q", 128), kw.get("block_k", 128),
                            kw.get("causal", False), kw.get("window"))
    assert all(c["visited"] >= 9 for c in census.values())
    if kw.get("causal"):
        assert all(c["outside"] > 0 for c in census.values())
    gold = np.load(FIXTURE)
    for key, got in _run_case(name).items():
        assert got.dtype == gold[key].dtype, key
        assert np.array_equal(got, gold[key]), key


# ---- the fused backward against the dK/dV and dQ pair -----------------------

@pytest.mark.parametrize("lpq,d,static,want", [
    (16384, 128, True, True),       # the sequence cells' rows: 8 MiB
    (8192, 128, True, True),
    (32768, 128, True, True),       # the budget: 16 MiB
    (32768 + 128, 128, True, False),
    (16384, 256, True, True),       # a 256-lane head halves the length
    (16384 + 128, 256, True, False),
    (256, 128, False, False),       # ring attention's traced offsets
])
def test_the_backward_fuses_where_offsets_are_static_and_the_row_fits(
        lpq, d, static, want):
    assert FA._DQ_ROW_BYTES == 16 * 2 ** 20 <= FA._VMEM_LIMIT // 4
    assert FA.fused_bwd(lpq, d, static) is want
    assert FA.fused_bwd_census(3, lpq, d if d > 128 else 64, 128) == (
        (3, 0) if FA.fused_bwd(lpq, d) else (0, 3))


# beside PARENT_CASES (causal / plain / window, unequal blocks, grouped k and
# a wider grouped v as phi4's in small, a scale that folds and one that does
# not): a bidirectional grouped bf16 shape, a window under unequal blocks and
# a float32 scale that is no power of two
FUSED_CASES = dict(PARENT_CASES, **{
    "plain_bf16_grouped_dv": (530, 4, 2, 1, 16, 32, "bfloat16", dict(
        dot_dtype=jnp.bfloat16)),
    "window_bq128_bk256_f32": (600, 2, 1, 1, 16, 16, "float32", dict(
        causal=True, window=10, block_q=128, block_k=256)),
    "causal_f32_scale_not_pow2": (530, 2, 2, 2, 16, 16, "float32", dict(
        causal=True, scale=0.3)),
    "one_tile_bf16": (100, 2, 1, 1, 64, 64, "bfloat16", dict(
        causal=True, dot_dtype=jnp.bfloat16)),
})


def _grads(name):
    l, h, hk, hv, d, dv, dtype, kw = FUSED_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(33), 4)
    q = jax.random.normal(ks[0], (2, l, h, d), dtype)
    k = jax.random.normal(ks[1], (2, l, hk, d), dtype)
    v = jax.random.normal(ks[2], (2, l, hv, dv), dtype)
    w = jax.random.normal(ks[3], (2, l, h, dv), jnp.float32)
    return jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, **kw).astype(jnp.float32) * w), (0, 1, 2))(
            q, k, v)


def _spy(monkeypatch, *names):
    """Count the launches of the module's builders ``names``."""
    calls = dict.fromkeys(names, 0)
    for name in names:
        def counted(*a, _name=name, _fn=getattr(FA, name), **kw):
            calls[_name] += 1
            return _fn(*a, **kw)
        monkeypatch.setattr(FA, name, counted)
    return calls


@pytest.mark.parametrize("name", sorted(FUSED_CASES))
def test_the_fused_backward_equals_the_split_pair_bit_for_bit(
        name, monkeypatch):
    """One launch on the dK/dV grid gives dQ, dK and dV the bits the two
    kernels give: the same expression a tile, and every query tile's key
    blocks added in the dQ kernel's order."""
    calls = _spy(monkeypatch, "_bwd_fused", "_bwd_dkv", "_bwd_dq")
    fused = _grads(name)
    assert calls == {"_bwd_fused": 1, "_bwd_dkv": 0, "_bwd_dq": 0}
    monkeypatch.setattr(FA, "fused_bwd", lambda *a, **kw: False)
    split = _grads(name)
    assert calls == {"_bwd_fused": 1, "_bwd_dkv": 1, "_bwd_dq": 1}
    for n, a, b in zip(("dq", "dk", "dv"), fused, split):
        assert a.dtype == b.dtype and a.shape == b.shape, n
        assert np.isfinite(np.asarray(a, np.float32)).all(), n
        assert np.array_equal(_bits(a), _bits(b)), n


@pytest.mark.parametrize("refusal", ["traced-offsets", "row-past-the-budget"])
def test_what_the_predicate_refuses_takes_the_split_pair(refusal,
                                                         monkeypatch):
    """Ring attention's traced offsets and a dQ row too long for VMEM run
    dK/dV and dQ as two launches, with the fused kernel's bits."""
    l, blk = 512, 128
    q, k, v, do = _kernel_operands(l)
    args = (128 ** -0.5, blk, blk, True, l - 20, True)
    out, lse = FA._fwd(q, k, v, *args)
    operands = (q, k, v, do, lse, FA._delta(do, out), *args)
    fused = FA._bwd_kernels(*operands)
    calls = _spy(monkeypatch, "_bwd_fused", "_bwd_dkv", "_bwd_dq")
    if refusal == "traced-offsets":
        split = FA._bwd_kernels(*operands, jnp.int32(0), jnp.int32(0))
    else:
        monkeypatch.setattr(FA, "_DQ_ROW_BYTES", l * 128 * 4 - 1)
        split = FA._bwd_kernels(*operands)
    assert calls == {"_bwd_fused": 0, "_bwd_dkv": 1, "_bwd_dq": 1}
    for a, b in zip(fused, split):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,seq_len,layers,tiles", [
    # 40 heads: the full and the cross layer 136 of 256 cells at blocks of
    # 1024, the window layer 63 of 96 at blocks of 512
    ("phi4_mini_flash_6l", 16384, (3, 0), 40 * 2 * (136 + 136 + 63)),
    ("granite4_h_micro_10l", 16384, (1, 0), 32 * 2 * 136),
    ("lfm2_24b_a2b_5l", 8192, (1, 0), 32 * 2 * 36),
    # five latent-attention layers of 20 heads whose keys are 256 wide:
    # an 8 MiB dQ row, fused like the others
    ("glm47_flash_5l", 8192, (5, 0), 5 * 20 * 2 * 36),
    ("granite4_h_micro_10l", 32769, (0, 1), 32 * 3 * 561),
], ids=["phi4", "granite", "lfm2", "glm47flash",
        "granite-row-past-the-budget"])
def test_the_sequence_cells_models_count_their_layers_by_the_predicate(
        name, seq_len, layers, tiles):
    """What ``build_program`` logs and ``run_start`` carries for the three
    sequence configurations at their cells' lengths, on any backend, and the
    census of cells that go with it: forward + one backward grid."""
    from deepfake_detection_tpu.models import create_model
    model = create_model(name)
    assert model.attn_bwd_layers(seq_len) == layers
    assert model.attn_tiles_visited(seq_len) == tiles


@pytest.mark.parametrize("l,block", [(600, 256), (1024, 512)],
                         ids=["ragged-blocks-of-256", "blocks-of-512"])
def test_heads_of_256_lanes_match_dense_attention(monkeypatch, l, block):
    """Keys and values 256 wide, two lane tiles a head (latent attention's
    192 + 64 channels, models/glm4moelite.py), causal, scale 1/16: the
    forward and the one fused backward (it is the one that runs) against
    plain softmax attention, float32 under the interpreter."""
    from deepfake_detection_tpu.parallel.ring_attention import \
        full_attention
    calls = _spy(monkeypatch, "_bwd_fused", "_bwd_dkv", "_bwd_dq")
    q, k, v, w = (jax.random.normal(kk, (1, l, 2, 256), jnp.float32)
                  for kk in jax.random.split(jax.random.PRNGKey(l), 4))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)
    flash = lambda q, k, v: FA.flash_attention(            # noqa: E731
        q, k, v, causal=True, scale=1 / 16, block_q=block, block_k=block)
    dense = lambda q, k, v: full_attention(                # noqa: E731
        q, k, v, causal=True, scale=1 / 16)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                                   atol=2e-5)
        got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(dense), (0, 1, 2))(q, k, v)
    assert calls == {"_bwd_fused": 1, "_bwd_dkv": 0, "_bwd_dq": 0}
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(
            jnp.abs(b).max()))

if __name__ == "__main__":
    # PYTHONPATH=<a checkout of the PARENT commit> python tests/test_flash_tiles.py
    # writes the bits of the cases the fixture lacks, from that checkout's
    # kernels, beside the ones it holds
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert not os.path.abspath(FA.__file__).startswith(here + os.sep), \
        f"{FA.__file__} is this tree's: the bits must be another commit's"
    arrays = dict(np.load(FIXTURE)) if os.path.exists(FIXTURE) else {}
    for case in sorted(PARENT_CASES):
        if f"{case}_out" not in arrays:
            arrays.update(_run_case(case))
            print("wrote", case)
    np.savez(FIXTURE, **arrays)
    print(FIXTURE, os.path.getsize(FIXTURE), "bytes from", FA.__file__)
