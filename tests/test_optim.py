"""Optimizer tests: TF-parity RMSprop semantics, factory dispatch, lookahead."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deepfake_detection_tpu.optim import (create_optimizer, lookahead,
                                          rmsprop_tf, weight_decay_mask)

pytestmark = pytest.mark.smoke  # fast tier: see pyproject [tool.pytest]


def _np_rmsprop_tf_steps(p0, grads, lr, alpha=0.9, eps=1e-10, momentum=0.9):
    """Independent numpy model of the TF-RMSprop semantics documented in
    rmsprop_tf.py (ones-init accumulator, eps in sqrt, lr in momentum buf)."""
    p = p0.copy()
    sa = np.ones_like(p)      # ones init
    buf = np.zeros_like(p)
    for g in grads:
        sa = sa + (1 - alpha) * (g * g - sa)
        rms = np.sqrt(sa + eps)          # eps inside sqrt
        buf = momentum * buf + lr * g / rms   # lr folded into buffer
        p = p - buf
    return p


class TestRMSpropTF:
    def test_matches_reference_semantics(self):
        rng = np.random.default_rng(0)
        p0 = rng.normal(size=(5, 3)).astype(np.float32)
        grads = [rng.normal(size=(5, 3)).astype(np.float32) for _ in range(4)]
        lr = 0.01

        tx = rmsprop_tf(lr, alpha=0.9, eps=1e-10, momentum=0.9)
        params = {"w": jnp.asarray(p0)}
        state = tx.init(params)
        for g in grads:
            updates, state = tx.update({"w": jnp.asarray(g)}, state, params)
            params = optax.apply_updates(params, updates)

        expected = _np_rmsprop_tf_steps(p0, grads, lr)
        np.testing.assert_allclose(np.asarray(params["w"]), expected,
                                   rtol=1e-5, atol=1e-6)

    def test_ones_init_damps_first_step(self):
        # zero-init RMSprop would give |step| ~ lr/sqrt(eps) >> lr for small
        # grads; ones-init gives |step| ~ lr * g.
        tx = rmsprop_tf(0.1, momentum=0.0)
        params = {"w": jnp.zeros(3)}
        state = tx.init(params)
        g = {"w": jnp.full(3, 1e-3)}
        updates, _ = tx.update(g, state, params)
        assert float(jnp.abs(updates["w"]).max()) < 0.1 * 2e-3

    def test_no_momentum_path(self):
        tx = rmsprop_tf(0.05, momentum=0.0)
        params = {"w": jnp.ones(4)}
        state = tx.init(params)
        g = {"w": jnp.ones(4)}
        updates, state = tx.update(g, state, params)
        # sa = 1 + 0.1*(1-1) = 1; delta = -lr*g/sqrt(1+eps) ≈ -lr
        np.testing.assert_allclose(np.asarray(updates["w"]), -0.05, rtol=1e-5)

    def test_centered(self):
        tx = rmsprop_tf(0.01, momentum=0.9, centered=True)
        params = {"w": jnp.ones(4)}
        state = tx.init(params)
        updates, state = tx.update({"w": jnp.ones(4)}, state, params)
        assert jnp.all(jnp.isfinite(updates["w"]))


class _Cfg:
    opt = "rmsproptf"
    opt_eps = 1e-8
    momentum = 0.9
    weight_decay = 1e-5
    lr = 1e-3


@pytest.mark.parametrize("name", [
    "sgd", "adam", "adamw", "nadam", "radam", "adadelta", "rmsprop",
    "rmsproptf", "novograd", "nvnovograd", "lookahead_rmsproptf",
    "fusedsgd", "fusedadamw", "fusedlamb",
])
def test_factory_dispatch_and_step(name):
    cfg = _Cfg()
    cfg.opt = name
    tx = create_optimizer(cfg)
    params = {"kernel": jnp.ones((3, 4)), "bias": jnp.zeros(4)}
    state = tx.init(params)
    grads = jax.tree.map(jnp.ones_like, params)
    updates, state = tx.update(grads, state, params)
    new_params = optax.apply_updates(params, updates)
    assert jax.tree.all(jax.tree.map(
        lambda a: bool(jnp.all(jnp.isfinite(a))), new_params))
    # lr is injectable
    assert "learning_rate" in state.hyperparams


def _three_steps(tx, seed=0):
    rng = np.random.default_rng(seed)
    params = {"kernel": jnp.ones((3, 4)), "bias": jnp.zeros(4)}
    state = tx.init(params)
    for _ in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), params)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    return jax.tree.leaves(params)


@pytest.mark.parametrize("name", ["adam", "adamw"])
def test_opt_beta2_reaches_the_second_moment(name):
    """``--opt-beta2`` is the recipe's b2; left out (or None) the program is
    optax's default, bit for bit."""
    cfg = _Cfg()
    cfg.opt, cfg.weight_decay = name, 0.0
    default = _three_steps(create_optimizer(cfg))
    cfg.opt_beta2 = None
    for a, b in zip(_three_steps(create_optimizer(cfg)), default):
        np.testing.assert_array_equal(a, b)
    cfg.opt_beta2 = 0.95
    decay = {"weight_decay": 0.0} if name == "adamw" else {}
    plain = getattr(optax, name)(cfg.lr, b2=0.95, eps=cfg.opt_eps, **decay)
    got = _three_steps(create_optimizer(cfg))
    for a, b, c in zip(got, _three_steps(plain), default):
        np.testing.assert_array_equal(a, b)
        assert float(jnp.max(jnp.abs(a - c))) > 1e-6


def test_factory_invalid_name():
    cfg = _Cfg()
    cfg.opt = "doesnotexist"
    with pytest.raises(ValueError):
        create_optimizer(cfg)


def test_weight_decay_mask():
    params = {"conv": {"kernel": jnp.ones((3, 3, 4, 8)), "bias": jnp.ones(8)},
              "bn": {"scale": jnp.ones(8)}}
    mask = weight_decay_mask(params)
    assert mask["conv"]["kernel"] is True
    assert mask["conv"]["bias"] is False
    assert mask["bn"]["scale"] is False


def test_lookahead_sync():
    inner = optax.sgd(1.0)
    tx = lookahead(inner, sync_period=2, alpha=0.5)
    params = {"w": jnp.zeros(2)}
    state = tx.init(params)
    g = {"w": jnp.ones(2)}
    # step 1 (no sync): p = -1
    u, state = tx.update(g, state, params)
    params = optax.apply_updates(params, u)
    np.testing.assert_allclose(np.asarray(params["w"]), -1.0)
    # step 2 (sync): fast would be -2; target = 0 + 0.5*(-2-0) = -1
    u, state = tx.update(g, state, params)
    params = optax.apply_updates(params, u)
    np.testing.assert_allclose(np.asarray(params["w"]), -1.0)
    np.testing.assert_allclose(np.asarray(state.slow_params["w"]), -1.0)


class TestNovogradWeightDecayMask:
    def test_bias_and_norm_params_not_decayed(self):
        import jax.numpy as jnp
        from types import SimpleNamespace

        def updates(wd):
            cfg = SimpleNamespace(opt="novograd", opt_eps=1e-8, momentum=0.9,
                                  weight_decay=wd, lr=0.1)
            tx = create_optimizer(cfg, inject=False)
            params = {"kernel": jnp.ones((3, 3)), "bias": jnp.ones((3,))}
            g = {"kernel": jnp.ones((3, 3)) * 0.5, "bias": jnp.ones((3,)) * 0.5}
            u, _ = tx.update(g, tx.init(params), params)
            return u

        u_wd, u_nowd = updates(0.5), updates(0.0)
        # bias (1-dim) exempt from decay → identical with/without wd
        assert jnp.allclose(u_wd["bias"], u_nowd["bias"])
        # kernel is decayed → differs
        assert not jnp.allclose(u_wd["kernel"], u_nowd["kernel"])


class TestNvNovoGrad:
    def _torch_reference_step(self, params, grads, steps, lr=0.1, b1=0.95,
                              b2=0.98, eps=1e-8, wd=0.01):
        """Literal numpy transcription of reference nvnovograd.py:60-118."""
        p = {k: v.copy() for k, v in params.items()}
        state = {k: {"exp_avg": np.zeros_like(v), "exp_avg_sq": 0.0}
                 for k, v in params.items()}
        for t in range(steps):
            for k in p:
                g = grads[t][k].copy()
                st = state[k]
                norm = float(np.sum(g ** 2))
                if st["exp_avg_sq"] == 0.0:
                    st["exp_avg_sq"] = norm
                else:
                    st["exp_avg_sq"] = st["exp_avg_sq"] * b2 + (1 - b2) * norm
                g = g / (np.sqrt(st["exp_avg_sq"]) + eps)
                g = g + wd * p[k]
                st["exp_avg"] = b1 * st["exp_avg"] + g
                p[k] = p[k] - lr * st["exp_avg"]
        return p

    def test_matches_reference_semantics(self):
        from deepfake_detection_tpu.optim.nvnovograd import nvnovograd
        rng = np.random.default_rng(0)
        params = {"w": rng.normal(size=(4, 3)).astype(np.float32),
                  "b": rng.normal(size=(3,)).astype(np.float32)}
        grads = [{k: rng.normal(size=v.shape).astype(np.float32)
                  for k, v in params.items()} for _ in range(4)]
        want = self._torch_reference_step(params, grads, 4)

        tx = nvnovograd(0.1, weight_decay=0.01)
        jp = {k: jnp.asarray(v) for k, v in params.items()}
        st = tx.init(jp)
        for t in range(4):
            deltas, st = tx.update(
                {k: jnp.asarray(v) for k, v in grads[t].items()}, st, jp)
            jp = jax.tree.map(lambda p, d: p + d, jp, deltas)
        for k in params:
            np.testing.assert_allclose(np.asarray(jp[k]), want[k],
                                       rtol=1e-5, atol=1e-6)

    def test_factory_dispatch_distinct(self):
        from types import SimpleNamespace
        from deepfake_detection_tpu.optim import create_optimizer
        for name in ("novograd", "nvnovograd"):
            cfg = SimpleNamespace(opt=name, opt_eps=1e-8, momentum=0.9,
                                  weight_decay=1e-5, lr=1e-3)
            tx = create_optimizer(cfg)
            params = {"kernel": jnp.ones((3, 3)), "bias": jnp.ones((3,))}
            st = tx.init(params)
            deltas, _ = tx.update(
                {"kernel": jnp.ones((3, 3)) * 0.1,
                 "bias": jnp.ones((3,)) * 0.1}, st, params)
            assert all(bool(jnp.all(jnp.isfinite(d)))
                       for d in jax.tree.leaves(deltas)), name
