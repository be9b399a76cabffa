"""Faults planted in the program's LFM2 mixture-of-experts model, for the
tests and the calibration of its cell: each leaves every shape and parameter
as it was and changes the mathematics of one mechanism.
``drivers/train_seq.py`` finds this file by the name the configuration gives
(``reference.faults``) and asks it for ``MODEL_FAULTS`` and
``faulty_model``.

* ``bias_weighs``: the selected experts' weights are gathered from ``s +
  bias`` and not from ``s``: the selection bias weighs as well as selects;
* ``held_renormalised``: the weights are normalised over the selected
  experts *that this chip holds* and not over all the selected: the bug a
  share of an expert-parallel layer makes on its own, which no test of a
  whole layer sees;
* ``rope_dropped``: q and k are not rotated;
* ``gate_after_conv``: ``C * B * conv(u)`` in place of ``C * conv(B * u)``.
"""

from __future__ import annotations

import contextlib
import dataclasses

MODEL_FAULTS = ("bias_weighs", "held_renormalised", "rope_dropped",
                "gate_after_conv")


def _wrong(fault: str, L, held):
    """(name of the model file's function the fault replaces, its faulty
    stand-in)."""
    import jax
    import jax.numpy as jnp
    from deepfake_detection_tpu.ops.moe import Routing

    if fault == "rope_dropped":
        return "rope", lambda x, theta: x
    if fault == "gate_after_conv":
        return "gated_short_conv", lambda b, c, u, w: c * b * \
            L.causal_conv1d(u, w, None, activation=None)

    def route(logits, bias, k, scale=1.0, norm_eps=1e-6):
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        biased = s + bias.astype(jnp.float32)
        _, sel = jax.lax.top_k(biased, k)
        picked = jnp.take_along_axis(
            biased if fault == "bias_weighs" else s, sel, axis=-1)
        counted = picked
        if fault == "held_renormalised":
            here = (sel >= held[0]) & (sel < held[0] + held[1])
            counted = jnp.where(here, picked, 0.0)
        weight = picked / (jnp.sum(counted, -1, keepdims=True) + norm_eps) \
            * scale
        return Routing(sel.astype(jnp.int32), weight)
    return "route", route


def faulty_model(model, fault):
    """The program's model with one of the faults planted (the model itself
    for None): a subclass that traces its layers with one function of
    ``models/lfm2moe.py`` replaced."""
    if fault is None:
        return model
    assert fault in MODEL_FAULTS, fault
    from deepfake_detection_tpu.models import lfm2moe as L
    name, wrong = _wrong(fault, L, tuple(model.held))

    @contextlib.contextmanager
    def planted():
        real = getattr(L, name)
        setattr(L, name, wrong)
        try:
            yield
        finally:
            setattr(L, name, real)

    class Faulty(type(model)):
        def hidden(self, ids, training: bool = False):
            with planted():
                return super().hidden(ids, training)

    return Faulty(**{f.name: getattr(model, f.name)
                     for f in dataclasses.fields(model)
                     if f.init and f.name not in ("parent", "name")})
