"""Faults planted in the program's GLM-4.7-Flash model, for the tests and
the calibration of its cell: each leaves every shape and parameter as it was
and changes the mathematics of one mechanism.  ``drivers/train_seq.py``
finds this file by the name the configuration gives (``reference.faults``)
and asks it for ``MODEL_FAULTS`` and ``faulty_model``.

* ``softmax_scale_192``: scores over ``sqrt(192)``, the width of a head's
  own key, and not over ``sqrt(192 + 64)``: the usual slip of latent
  attention, which forgets the rotary channels in the score's width;
* ``kv_norm_dropped``: the key / value latent reaches its up projection
  without its RMSNorm;
* ``rope_per_head_key``: each head's rotary key is its own, the rotated
  first 64 channels of its up-projected key, where the model has one rotary
  key head, from the down projection, that every head shares;
* ``shared_expert_dropped``: the expert layer's output is the routed
  experts' part alone.
"""

from __future__ import annotations

import contextlib
import dataclasses

MODEL_FAULTS = ("softmax_scale_192", "kv_norm_dropped", "rope_per_head_key",
                "shared_expert_dropped")


def _wrong(fault: str, G):
    """(name of the model file's function the fault replaces, its faulty
    stand-in)."""
    import jax.numpy as jnp

    if fault == "softmax_scale_192":
        return "mla_scale", lambda nope_dim, rope_dim: nope_dim ** -0.5
    if fault == "kv_norm_dropped":
        return "kv_latent", lambda c_kv, norm: c_kv
    if fault == "shared_expert_dropped":
        return "moe_sum", lambda routed, shared: routed

    def per_head(k_n, k_r, theta):
        rot = G.rope(k_n[..., :k_r.shape[-1]], theta)
        return jnp.concatenate([k_n, rot], -1)
    return "latent_keys", per_head


def faulty_model(model, fault):
    """The program's model with one of the faults planted (the model itself
    for None): a subclass that traces its layers with one function of
    ``models/glm4moelite.py`` replaced."""
    if fault is None:
        return model
    assert fault in MODEL_FAULTS, fault
    from deepfake_detection_tpu.models import glm4moelite as G
    name, wrong = _wrong(fault, G)

    @contextlib.contextmanager
    def planted():
        real = getattr(G, name)
        setattr(G, name, wrong)
        try:
            yield
        finally:
            setattr(G, name, real)

    class Faulty(type(model)):
        def hidden(self, ids, training: bool = False):
            with planted():
                return super().hidden(ids, training)

    return Faulty(**{f.name: getattr(model, f.name)
                     for f in dataclasses.fields(model)
                     if f.init and f.name not in ("parent", "name")})
