"""Faults planted in the program's granite-4.0-h model, for the tests and the
calibration of its cell: each leaves every shape and parameter as it was and
changes the mathematics of the Mamba-2 mixer.  ``drivers/train_seq.py``
finds this file by the name the configuration gives
(``reference.faults``) and asks it for ``MODEL_FAULTS`` and
``faulty_model``.

* ``chunk_state_dropped``: every chunk of the scan starts from a zero state
  (the chunks are scanned as separate rows), which is what a dual-form scan
  that loses its inter-chunk recurrence computes;
* ``norm_before_gate``: RMSNorm(y) * silu(z) in place of RMSNorm(y *
  silu(z)), the other order the Mamba-2 mixer is published with.
"""

from __future__ import annotations

import contextlib
import dataclasses

MODEL_FAULTS = ("chunk_state_dropped", "norm_before_gate")


def _wrong(fault: str, G):
    """(name of the model file's function the fault replaces, its faulty
    stand-in)."""
    if fault == "chunk_state_dropped":
        real = G.ssd_scan

        def ssd_scan(x, dt, a, b, c, d, chunk=256, impl=None):
            rows, l = x.shape[:2]
            q = min(chunk, l)
            assert l % q == 0, (l, q)

            def cut(v):
                return v.reshape((rows * (l // q), q) + v.shape[2:])
            return real(cut(x), cut(dt), a, cut(b), cut(c), d, chunk=q,
                        impl=impl).reshape(x.shape)
        return "ssd_scan", ssd_scan

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    def gated_rms_norm(y, z, scale, eps):
        y = y.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return y * scale * nn.silu(z.astype(jnp.float32))
    return "gated_rms_norm", gated_rms_norm


def faulty_model(model, fault):
    """The program's model with one of the faults planted (the model itself
    for None): a subclass that traces its layers with one function of
    ``models/granite4h.py`` replaced."""
    if fault is None:
        return model
    assert fault in MODEL_FAULTS, fault
    from deepfake_detection_tpu.models import granite4h as G
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
