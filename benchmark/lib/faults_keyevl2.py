"""Faults planted in the program's Keye-VL-2.0 model, for the tests and the
calibration of its cell: each leaves every shape and parameter as it was
and changes the mathematics of one mechanism.  ``drivers/train_seq.py``
finds this file by the name the configuration gives (``reference.faults``)
and asks it for ``MODEL_FAULTS`` and ``faulty_model``.

* ``dense_attention``: every causal key is attended (the selection keeps
  all of them), the plain causal attention the sparse layer replaces;
* ``topk_halved``: each query keeps 1,024 keys and not 2,048;
* ``indexer_relu_dropped``: the index score sums ``w . (qi . ki)`` without
  the ReLU between;
* ``indexer_not_detached``: the indexer reads the normed input without the
  hold, so its loss trains the layers below it too;
* ``router_sigmoid``: the experts are selected and weighed by a sigmoid of
  the router's logits, normalised over the eight, and not by the softmax
  over all 128;
* ``qk_norm_dropped``: queries and keys reach the rotation without their
  RMSNorm.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib

MODEL_FAULTS = ("dense_attention", "topk_halved", "indexer_relu_dropped",
                "indexer_not_detached", "router_sigmoid", "qk_norm_dropped")


def _wrong(fault: str, K, SA):
    """[(module, name of its function the fault replaces, the faulty
    stand-in)]."""
    import jax
    import jax.numpy as jnp

    if fault in ("dense_attention", "topk_halved"):
        real = K.select_keys

        def select(qi, ki, w, topk, impl=None, interpret=None):
            return real(qi, ki, w,
                        1 << 30 if fault == "dense_attention" else topk // 2,
                        impl=impl, interpret=interpret)
        return [(K, "select_keys", select)]
    if fault == "indexer_relu_dropped":
        return [(SA, "_relu", lambda x: x),
                (SA, "_relu_on", lambda x: jnp.ones(x.shape, bool))]
    if fault == "indexer_not_detached":
        return [(K, "indexer_input", lambda z: z)]
    if fault == "qk_norm_dropped":
        return [(K, "qk_normed", lambda x, norm: x)]

    def route_sigmoid(logits, k):
        from deepfake_detection_tpu.ops.moe import Routing
        s = jax.nn.sigmoid(logits.astype(jnp.float32))
        picked, sel = jax.lax.top_k(s, k)
        return Routing(sel.astype(jnp.int32),
                       picked / jnp.sum(picked, -1, keepdims=True))
    return [(K, "route_softmax", route_sigmoid)]


def faulty_model(model, fault):
    """The program's model with one of the faults planted (the model itself
    for None): a subclass that traces its layers with functions of
    ``models/keyevl2.py`` or ``ops/sparse_attention.py`` replaced."""
    if fault is None:
        return model
    assert fault in MODEL_FAULTS, fault
    from deepfake_detection_tpu.models import keyevl2 as K
    SA = importlib.import_module(
        "deepfake_detection_tpu.ops.sparse_attention")
    wrong = _wrong(fault, K, SA)

    @contextlib.contextmanager
    def planted():
        real = [getattr(mod, name) for mod, name, _ in wrong]
        for mod, name, fn in wrong:
            setattr(mod, name, fn)
        try:
            yield
        finally:
            for (mod, name, _), fn in zip(wrong, real):
                setattr(mod, name, fn)

    class Faulty(type(model)):
        def hidden(self, ids, training: bool = False):
            with planted():
                return super().hidden(ids, training)

    return Faulty(**{f.name: getattr(model, f.name)
                     for f in dataclasses.fields(model)
                     if f.init and f.name not in ("parent", "name")})
