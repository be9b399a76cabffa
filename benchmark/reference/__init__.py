"""Plain references, found by the names a configuration file gives.

``reference.module`` names the model's reference (a file under
``benchmark/reference/``) and ``reference.optimizer.name`` the optimizer's
(``optim_<name>.py``).  A model reference exports

* ``model_spec(config)`` (a dict with ``num_classes``), ``param_shapes(spec)``
  -> (parameter shapes, running-statistic shapes) as nested dicts of tuples,
* ``inference_forward(params, stats, x, spec)``: one traceable forward, whose
  jaxpr the FLOP and byte walk reads,
* ``prologue(images_u8, step_index, aug, seed)``: the step's feed from the
  host's uint8 batch,
* ``loss_and_grads(params, stats, x, y, spec, quant=None)`` -> (loss,
  gradients, new statistics, logits); ``quant`` switches the control's lower
  precision on,
* optionally ``residual_gains(spec)`` and ``init_leaf(key, path, shape)`` for
  the seeded weights (``benchmark/lib/weights.py`` has the defaults).

Another model family adds a file beside these and names it in its
configuration; nothing here or in the drivers names a family.
"""

from __future__ import annotations

import importlib
import os
from typing import Any, Dict


def _module_of(path: str):
    name = os.path.splitext(path)[0].replace("/", ".").replace("\\", ".")
    return importlib.import_module(name)


def model(config: Dict[str, Any]):
    return _module_of(config["reference"]["module"])


def optimizer(config: Dict[str, Any]):
    return importlib.import_module(
        "benchmark.reference.optim_" + config["reference"]["optimizer"]["name"])
