"""Device mesh construction + multi-host initialization.

TPU-native replacement for the reference's NCCL process-group setup
(``/root/reference/dfd/runners/train.py:279-282``: ``init_process_group('nccl',
file://<shared_nfs_file>)`` with rank arithmetic from a JSON server map,
``server_json.py:25-45``).  Here:

* :func:`initialize_distributed` wraps ``jax.distributed.initialize`` — the
  coordinator address replaces the shared-file rendezvous; on TPU pods the
  runtime discovers topology natively and the call is a no-op-safe default.
  The legacy server-JSON still works: hostname → process_id mapping comes
  from :class:`~deepfake_detection_tpu.config.ClusterConfig`.
* :func:`make_mesh` builds the ``jax.sharding.Mesh`` every sharded
  computation runs over.  Default is a 1-D ``('data',)`` mesh (pure DP — the
  only strategy the reference has, SURVEY.md §2.7); any shape/axis tuple
  works for dp×fsdp×tp×sp meshes.  Axis order maps the *innermost* axis to
  the fastest ICI links, so put model/tensor axes last.
* :func:`make_train_mesh` is the unified GSPMD training mesh (ISSUE 12):
  ONE logical 2-D ``('batch', 'model')`` mesh under which the train step is
  a plain ``jax.jit`` with ``NamedSharding`` annotations — the same program
  compiles for 1 chip and a v5e-256 pod without code changes (SNIPPETS.md
  [1]–[3]).  ``data_axis_name`` resolves which axis the global batch shards
  over so loaders/steps work on both the unified and legacy axis layouts.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_logger = logging.getLogger(__name__)

__all__ = ["initialize_distributed", "make_mesh", "make_train_mesh",
           "data_axis_name", "local_batch_size",
           "process_count", "process_index", "BATCH_AXIS", "MODEL_AXIS"]

#: canonical axis names of the unified 2-D training mesh.  ``BATCH_AXIS``
#: carries pure data parallelism (and FSDP parameter sharding); MODEL_AXIS
#: carries tensor/expert parallelism.  Innermost (= fastest ICI) axis last.
BATCH_AXIS = "batch"
MODEL_AXIS = "model"


def initialize_distributed(cluster=None, hostname: Optional[str] = None,
                           local_rank: int = 0, retries: Optional[int] = None,
                           backoff: float = 2.0) -> None:
    """Multi-host JAX runtime init (replaces NCCL file rendezvous).

    ``cluster`` is a :class:`ClusterConfig` (or None).  Single-process setups
    return immediately.  Safe to call multiple times (subsequent calls
    no-op).

    The rendezvous is retried with exponential backoff (``retries``
    attempts, default 4, env-overridable via ``DFD_INIT_RETRIES``): after a
    preemption the restart wrapper relaunches hosts at skewed times, and a
    coordinator that is itself still being rescheduled must not turn every
    late-arriving worker's bounded connect timeout into a permanent abort.
    The LAST failure still raises — a genuinely unreachable coordinator on
    a required multi-host setup must abort the job (swallowing it would
    silently train N isolated copies).
    """
    if cluster is None or cluster.world_size <= 1:
        return
    # NOTE: must run before anything touches the XLA backend (so no
    # jax.process_count()/jax.devices() here — they'd initialize it and make
    # the distributed init fail).
    if jax.distributed.is_initialized():
        return  # already initialized (e.g. by the TPU pod runtime)
    kwargs = {}
    if cluster.coordinator_address:
        kwargs["coordinator_address"] = cluster.coordinator_address
        kwargs["num_processes"] = cluster.world_size
        kwargs["process_id"] = cluster.process_id(hostname, local_rank)
    if retries is None:
        retries = int(os.environ.get("DFD_INIT_RETRIES", "4"))
    attempts = max(1, retries)
    delay = 1.0
    for attempt in range(attempts):
        try:
            jax.distributed.initialize(**kwargs)
            break
        except Exception as e:  # noqa: BLE001 — re-raised on the last try
            if attempt == attempts - 1:
                raise
            _logger.warning(
                "jax.distributed.initialize failed (attempt %d/%d: %r); "
                "retrying in %.1fs", attempt + 1, attempts, e, delay)
            time.sleep(delay)
            delay = min(delay * backoff, 30.0)
    _logger.info("jax.distributed initialized: process %d/%d",
                 jax.process_index(), jax.process_count())


def make_mesh(mesh_shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = ("data",),
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a device mesh.

    Defaults to all devices on one ``'data'`` axis.  ``mesh_shape`` must
    multiply out to the device count; ``-1`` in one position infers it.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = (n,) + (1,) * (len(axis_names) - 1)
    shape = list(mesh_shape)
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape[shape.index(-1)] = n // known
    assert int(np.prod(shape)) == n, \
        f"mesh shape {shape} != device count {n}"
    assert len(shape) == len(axis_names), (shape, axis_names)
    return Mesh(np.asarray(devices).reshape(shape), tuple(axis_names))


def make_train_mesh(batch: int = -1, model: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
    """The ONE 2-D ``('batch', 'model')`` mesh unified training runs over.

    ``batch=-1`` infers the data-parallel extent from the device count so
    the same call works from 1 chip to a full pod; ``model`` is the
    tensor-parallel extent (1 = pure DP).  Every sharding rule in
    :func:`~deepfake_detection_tpu.parallel.sharding.train_state_shardings`
    names these axes, and the train step is a plain ``jax.jit`` over them —
    no per-topology code.
    """
    return make_mesh((batch, model), (BATCH_AXIS, MODEL_AXIS),
                     devices=devices)


def data_axis_name(mesh: Mesh) -> str:
    """The mesh axis the global batch shards over.

    ``'batch'`` on the unified mesh, ``'data'`` on legacy 1-D / explicit
    ``--mesh-axes`` layouts, else the first (outermost) axis — so loader
    sharding and the train step agree on any mesh a user can construct.
    """
    names = tuple(mesh.axis_names)
    for cand in (BATCH_AXIS, "data"):
        if cand in names:
            return cand
    return names[0]


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def local_batch_size(global_batch_size: int) -> int:
    """Per-host batch for a data-sharded global batch."""
    assert global_batch_size % jax.process_count() == 0, \
        (global_batch_size, jax.process_count())
    return global_batch_size // jax.process_count()
