"""Training-loop checkpointing: top-K retention, best-copy, recovery.

Parity with ``CheckpointSaver`` (``/root/reference/dfd/timm/utils.py:36-149``):

* keeps the top ``max_history`` (10) checkpoints ranked by the eval metric
  (``decreasing=True`` for loss, :66-79);
* ``checkpoint-<epoch>.ckpt`` + ``model_best.ckpt`` copy (:86-89) + mirror of
  the best into a ``_bak`` backup dir (:92-93);
* payload = epoch / arch / model state / optimizer state / EMA / config /
  metric / version (:97-112) — here the whole :class:`TrainState` pytree in
  one flax-serialization msgpack blob;
* in-epoch ``save_recovery`` with previous-file cleanup (:128-140) and
  ``find_recovery`` (:142-147).

Atomic writes (tmp + rename) so a preempted TPU host never leaves a torn
checkpoint — the reference's ``torch.save`` has no such guard.
"""

from __future__ import annotations

import functools
import glob
import logging
import operator
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

_logger = logging.getLogger(__name__)

__all__ = ["CheckpointSaver", "ShardedCheckpointSaver", "CheckpointCorrupt",
           "save_checkpoint_file", "load_checkpoint_file",
           "replicate_for_save", "restore_train_state",
           "restore_resharded", "wait_pending_saves",
           "save_sharded_checkpoint", "restore_sharded_checkpoint",
           "load_sharded_for_eval", "find_resume_candidates", "restore_any",
           "restore_with_fallback", "resume_position"]

_EXT = ".ckpt"


class CheckpointCorrupt(RuntimeError):
    """A checkpoint file exists but cannot be decoded (truncated write,
    torn copy, disk corruption).  Carries the offending path so callers
    can fall back to an older snapshot instead of crashing."""

    def __init__(self, path: str, cause: str):
        super().__init__(
            f"checkpoint {path} is corrupt or truncated ({cause}); "
            "if this was a recovery snapshot, --auto-resume falls back "
            "to the previous one automatically")
        self.path = path


def _recovery_key(path: str):
    """(epoch, batch_idx) ints parsed from recovery-<e>-<b>[.ckpt]."""
    import re
    return tuple(int(n) for n in re.findall(r"\d+", os.path.basename(path)))


def _needs_gather(x: Any) -> bool:
    """True for leaves only a cross-process collective can fetch: sharded
    over devices this process cannot address AND not replicated."""
    return isinstance(x, jax.Array) and not x.is_fully_addressable \
        and not x.is_fully_replicated


def _to_host(x: Any, copy: bool = False) -> np.ndarray:
    """Fetch a (possibly sharded) array to host numpy.

    Fully-replicated and fully-addressable arrays convert directly (the
    local replica / local shards suffice) — this covers single-host runs of
    any sharding and multi-host pure-DP.  Multi-host *model-sharded* leaves
    would need a collective gather that every process enters; the saver runs
    on rank 0 only, so raise with the remedy instead of deadlocking in a
    one-sided all-gather.

    ``copy=True`` guarantees the result OWNS its bytes.  On the CPU
    backend ``np.asarray(jax.Array)`` is a zero-copy VIEW of the device
    buffer — and the train step DONATES its state, so XLA reuses that
    buffer for later steps' outputs and intermediates.  A background
    checkpoint writer serializing such a view races the hot loop and
    produces a silently TORN snapshot (observed: step counter from N steps
    later, params overwritten with unrelated intermediates).  Owning the
    bytes before handing them to the writer thread is the fix; backends
    whose fetch already materializes fresh host memory (TPU/GPU) skip the
    second copy via the ownership check.
    """
    if _needs_gather(x):
        raise RuntimeError(
            "checkpoint save of a multi-host model-sharded array: call "
            "replicate_for_save(state) on ALL processes before saving "
            "(rank-0-only saving cannot enter a collective)")
    a = np.asarray(x)
    if copy and not a.flags["OWNDATA"]:
        a = a.copy()
    return a


def replicate_for_save(state: Any) -> Any:
    """Gather multi-host model-sharded leaves to a replicated layout.

    A rank-0-only saver cannot all-gather (the other ranks never enter the
    collective), so EVERY process calls this first; rank 0 then serializes
    from its local replica.  The gather is a jit identity with replicated
    ``out_shardings`` — the one mechanism that reshards across processes
    (an eager ``device_put`` cannot move non-addressable shards and
    deadlocks).  No-op unless tensor/expert-parallel state actually spans
    hosts (single-host any-sharding and multi-host pure-DP pass through).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    leaves, treedef = jax.tree.flatten(state)
    idx = [i for i, x in enumerate(leaves) if _needs_gather(x)]
    if not idx:
        return state
    # gather ONLY the offending leaves: other leaves (e.g. the step counter
    # on a single device) belong to different device sets and cannot join
    # the same jitted computation
    sub = [leaves[i] for i in idx]
    out_sh = tuple(NamedSharding(x.sharding.mesh, PartitionSpec())
                   for x in sub)
    gathered = _gather_identity(out_sh)(*sub)
    for i, g in zip(idx, gathered):
        leaves[i] = g
    return jax.tree.unflatten(treedef, leaves)


@functools.lru_cache(maxsize=8)
def _gather_identity(out_sh: tuple):
    """Cached jitted identity per output-sharding tuple — a fresh lambda per
    save would retrace + recompile the all-gather every epoch (and expose
    every rank to compile-skew at exactly the rendezvous window)."""
    return jax.jit(lambda *t: t, out_shardings=out_sh)


# one background writer: at most one save in flight, joined before the next
# (in-epoch recovery snapshots must not stall the train loop on disk IO —
# the reference's torch.save blocked the epoch, utils.py:128-140)
_write_pool = ThreadPoolExecutor(max_workers=1,
                                 thread_name_prefix="ckpt-write")
_pending: List = []


def wait_pending_saves() -> None:
    """Block until any in-flight async checkpoint write has completed.

    Async writes are recovery snapshots — best-effort by design — so a
    failed background write is logged against its own path, not raised
    from whichever unrelated checkpoint call happens to join it.
    """
    while _pending:
        path, fut = _pending.pop()
        try:
            fut.result()
        except Exception as e:  # noqa: BLE001 — best-effort snapshot
            _logger.error("async checkpoint write of %s failed: %r", path, e)


def save_checkpoint_file(path: str, state: Any,
                         meta: Optional[Dict[str, Any]] = None,
                         async_write: bool = False) -> None:
    """Serialize {state, meta} atomically to ``path``.

    ``async_write=True`` fetches the state to host *now* (cheap; device
    sync) but serializes + writes on a background thread so the caller
    returns immediately.  Writes are ordered: a new save joins the
    previous one BEFORE building its host payload (bounding host residency
    to one state copy), and :func:`wait_pending_saves` flushes at exit.
    """
    wait_pending_saves()              # at most one write/payload at a time
    from ..models.helpers import stamp_qkv_layout
    sd_dev = serialization.to_state_dict(state)
    # start every device->host copy before the first blocking np.asarray:
    # a per-leaf blocking fetch serializes O(leaves) transfer round trips
    # (painful on high-latency backends; the async pre-pass overlaps them)
    for x in jax.tree.leaves(sd_dev):
        if isinstance(x, jax.Array):
            try:
                x.copy_to_host_async()
            except Exception:  # noqa: BLE001 — _to_host surfaces real errors
                pass
    # async: the background writer must own its bytes (zero-copy views of
    # donated buffers tear — see _to_host); sync serializes before the
    # caller can dispatch another donating step, so views are safe
    sd = jax.tree.map(
        functools.partial(_to_host, copy=async_write), sd_dev)
    meta = stamp_qkv_layout(meta, sd)  # meta stays plain python
    payload = {"state": sd, "meta": meta}

    def _write() -> None:
        blob = serialization.msgpack_serialize(payload)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)

    if async_write:
        _pending.append((path, _write_pool.submit(_write)))
    else:
        _write()


def load_checkpoint_file(path: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Read a raw {state_dict, meta} pair.

    A truncated or undecodable file raises :class:`CheckpointCorrupt`
    naming the file — a msgpack stream cut mid-write otherwise surfaces as
    an opaque unpacker exception deep inside flax, and the distinction
    matters: corrupt means "fall back to an older snapshot", not "bug".
    """
    wait_pending_saves()
    with open(path, "rb") as f:
        blob = f.read()
    if not blob:
        raise CheckpointCorrupt(path, "empty file")
    try:
        payload = serialization.msgpack_restore(blob)
    except Exception as e:  # msgpack raises several unpacker classes
        raise CheckpointCorrupt(path, f"msgpack decode failed: {e!r}") \
            from e
    if not isinstance(payload, dict) or "state" not in payload:
        raise CheckpointCorrupt(path, "payload missing 'state'")
    sd, meta = payload["state"], payload.get("meta", {})
    from ..models.helpers import check_qkv_layout
    check_qkv_layout(sd, meta, path)
    return sd, meta


def _meta_json_default(v: Any):
    """json.dumps fallback for checkpoint meta: numpy scalars and arrays
    convert to their Python equivalents; anything else fails fast with a
    TypeError instead of being silently stringified (a str(ndarray) meta
    value survives the save but is garbage at restore time)."""
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    raise TypeError(
        f"checkpoint meta value of type {type(v).__name__} is not "
        "JSON-serializable; convert it to int/float/str/list before save")


def save_sharded_checkpoint(path: str, state: Any,
                            meta: Optional[Dict[str, Any]] = None) -> None:
    """Collective SHARDED save (Orbax/TensorStore): every process calls
    this, and each host writes only its own addressable shards.

    This is the multi-host model-parallel save path the single-file
    msgpack format cannot offer: no :func:`replicate_for_save` all-gather,
    no O(model) host copy on rank 0 (the reference's ``torch.save``
    serializes the full model on one rank, utils.py:97-112).  Restore can
    RE-SHARD onto a different mesh — the template's shardings decide.

    ``path`` becomes a checkpoint directory; ``meta`` goes to
    ``<path>/dfd_meta.json`` (written by process 0 after the collective
    save completes, so a meta file implies a complete checkpoint).
    """
    import orbax.checkpoint as ocp

    import json

    from ..models.helpers import stamp_qkv_layout

    path = os.path.abspath(path)
    sd = serialization.to_state_dict(state)
    if jax.process_count() > 1:
        # host-local leaves (the step counter, injected lr — single-device
        # arrays identical on every rank) cannot join a multi-host
        # collective write; serialize them as host numpy instead (the
        # restore side reloads them placement-free, matching)
        from jax.sharding import NamedSharding
        sd = jax.tree.map(
            lambda x: np.asarray(x)
            if isinstance(x, jax.Array)
            and not isinstance(x.sharding, NamedSharding) else x, sd)
    # serialize meta BEFORE the expensive collective save so a
    # non-serializable value fails fast (numpy scalars/arrays — accepted
    # by the msgpack path's meta — are converted; anything else raises
    # here rather than round-tripping as a useless str() on restore)
    meta_blob = json.dumps(stamp_qkv_layout(meta, sd),
                           default=_meta_json_default)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, sd, force=True)
        ckptr.wait_until_finished()
    if jax.process_index() == 0:
        # atomic, and written only after the collective save returned:
        # the meta file's existence marks a complete checkpoint
        meta_path = os.path.join(path, "dfd_meta.json")
        with open(meta_path + ".tmp", "w") as f:
            f.write(meta_blob)
        os.replace(meta_path + ".tmp", meta_path)
    if jax.process_count() > 1:
        # other ranks must not observe save() as done before the meta
        # marker exists (a save-then-restore flow would read meta={})
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("dfd_sharded_save_meta")


def _fresh_opt_sd(sd: Dict[str, Any], target_state: Any) -> Dict[str, Any]:
    """``--no-resume-opt`` substitution shared by both restore paths:
    weights/EMA from the checkpoint, optimizer state + step fresh."""
    sd = dict(sd)
    sd["opt_state"] = serialization.to_state_dict(target_state.opt_state)
    sd["step"] = serialization.to_state_dict(target_state.step)
    return sd


def restore_sharded_checkpoint(path: str, target_state: Any,
                               load_opt: bool = True
                               ) -> Tuple[Any, Dict[str, Any]]:
    """Collective sharded restore into ``target_state``'s structure AND
    shardings — each process reads only the shards its template layout
    asks for, resharding from the saved layout where they differ (the
    cross-process TP resume re-layout, without ever materializing the
    full model on any single host).

    ``load_opt=False``: optimizer state and step are neither read from
    disk nor required to match the checkpoint's optimizer — the saved
    ``opt_state``/``step`` entries are skipped entirely, so resuming
    weights under a *different* optimizer works.
    """
    import json

    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    # the completeness marker is checked BEFORE the (potentially many-GB,
    # cross-host) shard read — its absence fails in milliseconds
    meta = _check_complete_sharded(path)
    target_sd = serialization.to_state_dict(target_state)

    from jax.sharding import NamedSharding

    def abstract(x):
        # only mesh (NamedSharding) layouts are pinned; leaves the
        # template holds on a single device restore PLACEMENT-FREE (as
        # host arrays below, like the msgpack path) — committing them to
        # one device would fight the train step's mesh placement
        if isinstance(x, jax.Array) and isinstance(x.sharding,
                                                   NamedSharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    def uncommit(t, r):
        if isinstance(r, jax.Array) and not (
                isinstance(t, jax.Array)
                and isinstance(t.sharding, NamedSharding)):
            return np.asarray(r)
        return r

    template = {k: jax.tree.map(abstract, v) for k, v in target_sd.items()
                if load_opt or k not in ("opt_state", "step")}
    # None-valued entries (e.g. ema when EMA is off) break the
    # partial-restore metadata walk — drop them there and re-add after
    # (the full restore, conversely, REQUIRES them for the structure match)
    nones = [] if load_opt else [k for k, v in template.items()
                                 if v is None]
    template = {k: v for k, v in template.items() if k not in nones}
    restore_args = ocp.checkpoint_utils.construct_restore_args(template)
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        # partial_restore skips the saved opt_state/step entirely under
        # load_opt=False — no structure match against (possibly different)
        # optimizer state, no wasted shard reads
        sd = dict(ckptr.restore(path, args=ocp.args.PyTreeRestore(
            item=template, restore_args=restore_args,
            partial_restore=not load_opt)))
    sd = {k: jax.tree.map(uncommit, target_sd[k], v) for k, v in sd.items()}
    for k in nones:
        sd[k] = None
    if not load_opt:
        sd = _fresh_opt_sd(sd, target_state)
    from ..models.helpers import check_qkv_layout
    check_qkv_layout(sd, meta, path)
    state = serialization.from_state_dict(target_state, sd)
    return state, meta


def _check_complete_sharded(path: str) -> Dict[str, Any]:
    """Validate the completeness marker; returns the checkpoint meta.

    Diagnoses the common wrong-path mistake (the RUN directory, which
    contains checkpoint-N subdirectories, instead of one of them).
    """
    import json

    meta_path = os.path.join(path, "dfd_meta.json")
    if not os.path.exists(meta_path):
        subdirs = [d for d in sorted(glob.glob(os.path.join(path, "*")))
                   if os.path.isfile(os.path.join(d, "dfd_meta.json"))]
        if subdirs:
            raise FileNotFoundError(
                f"{path} is a run directory, not a checkpoint; use one of "
                f"its checkpoints, e.g. {subdirs[-1]} (model_best.json "
                "points at the best one)")
        raise FileNotFoundError(
            f"{path}: no dfd_meta.json — the save was interrupted before "
            "completion (the marker is written last); do not load this "
            "checkpoint")
    with open(meta_path) as f:
        return json.load(f)


def load_sharded_for_eval(path: str, variables: Dict[str, Any],
                          use_ema: bool = True) -> Dict[str, Any]:
    """Model variables {params, batch_stats} from a sharded TRAIN
    checkpoint directory — the serving path for ``--ckpt-sharded`` runs.

    Prefers the EMA stream when the checkpoint carries one (the
    reference ships its released model from the EMA stream,
    ``model_half``); reads ONLY the selected streams, placement-free.
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    meta = _check_complete_sharded(path)

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype) \
            if isinstance(x, (jax.Array, np.ndarray)) else x

    tmpl = {k: jax.tree.map(abstract, variables[k])
            for k in ("params", "batch_stats") if k in variables}
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        # key presence is not enough: an EMA-less TrainState serializes
        # ema=None, which still appears in the tree metadata
        md = ckptr.metadata(path)
        ema_md = (md.item_metadata or {}).get("ema")
        has_ema = use_ema and isinstance(ema_md, dict) and "params" in ema_md
        item = {"ema": tmpl} if has_ema else tmpl
        restore_args = ocp.checkpoint_utils.construct_restore_args(item)
        out = ckptr.restore(path, args=ocp.args.PyTreeRestore(
            item=item, restore_args=restore_args,
            partial_restore=True))
    out = dict(out["ema"] if has_ema else out)
    if has_ema:
        _logger.info("Loaded EMA stream from %s", path)
    out = {k: jax.tree.map(np.asarray, v) for k, v in out.items()}
    from ..models.helpers import check_qkv_layout
    check_qkv_layout(out, meta, path)
    return out


def find_resume_candidates(checkpoint_dir: str, bak_dir: str = "",
                           sharded: bool = False,
                           recovery_prefix: str = "recovery") -> List[str]:
    """Paths ``--auto-resume`` should try, best first: recovery snapshots
    newest-first, then the ``_bak`` best-copy mirror, then ``model_best``
    itself.  A torn newest snapshot (:class:`CheckpointCorrupt`) makes the
    caller step down this list instead of crashing.

    Standalone (no saver needed) so every rank of a multi-host run can
    compute the same list from the shared filesystem.  ``sharded``
    restricts to COMPLETE Orbax checkpoint directories (dfd_meta.json is
    written last, so its presence marks completion).
    """
    out: List[str] = []
    if sharded:
        cands = [c for c in glob.glob(os.path.join(checkpoint_dir,
                                                   recovery_prefix + "*"))
                 if os.path.isfile(os.path.join(c, "dfd_meta.json"))]
        out.extend(sorted(cands, key=_recovery_key, reverse=True))
        best_ptr = os.path.join(checkpoint_dir, "model_best.json")
        if os.path.isfile(best_ptr):
            import json
            try:
                with open(best_ptr) as f:
                    best = json.load(f).get("checkpoint", "")
            except (OSError, ValueError):
                best = ""
            if best and os.path.isfile(os.path.join(best, "dfd_meta.json")):
                out.append(best)
        return out
    out.extend(sorted(
        glob.glob(os.path.join(checkpoint_dir,
                               recovery_prefix + "*" + _EXT)),
        key=_recovery_key, reverse=True))
    for d in (bak_dir, checkpoint_dir):
        best = os.path.join(d, "model_best" + _EXT) if d else ""
        if best and os.path.isfile(best):
            out.append(best)
    return out


def restore_train_state(path: str, target_state: Any,
                        load_opt: bool = True) -> Tuple[Any, Dict[str, Any]]:
    """Rebuild a TrainState from file given a freshly-built template.

    ``load_opt=False`` mirrors ``--no-resume-opt`` (train.py:89,:365-373):
    weights/EMA restore but the optimizer state stays fresh.
    """
    sd, meta = load_checkpoint_file(path)
    if not load_opt:
        sd = _fresh_opt_sd(sd, target_state)
    state = serialization.from_state_dict(target_state, sd)
    return state, meta


def restore_resharded(path: str, target_state: Any,
                      load_opt: bool = True) -> Tuple[Any, Dict[str, Any]]:
    """msgpack restore into ``target_state``'s structure AND device layout.

    This is the mesh-portable restore (ISSUE 12): the checkpoint file
    carries plain host arrays, the TEMPLATE carries the sharding-rule
    table's ``NamedSharding`` annotations — so a checkpoint written on a
    (1, 1) mesh restores onto an (8, 1) layout (and vice versa) by
    re-laying every leaf onto the template's sharding at load time.
    Shared by ``--resume``, ``--auto-resume`` and the guard's rewind path.

    msgpack restore yields HOST numpy leaves; the compiled train step
    DONATES its state, and jax's CPU backend zero-copies suitably-aligned
    host buffers into jax arrays — donating such an alias frees memory
    numpy still owns, a use-after-free that surfaced as a native
    SIGSEGV/SIGABRT on the first resumed steps of a tp run.  Every
    restored host leaf is therefore copied into a device-OWNED array
    (re-applying the template's sharding where it had one).
    """
    from jax.sharding import NamedSharding

    from ..parallel.sharding import own_and_place

    shard_tree = jax.tree.map(
        lambda x: x.sharding if isinstance(x, jax.Array)
        and isinstance(x.sharding, NamedSharding) else None,
        target_state)
    restored, meta = restore_train_state(path, target_state,
                                         load_opt=load_opt)
    # own_and_place carries the whole ownership discipline: restored host
    # numpy leaves become JAX-OWNED copies (never zero-copy aliases the
    # donating step could free — the PR 2 SIGSEGV class) laid onto the
    # template's sharding, cross-host via per-shard assembly
    return jax.tree.map(own_and_place, restored, shard_tree), meta


def restore_any(path: str, template: Any, load_opt: bool = True
                ) -> Tuple[Any, Dict[str, Any]]:
    """Restore ``path`` (msgpack file or sharded Orbax directory) into the
    template's structure and layout: what ``--resume``, ``--auto-resume``
    and the guard's rewind all read through."""
    if os.path.isdir(path):
        # sharded (Orbax) checkpoint directory: collective restore
        # directly into the template's shardings — re-layout
        # (incl. a different tp_size) happens inside the read
        st, meta = restore_sharded_checkpoint(path, template,
                                              load_opt=load_opt)
        # re-own every restored leaf before it reaches the donating
        # step: with the sharding table pinning ALL template leaves,
        # the restore no longer demotes anything to host numpy, and
        # orbax/tensorstore-backed buffers donated by the step
        # corrupt the heap (observed: glibc abort on --ckpt-sharded
        # resume).  jnp.copy preserves each leaf's sharding.
        st = jax.tree.map(
            lambda x: jnp.copy(x)
            if isinstance(x, (jax.Array, np.ndarray)) else x, st)
        return st, meta
    # msgpack: host arrays re-laid onto the template's sharding-table
    # annotations — a (1,1)-mesh checkpoint restores onto this run's mesh
    # and vice versa
    return restore_resharded(path, template, load_opt=load_opt)


def restore_with_fallback(checkpoint_dir: str, template: Any,
                          load_opt: bool = True, sharded: bool = False
                          ) -> Optional[Tuple[Any, Dict[str, Any], str]]:
    """Walk the resume ladder of a run directory (recovery snapshots
    newest-first, then the ``_bak`` best-copy, then model_best), skipping
    torn/corrupt files instead of crashing on them.  Returns
    (state, meta, path) or None."""
    # an in-flight async recovery write hasn't renamed into place yet
    # — join it BEFORE listing, or a guard rewind a step or two after
    # the snapshot finds an empty ladder (loads already join; the
    # listing must too)
    wait_pending_saves()
    for path in find_resume_candidates(
            checkpoint_dir, bak_dir=os.path.join(checkpoint_dir, "_bak"),
            sharded=sharded):
        try:
            state, meta = restore_any(path, template, load_opt)
            return state, meta, path
        except (CheckpointCorrupt, FileNotFoundError) as e:
            _logger.warning("auto-resume: skipping unusable "
                            "checkpoint %s (%s)", path, e)
    return None


def resume_position(meta: Dict[str, Any], batches_per_epoch: int
                    ) -> Tuple[int, int]:
    """(epoch, batch) at which the loop re-enters after restoring a
    checkpoint whose meta is ``meta`` — at start-up and after a rewind
    alike.  A recovery snapshot carries its exact mid-epoch position
    (``batch_idx`` is the last batch it holds); one taken at the LAST batch
    of an epoch resumes at the next epoch's first batch.  An epoch-boundary
    checkpoint resumes at the epoch after its own."""
    if "batch_idx" not in meta:
        return int(meta.get("epoch", -1)) + 1, 0
    epoch, batch = int(meta["epoch"]), int(meta["batch_idx"]) + 1
    if batch >= batches_per_epoch > 0:
        return epoch + 1, 0
    return epoch, batch


class CheckpointSaver:
    #: collective savers (sharded) must be driven by EVERY process;
    #: file-based savers run on rank 0 only
    collective = False
    _ext = _EXT

    def __init__(self, checkpoint_dir: str = "",
                 recovery_dir: str = "", bak_dir: str = "",
                 decreasing: bool = False, max_history: int = 10,
                 checkpoint_prefix: str = "checkpoint",
                 recovery_prefix: str = "recovery"):
        self.checkpoint_files: List[Tuple[str, float]] = []  # (path, metric)
        self.best_epoch: Optional[int] = None
        self.best_metric: Optional[float] = None
        self.curr_recovery_file = ""
        self.last_recovery_file = ""
        self.checkpoint_dir = checkpoint_dir
        self.recovery_dir = recovery_dir or checkpoint_dir
        self.bak_dir = bak_dir
        self.checkpoint_prefix = checkpoint_prefix
        self.recovery_prefix = recovery_prefix
        self.decreasing = decreasing          # lower is better (loss)
        self.cmp = operator.lt if decreasing else operator.gt
        self.max_history = max_history
        assert self.max_history >= 1
        for d in (checkpoint_dir, self.recovery_dir, bak_dir):
            if d:
                os.makedirs(d, exist_ok=True)

    # ------------------------------------------------------------------
    def save_checkpoint(self, state: Any, meta: Dict[str, Any], epoch: int,
                        metric: Optional[float] = None) -> Tuple[Optional[float], Optional[int]]:
        """Epoch-boundary save with top-K pruning (reference :66-95)."""
        worst = self.checkpoint_files[-1] if self.checkpoint_files else None
        if len(self.checkpoint_files) < self.max_history or metric is None \
                or worst[1] is None or self.cmp(metric, worst[1]):
            if len(self.checkpoint_files) >= self.max_history:
                self._cleanup_checkpoints(1)
            path = os.path.join(
                self.checkpoint_dir,
                f"{self.checkpoint_prefix}-{epoch}{self._ext}")
            meta = dict(meta, epoch=epoch, metric=metric)
            self._write(path, state, meta)
            self.checkpoint_files.append((path, metric))
            # best-first; metric-less entries always rank worst (last) so
            # they are the first pruned
            with_metric = sorted(
                (c for c in self.checkpoint_files if c[1] is not None),
                key=lambda x: x[1], reverse=not self.decreasing)
            self.checkpoint_files = with_metric + [
                c for c in self.checkpoint_files if c[1] is None]
            files_str = "\n".join(f" {c}" for c in self.checkpoint_files)
            _logger.info("Current checkpoints:\n%s", files_str)
            if metric is not None and (self.best_metric is None
                                       or self.cmp(metric, self.best_metric)):
                self.best_epoch = epoch
                self.best_metric = metric
                self._mark_best(path, os.path.join(
                    self.checkpoint_dir, f"model_best{self._ext}"))
                if self.bak_dir:
                    self._mark_best(path, os.path.join(
                        self.bak_dir, f"model_best{self._ext}"))
        return (None, None) if self.best_metric is None \
            else (self.best_metric, self.best_epoch)

    def _cleanup_checkpoints(self, trim: int = 0) -> None:
        """Drop the worst ``trim`` retained checkpoints (reference :114-126)."""
        delete_index = self.max_history - trim
        if delete_index < 0 or len(self.checkpoint_files) <= delete_index:
            return
        to_delete = self.checkpoint_files[delete_index:]
        for path, _ in to_delete:
            try:
                _logger.debug("Cleaning checkpoint: %s", path)
                self._delete(path)
            except OSError as e:
                _logger.error("Exception %r while deleting checkpoint", e)
        self.checkpoint_files = self.checkpoint_files[:delete_index]

    # ------------------------------------------------------------------
    def save_recovery(self, state: Any, meta: Dict[str, Any], epoch: int,
                      batch_idx: int = 0, sync: bool = False) -> None:
        """In-epoch recovery snapshot, previous one removed (reference
        :128-140).  ``sync=True`` blocks until the file is durably renamed
        into place — the preemption path needs the snapshot ON DISK before
        the process exits, not queued on a background writer the exit
        would race."""
        path = os.path.join(
            self.recovery_dir,
            f"{self.recovery_prefix}-{epoch}-{batch_idx}{self._ext}")
        self._write_recovery(path, state, dict(meta, epoch=epoch,
                                               batch_idx=batch_idx),
                             sync=sync)
        if os.path.exists(self.last_recovery_file):
            try:
                _logger.debug("Cleaning recovery: %s",
                              self.last_recovery_file)
                self._delete(self.last_recovery_file)
            except OSError as e:
                _logger.error("Exception %r while removing %s", e,
                              self.last_recovery_file)
        self.last_recovery_file = self.curr_recovery_file
        self.curr_recovery_file = path

    def find_recovery(self) -> str:
        """Most recent recovery file, '' if none (reference :142-147;
        numeric epoch/batch ordering — a lexicographic sort would prefer
        recovery-0-999 over recovery-0-1099)."""
        files = glob.glob(os.path.join(
            self.recovery_dir, self.recovery_prefix + "*" + self._ext))
        return max(files, key=_recovery_key) if files else ""

    # -- IO hooks (overridden by the sharded saver) --------------------
    def _write(self, path: str, state: Any, meta: Dict[str, Any]) -> None:
        save_checkpoint_file(path, state, meta)

    def _write_recovery(self, path: str, state: Any,
                        meta: Dict[str, Any], sync: bool = False) -> None:
        save_checkpoint_file(path, state, meta, async_write=not sync)

    def _delete(self, path: str) -> None:
        os.remove(path)

    def _mark_best(self, src: str, dst: str) -> None:
        shutil.copyfile(src, dst)


class ShardedCheckpointSaver(CheckpointSaver):
    """Sharded (Orbax) retention saver: checkpoints are DIRECTORIES and
    saves are COLLECTIVE — drive :meth:`save_checkpoint` /
    :meth:`save_recovery` from EVERY process (the retention decisions are
    deterministic given identical metrics, so ranks stay in lockstep);
    only process 0 touches the filesystem for bookkeeping.

    ``model_best`` is a small JSON pointer to the best checkpoint
    directory, not a copy — duplicating a sharded tree would double
    checkpoint storage.  Recovery snapshots are synchronous (a collective
    cannot run on a background thread).
    """

    collective = True
    _ext = ""

    def _write(self, path: str, state: Any, meta: Dict[str, Any]) -> None:
        save_sharded_checkpoint(path, state, meta)

    def _write_recovery(self, path: str, state: Any,
                        meta: Dict[str, Any], sync: bool = False) -> None:
        # a collective save cannot ride a background thread; always sync
        save_sharded_checkpoint(path, state, meta)

    def _delete(self, path: str) -> None:
        if jax.process_index() == 0:
            shutil.rmtree(path, ignore_errors=True)

    def _mark_best(self, src: str, dst: str) -> None:
        if jax.process_index() != 0:
            return
        if self.bak_dir and dst.startswith(self.bak_dir):
            # a pointer in _bak would reference the SAME primary tree —
            # no durability gained; duplicating a sharded tree would
            # double checkpoint storage, so the bak mirror is skipped
            return
        import json
        with open(dst + ".json.tmp", "w") as f:
            json.dump({"checkpoint": src}, f)
        os.replace(dst + ".json.tmp", dst + ".json")

    def find_recovery(self) -> str:
        """Most recent COMPLETE recovery dir: Orbax leaves
        ``*.orbax-checkpoint-tmp-*`` droppings for torn saves, and only
        dirs whose dfd_meta.json exists finished their collective save."""
        cands = glob.glob(os.path.join(self.recovery_dir,
                                       self.recovery_prefix + "*"))
        done = [c for c in cands
                if os.path.isfile(os.path.join(c, "dfd_meta.json"))]
        return max(done, key=_recovery_key) if done else ""
