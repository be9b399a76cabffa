"""Distributed training runner.

Re-design of ``/root/reference/dfd/runners/train.py`` (819 LoC) for TPU:

* ``launch_main`` (:769-816) — arg parse, cluster config, output-dir setup,
  linear LR scaling — maps to :func:`launch_main`.  The ``mp.spawn``
  per-GPU process fan-out and the NCCL file rendezvous disappear: one process
  per *host* drives all local devices through the mesh, and
  ``jax.distributed.initialize`` handles multi-host (parallel/mesh.py).
* ``main`` (:256-592) — model/optimizer/scheduler/dataset construction,
  resume, epoch loop — maps to :func:`build_program`, :func:`init_state`,
  :func:`build_loaders`, :func:`build_steps`, :func:`build_telemetry` (each
  callable alone: what a tool or the benchmark builds, it builds through
  these) and :func:`main`, which is those calls and the loop.
* apex AMP O1 (:353) → bfloat16 compute policy (``--compute-dtype``), no
  loss scaling needed on TPU.
* apex DDP (:402) → the jitted train step over the mesh (train/steps.py).

Safety deviation: the reference's rank-0 setup *deletes* an existing output
dir (``dfd/utils.py:77-80``); here collisions get a ``-N`` suffix instead
(utils.get_outdir(inc=True)).

Usage::

    python -m deepfake_detection_tpu.runners.train \
        --data /path/DFDC --model efficientnet_deepfake_v4 \
        --input-size-v2 12,600,600 -b 3 --opt rmsproptf --basic-lr 5e-7 \
        --sched step --decay-epochs 2 --decay-rate .92 --amp \
        --reprob 0.2 --remax 0.05 --flicker 0.05 --rotate-range 5 \
        --blur-prob 0.05 --bn-momentum 0.001 --mixup 0.1 --label-balance \
        --eval-metric loss      # == scripts/train.sh:3-22
"""

from __future__ import annotations

import dataclasses
import logging
import os
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ClusterConfig, TrainConfig
from ..data import (DeepFakeClipDataset, FastCollateMixup, SyntheticDataset,
                    SyntheticTokenDataset, TokenFileDataset,
                    create_deepfake_loader_v3, create_token_loader,
                    resolve_data_config)
from ..losses import create_loss_fn, cross_entropy
from ..models import (create_deepfake_model, create_deepfake_model_v3,
                      create_deepfake_model_v4, create_model, init_model)
from ..ops.flash_attention import saved_fwd_census
from ..optim import create_optimizer
from ..parallel import (batch_sharding, data_axis_name,
                        initialize_distributed, make_mesh, make_train_mesh,
                        own_and_place, place_train_state,
                        replicated_sharding, train_state_shardings,
                        transformer_tp_sharding)
from ..scheduler import create_scheduler
from ..train import (EXIT_PREEMPTED, CheckpointSaver, Preempted, Resilience,
                     RewindRequested, ShardedCheckpointSaver,
                     create_train_state, make_eval_step, make_train_step,
                     replicate_for_save, restore_any, restore_with_fallback,
                     resume_position, set_learning_rate, train_one_epoch,
                     validate, wait_pending_saves)
from ..utils import get_outdir, setup_default_logging, update_summary
from ..utils.compile_cache import setup_compile_cache

_logger = logging.getLogger("train")

_MODEL_FACTORIES = {
    "": create_model,
    "v1": create_deepfake_model,
    "v3": create_deepfake_model_v3,
    "v4": create_deepfake_model_v4,
}


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def build_model(cfg: TrainConfig, in_chans: int):
    """Model construction (reference train.py:305-320)."""
    factory = _MODEL_FACTORIES.get(cfg.model_version, create_model)
    kwargs: Dict[str, Any] = dict(
        pretrained=cfg.pretrained, num_classes=cfg.num_classes,
        in_chans=in_chans, drop_rate=cfg.drop,
        drop_path_rate=cfg.drop_path, drop_block_rate=cfg.drop_block,
        bn_tf=cfg.bn_tf,
        bn_momentum=cfg.bn_momentum, bn_eps=cfg.bn_eps,
        global_pool=cfg.gp,
        remat_policy=cfg.checkpoint_policy,
        fused_depthwise=cfg.fused_depthwise,
        stem_s2d=cfg.stem_s2d,
        dtype=_dtype(cfg.compute_dtype) if (cfg.amp or
                                            cfg.compute_dtype != "float32")
        else None)
    kwargs = {k: v for k, v in kwargs.items() if v is not None}
    if cfg.split_bn:
        # AdvProp split BN (reference train.py:335-337): a separate BN per
        # augmentation split — meaningless without >1 split.  ValueError,
        # not assert: CLI validation must survive python -O
        if not (cfg.aug_splits > 1 or cfg.resplit):
            raise ValueError("--split-bn needs --aug-splits > 1 or "
                             "--resplit")
        kwargs["norm_layer"] = f"split{max(cfg.aug_splits, 2)}"
    if cfg.attn_impl:
        if cfg.attn_impl in ("ring", "ring_flash", "ulysses"):
            raise ValueError(
                f"--attn-impl {cfg.attn_impl}: sequence-parallel attention "
                f"needs an sp mesh and token-sharded inputs — construct the "
                f"model with sp_mesh/seq_axis directly (models/vit.py); the "
                f"CLI supports 'full' and 'flash'")
        kwargs["attn_impl"] = cfg.attn_impl   # ViT/TimeSformer families
    if factory is create_model:
        return create_model(cfg.model, **kwargs)
    return factory(cfg.model, **kwargs)


def build_datasets(cfg: TrainConfig, input_size, pack_dir=None,
                   pack_image_size=None, vocab_rows: int = 0
                   ) -> Tuple[Any, Any]:
    """Train/eval dataset construction (reference train.py:422-504).

    ``pack_dir`` (``--data-packed``, resolved through
    ``data/config.py::resolve_data_config``) swaps the JPEG-decode clip
    source for the packed pre-decoded cache (``data/packed.py``) — the
    split/balance/RNG machinery is shared, so downstream batches are
    bit-identical at matching pack resolution.  A stale or mismatched
    pack raises at construction, never trains on skewed data.
    """
    if cfg.dataset in ("synthetic-tokens", "tokens"):
        # the sequence models' rows (data/tokens.py): whole documents of
        # --seq-len ids below the vocabulary rows the model holds
        if cfg.seq_len <= 0 or vocab_rows <= 0:
            raise ValueError(f"--dataset {cfg.dataset} needs --seq-len and a "
                             "model of the sequence task")
        if cfg.dataset == "synthetic-tokens":
            n = max(cfg.batch_size * 8, 16)
            return (SyntheticTokenDataset(n, cfg.seq_len, vocab_rows,
                                          cfg.seed),
                    SyntheticTokenDataset(max(n // 2, 8), cfg.seq_len,
                                          vocab_rows, cfg.seed + 1))
        return (TokenFileDataset(cfg.data, cfg.seq_len, vocab_rows),
                TokenFileDataset(cfg.eval_data or cfg.data, cfg.seq_len,
                                 vocab_rows))
    c, h, w = input_size
    if cfg.dataset == "synthetic":
        if pack_dir:
            raise ValueError("--data-packed requires --dataset deepfake_v3")
        n = max(cfg.batch_size * 8, 16)
        return (SyntheticDataset(n, (h, w, c), cfg.num_classes, cfg.seed),
                SyntheticDataset(max(n // 2, 8), (h, w, c), cfg.num_classes,
                                 cfg.seed + 1))
    if cfg.dataset == "deepfake_v3":
        common = dict(frames_per_clip=max(1, c // 3),
                      label_balance=cfg.label_balance,
                      noise_fake=cfg.noise_fake > 0,
                      split_seed=cfg.split_seed)
        if pack_dir:
            from ..data import PackedDataset
            packed = dict(roots=cfg.data or None,
                          image_size=pack_image_size)

            def make_train(**kw):
                return PackedDataset(pack_dir, **packed, **kw)
        else:
            def make_train(**kw):
                return DeepFakeClipDataset(cfg.data, **kw)
        if cfg.eval_data:
            # a separate eval root always reads through the decode path:
            # the pack is fingerprinted against the TRAIN lists only
            train_ds = make_train(**common)
            eval_ds = DeepFakeClipDataset(cfg.eval_data,
                                          frames_per_clip=max(1, c // 3),
                                          split_seed=cfg.split_seed)
        else:  # seeded split out of the train roots (reference :424-438)
            train_ds = make_train(
                train_split=True, train_ratio=cfg.train_split,
                is_training=True, **common)
            eval_ds = make_train(
                train_split=True, train_ratio=cfg.train_split,
                is_training=False, frames_per_clip=max(1, c // 3),
                split_seed=cfg.split_seed)
        return train_ds, eval_ds
    raise ValueError(f"unknown dataset {cfg.dataset!r}")


@dataclasses.dataclass
class Program:
    """What a :class:`TrainConfig` determines before any array exists or a
    file is touched; :func:`init_state` and the ``build_*`` functions take
    it."""
    cfg: TrainConfig
    mesh: Any
    n_dev: int
    batch_axis: str
    dp: int                     # data-parallel degree (a tp group is ONE)
    data_config: Dict[str, Any]
    input_size: Tuple[int, ...]
    model: Any
    sequence_task: bool         # the model takes (batch, L) ids
    lr: float
    tx: Any
    lr_scheduler: Any
    num_epochs: int
    loss_fn: Any
    global_batch: int           # rows per optimizer step, all processes
    bn_mode: str
    # depthwise stages of the train step by who computes their filter
    # gradient, (kernel, xla): ops/conv.py:dw_grad_impl
    dw_grad_stages: Tuple[int, int] = (0, 0)
    # Mamba layers of the train step by the form of their causal
    # convolution, (kernels, array form): ops/causal_conv.py
    causal_conv_layers: Tuple[int, int] = (0, 0)
    # routed expert layers of the train step by the form of their grouped
    # products, (kernels, array form): ops/moe.py
    moe_layers: Tuple[int, int] = (0, 0)
    # attention layers of the train step by the form of their backward,
    # (fused, split): ops/flash_attention.py:fused_bwd
    attn_bwd_layers: Tuple[int, int] = (0, 0)
    # attention layers whose backward reuses the forward kernel's saved
    # output under the remat policy: ops/flash_attention.py:saved_fwd_census
    attn_fwd_saved_layers: int = 0
    # layers of the train step with latent attention (models/glm4moelite.py)
    mla_layers: int = 0
    # layers of the train step with learned sparse attention
    # (models/keyevl2.py)
    dsa_layers: int = 0
    # of those, the layers whose attention forward reads the selection
    # kernel's bits (ops/sparse_attention.py)
    dsa_fwd_bits_layers: int = 0


def _choose_mesh(cfg: TrainConfig):
    if cfg.tp_size > 1:
        if cfg.mesh_shape is not None or cfg.fsdp:
            raise ValueError(
                "--tp-size conflicts with an explicit --mesh-shape/--fsdp; "
                "configure one parallelism layout at a time")
        # dp×tp on the unified mesh; parameter shardings applied after
        # init (transformer_tp_sharding names the 'model' axis)
        return make_train_mesh(batch=-1, model=cfg.tp_size)
    if cfg.mesh_shape is not None or tuple(cfg.mesh_axes) != ("data",):
        # explicit legacy layout: honored verbatim (tests / sp meshes)
        return make_mesh(cfg.mesh_shape, cfg.mesh_axes)
    # the default: ONE 2-D ('batch', 'model') mesh — the same program
    # compiles for 1 chip and a pod (ISSUE 12)
    return make_train_mesh()


def _validate(cfg: TrainConfig, n_dev: int, dp_size: int) -> None:
    """Flag combinations this mesh or backend cannot run."""
    if (cfg.fused_depthwise == "pallas" or cfg.attn_impl == "flash") and \
            jax.default_backend() != "tpu" and \
            "cpu" not in (jax.config.jax_platforms or ""):
        # the kernels' interpret default is "not on TPU": right where the
        # CPU was asked for by name (tests, CI), wrong for a chip run that
        # lost its chip — that one must not train on the interpreter
        raise RuntimeError(
            f"--fused-depthwise pallas / --attn-impl flash need a TPU; jax "
            f"fell back to {jax.default_backend()!r} without being asked "
            f"(JAX_PLATFORMS={jax.config.jax_platforms!r})")
    if cfg.fused_depthwise == "pallas" and n_dev > 1 and \
            jax.default_backend() == "tpu":
        # chip-gated residue of the GSPMD migration (ROADMAP chip-debt):
        # the compiled Mosaic pallas_call has no SPMD partitioning rule,
        # so embedding it in the unified jit over a >1-chip mesh would at
        # best replicate the batch around every dw stage and at worst
        # fail to lower — the old shard_map wrapper that guaranteed
        # per-device execution is gone.  Interpret mode (off-TPU CI)
        # partitions fine; on real multi-chip, fail loudly until the
        # kernel grows its own partitioning (shard_map island or
        # custom_partitioning).
        raise NotImplementedError(
            "--fused-depthwise pallas on a multi-chip mesh is not yet "
            "verified under the unified GSPMD step; run with "
            "--fused-depthwise off (or a single chip) until the kernel's "
            "multi-chip migration lands")
    if cfg.split_bn and dp_size > 1:
        # the loader's split-major batch layout ([all clean, all aug])
        # does not survive contiguous per-device sharding — device d
        # would feed its main BN augmented samples, corrupting exactly
        # the clean/aug separation AdvProp split BN exists for
        raise NotImplementedError(
            "--split-bn requires a single data-parallel replica "
            "(dp=1); an interleaved per-device batch layout is needed "
            "for dp>1 and is not implemented")


def _model_input_shape(cfg: TrainConfig, input_size, rows: int):
    """NHWC shape of ``rows`` rows as the LOADER feeds the model:
    pixel-shuffled under ``--stem-s2d``."""
    c, h, w = input_size
    return (rows, h // 2, w // 2, 4 * c) if cfg.stem_s2d \
        else (rows, h, w, c)


def build_program(cfg: TrainConfig, mesh=None) -> Program:
    """Everything of the program that needs no arrays and no file system.

    ``mesh`` overrides the configuration's choice (an abstract topology for
    an AOT compile, a sub-mesh of the visible chips)."""
    if mesh is None:
        mesh = _choose_mesh(cfg)
    n_dev = int(mesh.size)
    batch_axis = data_axis_name(mesh)
    # the data-parallel degree: batch and linear-LR scaling follow it, not
    # the raw device count (a tp group is ONE model replica)
    dp_size = int(mesh.shape.get(batch_axis, n_dev))
    _logger.info("Training with %d devices, mesh %s, process %d/%d",
                 n_dev, dict(mesh.shape), jax.process_index(),
                 jax.process_count())
    _validate(cfg, n_dev, dp_size)
    data_config = resolve_data_config(cfg.to_dict(),
                                      verbose=jax.process_index() == 0)
    input_size = tuple(data_config["input_size"])
    model = build_model(cfg, input_size[0])
    # linear LR scaling: per-device batch × total devices (train.py:814)
    # effective batch per optimizer step includes the accumulated
    # microbatches — the linear rule must see it, or the flagship config
    # trains with an LR grad_accum-times below the reference's
    lr = cfg.resolved_lr(world_size=dp_size * cfg.grad_accum)
    lr_scheduler, num_epochs = create_scheduler(cfg, base_lr=lr)
    if cfg.dist_bn:
        _logger.info("--dist-bn %s accepted for flag parity; BN stats are "
                     "pmean-reduced inside every train step here, which "
                     "supersedes the reference's per-epoch distribute_bn",
                     cfg.dist_bn)
    sequence_task = bool(getattr(model, "sequence_task", False))
    dw_grad_stages = (0, 0)
    if not sequence_task:
        # what make_train_step's trace will decide, stage by stage, for the
        # rows one step takes (the microbatch under --grad-accum)
        from ..ops.conv import dw_grad_census
        census = dw_grad_census(
            model, _model_input_shape(cfg, input_size,
                                      cfg.batch_size * dp_size),
            jnp.float32, devices=n_dev)
        dw_grad_stages = (len(census.get("kernel", ())),
                          len(census.get("xla", ())))
        _logger.info("Depthwise filter gradients: dw_grad_kernel_stages=%d "
                     "dw_grad_xla_stages=%d", *dw_grad_stages)
    causal_conv_layers = (0, 0)
    if hasattr(model, "causal_conv_layers"):
        causal_conv_layers = model.causal_conv_layers(cfg.seq_len)
        _logger.info("Causal convolutions: causal_conv_kernel_layers=%d "
                     "causal_conv_xla_layers=%d", *causal_conv_layers)
    moe_layers = (0, 0)
    if hasattr(model, "moe_layers"):
        # the tokens one pass routes (the microbatch under --grad-accum)
        moe_layers = model.moe_layers(
            cfg.batch_size * dp_size * cfg.seq_len)
        _logger.info("Routed expert layers: moe_kernel_layers=%d "
                     "moe_xla_layers=%d", *moe_layers)
    attn_bwd_layers, attn_fwd_saved_layers = (0, 0), 0
    if hasattr(model, "attn_bwd_layers"):
        attn_bwd_layers = model.attn_bwd_layers(cfg.seq_len)
        attn_fwd_saved_layers = saved_fwd_census(sum(attn_bwd_layers),
                                                 model.remat_policy)
        _logger.info("Attention: attn_fused_bwd_layers=%d "
                     "attn_split_bwd_layers=%d attn_fwd_saved_layers=%d",
                     *attn_bwd_layers, attn_fwd_saved_layers)
    mla_layers = 0
    if hasattr(model, "mla_layers"):
        mla_layers = model.mla_layers()
        _logger.info("Latent attention: mla_layers=%d", mla_layers)
    dsa_layers, dsa_fwd_bits_layers = 0, 0
    if hasattr(model, "dsa_layers"):
        dsa_layers = model.dsa_layers()
        dsa_fwd_bits_layers = model.dsa_fwd_bits_layers(cfg.seq_len)
        _logger.info("Learned sparse attention: dsa_layers=%d "
                     "dsa_fwd_bits_layers=%d", dsa_layers,
                     dsa_fwd_bits_layers)
    return Program(
        cfg=cfg, mesh=mesh, n_dev=n_dev, batch_axis=batch_axis, dp=dp_size,
        data_config=data_config, input_size=input_size, model=model,
        sequence_task=sequence_task, dw_grad_stages=dw_grad_stages,
        causal_conv_layers=causal_conv_layers, moe_layers=moe_layers,
        attn_bwd_layers=attn_bwd_layers,
        attn_fwd_saved_layers=attn_fwd_saved_layers, mla_layers=mla_layers,
        dsa_layers=dsa_layers, dsa_fwd_bits_layers=dsa_fwd_bits_layers,
        lr=lr, tx=create_optimizer(cfg, learning_rate=lr),
        lr_scheduler=lr_scheduler, num_epochs=num_epochs,
        loss_fn=create_loss_fn(cfg),
        # grad_accum microbatches ride inside one compiled step: the loader
        # assembles the full effective batch per step
        global_batch=cfg.batch_size * dp_size * cfg.grad_accum,
        # tp runs use global-BN semantics: the transformer families carry
        # no BN, so local-stat grouping would only add layout churn
        bn_mode="global" if (cfg.sync_bn or cfg.tp_size > 1) else "local")


def _load_initial_checkpoint(cfg: TrainConfig, variables):
    """Pretrained weights into the fresh tree (reference train.py:316 /
    helpers.py:31-44): non-strict — head/in_chans mismatches drop, but
    loudly, and a checkpoint matching NOTHING is an error (a silent
    from-scratch "fine-tune" is worse than failing)."""
    from ..models.helpers import (_flatten, expand_split_bn,
                                  filter_shape_mismatch, load_state_dict)
    loaded = load_state_dict(cfg.initial_checkpoint)
    if cfg.split_bn:
        # plain-BN checkpoints fan out into main + aux BNs, like the
        # reference's load-then-convert order (split_batchnorm.py:41)
        loaded = expand_split_bn(loaded, variables)
    n_init = len(_flatten(variables))
    n_hit = len(set(_flatten(variables)) & set(_flatten(loaded)))
    variables, dropped = filter_shape_mismatch(variables, loaded)
    applied = n_hit - dropped
    if applied == 0:
        raise ValueError(
            f"--initial-checkpoint {cfg.initial_checkpoint} matches no "
            f"parameter of model {cfg.model!r} — wrong architecture?")
    _logger.info(
        "Loaded initial checkpoint %s: %d/%d leaves applied "
        "(%d shape-mismatched, %d missing keep their fresh init)",
        cfg.initial_checkpoint, applied, n_init, dropped, n_init - n_hit)
    return variables


def init_state(program: Program, rng, variables=None):
    """(placed TrainState, its sharding table) from a fresh init under
    ``rng``, or from the caller's ``variables`` (consumed)."""
    cfg, mesh = program.cfg, program.mesh
    if variables is None and program.sequence_task:
        # no parameter's shape depends on L, so a short row initializes it
        variables = init_model(program.model, rng, (1, 8), training=True,
                               dtype=jnp.int32)
    elif variables is None:
        c, h, w = program.input_size
        variables = init_model(program.model, rng, (1, h, w, c),
                               training=True)
    n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
    _logger.info("Model %s created, param count: %d", cfg.model, n_params)
    if cfg.initial_checkpoint:
        variables = _load_initial_checkpoint(cfg, variables)
    if cfg.tp_size > 1:
        # place params under the Megatron-paired TP shardings; non-matching
        # leaves (and non-transformer models) stay replicated
        variables = dict(variables)
        variables["params"] = jax.device_put(
            variables["params"], transformer_tp_sharding(
                variables["params"], mesh, axis="model"))
        _logger.info("Tensor parallelism: params sharded over 'model' "
                     "axis (tp_size=%d)", cfg.tp_size)
    state = create_train_state(variables, program.tx,
                               with_ema=cfg.model_ema)
    # the sharding-rule table (parallel/sharding.py): every TrainState leaf
    # gets its NamedSharding — params replicated/FSDP/TP per rule, opt
    # moments and EMA following their params, BN stats and step replicated
    # — and the state is laid onto the mesh accordingly.  Everything
    # downstream (the jitted step's in/out_shardings, checkpoint restore
    # re-layout, the guard's rewind template) reads layout from this one
    # table.
    state_shardings = train_state_shardings(
        state, mesh, fsdp=cfg.fsdp, axis=program.batch_axis)
    return place_train_state(state, state_shardings), state_shardings


def build_steps(program: Program, state_shardings):
    """(train_step, eval_step, eval_step_ema or None)."""
    cfg, model = program.cfg, program.model
    train_step = make_train_step(
        model, program.tx, program.loss_fn, mesh=program.mesh,
        axis=program.batch_axis, bn_mode=program.bn_mode,
        ema_decay=cfg.model_ema_decay if cfg.model_ema else 0.0,
        clip_grad=cfg.clip_grad, grad_accum=cfg.grad_accum,
        nonfinite_guard=cfg.guard_nonfinite == "skip",
        state_shardings=state_shardings)
    eval_step = make_eval_step(model, cross_entropy)
    eval_step_ema = make_eval_step(model, cross_entropy, use_ema=True) \
        if cfg.model_ema else None
    return train_step, eval_step, eval_step_ema


def build_loaders(program: Program, train_ds, eval_ds=None,
                  seed: Optional[int] = None):
    """(train_loader, eval_loader or None) over the caller's datasets.

    Loaders produce the *per-process* slice of the global batch; the device
    prologue assembles the global sharded array.  Eval is a single forward,
    so it does NOT inherit the accumulation factor: its batch is twice the
    per-step rows (reference train.py:492)."""
    cfg = program.cfg
    n_proc = jax.process_count()
    local_batch = program.global_batch // n_proc
    eval_local_batch = cfg.batch_size * program.dp * 2 // n_proc
    common = dict(
        num_workers=cfg.workers, seed=cfg.seed if seed is None else seed,
        sharding=batch_sharding(program.mesh), distributed=n_proc > 1,
        num_shards=n_proc, shard_index=jax.process_index(),
        prefetch_depth=cfg.prefetch_depth)
    if program.sequence_task:
        train_loader = create_token_loader(train_ds, local_batch,
                                           is_training=True, **common)
        eval_loader = create_token_loader(
            eval_ds, eval_local_batch, is_training=False, **common) \
            if eval_ds is not None else None
        return train_loader, eval_loader
    common.update(
        mean=program.data_config["mean"], std=program.data_config["std"],
        dtype=_dtype(cfg.compute_dtype), loader_backend=cfg.loader_backend,
        ring_depth=cfg.ring_depth, worker_heartbeat=cfg.worker_heartbeat,
        stem_s2d=cfg.stem_s2d)
    collate_mixup = FastCollateMixup(cfg.mixup, cfg.smoothing,
                                     cfg.num_classes) if cfg.mixup > 0 \
        else None
    train_loader = create_deepfake_loader_v3(
        train_ds, program.input_size, local_batch, is_training=True,
        re_prob=cfg.reprob, re_mode=cfg.remode, re_count=cfg.recount,
        re_split=cfg.resplit, re_max=cfg.remax,
        color_jitter=cfg.color_jitter,
        num_aug_splits=cfg.aug_splits, collate_mixup=collate_mixup,
        flicker=cfg.flicker, rotate_range=cfg.rotate_range,
        blur_radius=1, blur_prob=cfg.blur_prob,
        device_color_jitter=not cfg.host_color_jitter,
        fused_geom=not cfg.host_geom,
        augment_device=cfg.augment_device == "on", **common)
    eval_loader = create_deepfake_loader_v3(
        eval_ds, program.input_size, eval_local_batch, is_training=False,
        eval_crop=cfg.eval_crop, **common) if eval_ds is not None else None
    return train_loader, eval_loader


def build_telemetry(program: Program, state, train_loader,
                    output_dir: str = "", resilience=None):
    """The run's observability (obs/): the telemetry tracker with its JSONL
    event log (rank 0 — one coherent stream per run dir) and collectors,
    the optional ``--metrics-port`` Prometheus endpoint and the on-demand
    profiler capture.  Returns (telemetry, metrics server or None,
    profiler or None)."""
    from ..obs import (EventLog, ProfilerCapture, TrainTelemetry,
                       forward_flops_per_sample, loader_collector,
                       native_warp_collector, peak_flops,
                       resilience_collector, start_metrics_server)
    cfg, mesh, model = program.cfg, program.mesh, program.model
    # per-sample forward FLOPs for the live MFU gauge: an abstract jaxpr
    # walk over the shape the LOADER feeds the model — pixel-shuffled under
    # --stem-s2d (0 for sequence models: ROADMAP D13)
    fwd_flops = 0.0
    if not program.sequence_task:
        fwd_flops = forward_flops_per_sample(
            model, {"params": state.params,
                    "batch_stats": state.batch_stats},
            _model_input_shape(cfg, program.input_size, 1))
    event_log = EventLog(os.path.join(output_dir, "telemetry.jsonl")) \
        if output_dir and jax.process_index() == 0 else None
    telemetry = TrainTelemetry(
        event_log=event_log, flops_per_sample=fwd_flops,
        attn_tiles_per_sample=model.attn_tiles_visited(cfg.seq_len)
        if program.sequence_task else 0,
        ssd_chunks_per_sample=model.ssd_chunks(cfg.seq_len)
        if hasattr(model, "ssd_chunks") else 0,
        dw_grad_stages=program.dw_grad_stages,
        causal_conv_layers=program.causal_conv_layers,
        attn_bwd_layers=program.attn_bwd_layers,
        attn_fwd_saved_layers=program.attn_fwd_saved_layers,
        mla_layers=program.mla_layers,
        dsa_layers=program.dsa_layers,
        dsa_fwd_bits_layers=program.dsa_fwd_bits_layers,
        # throughput is measured on the GLOBAL batch (the loader
        # assembles the global sharded array), so the MFU denominator
        # is the whole MESH's peak — n_dev == mesh.size, which a
        # sub-mesh run may set below the visible device count
        peak_flops=peak_flops() * program.n_dev,
        meta=dict(model=cfg.model, global_batch=program.global_batch,
                  mesh_shape=[int(s) for s in mesh.shape.values()],
                  axis_names=list(mesh.axis_names)))
    telemetry.register_collector(loader_collector(train_loader))
    telemetry.register_collector(native_warp_collector())
    if resilience is not None:
        telemetry.register_collector(resilience_collector(resilience))
    obs_server = start_metrics_server(telemetry, port=cfg.metrics_port) \
        if cfg.metrics_port else None
    profiler = None
    if output_dir and cfg.profile_capture > 0:
        profiler = ProfilerCapture(output_dir, num_steps=cfg.profile_capture,
                                   telemetry=telemetry)
        telemetry.profiler = profiler
    return telemetry, obs_server, profiler


def open_run_dir(cfg: TrainConfig):
    """(output_dir, saver): the run directory with its config dump
    (reference :785-808, :527-532) and the checkpoint saver of the ranks
    that write; ("", None) on a rank that has neither."""
    rank = jax.process_index()
    if not (rank == 0 or cfg.ckpt_sharded or cfg.auto_resume):
        return "", None
    exp_name = cfg.experiment or "-".join(
        [cfg.model_version or cfg.model,
         os.path.basename(cfg.data.split(":")[0]) or cfg.dataset])
    # the sharded saver is COLLECTIVE: every rank drives it and all
    # must agree on the directory, so multi-process sharded runs skip
    # the auto-increment (a per-rank race) — name runs via --experiment.
    # --auto-resume equally needs a STABLE directory across relaunches
    # (the -N increment would "resume" into a fresh empty dir).
    multiproc_sharded = cfg.ckpt_sharded and jax.process_count() > 1
    output_dir = get_outdir(cfg.output, exp_name,
                            inc=not (multiproc_sharded or cfg.auto_resume))
    if multiproc_sharded and rank == 0 and not cfg.resume and \
            not cfg.auto_resume and \
            os.path.exists(os.path.join(output_dir, "args.yaml")):
        # inc=False means a rerun would silently overwrite the
        # previous run's checkpoints and records.  Rank 0 ONLY: other
        # ranks would race against rank 0's own args.yaml write of
        # THIS run; rank 0's failure propagates through the
        # coordination service
        raise ValueError(
            f"{output_dir} already holds a run; multi-process "
            "--ckpt-sharded disables output-dir auto-increment — "
            "name this run with --experiment, or --resume it")
    if rank == 0:
        with open(os.path.join(output_dir, "args.yaml"), "w") as f:
            f.write(cfg.to_yaml())
    saver = None
    if rank == 0 or cfg.ckpt_sharded:
        saver_cls = ShardedCheckpointSaver if cfg.ckpt_sharded \
            else CheckpointSaver
        saver = saver_cls(
            checkpoint_dir=output_dir,
            bak_dir=os.path.join(output_dir, "_bak"),
            decreasing=cfg.eval_metric == "loss")
    return output_dir, saver


def _resume(cfg: TrainConfig, state, output_dir: str):
    """``--resume`` and ``--auto-resume``: (state, start epoch, the meta of
    the run's own snapshot or None, that snapshot's path or "")."""
    start_epoch, auto_meta, resumed_from = cfg.start_epoch or 0, None, ""
    if cfg.resume:
        state, meta = restore_any(cfg.resume, state,
                                  load_opt=not cfg.no_resume_opt)
        start_epoch = cfg.start_epoch if cfg.start_epoch is not None \
            else int(meta.get("epoch", -1)) + 1   # helpers.py:47-73
        _logger.info("Resumed from %s (epoch %d)", cfg.resume, start_epoch)
    if cfg.auto_resume:
        # newer than any --resume argument when present: a relaunch after
        # preemption continues from its own recovery snapshot, not the
        # checkpoint the run was originally seeded from
        restored = restore_with_fallback(
            output_dir, state, load_opt=not cfg.no_resume_opt,
            sharded=cfg.ckpt_sharded)
        if restored is not None:
            state, auto_meta, resumed_from = restored
        else:
            _logger.info("--auto-resume: nothing to resume in %s; "
                         "starting fresh", output_dir)
    return state, start_epoch, auto_meta, resumed_from


def _enter_at(state, lr_scheduler, epoch: int, batch: int):
    """The state as the loop takes it at (epoch, batch), at start-up and
    after a rewind alike: a mid-epoch entry keeps the snapshot's injected
    LR exactly (it already carries any per-update scheduling); an
    epoch-boundary entry re-derives it like the reference
    (train.py:416-417).  Call it with the position ``resume_position``
    gave: a snapshot taken at the final batch of epoch E enters as
    (E+1, batch 0) and needs E+1's LR, not the snapshot's epoch-E value."""
    if lr_scheduler is not None and epoch > 0 and batch == 0:
        state = set_learning_rate(state, lr_scheduler.step(epoch))
    return state


def _rewind(cfg: TrainConfig, cause: RewindRequested, state, output_dir: str,
            resilience, telemetry, lr_scheduler, batches_per_epoch: int):
    """K consecutive bad steps: continuing would train on (or EMA-blend in)
    corrupted state — reload the last good snapshot and give the position
    to fast-forward back to.  Multi-process, the verdict was max-reduced
    in-band (Resilience.sync_verdicts at the drain cadence), so every host
    raises at the SAME boundary and the collective restore stays in
    lockstep.  Returns (state, epoch, batch)."""
    if jax.process_count() > 1 and not (cfg.ckpt_sharded or cfg.auto_resume):
        # rank != 0 has no output_dir on this layout (inc=True names are
        # rank-0-local), so a per-rank restore would diverge — one rank
        # reloading while others error is a guaranteed collective hang.
        # The config-derived condition is identical on every host: ALL
        # ranks abort in lockstep instead.
        raise RuntimeError(
            "guard rewind on a multi-process run needs a rank-agnostic "
            "run dir: relaunch with --auto-resume (+--experiment) or "
            "--ckpt-sharded") from cause
    resilience.start_rewind(str(cause))      # raises budget-spent
    # load_opt=True always: a rewind restores the run's OWN snapshot
    # (--no-resume-opt governs seeding from a foreign checkpoint), and the
    # --no-resume-opt substitution would copy opt/step leaves out of the
    # template — here the epoch-entry state, whose buffers the donating
    # train step already deleted
    restored = restore_with_fallback(output_dir, state, load_opt=True,
                                     sharded=cfg.ckpt_sharded)
    if restored is None:
        raise RuntimeError(
            "rewind requested but no loadable recovery snapshot exists — "
            "enable --recovery-interval so the guard has somewhere to "
            "rewind to") from cause
    state, meta, path = restored
    _logger.warning("rewound to %s", path)
    if telemetry is not None:
        telemetry.event("rewind", reason=str(cause), restored_from=path)
    epoch, batch = resume_position(meta, batches_per_epoch)
    return _enter_at(state, lr_scheduler, epoch, batch), epoch, batch


def _evaluate(cfg: TrainConfig, state, eval_step, eval_step_ema, eval_loader,
              resilience) -> Dict[str, float]:
    eval_metrics = validate(eval_step, state, eval_loader, cfg,
                            resilience=resilience)
    if eval_step_ema is not None:
        # EMA eval *replaces* the metrics (reference :563-569)
        eval_metrics = validate(eval_step_ema, state, eval_loader, cfg,
                                log_suffix=" (EMA)", resilience=resilience)
    return eval_metrics


def _record_epoch(cfg: TrainConfig, epoch: int, state, saver, meta,
                  train_metrics, eval_metrics, output_dir: str):
    """The epoch's row of summary.csv and its checkpoint.  Returns the
    saver's (best metric, best epoch), (None, None) without a saver."""
    if output_dir and jax.process_index() == 0:
        csv_path = os.path.join(output_dir, "summary.csv")
        # header iff the file doesn't exist yet: an epoch counter (the old
        # rule) or a process-local flag would append a second header
        # mid-file on every auto-resume relaunch, corrupting the CSV for
        # plot_csv/pandas
        update_summary(epoch, train_metrics, eval_metrics, csv_path,
                       os.path.join(output_dir, "plots"),
                       write_header=not os.path.exists(csv_path))
    # sharded saver: the collective save IS the cross-host path — no
    # gather. Otherwise multi-host TP/EP: every rank gathers model-sharded
    # leaves so rank 0 can serialize; no-op else
    collective = saver is not None and saver.collective
    save_state = replicate_for_save(state) \
        if jax.process_count() > 1 and not collective else state
    if saver is None:
        return None, None
    return saver.save_checkpoint(save_state, meta, epoch,
                                 metric=eval_metrics[cfg.eval_metric])


def main(cfg: TrainConfig) -> Dict[str, float]:
    """Train to completion; returns the best eval metrics."""
    # restarted runs skip the XLA compile wall — before the first compile
    setup_compile_cache(cfg.compile_cache_dir)
    program = build_program(cfg)
    mesh, lr_scheduler = program.mesh, program.lr_scheduler
    # ONE seed for every host: params are logically replicated, so init must
    # be identical everywhere (the reference's per-rank seed, train.py:299,
    # was safe only because DDP broadcast rank-0's weights; SPMD has no such
    # broadcast).  The unified step draws dropout noise over the GLOBAL
    # batch from one mesh-replicated key (the key is pinned replicated
    # before the loop below) — do NOT re-add a per-device fold; it would
    # break the replicated-key in_shardings contract.
    init_rng, rng = jax.random.split(jax.random.PRNGKey(cfg.seed))
    state, state_shardings = init_state(program, init_rng)

    # the run directory comes BEFORE resume handling so --auto-resume can
    # consult its recovery snapshots at startup
    output_dir, saver = open_run_dir(cfg)
    state, start_epoch, auto_meta, resumed_from = _resume(cfg, state,
                                                          output_dir)

    train_ds, eval_ds = build_datasets(
        cfg, program.input_size, pack_dir=program.data_config.get("pack_dir"),
        pack_image_size=program.data_config.get("pack_image_size"),
        vocab_rows=getattr(program.model, "vocab_rows", 0))
    train_loader, eval_loader = build_loaders(program, train_ds, eval_ds)
    train_step, eval_step, eval_step_ema = build_steps(program,
                                                       state_shardings)
    resume_batch = 0
    if auto_meta is not None:
        start_epoch, resume_batch = resume_position(auto_meta,
                                                    len(train_loader))
        _logger.info("Auto-resumed from %s (epoch %d, batch %d)",
                     resumed_from, start_epoch, resume_batch)
    state = _enter_at(state, lr_scheduler, start_epoch, resume_batch)

    if jax.process_count() > 1:
        # all host-side setup (datasets, eager init, output dir) is done —
        # meet here so a fast rank doesn't reach the first collective while
        # a slow one is still initializing: cross-process collective-context
        # creation (gloo on CPU; similar rendezvous on DCN) has a short
        # deadline that host-side skew alone can blow
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("train_start")

    # the jitted step declares its rng argument replicated over the mesh
    # (in_shardings); fold_in of a mesh-replicated key yields another
    # mesh-replicated key, so one placement here covers every step of the
    # run (a committed single-device key would be an in_shardings
    # mismatch).  own_and_place owns the bytes and covers multi-host,
    # where every process holds the same host key.
    rng = own_and_place(np.asarray(rng), replicated_sharding(mesh))

    meta = {"arch": cfg.model, "version": 2}
    best_metric, best_epoch = None, None
    eval_metrics: Dict[str, float] = {}
    exit_code: Optional[int] = None
    resilience = Resilience.from_config(cfg, output_dir=output_dir)
    telemetry, obs_server, profiler = None, None, None
    if not cfg.no_telemetry:                       # default-on
        telemetry, obs_server, profiler = build_telemetry(
            program, state, train_loader, output_dir, resilience)
        telemetry.event("run_start", model=cfg.model,
                        epochs=program.num_epochs, start_epoch=start_epoch,
                        global_batch=program.global_batch,
                        world_size=program.n_dev,
                        mesh_shape=[int(s) for s in mesh.shape.values()],
                        axis_names=list(mesh.axis_names),
                        dw_grad_kernel_stages=program.dw_grad_stages[0],
                        dw_grad_xla_stages=program.dw_grad_stages[1],
                        causal_conv_kernel_layers=program
                        .causal_conv_layers[0],
                        causal_conv_xla_layers=program.causal_conv_layers[1],
                        moe_kernel_layers=program.moe_layers[0],
                        moe_xla_layers=program.moe_layers[1],
                        attn_fused_bwd_layers=program.attn_bwd_layers[0],
                        attn_split_bwd_layers=program.attn_bwd_layers[1],
                        attn_fwd_saved_layers=program.attn_fwd_saved_layers,
                        mla_layers=program.mla_layers,
                        dsa_layers=program.dsa_layers,
                        dsa_fwd_bits_layers=program.dsa_fwd_bits_layers)
        if resumed_from:
            telemetry.event("resume", path=resumed_from,
                            epoch=start_epoch, batch=resume_batch)
    try:
        with resilience:
            if profiler is not None and not profiler.install():
                _logger.warning("not in the main thread: SIGUSR2 profiler "
                                "trigger not installed (the PROFILE file "
                                "trigger still works)")
            epoch = start_epoch
            while epoch < program.num_epochs:
                train_loader.set_epoch(epoch)      # reference :549
                if resume_batch:
                    train_loader.fast_forward(resume_batch)
                epoch_rng = jax.random.fold_in(rng, epoch)
                # note, not heartbeat: a beat here would end the watchdog's
                # first-compile grace window before the first step compiles
                resilience.note(f"epoch {epoch} start "
                                f"(batch {resume_batch})")
                try:
                    state, train_metrics = train_one_epoch(
                        epoch, train_step, state, train_loader, cfg,
                        epoch_rng, lr_scheduler=lr_scheduler, saver=saver,
                        output_dir=output_dir, meta=meta,
                        world_size=program.n_dev, start_batch=resume_batch,
                        resilience=resilience, telemetry=telemetry)
                except RewindRequested as e:
                    state, epoch, resume_batch = _rewind(
                        cfg, e, state, output_dir, resilience, telemetry,
                        lr_scheduler, len(train_loader))
                    continue
                resume_batch = 0

                eval_metrics = _evaluate(cfg, state, eval_step, eval_step_ema,
                                         eval_loader, resilience)
                if lr_scheduler is not None:
                    new_lr = lr_scheduler.step(
                        epoch + 1, eval_metrics[cfg.eval_metric])  # :571-573
                    state = set_learning_rate(state, new_lr)
                best_metric, best_epoch = _record_epoch(
                    cfg, epoch, state, saver, meta, train_metrics,
                    eval_metrics, output_dir)
                if telemetry is not None:
                    telemetry.event("epoch_end", epoch=epoch,
                                    train=dict(train_metrics),
                                    eval=dict(eval_metrics))
                resilience.heartbeat(f"epoch {epoch} done")
                epoch += 1
    except Preempted as e:
        # the recovery snapshot is already on disk (written synchronously
        # at the step boundary); exit with the distinct preemption code so
        # scripts/train.sh's restart wrapper relaunches into --auto-resume
        _logger.warning("%s — exiting with code %d", e, EXIT_PREEMPTED)
        exit_code = EXIT_PREEMPTED
        if telemetry is not None:
            telemetry.event("preempted", epoch=e.epoch, batch=e.batch_idx,
                            signum=e.signum)
    except KeyboardInterrupt:                      # reference :588
        pass
    finally:
        # shm-backend loaders own worker processes + a shared-memory
        # segment; release them even on interrupt (thread backend: no-op),
        # and flush any in-flight async recovery write on EVERY exit path
        # — flushing after this block skipped it on exceptions, silently
        # discarding the newest snapshot
        train_loader.close()
        eval_loader.close()
        wait_pending_saves()
        if profiler is not None:
            profiler.close()            # stops a live trace, restores SIGUSR2
        if obs_server is not None:
            obs_server.shutdown()
            obs_server.server_close()
        if telemetry is not None:
            telemetry.event("run_end", exit_code=exit_code,
                            best_metric=best_metric, best_epoch=best_epoch)
            telemetry.close()
    if exit_code is not None:
        raise SystemExit(exit_code)
    if best_metric is not None:
        _logger.info("*** Best metric: %s (epoch %s)", best_metric,
                     best_epoch)
    return {"best_metric": best_metric, "best_epoch": best_epoch,
            **eval_metrics}


def _looks_like_torch_checkpoint(path: str) -> bool:
    """Lexical suffixes torch users actually ship (.pth/.pt/.tar/.bin and
    compounds), plus a magic sniff for existing files: torch's zip format
    starts 'PK\\x03\\x04', its legacy format is a protocol-2+ pickle
    (0x80 0x02..0x05 — a flax msgpack stream can't start with that pair:
    0x80 is the EMPTY fixmap).  Cheap, runs before mesh construction."""
    if not path:
        return False
    if path.endswith((".pth", ".pth.tar", ".pt", ".tar", ".bin")):
        return True
    try:
        with open(path, "rb") as f:
            magic = f.read(4)
    except OSError:
        return False
    return magic[:4] == b"PK\x03\x04" or (
        len(magic) >= 2 and magic[0] == 0x80 and 2 <= magic[1] <= 5)


def launch_main(argv=None) -> Dict[str, float]:
    """CLI entry (reference launch_main, train.py:769-816)."""
    setup_default_logging()
    cfg = TrainConfig.from_args(argv)
    if _looks_like_torch_checkpoint(cfg.initial_checkpoint):
        # fail before mesh construction and the jitted
        # init, not minutes into main() with a cryptic msgpack error
        raise ValueError(
            f"--initial-checkpoint {cfg.initial_checkpoint} is a torch "
            "checkpoint; convert it first: python "
            "tools/convert_torch_checkpoint.py <file> <out.msgpack> "
            f"--model {cfg.model} --verify")
    if cfg.json_file:
        cluster = ClusterConfig.from_json(cfg.json_file)
        initialize_distributed(cluster, local_rank=cfg.local_rank)
    return main(cfg)


def cli(argv=None) -> None:
    """Console-script entry: discard launch_main's metrics dict so the
    setuptools wrapper's ``sys.exit(...)`` sees None (exit 0)."""
    launch_main(argv)


if __name__ == "__main__":
    launch_main(sys.argv[1:])
