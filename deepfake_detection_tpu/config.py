"""Configuration system.

Replaces the reference's three config mechanisms with one dataclass tree:

* the ~60-flag argparse surface (``/root/reference/dfd/runners/train.py:55-235``),
* the two-stage ``--config`` YAML-overrides-defaults parse (``train.py:238-249``),
* the cluster-topology JSON (``/root/reference/dfd/server_json.py``).

Every field keeps the reference flag's name (dashes→underscores) and default so
a reference user can map their launch scripts 1:1.  ``TrainConfig.from_args``
reproduces the two-stage semantics: YAML file (if given) resets defaults, CLI
flags override YAML.  The resolved config serialises back to YAML
(``args.yaml`` parity, ``train.py:251-253``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import socket
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List, Optional, Sequence, Tuple

try:
    import yaml
    _HAS_YAML = True
except ImportError:  # pragma: no cover
    _HAS_YAML = False


# ---------------------------------------------------------------------------
# Cluster topology (server_json.py parity)
# ---------------------------------------------------------------------------

@dataclass
class ServerSpec:
    """One host in the cluster map (``server_json.py:25-45``)."""
    name: str
    gpus: str = ""           # kept for config-file compatibility; unused on TPU
    local_size: int = 1      # processes on this host
    start_rank: int = 0      # first global process index on this host


@dataclass
class ClusterConfig:
    """Topology for multi-host runs.

    On TPU pods ``jax.distributed.initialize`` discovers topology natively, so
    this config is only needed to (a) run the same JSON files the reference
    shipped (``scripts/train_server_config.json``) and (b) drive explicit
    coordinator-based init on non-pod clusters.
    """
    servers: List[ServerSpec] = field(default_factory=list)
    world_size: int = 1
    share_file: str = ""                 # legacy rendezvous file (unused)
    coordinator_address: Optional[str] = None  # "host:port" for jax.distributed

    @classmethod
    def from_json(cls, path: str) -> "ClusterConfig":
        with open(path) as f:
            raw = json.load(f)
        servers = [ServerSpec(
            name=s.get("name", ""),
            gpus=str(s.get("gpus", "")),
            local_size=int(s.get("local_size", 1)),
            start_rank=int(s.get("start_rank", 0)),
        ) for s in raw.get("servers", [])]
        return cls(servers=servers,
                   world_size=int(raw.get("world_size", 1)),
                   share_file=raw.get("share_file", ""),
                   coordinator_address=raw.get("coordinator_address"))

    def local_spec(self, hostname: Optional[str] = None) -> ServerSpec:
        """Match this host against the server map (``server_json.py:29-30``)."""
        hostname = hostname or socket.gethostname()
        for s in self.servers:
            if s.name == hostname:
                return s
        raise LookupError(
            f"hostname {hostname!r} not found in cluster config "
            f"(servers: {[s.name for s in self.servers]})")

    def process_id(self, hostname: Optional[str] = None, local_rank: int = 0) -> int:
        return self.local_spec(hostname).start_rank + local_rank


# ---------------------------------------------------------------------------
# Training config (train.py argparse parity)
# ---------------------------------------------------------------------------

def _tuple_of_ints(s) -> Optional[Tuple[int, ...]]:
    """Parse ``--input-size-v2 "12,600,600"`` style strings (config.py:17-21)."""
    if s is None or s == "":
        return None
    if isinstance(s, (tuple, list)):
        return tuple(int(x) for x in s)
    return tuple(int(x) for x in str(s).split(","))


# ---------------------------------------------------------------------------
# Shared dataclass→CLI machinery (TrainConfig + ServeConfig): one flag per
# field (dashes), bools as store_true, and the reference's two-stage parse
# semantics — a ``-c`` YAML file resets defaults, CLI flags override it.
# ---------------------------------------------------------------------------

def _convert_field(field_, v):
    """Coerce a CLI string to the field's annotated type (defaults of
    ``None`` carry no type, so the annotation is authoritative)."""
    ann = str(field_.type)
    default = field_.default
    if isinstance(default, bool) or ann == "bool":
        return bool(v)
    if not isinstance(v, str):
        return v
    if "Tuple[float" in ann:
        return tuple(float(x) for x in v.split(","))
    if "Tuple[int" in ann:
        return _tuple_of_ints(v)
    if "Tuple[str" in ann:
        return tuple(x for x in v.split(",") if x)
    if "float" in ann or isinstance(default, float):
        return float(v)
    if "int" in ann or (isinstance(default, int)
                        and not isinstance(default, bool)):
        return int(v)
    return v


def _dataclass_parser(cls, description: str) -> argparse.ArgumentParser:
    """Argparse surface generated from a config dataclass."""
    p = argparse.ArgumentParser(description=description)
    p.add_argument("-c", "--config", default="", metavar="FILE",
                   help="YAML config; its values reset defaults, CLI "
                        "overrides")
    for f_ in fields(cls):
        flag = "--" + f_.name.replace("_", "-")
        if f_.type == "bool" or isinstance(f_.default, bool):
            p.add_argument(flag, action="store_true", default=None,
                           dest=f_.name)
            continue
        p.add_argument(flag, default=None, dest=f_.name)
    return p


def _two_stage_parse(cls, argv: Optional[Sequence[str]],
                     parser: argparse.ArgumentParser):
    """YAML resets defaults, CLI overrides (train.py:238-249)."""
    ns, _ = parser.parse_known_args(argv)
    base = cls.from_yaml(ns.config) if ns.config else cls()
    out = dataclasses.asdict(base)
    hints = {f_.name: f_ for f_ in fields(cls)}
    for k, v in vars(ns).items():
        if k == "config" or v is None or k not in hints:
            continue
        out[k] = _convert_field(hints[k], v)
    return cls.from_dict(out)


@dataclass
class TrainConfig:
    # --- data ---
    data: str = ""                       # root dir(s), ':'-separated for multi-dir
    eval_data: str = ""                  # separate eval root(s); default: split from train
    dataset: str = "deepfake_v3"         # deepfake_v3 | folder | synthetic
    # | synthetic-tokens | tokens (rows of int32 ids from --data FILE): the
    # sequence models' datasets (data/tokens.py)
    seq_len: int = 0                     # tokens a row, for the token datasets
    train_split: float = 0.95            # seeded train/val split fraction
    split_seed: int = 42
    label_balance: bool = False          # fake-bucket balancing (dataset.py:460-491)
    noise_fake: float = 0.0              # label-flip prob for fakes (dataset.py:520-521)
    img_num: int = 4                     # frames per clip
    workers: int = 8
    pin_memory: bool = False
    prefetch_depth: int = 2
    # host input-pipeline backend: 'thread' = in-process pool (GIL-release
    # scaling), 'shm' = spawned worker processes writing into a shared-
    # memory ring of batch slabs (zero-copy collate; data/shm_ring.py)
    loader_backend: str = "thread"
    ring_depth: int = 4                  # shm backend: batch slabs in flight
    worker_heartbeat: float = 120.0      # shm backend: stalled-worker kill (s)
    # packed pre-decoded dataset cache (tools/pack_dataset.py): mmap-read
    # fixed-stride uint8 clips instead of decoding JPEGs every epoch.
    # Replaces the decode STAGE only — composes with either loader backend,
    # and batches are bit-identical to the decode path at matching pack
    # resolution (data/packed.py)
    data_packed: str = ""                # pack dir ("" = decode JPEGs)
    pack_image_size: int = 0             # expected pack resolution (0 = any)

    # --- model ---
    model: str = "efficientnet_deepfake_v4"
    model_version: str = "v4"            # create_deepfake_model | _v3 | _v4 selection
    pretrained: bool = False
    initial_checkpoint: str = ""
    resume: str = ""
    no_resume_opt: bool = False
    # sharded (Orbax) checkpointing: collective per-host shard writes +
    # resharding restore — no rank-0 full-model gather (beyond reference)
    ckpt_sharded: bool = False
    num_classes: int = 2
    gp: str = "avg"                      # global pool: avg|max|avgmax|catavgmax
    in_chans: Optional[int] = None       # derived from input_size if None
    drop: float = 0.0
    drop_path: Optional[float] = None
    drop_block: Optional[float] = None
    bn_tf: bool = False
    bn_momentum: Optional[float] = None
    bn_eps: Optional[float] = None

    # --- input geometry ---
    input_size: Optional[Tuple[int, ...]] = None      # (C,H,W) — reference order
    input_size_v2: Optional[Tuple[int, ...]] = None   # (12,600,600) string flag
    img_size: Optional[int] = None
    crop_pct: Optional[float] = None
    mean: Optional[Tuple[float, ...]] = None
    std: Optional[Tuple[float, ...]] = None
    interpolation: str = ""

    # --- optimization ---
    opt: str = "rmsproptf"
    opt_eps: float = 1e-8
    opt_beta2: Optional[float] = None    # adam/adamw b2; None: optax's 0.999
    momentum: float = 0.9
    weight_decay: float = 1e-5
    lr: Optional[float] = None           # if None: batch*world*basic_lr (train.py:814)
    basic_lr: float = 5e-7
    sched: str = "step"
    epochs: int = 200
    start_epoch: Optional[int] = None
    decay_epochs: float = 2.0
    decay_rate: float = 0.92
    warmup_lr: float = 1e-4
    warmup_epochs: int = 0
    cooldown_epochs: int = 10
    patience_epochs: int = 10
    lr_noise: Optional[Tuple[float, ...]] = None
    lr_noise_pct: float = 0.67
    lr_noise_std: float = 1.0
    lr_cycle_mul: float = 1.0
    lr_cycle_limit: int = 1
    min_lr: float = 1e-5
    batch_size: int = 3
    clip_grad: Optional[float] = None

    # --- augmentation ---
    no_aug: bool = False
    scale: Tuple[float, float] = (0.08, 1.0)
    ratio: Tuple[float, float] = (3. / 4., 4. / 3.)
    hflip: float = 0.5
    vflip: float = 0.0
    color_jitter: float = 0.4
    aa: Optional[str] = None             # AutoAugment / RandAugment policy string
    aug_splits: int = 0
    jsd: bool = False
    reprob: float = 0.0                  # RandomErasing prob
    remode: str = "const"
    recount: int = 1
    remax: float = 0.4                   # max erase area fraction
    resplit: bool = False
    mixup: float = 0.0
    mixup_off_epoch: int = 0
    smoothing: float = 0.1
    train_interpolation: str = "random"
    # multi-frame (deepfake) specific
    rotate_range: float = 0.0
    blur_prob: float = 0.0
    flicker: float = 0.0
    # 'on' moves the remaining host augment — the fused geometric warp,
    # per-frame Gaussian blur, and the mixup blend — into the loader's
    # jitted device prologue, keyed by the same absolute (seed, epoch,
    # index) RNG streams (data/device_augment.py); the host then only
    # memcpys raw source clips into slabs.  'off' keeps the host chain
    # (the parity escape hatch).  Host-only stages (AugMix aug-splits,
    # hue jitter) fall back to the host chain with a log line.
    augment_device: str = "off"

    # --- batch norm ---
    sync_bn: bool = False
    # '' | 'broadcast' | 'reduce' — accepted for launch-script parity; the
    # TPU build pmean's BN stats inside every step (train/steps.py), which
    # strictly supersedes the reference's per-epoch distribute_bn
    dist_bn: str = ""

    split_bn: bool = False

    # --- EMA ---
    model_ema: bool = False
    model_ema_decay: float = 0.9998

    # --- precision / compile ---
    amp: bool = False                    # reference flag; maps to bf16 compute on TPU
    compute_dtype: str = "bfloat16"      # bfloat16 | float32
    param_dtype: str = "float32"

    # --- fault tolerance (train/resilience.py) ---
    # consult the run dir's recovery snapshots at startup and fast-forward
    # to the exact (epoch, batch) loop position (bit-continuous resume);
    # implies a STABLE output dir (no -N auto-increment) — name runs with
    # --experiment when launching many
    auto_resume: bool = False
    # non-finite loss/grad-norm policy inside the jitted step:
    # 'skip' selects the pre-step state (params/moments/EMA/stats
    # untouched), 'off' reproduces the reference (poisoned update applied)
    guard_nonfinite: str = "skip"
    guard_spike_window: int = 0     # rolling robust-stats window (0 = off)
    guard_spike_zmax: float = 8.0   # spike threshold in MAD-scaled z units
    guard_rewind_after: int = 3     # K consecutive bad steps → rewind
    guard_rewind_limit: int = 2     # rewind budget per run
    # seconds without a completed step before the stall watchdog dumps all
    # thread stacks and aborts with exit code 85 (0 = off)
    watchdog_timeout: float = 0.0

    # --- observability (deepfake_detection_tpu/obs) ---
    # the telemetry tracker (per-step time breakdown, throughput/MFU
    # gauges, JSONL event log in the run dir) is DEFAULT ON — it rides the
    # existing drain cadence with zero extra device syncs; this opts out
    no_telemetry: bool = False
    # stdlib trainer HTTP endpoint: GET /metrics (Prometheus text) +
    # /healthz while the run is live (0 = off)
    metrics_port: int = 0
    # on-demand profiler capture window, in steps: SIGUSR2 or
    # `touch <outdir>/PROFILE` traces the next N steps on a RUNNING job,
    # rank-0-gated (0 disables the triggers)
    profile_capture: int = 20

    # --- misc / infra ---
    # jax persistent compilation cache dir; JAX_COMPILATION_CACHE_DIR wins
    # over it, "" = <checkout>/.jax_cache (utils/compile_cache.py): repeat
    # runs of an unchanged (program, jax/jaxlib, backend, topology) skip XLA
    # backend compilation — re-tracing/lowering still happens, which is
    # why serving layers an AOT executable store on top (PERF.md §9)
    compile_cache_dir: str = ""
    seed: int = 42
    log_interval: int = 50
    profile: int = 0      # trace N train steps with jax.profiler (SURVEY §5)
    recovery_interval: int = 0
    save_images: bool = False
    output: str = "./output"
    eval_metric: str = "loss"
    eval_crop: str = "random"  # random = reference parity; center = deterministic eval
    # host-pipeline parity escape hatches (default: TPU-fast paths — one
    # native warp for the geometric chain, jitter/flicker on device)
    host_color_jitter: bool = False
    host_geom: bool = False
    tta: int = 0
    use_multi_epochs_loader: bool = False
    json_file: str = ""                  # cluster topology JSON
    local_rank: int = 0
    experiment: str = ""

    # --- parallelism (TPU-native; no reference analog) ---
    # default mesh: the unified 2-D ('batch': n_devices, 'model': 1) GSPMD
    # mesh (parallel/mesh.py make_train_mesh); explicit --mesh-shape/
    # --mesh-axes select a legacy layout verbatim
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axes: Tuple[str, ...] = ("data",)
    fsdp: bool = False          # shard params (+moments/EMA) over the
    # batch axis per the sharding-rule table (train_state_shardings)
    grad_accum: int = 1  # microbatches accumulated per optimizer step
    tp_size: int = 1     # model-axis extent for transformer tensor
    # parallelism: builds a (data, model) 2-D mesh and applies the
    # Megatron-paired shardings from parallel/tp.py (ViT/TimeSformer)
    # remat policy (models/helpers.py:maybe_remat): none | full (each layer
    # computed again in the backward) | dots (keeps matmul/conv outputs);
    # full and dots also keep the flash attention op's output and one
    # float32 a row of its statistics, so its forward kernel runs once
    checkpoint_policy: str = "none"
    # transformer attention kernel: "" = model default (full). 'flash' runs
    # the Pallas kernels; 'ring'/'ring_flash'/'ulysses' are sequence-
    # parallel and need an sp mesh — library-level for now (models/vit.py)
    attn_impl: str = ""
    # --- step-time optimization layer (PERF.md post-fusion roofline) ---
    # 'pallas' routes the EfficientNet-family dw → BN → act stages through
    # the fused VMEM-resident kernel (ops/depthwise_pallas.py); 'off' keeps
    # the stock XLA lowering.  Numerically equivalent either way (≤2 ulp,
    # tests/test_depthwise_pallas.py); the parameter tree is identical.
    fused_depthwise: str = "off"
    # rewrite the stride-2 stem as a stride-1 conv over 2×2 pixel-shuffled
    # input (MLPerf s2d trick) — the shuffle runs in the DeviceLoader
    # prologue; checkpoints stay bit-compatible via a pure weight reshape
    stem_s2d: bool = False

    # ------------------------------------------------------------------
    def __post_init__(self):
        for f_ in ("input_size", "input_size_v2", "lr_noise"):
            v = getattr(self, f_)
            if isinstance(v, str):
                setattr(self, f_, _tuple_of_ints(v) if f_ != "lr_noise"
                        else tuple(float(x) for x in v.split(",")))
        if isinstance(self.scale, list):
            self.scale = tuple(self.scale)
        if isinstance(self.ratio, list):
            self.ratio = tuple(self.ratio)
        if int(self.grad_accum) < 1:
            raise ValueError(f"--grad-accum must be >= 1, "
                             f"got {self.grad_accum}")
        if self.checkpoint_policy not in ("none", "full", "dots"):
            raise ValueError("checkpoint_policy must be none|full|dots, got "
                             f"{self.checkpoint_policy!r}")
        if self.loader_backend not in ("thread", "shm"):
            raise ValueError("loader_backend must be thread|shm, got "
                             f"{self.loader_backend!r}")
        if self.guard_nonfinite not in ("off", "skip"):
            raise ValueError("guard_nonfinite must be off|skip, got "
                             f"{self.guard_nonfinite!r}")
        if self.augment_device not in ("off", "on"):
            raise ValueError("augment_device must be off|on, got "
                             f"{self.augment_device!r}")
        if self.augment_device == "on" and self.host_geom:
            raise ValueError("--augment-device on renders the geometric "
                             "warp on device; it conflicts with the "
                             "--host-geom parity escape hatch — pick one")
        if self.augment_device == "on" and self.host_color_jitter:
            raise ValueError("--augment-device on leaves no host transform "
                             "stage for --host-color-jitter to run in — "
                             "pick one")
        if self.fused_depthwise not in ("off", "pallas"):
            raise ValueError("fused_depthwise must be off|pallas, got "
                             f"{self.fused_depthwise!r}")
        if int(self.ring_depth) < 3:
            raise ValueError("--ring-depth must be >= 3 (double buffering "
                             f"needs one spare slab), got {self.ring_depth}")
        if int(self.pack_image_size) < 0:
            raise ValueError("--pack-image-size must be >= 0, got "
                             f"{self.pack_image_size}")
        if self.pack_image_size and not self.data_packed:
            raise ValueError("--pack-image-size only makes sense with "
                             "--data-packed (it asserts the pack's "
                             "resolution, not a resize)")
        if not 0 <= int(self.metrics_port) <= 65535:
            raise ValueError(f"--metrics-port must be 0..65535, got "
                             f"{self.metrics_port}")
        if int(self.profile_capture) < 0:
            raise ValueError(f"--profile-capture must be >= 0, got "
                             f"{self.profile_capture}")

    # ------------------------------------------------------------------
    @property
    def resolved_input_size(self) -> Tuple[int, int, int]:
        """(C, H, W) with the v2 string flag taking priority (config.py:12-24)."""
        if self.input_size_v2:
            return tuple(self.input_size_v2)  # type: ignore
        if self.input_size:
            return tuple(self.input_size)     # type: ignore
        if self.img_size:
            return (3, self.img_size, self.img_size)
        return (3, 224, 224)

    @property
    def resolved_in_chans(self) -> int:
        return self.in_chans if self.in_chans is not None else self.resolved_input_size[0]

    def resolved_lr(self, world_size: int) -> float:
        """Linear LR scaling rule (``train.py:814``)."""
        if self.lr is not None:
            return self.lr
        return self.batch_size * world_size * self.basic_lr

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_yaml(self) -> str:
        if _HAS_YAML:
            return yaml.safe_dump(self.to_dict(), default_flow_style=False)
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TrainConfig":
        known = {f_.name for f_ in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_yaml(cls, path: str) -> "TrainConfig":
        with open(path) as f:
            if _HAS_YAML:
                d = yaml.safe_load(f)
            else:
                d = json.load(f)
        return cls.from_dict(d or {})

    # ------------------------------------------------------------------
    @classmethod
    def argument_parser(cls) -> argparse.ArgumentParser:
        """Argparse surface generated from the dataclass (flag-name parity)."""
        p = _dataclass_parser(cls, "TPU deepfake-detection training")
        p.add_argument("-b", dest="batch_size", default=None)
        return p

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "TrainConfig":
        """Two-stage parse: YAML resets defaults, CLI overrides (train.py:238-249)."""
        return _two_stage_parse(cls, argv, cls.argument_parser())


# ---------------------------------------------------------------------------
# Serving config (runners/serve.py)
# ---------------------------------------------------------------------------

#: serving PTQ dtypes (canonical + accepted aliases; serving/quant.py
#: owns the transform — config stays jax-free so only the names live here)
_QUANT_DTYPES = {"f32": "f32", "float32": "f32",
                 "bf16": "bf16", "bfloat16": "bf16", "int8": "int8"}


def _canon_quant_dtype(s: str, flag: str) -> str:
    try:
        return _QUANT_DTYPES[str(s).lower()]
    except KeyError:
        raise ValueError(f"{flag} must be one of f32|bf16|int8 (aliases "
                         f"float32, bfloat16), got {s!r}") from None


def parse_model_spec(spec: str, *, default_size: int,
                     default_img_num: int) -> Dict[str, Any]:
    """One ``--models`` entry → spec dict.

    Grammar: ``id=family[,path=CKPT][,size=N][,img_num=K][,dtype=D]
    [,reload=DIR]`` — the first token names the table id and the model
    family; the rest override the primary model's geometry/dtype
    defaults.  Example::

        student=mobilenetv3_small_100,size=224,dtype=int8
    """
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if not parts or "=" not in parts[0]:
        raise ValueError(f"--models entry {spec!r} must start with "
                         f"id=family")
    model_id, family = parts[0].split("=", 1)
    out: Dict[str, Any] = {"id": model_id.strip(),
                           "family": family.strip(), "path": "",
                           "size": int(default_size),
                           "img_num": int(default_img_num),
                           "dtype": "f32", "reload": ""}
    if not out["id"] or not out["family"]:
        raise ValueError(f"--models entry {spec!r}: empty id or family")
    for part in parts[1:]:
        if "=" not in part:
            raise ValueError(f"--models entry {spec!r}: {part!r} is not "
                             f"key=value")
        k, v = part.split("=", 1)
        k, v = k.strip(), v.strip()
        if k == "path" or k == "reload":
            out[k] = v
        elif k == "size" or k == "img_num":
            out[k] = int(v)
            if out[k] < 1:
                raise ValueError(f"--models entry {spec!r}: {k} must be "
                                 f">= 1")
        elif k == "dtype":
            out[k] = _canon_quant_dtype(v, f"--models {out['id']} dtype")
        else:
            raise ValueError(f"--models entry {spec!r}: unknown key "
                             f"{k!r} (path|size|img_num|dtype|reload)")
    return out


@dataclass
class ServeConfig:
    """Knob surface of the dynamic-batching inference server.

    Same conventions as :class:`TrainConfig`: every field is a
    ``--dashed-flag``, a YAML ``-c`` file resets defaults, CLI overrides.
    The batch **buckets** are the compile cache: every entry is AOT-warmed
    at startup and every device call pads to one of them — a request mix
    can never trigger a mid-traffic recompile.
    """
    # --- network ---
    host: str = "127.0.0.1"
    port: int = 8377

    # --- model (mirrors runners/test.py) ---
    model: str = "efficientnet_deepfake_v4"
    model_path: str = ""                 # msgpack file or sharded ckpt dir;
    # empty serves a seed-0 random init (bench/demo, like test.py)
    use_ema: bool = False                # prefer the EMA stream on load
    image_size: int = 600                # canvas side (params.py flagship 600)
    img_num: int = 4                     # frame replication => in_chans 3*num
    num_classes: int = 2

    # host→device wire format: 'float32' ships the fully CLI-preprocessed
    # tensor (server scores == runners/test.py bit-for-bit); 'uint8' ships
    # the uint8 canvas and normalizes/replicates inside the batched device
    # call (4·img_num× less transfer; ulp-level drift vs the CLI)
    wire: str = "float32"
    # multi-frame clips on the uint8 wire need a SECOND compiled
    # executable per bucket (≈2× warmup); a deployment that only ever
    # scores single frames can opt out (float32 wire serves clips for
    # free either way, so this flag is a no-op there)
    single_frame_only: bool = False

    # --- post-training quantization (serving/quant.py) ---
    # serving dtype of the PRIMARY model's device-resident weights:
    # 'f32' = reference parity, 'bf16' = params cast, 'int8' = weight-only
    # per-output-channel symmetric kernels, dequant fused into the
    # compiled call.  Checkpoints on disk (incl. hot reloads) stay f32;
    # tools/quant_parity.py measures the score drift/AUC bounds
    dtype: str = "f32"

    # --- multi-model serving (ISSUE 14) ---
    # extra model-table entries, ';'-separated specs:
    #   id=family[,path=CKPT][,size=N][,img_num=K][,dtype=D][,reload=DIR]
    # every entry is AOT-warmed before /readyz; POST /score routes via
    # its 'model' field / ?model= query param (default: the flagship)
    models: str = ""

    # --- two-tier cascade (serving/cascade.py) ---
    # model-table id of the triage student ("" = no cascade).  When set,
    # un-routed requests score student-first; student fake scores inside
    # [cascade_low, cascade_high] escalate to the flagship, everything
    # else returns the student verdict.  The student must share the
    # flagship's img_num (same clips flow through both tiers)
    cascade: str = ""
    cascade_low: float = 0.2
    cascade_high: float = 0.8

    # --- micro-batching / compile cache ---
    buckets: Tuple[int, ...] = (1, 4, 16, 64)
    batch_deadline_ms: float = 5.0       # partial-batch flush window
    max_queue: int = 128                 # load-shed (429) past this depth
    request_timeout_ms: float = 2000.0   # per-request deadline (504)

    # --- hot weight reload ---
    reload_dir: str = ""                 # "" disables the watcher
    reload_interval_s: float = 5.0
    # golden-batch canary score-drift tolerance for hot reloads: new
    # weights whose canary scores move more than this (max abs diff vs
    # the serving weights on the same input) are rejected; < 0 disables
    # the drift gate (finiteness + shape always gate)
    reload_drift_tol: float = -1.0

    # --- resilience (serving/resilience.py) ---
    # stuck-batch watchdog: a device batch older than this fails its
    # requests 503, restarts the engine worker and re-warms every bucket
    # (readiness drops until done); 0 disables
    watchdog_timeout_s: float = 30.0
    # circuit breaker: this many CONSECUTIVE batch failures open it
    # (immediate 503 + Retry-After at the HTTP edge); 0 disables
    breaker_threshold: int = 5
    breaker_open_s: float = 5.0          # open cooldown before the
    # half-open probe batch
    # bounded uniform jitter added to shed Retry-After values (a constant
    # synchronizes every shed client into one thundering-herd resend)
    retry_jitter_s: float = 2.0

    # --- verdict cache (cache/, ISSUE 17) ---
    # bounded LRU+TTL dedup tier keyed (content_hash, model_id,
    # checkpoint_fingerprint): a repeat of an already-scored clip resolves
    # without entering a bucket, concurrent copies of one clip coalesce
    # into ONE dispatch.  0 entries disables the tier entirely
    cache_entries: int = 0
    cache_ttl_s: float = 300.0
    # opt-in near-dup perceptual index (dHash/aHash over the downsampled
    # canvas, Hamming-radius probe): a near hit serves a DIFFERENT clip's
    # verdict by construction — its own knob, its own hit counter, never
    # conflated with exact hits
    cache_near_dup: bool = False
    cache_near_radius: int = 3

    # --- observability ---
    throughput_window_s: float = 30.0

    # --- CPU-host tuning ---
    # Cap XLA's CPU backend to one eigen thread.  Small models gain
    # nothing from intra-op threading (measured: vit-tiny b16 23 ms both
    # ways on this class of host) and the freed cores go to request
    # decode/preprocess — worth 2× served throughput on a 2-core box.
    # Leave off for large models, where intra-op threads do pay.
    single_thread_xla: bool = False

    # --- warm start (ISSUE 19) ---
    # persistent AOT executable store: a replica spawn deserializes its
    # bucket executables from this dir instead of re-paying XLA
    # compilation (serving/warmstart.py; "" disables).  Safe by
    # construction: key mismatch / corrupt entry = counted fallback to a
    # fresh compile, and a golden-batch canary gates every store hit.
    warmstart_dir: str = ""
    # fallback tier underneath the AOT store: jax's own persistent
    # compilation cache (caches HLO→binary, still re-traces; PERF.md §9)
    compile_cache_dir: str = ""
    # staged readiness: warm the first priority bucket, report /readyz
    # 200 in phase "degraded" serving the warm subset, finish the rest
    # in background (the scraper routes degraded capacity as ready)
    warm_staged: bool = False
    # comma-separated bucket warm order ("" = smallest-first); must be a
    # subset of --buckets
    warm_priority: str = ""
    # concurrent bucket compiles during warmup (0 = auto, 1 = serial)
    warm_parallel: int = 0

    # --- on-demand profiler capture (obs/profiler.py) ---
    # SIGUSR2 or `touch <profile-dir>/PROFILE` traces the engine worker's
    # next --profile-capture device batches into
    # <profile-dir>/profile/ondemand-<batch>; "" = no capture hook
    profile_dir: str = ""
    profile_capture: int = 20

    # ------------------------------------------------------------------
    def warm_priority_buckets(self) -> Tuple[int, ...]:
        s = str(self.warm_priority).strip()
        return _tuple_of_ints(s) if s else ()

    def __post_init__(self):
        if isinstance(self.buckets, str):
            self.buckets = _tuple_of_ints(self.buckets)
        self.buckets = tuple(sorted(set(int(b) for b in self.buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"--buckets must be positive ints, got "
                             f"{self.buckets}")
        if self.batch_deadline_ms < 0:
            raise ValueError("--batch-deadline-ms must be >= 0")
        if self.max_queue < self.buckets[-1]:
            raise ValueError(
                f"--max-queue ({self.max_queue}) below the largest bucket "
                f"({self.buckets[-1]}) could never fill a full batch")
        if self.img_num < 1:
            raise ValueError("--img-num must be >= 1")
        if self.wire not in ("float32", "uint8"):
            raise ValueError(f"--wire must be float32|uint8, "
                             f"got {self.wire!r}")
        if self.watchdog_timeout_s < 0 or self.retry_jitter_s < 0:
            raise ValueError("--watchdog-timeout-s / --retry-jitter-s "
                             "must be >= 0")
        if self.breaker_threshold < 0:
            raise ValueError("--breaker-threshold must be >= 0 (0 = off)")
        if self.breaker_open_s <= 0:
            raise ValueError("--breaker-open-s must be > 0")
        if int(self.cache_entries) < 0:
            raise ValueError(f"--cache-entries must be >= 0 (0 = off), "
                             f"got {self.cache_entries}")
        if float(self.cache_ttl_s) <= 0:
            raise ValueError(f"--cache-ttl-s must be > 0, got "
                             f"{self.cache_ttl_s}")
        if not 0 <= int(self.cache_near_radius) <= 8:
            raise ValueError(f"--cache-near-radius must be in [0, 8], "
                             f"got {self.cache_near_radius}")
        if int(self.warm_parallel) < 0:
            raise ValueError("--warm-parallel must be >= 0 (0 = auto)")
        if int(self.profile_capture) < 1:
            raise ValueError(f"--profile-capture must be >= 1, got "
                             f"{self.profile_capture}")
        bad = [b for b in self.warm_priority_buckets()
               if b not in self.buckets]
        if bad:
            raise ValueError(f"--warm-priority buckets {bad} not in "
                             f"--buckets {self.buckets}")
        self.dtype = _canon_quant_dtype(self.dtype, "--dtype")
        specs = self.model_specs()          # validates the grammar
        ids = [s["id"] for s in specs]
        if len(set(ids)) != len(ids):
            raise ValueError(f"--models ids must be unique, got {ids}")
        if self.model in ids:
            raise ValueError(f"--models id {self.model!r} collides with "
                             f"the primary --model")
        if not 0.0 <= float(self.cascade_low) <= \
                float(self.cascade_high) <= 1.0:
            raise ValueError(
                f"--cascade-low/--cascade-high must satisfy 0 <= low <= "
                f"high <= 1, got [{self.cascade_low}, "
                f"{self.cascade_high}]")
        if self.cascade:
            by_id = {s["id"]: s for s in specs}
            if self.cascade not in by_id:
                raise ValueError(
                    f"--cascade {self.cascade!r} must name a --models "
                    f"entry (got {sorted(by_id) or 'none'})")
            if by_id[self.cascade]["img_num"] != self.img_num:
                raise ValueError(
                    f"--cascade student img_num "
                    f"{by_id[self.cascade]['img_num']} != flagship "
                    f"img_num {self.img_num}: the same clips must flow "
                    f"through both tiers")

    def model_specs(self) -> List[Dict[str, Any]]:
        """Parsed ``--models`` entries (see :func:`parse_model_spec`)."""
        return [parse_model_spec(s, default_size=self.image_size,
                                 default_img_num=self.img_num)
                for s in str(self.models).split(";") if s.strip()]

    @property
    def max_batch_size(self) -> int:
        return self.buckets[-1]

    @property
    def in_chans(self) -> int:
        return 3 * self.img_num

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServeConfig":
        known = {f_.name for f_ in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_yaml(cls, path: str) -> "ServeConfig":
        with open(path) as f:
            d = yaml.safe_load(f) if _HAS_YAML else json.load(f)
        return cls.from_dict(d or {})

    @classmethod
    def argument_parser(cls) -> argparse.ArgumentParser:
        return _dataclass_parser(
            cls, "dynamic-batching deepfake-detection inference server")

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "ServeConfig":
        """Two-stage parse: YAML resets defaults, CLI overrides (the
        TrainConfig.from_args semantics)."""
        return _two_stage_parse(cls, argv, cls.argument_parser())


# ---------------------------------------------------------------------------
# Backfill config (runners/backfill.py)
# ---------------------------------------------------------------------------

@dataclass
class BackfillConfig:
    """Knob surface of the corpus-scale offline backfill runner.

    Same conventions as :class:`TrainConfig`/:class:`ServeConfig`: every
    field is a ``--dashed-flag``, a YAML ``-c`` file resets defaults, CLI
    overrides.  There is deliberately no deadline, queue or wire knob —
    backfill always runs the uint8 wire at ONE fixed batch bucket (the
    saturation shape), and concurrency comes from launching more worker
    processes against the same ``--out`` run dir.
    """
    # --- work ---
    manifest: str = ""                   # tools/make_lists.py --manifest
    out: str = ""                        # shared run dir (leases/, done/,
    # verdicts/, telemetry JSONL)
    data_packed: str = ""                # packed cache (zero-decode path)
    data: str = ""                       # v3 list roots, ':'-separated
    # (decode path; exactly one of data_packed/data)

    # --- model (mirrors runners/serve.py) ---
    model: str = "efficientnet_deepfake_v4"
    model_path: str = ""
    use_ema: bool = False
    num_classes: int = 2
    # raw-tree decode geometry: frames per clip and the canonical square
    # resample (0 keeps native resolution, which must then be uniform);
    # a packed source carries both in its index and ignores these
    frames: int = 4
    image_size: int = 0

    # --- pipeline ---
    batch_size: int = 16                 # THE bucket: one AOT compile,
    # partial shard tails pad up to it
    workers: int = 0                     # decode/memcpy threads
    # (0 = cpu count)
    stem_s2d: bool = False               # fold the s2d pixel shuffle into
    # the compiled prologue (EfficientNet family; PERF.md §6)

    # --- leasing ---
    lease_ttl_s: float = 600.0           # a lease not heartbeaten for
    # this long belonged to a dead host and may be re-leased; must
    # exceed the worst single-batch wall time
    worker_name: str = ""                # lease owner + telemetry file
    # suffix (default: <hostname>-<pid>)
    max_shards: int = 0                  # stop this worker after N
    # shards (0 = run to corpus completion; smoke/test hook)

    # --- dedup (cache/, ISSUE 17) ---
    # content-hash dedup pass over pack shards: clips whose canonical
    # pixel bytes already occur earlier in the manifest skip the device
    # and book a skipped_dup verdict row pointing at the canonical clip
    # (books: manifest == scored + failed + skipped_dup).  Packed source
    # only — the hash reads the mmap slabs without decoding
    dedup: bool = False

    # --- warm start (ISSUE 19; semantics as on ServeConfig) ---
    # every backfill worker re-pays THE bucket compile at launch without
    # this; the store key folds in the mesh/sharding signature, so a
    # topology change is a miss, never a wrong executable
    warmstart_dir: str = ""
    compile_cache_dir: str = ""

    # ------------------------------------------------------------------
    def __post_init__(self):
        # required-field checks live in validate_required(): the two-stage
        # parse (and YAML overlays) construct an all-defaults instance
        # before the CLI values land
        if int(self.batch_size) < 1:
            raise ValueError(f"--batch-size must be >= 1, got "
                             f"{self.batch_size}")
        if int(self.frames) < 1:
            raise ValueError(f"--frames must be >= 1, got {self.frames}")
        if float(self.lease_ttl_s) <= 0:
            raise ValueError(f"--lease-ttl-s must be > 0, got "
                             f"{self.lease_ttl_s}")
        if int(self.image_size) < 0 or int(self.max_shards) < 0 or \
                int(self.workers) < 0:
            raise ValueError("--image-size / --max-shards / --workers "
                             "must be >= 0")

    def validate_required(self) -> "BackfillConfig":
        """The launch-surface checks (run by ``from_args`` and the
        runner): what work, where, from which source."""
        if not self.manifest:
            raise ValueError("--manifest is required (build one with "
                             "tools/make_lists.py --manifest)")
        if not self.out:
            raise ValueError("--out is required (the shared run dir)")
        if bool(self.data_packed) == bool(self.data):
            raise ValueError("exactly one of --data-packed / --data "
                             "must be given (the clip source)")
        if self.dedup and not self.data_packed:
            raise ValueError("--dedup needs --data-packed (the dedup "
                             "index hashes pack slabs without decoding)")
        return self

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "BackfillConfig":
        known = {f_.name for f_ in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_yaml(cls, path: str) -> "BackfillConfig":
        with open(path) as f:
            d = yaml.safe_load(f) if _HAS_YAML else json.load(f)
        return cls.from_dict(d or {})

    @classmethod
    def argument_parser(cls) -> argparse.ArgumentParser:
        return _dataclass_parser(
            cls, "corpus-scale offline backfill scoring runner")

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None
                  ) -> "BackfillConfig":
        return _two_stage_parse(
            cls, argv, cls.argument_parser()).validate_required()


# ---------------------------------------------------------------------------
# Fleet router config (runners/router.py)
# ---------------------------------------------------------------------------

@dataclass
class RouterConfig:
    """Knob surface of the fleet replica router.

    Same conventions as the other configs: every field is a
    ``--dashed-flag``, a YAML ``-c`` file resets defaults, CLI
    overrides.  The router attaches to running replicas
    (``--replicas url,url``) and/or spawns its own local fleet
    (``--spawn N`` children of ``--spawn-runner`` with
    ``--replica-args`` passed through) — both sets join one registry.
    """
    # --- network ---
    host: str = "127.0.0.1"
    port: int = 8380                     # serve=8377, stream=8378

    # --- fleet membership ---
    replicas: str = ""                   # comma list of replica URLs
    # (host:port or http://host:port) to attach to
    spawn: int = 0                       # local replica children to spawn
    spawn_runner: str = "serve"          # serve | stream
    replica_args: str = ""               # extra CLI for every spawned
    # replica (shlex-split), e.g. "--model ... --single-thread-xla"

    # --- health (fleet/controller.py scraper) ---
    scrape_interval_s: float = 0.5
    health_fail_after: int = 3           # consecutive scrape failures
    # before a replica is marked down
    scrape_timeout_s: float = 2.0

    # --- routing (fleet/router.py) ---
    virtual_nodes: int = 64              # hash-ring vnodes per replica
    route_retries: int = 2               # failover attempts past the
    # first replica on shed/transport error (stateless traffic only)
    upstream_timeout_s: float = 30.0
    # router-level shed Retry-After: base + uniform [0, jitter) — the
    # serving stack's anti-thundering-herd idiom at the fleet edge
    shed_retry_after_s: float = 1.0
    retry_jitter_s: float = 2.0

    # --- data plane (fleet/dataplane.py) ---
    data_plane: str = "evloop"           # evloop | threads — the relay
    # hot path: a selectors-based event loop (the ~5x relays/s plane) or
    # the original thread-per-connection fallback
    relay_workers: int = 1               # evloop shards accepting on the
    # same port via SO_REUSEPORT (>1 needs kernel support; threads
    # plane ignores it)
    idle_timeout_s: float = 60.0         # close keep-alive connections
    # silent this long (counted dfd_router_idle_closed_total)
    header_timeout_s: float = 10.0       # slowloris bound: a request
    # head must arrive whole within this window (408 + close)
    max_buffer_bytes: int = 1 << 20      # per-connection relay buffer
    # bound: larger responses stream with backpressure (evloop); a
    # stalled reader whose buffer stays full between requests is shed

    # --- migration (fleet/migrate.py) ---
    migrate_timeout_s: float = 30.0      # per-stream export/restore bound
    drain_on_exit: bool = False          # drain spawned replicas' streams
    # before terminating them on shutdown

    # --- edge verdict cache (cache/, ISSUE 17) ---
    # optional response cache for POST /score at the routing tier, keyed
    # by raw body digest + the fleet weights-epoch (the set of per-model
    # checkpoint fingerprints scraped off every replica's /readyz): a
    # mixed-fingerprint rollout changes the epoch and bypasses the cache
    # until the fleet converges.  0 entries disables the edge probe
    edge_cache_entries: int = 0
    edge_cache_ttl_s: float = 2.0

    # --- autoscaling (fleet/autoscaler.py, ISSUE 18) ---
    # the SLO-driven control loop: sample the fleet every
    # --autoscale-interval-s, scale up when the router p99 / shed rate /
    # per-replica depth breach for --autoscale-up-samples consecutive
    # ticks, scale in (drain-first, lossless) after
    # --autoscale-down-samples idle ticks; decisions are deterministic
    # from the recorded sample trace (--autoscale-trace + the golden
    # replay test pin it)
    autoscale: bool = False
    slo_p99_ms: float = 250.0            # the breach line
    min_replicas: int = 1                # hard floor (dead children
    # re-spawn to it even with no load)
    max_replicas: int = 4                # capacity slots shared with
    # the backfill tenant
    autoscale_interval_s: float = 1.0
    autoscale_up_samples: int = 2
    autoscale_down_samples: int = 5
    autoscale_up_cooldown_s: float = 5.0
    autoscale_down_cooldown_s: float = 15.0
    autoscale_shed_high: float = 0.01    # shed fraction breach line
    autoscale_depth_high: float = 8.0    # per-replica depth breach line
    autoscale_depth_low: float = 1.0     # per-replica depth idle line
    autoscale_trace: str = ""            # JSONL decision trace path
    # (sample + decision per tick; replayable via
    # fleet.autoscaler.replay_trace)
    spawn_grace_s: float = 900.0         # a spawned child is *warming*,
    # not down, until it binds its port or this window expires
    settle_timeout_s: float = 20.0       # scale-in: bounded wait for a
    # drained replica's inflight to reach zero before terminate
    # standby pool (ISSUE 19): keep N fully-warmed but UNREGISTERED
    # replicas parked (counted as neither ready nor warming) so a
    # scale-up is a registry promotion in milliseconds instead of a
    # cold spawn; standbys occupy capacity slots (max_replicas) and the
    # backfill tenant's slot math counts them
    standby_replicas: int = 0

    # --- backfill tenant (ISSUE 18): idle capacity runs backfill ---
    backfill_tenant: str = ""            # manifest path (enables the
    # tenant: idle capacity slots run runners/backfill.py workers that
    # yield on a traffic spike via SIGTERM -> exit-75 lease release)
    backfill_out: str = ""               # the tenant's shared run dir
    backfill_args: str = ""              # extra CLI for every tenant
    # worker (shlex-split), e.g. "--data-packed ... --model ..."
    backfill_max_workers: int = 0        # cap (0 = all idle slots)
    backfill_yield_timeout_s: float = 30.0

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.spawn_runner not in ("serve", "stream"):
            raise ValueError(f"--spawn-runner must be serve|stream, got "
                             f"{self.spawn_runner!r}")
        if int(self.spawn) < 0:
            raise ValueError(f"--spawn must be >= 0, got {self.spawn}")
        if int(self.virtual_nodes) < 1:
            raise ValueError(f"--virtual-nodes must be >= 1, got "
                             f"{self.virtual_nodes}")
        if int(self.route_retries) < 0:
            raise ValueError(f"--route-retries must be >= 0, got "
                             f"{self.route_retries}")
        if int(self.health_fail_after) < 1:
            raise ValueError(f"--health-fail-after must be >= 1, got "
                             f"{self.health_fail_after}")
        if self.data_plane not in ("evloop", "threads"):
            raise ValueError(f"--data-plane must be evloop|threads, got "
                             f"{self.data_plane!r}")
        if int(self.relay_workers) < 1:
            raise ValueError(f"--relay-workers must be >= 1, got "
                             f"{self.relay_workers}")
        if int(self.max_buffer_bytes) < 4096:
            raise ValueError(f"--max-buffer-bytes must be >= 4096, got "
                             f"{self.max_buffer_bytes}")
        if int(self.edge_cache_entries) < 0:
            raise ValueError(f"--edge-cache-entries must be >= 0 "
                             f"(0 = off), got {self.edge_cache_entries}")
        if float(self.edge_cache_ttl_s) <= 0:
            raise ValueError(f"--edge-cache-ttl-s must be > 0, got "
                             f"{self.edge_cache_ttl_s}")
        for name in ("scrape_interval_s", "scrape_timeout_s",
                     "upstream_timeout_s", "migrate_timeout_s",
                     "shed_retry_after_s", "idle_timeout_s",
                     "header_timeout_s"):
            if float(getattr(self, name)) <= 0:
                raise ValueError(f"--{name.replace('_', '-')} must be "
                                 f"> 0, got {getattr(self, name)}")
        if float(self.retry_jitter_s) < 0:
            raise ValueError(f"--retry-jitter-s must be >= 0, got "
                             f"{self.retry_jitter_s}")
        if int(self.min_replicas) < 1:
            raise ValueError(f"--min-replicas must be >= 1, got "
                             f"{self.min_replicas}")
        if int(self.max_replicas) < int(self.min_replicas):
            raise ValueError(
                f"--max-replicas ({self.max_replicas}) must be >= "
                f"--min-replicas ({self.min_replicas})")
        if int(self.autoscale_up_samples) < 1 or \
                int(self.autoscale_down_samples) < 1:
            raise ValueError("--autoscale-up-samples / "
                             "--autoscale-down-samples must be >= 1")
        if float(self.autoscale_depth_low) > \
                float(self.autoscale_depth_high):
            raise ValueError("--autoscale-depth-low must be <= "
                             "--autoscale-depth-high (the hysteresis "
                             "dead band)")
        if int(self.backfill_max_workers) < 0:
            raise ValueError(f"--backfill-max-workers must be >= 0, "
                             f"got {self.backfill_max_workers}")
        if int(self.standby_replicas) < 0:
            raise ValueError(f"--standby-replicas must be >= 0, got "
                             f"{self.standby_replicas}")
        if int(self.standby_replicas) > 0 and not self.autoscale:
            raise ValueError("--standby-replicas needs --autoscale "
                             "(the autoscaler owns the standby pool)")
        for name in ("slo_p99_ms", "autoscale_interval_s",
                     "spawn_grace_s", "settle_timeout_s",
                     "backfill_yield_timeout_s"):
            if float(getattr(self, name)) <= 0:
                raise ValueError(f"--{name.replace('_', '-')} must be "
                                 f"> 0, got {getattr(self, name)}")
        for name in ("autoscale_up_cooldown_s",
                     "autoscale_down_cooldown_s",
                     "autoscale_shed_high", "autoscale_depth_low"):
            if float(getattr(self, name)) < 0:
                raise ValueError(f"--{name.replace('_', '-')} must be "
                                 f">= 0, got {getattr(self, name)}")

    def replica_urls(self) -> List[str]:
        return [u.strip() for u in str(self.replicas).split(",")
                if u.strip()]

    def validate_required(self) -> "RouterConfig":
        """Launch-surface check (two-stage parse builds an all-defaults
        instance first): the router needs a fleet to route over."""
        if not self.replica_urls() and int(self.spawn) < 1:
            raise ValueError("give the router a fleet: --replicas "
                             "url[,url...] and/or --spawn N")
        if self.backfill_tenant and not self.autoscale:
            raise ValueError("--backfill-tenant needs --autoscale (the "
                             "control loop is the tenant's scheduler)")
        if self.backfill_tenant and not self.backfill_out:
            raise ValueError("--backfill-tenant needs --backfill-out "
                             "(the tenant's shared run dir)")
        return self

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RouterConfig":
        known = {f_.name for f_ in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_yaml(cls, path: str) -> "RouterConfig":
        with open(path) as f:
            d = yaml.safe_load(f) if _HAS_YAML else json.load(f)
        return cls.from_dict(d or {})

    @classmethod
    def argument_parser(cls) -> argparse.ArgumentParser:
        return _dataclass_parser(
            cls, "fleet replica router (shared-nothing scale-out)")

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None
                  ) -> "RouterConfig":
        return _two_stage_parse(
            cls, argv, cls.argument_parser()).validate_required()


# ---------------------------------------------------------------------------
# Streaming config (runners/stream.py)
# ---------------------------------------------------------------------------

@dataclass
class StreamConfig(ServeConfig):
    """Knob surface of the streaming-video scoring server.

    Extends :class:`ServeConfig` (the engine/batcher knobs are the same
    machinery) with the stream-pipeline stages: face localization +
    tracking, temporal windowing, per-stream verdict hysteresis, and
    session lifecycle.  ``from_dict``/``from_yaml``/``from_args`` are
    inherited — every new field is a ``--dashed-flag``.
    """
    port: int = 8378                     # one above the serving default

    # --- face localization + tracking (streaming/tracker.py) ---
    # 'full_frame' (deterministic built-in, pre-cropped parity) or
    # 'callable:<module>:<attr>' plugging in a model-backed detector
    localizer: str = "full_frame"
    track_iou_min: float = 0.3           # greedy-IoU association floor
    track_ema_alpha: float = 0.6         # box smoothing (1.0 = raw boxes)
    track_max_coast: int = 10            # missed frames before track death
    track_min_hits: int = 1              # detections before a track scores
    crop_margin: float = 0.15            # face-box expansion before crop

    # --- temporal windowing (streaming/windows.py) ---
    window_stride: int = 1               # in-window frame spacing
    window_hop: int = 0                  # pushes between windows (0 = tile:
    # img_num*stride, non-overlapping)
    max_inflight_windows: int = 4        # per-stream bound; beyond it the
    # OLDEST pending window is dropped (drop-oldest backpressure)

    # --- host fast path (streaming/ring.py, ISSUE 20) ---
    # 'ring' = frame-once lifecycle: per-track preallocated crop rings,
    # one prepare_canvas + one sha256 per crop, zero-copy FrameStack
    # window payloads gathered straight into the engine's batch slab.
    # 'concat' = the historical standalone-canvas + np.concatenate path
    # (in-tree parity and bench reference)
    assembly: str = "ring"
    # consecutive-duplicate elision (frozen/low-motion streams): frames
    # whose encoded bytes match their predecessor skip decode, and a
    # window whose clip content equals the track's previous window skips
    # submission — both counted (frames_dup_elided / windows_dup_elided),
    # never silent.  Off by default: with it off the emitted-window
    # stream is exactly the pre-fast-path one
    dedup_frames: bool = False

    # --- verdict hysteresis (streaming/verdict.py) ---
    verdict_ema_alpha: float = 0.3       # EMA over window scores
    suspect_enter: float = 0.5
    suspect_exit: float = 0.35
    fake_enter: float = 0.8
    fake_exit: float = 0.65
    verdict_min_windows: int = 1         # EMA warmup before verdicts move

    # --- session lifecycle (streaming/ingest.py) ---
    max_streams: int = 64
    stream_ttl_s: float = 120.0          # idle eviction (0 = never)
    event_log_dir: str = ""              # per-stream verdict-event JSONL
    # session durability: snapshot per-stream tracker + verdict-machine +
    # window-position state here on shutdown/SIGTERM and restore on the
    # next start, so a server bounce RESUMES verdict streams instead of
    # resetting them ("" disables)
    state_dir: str = ""

    # --- bench/test instrumentation ---
    # planted per-window scores ("0.05*8,0.95*12"): windows still ride the
    # engine (load/latency are real) but the VERDICT machines consume the
    # planted sequence, so transition tests are deterministic
    verdict_vector: str = ""

    # ------------------------------------------------------------------
    def __post_init__(self):
        super().__post_init__()
        from .streaming.verdict import VerdictThresholds
        VerdictThresholds(self.suspect_enter, self.suspect_exit,
                          self.fake_enter, self.fake_exit)  # validates
        if not 0.0 < self.verdict_ema_alpha <= 1.0:
            raise ValueError(f"--verdict-ema-alpha must be in (0, 1], got "
                             f"{self.verdict_ema_alpha}")
        if not 0.0 < self.track_ema_alpha <= 1.0:
            raise ValueError(f"--track-ema-alpha must be in (0, 1], got "
                             f"{self.track_ema_alpha}")
        if not 0.0 <= self.track_iou_min <= 1.0:
            raise ValueError(f"--track-iou-min must be in [0, 1], got "
                             f"{self.track_iou_min}")
        for name in ("window_stride", "max_inflight_windows", "max_streams",
                     "verdict_min_windows", "track_min_hits"):
            if int(getattr(self, name)) < 1:
                raise ValueError(f"--{name.replace('_', '-')} must be "
                                 f">= 1, got {getattr(self, name)}")
        if int(self.window_hop) < 0 or int(self.track_max_coast) < 0 or \
                float(self.crop_margin) < 0 or float(self.stream_ttl_s) < 0:
            raise ValueError("window-hop / track-max-coast / crop-margin / "
                             "stream-ttl-s must be >= 0")
        if self.assembly not in ("ring", "concat"):
            raise ValueError(f"--assembly must be 'ring' or 'concat', "
                             f"got {self.assembly!r}")

    @classmethod
    def argument_parser(cls) -> argparse.ArgumentParser:
        return _dataclass_parser(
            cls, "streaming-video deepfake-detection scoring server")
