"""Data pipeline: host decode/augment → uint8 NHWC → jitted device prologue.

TPU-native re-design of ``/root/reference/dfd/timm/data/`` (SURVEY.md §2.4):
deterministic index-space sampling replaces stateful datasets/samplers, NHWC
uint8 host batches replace CHW float tensors, and the CUDA-stream prefetcher
becomes a jitted normalize/cast/erase prologue with async dispatch.

The jax-dependent modules (loader, mixup, random_erasing, device_augment)
are imported LAZILY (PEP 562): shm-ring loader workers unpickle datasets by
module path, which executes this package ``__init__`` — an eager jax import
would cost every spawned decode worker seconds of startup and hundreds of
MB of RSS for code it never runs (N workers × jax ≫ the slabs themselves).
"""

from .config import resolve_data_config
from .constants import (DEFAULT_CROP_PCT, IMAGENET_DEFAULT_MEAN,
                        IMAGENET_DEFAULT_STD, IMAGENET_INCEPTION_MEAN,
                        IMAGENET_INCEPTION_STD)
from .dataset import (ConcatDataset, DatasetTar, DeepFakeClipDataset,
                      FolderDataset, SyntheticDataset,
                      read_clip_list, split_clips)
from .packed import (PackedCacheStale, PackedDataset, PackedShardCorrupt,
                     verify_pack, write_pack)
from .samplers import (OrderedShardedSampler, ShardedTrainSampler,
                       epoch_batches)
from .shm_ring import ShmRing, ShmRingLoader
from .tokens import SyntheticTokenDataset, TokenFileDataset
from .transforms_factory import (create_transform, transforms_deepfake_eval_v3,
                                 transforms_deepfake_train_passthrough,
                                 transforms_deepfake_train_v3,
                                 transforms_imagenet_eval,
                                 transforms_imagenet_train)

# lazily-resolved (jax-importing) attributes: name -> submodule
_LAZY = {
    "DeviceLoader": "loader", "HostLoader": "loader",
    "create_deepfake_loader_v3": "loader", "create_loader": "loader",
    "fast_collate": "loader", "create_token_loader": "loader",
    "FastCollateMixup": "mixup", "mixup_batch": "mixup",
    "RandomErasing": "random_erasing", "random_erasing": "random_erasing",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        value = getattr(mod, name)
        globals()[name] = value        # cache: __getattr__ runs once per name
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
