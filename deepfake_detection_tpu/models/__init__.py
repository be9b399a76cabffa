"""Model zoo (reference layer L2, ``dfd/timm/models/``).

Importing this package registers every model family's entrypoints.
"""

from ..registry import (is_model, is_model_in_modules, list_models,
                        list_modules, model_entrypoint, register_model)
from . import efficientnet  # noqa: F401  (registers entrypoints)
from .efficientnet import EfficientNet
from .factory import (create_deepfake_model, create_deepfake_model_v3,
                      create_deepfake_model_v4, create_model,
                      create_model_and_params, init_model)
from .helpers import (load_checkpoint, load_pretrained, load_state_dict,
                      resume_checkpoint, save_model_checkpoint)

# Families added as they land; each import registers its entrypoints.
for _mod in ("resnet", "xception", "senet", "vit", "mobilenetv3", "densenet",
             "inception_v3", "inception_v4", "inception_resnet_v2", "dpn",
             "hrnet", "dla", "res2net", "sknet", "selecsls", "nasnet",
             "pnasnet", "gluon_resnet", "gluon_xception", "timesformer",
             "video", "phi4flash", "granite4h", "lfm2moe",
             "glm4moelite", "keyevl2"):
    try:
        __import__(f"{__name__}.{_mod}")
    except ModuleNotFoundError as e:      # tolerate only a missing family
        if e.name != f"{__name__}.{_mod}":
            raise                         # real import error inside a family
