from .base import (  # noqa: F401
    BatchField,
    ModelDef,
    get_model,
    register_model,
    registered_models,
)
from .deepfm import apply_deepfm, init_deepfm  # noqa: F401
from .dcnv2 import apply_dcnv2, init_dcnv2  # noqa: F401
from .xdeepfm import apply_xdeepfm, init_xdeepfm  # noqa: F401
from . import evabyte, keye_vl2, lfm2_moe, nemotron_h, two_tower  # noqa: F401  (register their families)
