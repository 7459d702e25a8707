"""Native JAX/flax model families for the BASELINE configs.

The reference loads models through 38 HF ``AutoModelFor*`` classes
(executors/accelerate/.../model.py:48-123). TPU-native equivalents: the
flagship families are defined natively here (static shapes, bf16 activations,
MXU-sized matmuls, sharding-friendly param trees); anything else resolves
through the registry's HF-conversion fallback (hypha_tpu.models.registry).
"""

from .afmoe import Afmoe, AfmoeConfig
from .lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from .lenet import LeNet, LeNetConfig
from .gpt2 import GPT2, GPT2Config
from .keye_vl2 import KeyeVL2, KeyeVL2Config
from .llama import Llama, LlamaConfig
from .mixtral import Mixtral, MixtralConfig
from .nemotron_h import NemotronH, NemotronHConfig
from .phi4flash import Phi4Flash, Phi4FlashConfig
from .registry import build_model, resolve_model_type

__all__ = [
    "Afmoe",
    "AfmoeConfig",
    "Lfm2Moe",
    "Lfm2MoeConfig",
    "LeNet",
    "LeNetConfig",
    "GPT2",
    "GPT2Config",
    "KeyeVL2",
    "KeyeVL2Config",
    "Llama",
    "LlamaConfig",
    "Mixtral",
    "MixtralConfig",
    "NemotronH",
    "NemotronHConfig",
    "Phi4Flash",
    "Phi4FlashConfig",
    "build_model",
    "resolve_model_type",
]
