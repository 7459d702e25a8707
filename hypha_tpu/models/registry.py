"""Model registry: resolve a job's model spec to a flax module.

The reference maps 38 ``ModelType`` variants to HF ``AutoModelFor*`` classes
(executors/accelerate/.../model.py:48-123). Here every variant resolves:
the flagship families (GPT-2, Llama + its Mistral/Qwen2/Gemma descendants,
Mixtral, afmoe, lfm2_moe, phi4flash, nemotron_h, keye_vl2, LeNet) are native JAX definitions; the 14 types with an HF **Flax**
head resolve through the hf fallback family (torch checkpoints convert via
``from_pt``); the remaining torch-only-head types resolve through the
``heads`` family — JAX task heads over Flax backbones (models/heads.py),
mirroring HF's own random-init-the-missing-head fine-tuning behavior.

A model spec is the ``model`` dict of a TrainExecutorConfig:
  {"model_type": ModelType, "family": "gpt2"|"llama"|"mixtral"|"lenet"|"hf",
   "config": {...family config overrides...}, "preset": "tiny"|"small"|...}
"""

from __future__ import annotations

from typing import Any

from ..messages import ModelType
from .afmoe import Afmoe, AfmoeConfig
from .gpt2 import GPT2, GPT2Config
from .keye_vl2 import KeyeVL2, KeyeVL2Config
from .lenet import LeNet, LeNetConfig
from .lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from .llama import Llama, LlamaConfig
from .mixtral import Mixtral, MixtralConfig
from .nemotron_h import NemotronH, NemotronHConfig
from .phi4flash import Phi4Flash, Phi4FlashConfig

__all__ = ["build_model", "resolve_model_type", "FAMILIES"]

_PRESETS = {
    "gpt2": {"tiny": GPT2Config.tiny, "small": GPT2Config.small},
    "llama": {"tiny": LlamaConfig.tiny, "llama2-7b": LlamaConfig.llama2_7b},
    "mixtral": {"tiny": MixtralConfig.tiny, "8x7b": MixtralConfig.mixtral_8x7b},
    "lenet": {"default": LeNetConfig},
    "afmoe": {"tiny": AfmoeConfig.tiny},
    "lfm2_moe": {"tiny": Lfm2MoeConfig.tiny},
    "phi4flash": {"tiny": Phi4FlashConfig.tiny},
    "nemotron_h": {"tiny": NemotronHConfig.tiny},
    "keye_vl2": {"tiny": KeyeVL2Config.tiny},
}

FAMILIES = {
    "gpt2": (GPT2, GPT2Config),
    "llama": (Llama, LlamaConfig),
    # Llama-architecture descendants HF ships no Flax port for — the
    # reference reaches them via torch AutoModel (model.py:48-123); here
    # they are the native Llama module under family-specific config defaults
    # with converted torch weights (models.convert).
    "mistral": (Llama, LlamaConfig),
    "qwen2": (Llama, LlamaConfig),
    "qwen3": (Llama, LlamaConfig),
    "gemma": (Llama, LlamaConfig),
    "mixtral": (Mixtral, MixtralConfig),
    # Arcee's afmoe (Trinity): sigmoid-routed experts with a selection bias,
    # a shared expert, gated attention, window (RoPE) and full (NoPE) layers;
    # one rank's share of the experts by ``experts_held``/``expert_offset``.
    "afmoe": (Afmoe, AfmoeConfig),
    # Liquid's lfm2_moe (LFM2-24B-A2B): a layer's operator is a gated short
    # convolution or QK-normed GQA with RoPE, by ``layer_types``; the routed
    # experts of afmoe with no shared expert; the head tied to the embedding.
    "lfm2_moe": (Lfm2Moe, Lfm2MoeConfig),
    # Microsoft's phi4flash (Phi-4-mini-flash-reasoning, SambaY): Mamba and
    # window differential attention, then one full layer and a cross-decoder of
    # GMU and cross-attention layers that read the scan output and the keys and
    # values of two earlier layers; a layer's kind by the source's rule on its
    # index, ``layers_run`` the source layers a cut keeps.
    "phi4flash": (Phi4Flash, Phi4FlashConfig),
    # NVIDIA's nemotron_h (Nemotron-H): a block is one norm and one part, a
    # Mamba-2 mixer, attention or routed squared-ReLU experts beside a shared
    # one, by the letter of ``pattern``; ``layers_run`` the source layers a cut
    # keeps; one rank's share of the experts as afmoe's.
    "nemotron_h": (NemotronH, NemotronHConfig),
    # Kwai Keye's KeyeVL2 language model (Keye-VL-2.0): QK-normed GQA over the
    # keys a learned indexer picks for each query, the indexer trained by a KL
    # objective of its own that the model returns beside its routing counts;
    # softmax-routed experts with nothing dense beside them.
    "keye_vl2": (KeyeVL2, KeyeVL2Config),
    "lenet": (LeNet, LeNetConfig),
}

# Architecture toggles implied by the family name.
_FAMILY_DEFAULTS: dict[str, dict[str, Any]] = {
    "qwen2": {"attn_bias": True},
    "qwen3": {"qk_norm": True},
    "gemma": {
        "mlp_act": "gelu_tanh",
        "rms_offset": True,
        "embed_scale": True,
        "tie_word_embeddings": True,
    },
}


def resolve_model_type(model_type: ModelType | str) -> ModelType:
    if isinstance(model_type, ModelType):
        return model_type
    return ModelType(model_type)


def _head_types():
    from .heads import HEAD_TYPES

    return HEAD_TYPES


def build_model(spec: dict[str, Any], attn_impl=None):
    """Build (module, config) from a job's model spec."""
    family = spec.get("family")
    if family is None:
        mt = resolve_model_type(spec.get("model_type", ModelType.CAUSAL_LM))
        if mt in _head_types():
            family = "heads"
        else:
            family = {
                ModelType.CAUSAL_LM: "gpt2",
                ModelType.IMAGE_CLASSIFICATION: "lenet",
            }.get(mt, "hf")
    if family == "hf":
        from .hf import build_hf_model

        mt = resolve_model_type(spec.get("model_type", ModelType.CAUSAL_LM))
        return build_hf_model(spec, mt)
    if family == "heads":
        from .heads import build_head_model

        mt = resolve_model_type(spec.get("model_type", ModelType.CAUSAL_LM))
        return build_head_model(spec, mt)
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}")
    module_cls, config_cls = FAMILIES[family]
    preset = spec.get("preset")
    hf_config = spec.get("hf_config")
    if preset is not None:
        presets = _PRESETS.get(family, {})
        if preset not in presets:
            raise KeyError(
                f"unknown preset {preset!r} for family {family!r} "
                f"(have {sorted(presets) or 'none'})"
            )
        cfg = presets[preset]()
    elif hf_config is not None and hasattr(config_cls, "from_hf"):
        # A fetched checkpoint's config.json fields drive the native config.
        # The family name stands in for a missing model_type so from_hf can
        # derive architecture toggles (gemma/qwen2) even from a bare field
        # dict — otherwise a caller-supplied hf_config without model_type
        # would silently build plain-Llama architecture.
        hf = dict(hf_config)
        hf.setdefault("model_type", family)
        cfg = config_cls.from_hf(hf)
    else:
        cfg = config_cls()
    # Family defaults fill gaps only when NO checkpoint config drove the
    # build — from_hf already derives architecture toggles from the
    # config.json (and may legitimately disagree with the defaults, e.g. an
    # untied-head gemma variant).
    base = {} if hf_config is not None else _FAMILY_DEFAULTS.get(family, {})
    overrides = {**base, **(spec.get("config") or {})}
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    if family == "lenet":  # no attention to plug
        return module_cls(cfg), cfg
    return module_cls(cfg, attn_impl), cfg
