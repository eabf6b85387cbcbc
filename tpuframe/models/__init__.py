"""Model zoo — the reference's workload models, rebuilt in flax.

Reference coverage (SURVEY.md §3a "Model defs", [B:7–10]):
  - MNIST ConvNet (custom nn.Module in the reference)  → ``convnet.ConvNet``
  - ResNet-18 / ResNet-50 (torchvision in the reference) → ``resnet``
  - BERT-base for GLUE (HF transformers in the reference) → ``bert``

All models are NHWC / bf16-compute-capable — the TPU-native layout/dtype
choices (MXU wants large bf16 matmuls; see task guidance + pallas_guide).
"""

from typing import Any, Callable

from tpuframe.models.afmoe import Afmoe, AfmoeConfig
from tpuframe.models.convnet import ConvNet
from tpuframe.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
from tpuframe.models.resnet import (ResNet, ResNet18, ResNet34,
                                    ResNet50, ResNet101, ResNet152)
from tpuframe.models.bert import BertConfig, BertForSequenceClassification
from tpuframe.models.transformer_lm import (LMConfig, ScanBlockLM,
                                             TransformerLM)

def _bert_base(dtype=None, **kwargs):
    """Registry adapter: flag-style kwargs → BertConfig (so get_model's
    uniform ``get_model(name, dtype=..., **kwargs)`` call shape works for
    BERT too)."""
    import numpy as np

    if dtype is not None:
        kwargs.setdefault("dtype", str(np.dtype(dtype)))
    return BertForSequenceClassification(BertConfig.base(**kwargs))


def _decoder_adapter(cls, config_cls):
    """Registry adapter shared by the decoders: flag-style kwargs → the
    config class (its ``tiny()`` preset under ``tiny=True``; lists from a
    JSON config become the tuples a frozen config hashes) → the module."""

    def build(dtype=None, tiny=False, **kwargs):
        import numpy as np

        if dtype is not None:
            kwargs.setdefault("dtype", str(np.dtype(dtype)))
        kwargs = {k: tuple(v) if isinstance(v, list) else v
                  for k, v in kwargs.items()}
        return cls(config_cls.tiny(**kwargs) if tiny
                   else config_cls(**kwargs))

    return build


# transformer-lm-pp: the pipeline-parallel variant (layer-stacked blocks;
# trained via tpuframe.parallel.pp_lm on a data x pipe mesh).
_transformer_lm = _decoder_adapter(TransformerLM, LMConfig)
_transformer_lm_pp = _decoder_adapter(ScanBlockLM, LMConfig)


_REGISTRY: dict[str, Callable[..., Any]] = {
    "convnet": ConvNet,
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "bert-base": _bert_base,
    "transformer-lm": _transformer_lm,
    "transformer-lm-pp": _transformer_lm_pp,
    "afmoe": _decoder_adapter(Afmoe, AfmoeConfig),
    "deepseek_v3": _decoder_adapter(DeepseekV3, DeepseekV3Config),
}


def get_model(name: str, **kwargs):
    """Construct a model by registry name (harness entry point)."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


__all__ = [
    "Afmoe",
    "AfmoeConfig",
    "ConvNet",
    "DeepseekV3",
    "DeepseekV3Config",
    "LMConfig",
    "ScanBlockLM",
    "TransformerLM",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "BertConfig",
    "BertForSequenceClassification",
    "get_model",
]
