"""Model zoo: ResNet family, 2-D UNet, decoder-only Transformer LM.

All models are Flax linen modules in NHWC layout (TPU-native; XLA tiles NHWC
convs onto the MXU without the transposes NCHW would need) with a ``dtype``
knob for bfloat16 compute and float32 parameters.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn

from deeplearning_mpi_tpu.models.resnet import (  # noqa: F401
    ResNet,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
)
from deeplearning_mpi_tpu.models.generate import (  # noqa: F401
    beam_search,
    beam_search_jit,
    decode_tokens,
    generate,
    generate_jit,
    prefill,
)
from deeplearning_mpi_tpu.models.moe import (  # noqa: F401
    MoEMLP,
    collect_aux_loss,
    collect_dropped_fraction,
)
from deeplearning_mpi_tpu.models.transformer import (  # noqa: F401
    LayerSpec,
    TransformerConfig,
    TransformerLM,
    draft_config,
    truncate_lm_params,
)
from deeplearning_mpi_tpu.models.unet import UNet  # noqa: F401
from deeplearning_mpi_tpu.models.vit import ViT, vit_small, vit_tiny  # noqa: F401

_RESNETS = {
    "resnet18": resnet18,
    "resnet34": resnet34,
    "resnet50": resnet50,
    "resnet101": resnet101,
    "resnet152": resnet152,
}

_VITS = {"vit_tiny": vit_tiny, "vit_small": vit_small}


def get_model(name: str, **kwargs: Any) -> nn.Module:
    """Build a model by name — the registry behind the trainers' ``--arch``."""
    if name in _RESNETS:
        return _RESNETS[name](**kwargs)
    if name in _VITS:
        kwargs.pop("stem", None)  # patchify IS the stem; CNN knob n/a
        return _VITS[name](**kwargs)
    if name == "unet":
        return UNet(**kwargs)
    if name == "unet3d":
        kwargs.setdefault("spatial_dims", 3)
        return UNet(**kwargs)
    if name == "transformer":
        config = kwargs.pop("config", None) or TransformerConfig()
        return TransformerLM(config=config, **kwargs)
    raise ValueError(
        f"unknown model '{name}'; choose from "
        f"{sorted(_RESNETS) + sorted(_VITS) + ['unet', 'unet3d', 'transformer']}"
    )
