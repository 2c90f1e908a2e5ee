"""Model zoo (reference C7: vgg.py / resnet.py / lstm.py / lstman4.py).

The reference ships one PyTorch nn.Module file per network family and the
trainer instantiates them by the ``--dnn`` flag string. Here each family is a
flax.linen module designed TPU-first: NHWC layouts (XLA's native conv layout),
``dtype`` plumbed through so the whole forward can run in bfloat16 on the MXU
with float32 params, and recurrent models built on ``lax.scan`` cells instead
of cuDNN.

``get_model(dnn)`` mirrors the reference's flag-string dispatch; the returned
``ModelSpec`` also carries the example input shape the trainer/benchmarks use.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import flax.linen as nn

from gtopkssgd_tpu.models import (
    kanana2, keye_vl2, kimi_linear, ouro, qwen3_next, sdar, trinity_mini)
from gtopkssgd_tpu.models.alexnet import AlexNet
from gtopkssgd_tpu.models.kanana2 import Kanana2
from gtopkssgd_tpu.models.keye_vl2 import KeyeVL2
from gtopkssgd_tpu.models.kimi_linear import KimiLinear
from gtopkssgd_tpu.models.lstm import PTBLSTM
from gtopkssgd_tpu.models.lstman4 import DeepSpeechAN4
from gtopkssgd_tpu.models.ouro import Ouro
from gtopkssgd_tpu.models.qwen3_next import Qwen3Next
from gtopkssgd_tpu.models.resnet import ResNetCIFAR, ResNetImageNet
from gtopkssgd_tpu.models.sdar import SDAR
from gtopkssgd_tpu.models.trinity_mini import TrinityMini
from gtopkssgd_tpu.models.vgg import VGG16


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """A zoo entry: constructor, canonical dataset, example input shape
    (without batch dim), whether the model is recurrent, and what the
    trainer looks up to run it: the batch leaf it reads (``input_key``),
    how its loss comes about (``loss``: ``classify`` logits against
    ``label``, ``tokens`` logits against ``targets``, ``ctc``, or ``own``
    for a model that takes the targets and returns its loss and counts
    itself) and whether it threads a ``carry`` from window to window.
    ``presets`` names the sizes a model is built at by ``--model-preset``
    (its constructor's ``preset``); most models have one size and none."""

    name: str
    build: Callable[..., nn.Module]
    dataset: str
    example_shape: Tuple[int, ...]
    recurrent: bool = False
    has_batchnorm: bool = True
    input_key: str = "image"
    loss: str = "classify"
    carry: bool = False
    presets: Tuple[str, ...] = ()


_ZOO: Dict[str, ModelSpec] = {}


def _register(spec: ModelSpec) -> None:
    _ZOO[spec.name] = spec


_register(ModelSpec("vgg16", VGG16, "cifar10", (32, 32, 3)))
_register(
    ModelSpec(
        "resnet20",
        lambda **kw: ResNetCIFAR(depth=20, **kw),
        "cifar10",
        (32, 32, 3),
    )
)
_register(
    ModelSpec(
        "resnet56",
        lambda **kw: ResNetCIFAR(depth=56, **kw),
        "cifar10",
        (32, 32, 3),
    )
)
_register(
    ModelSpec(
        "resnet50",
        ResNetImageNet,
        "imagenet",
        (224, 224, 3),
    )
)
_register(
    ModelSpec(
        "alexnet",
        AlexNet,
        "imagenet",
        (224, 224, 3),
        has_batchnorm=False,
    )
)
_register(
    ModelSpec(
        "lstm",
        PTBLSTM,
        "ptb",
        (35,),  # BPTT window of token ids
        recurrent=True,
        has_batchnorm=False,
        input_key="tokens",
        loss="tokens",
        carry=True,
    )
)
_register(
    ModelSpec(
        "lstman4",
        DeepSpeechAN4,
        "an4",
        (200, 161),  # (time frames, spectrogram bins)
        recurrent=True,
        input_key="spectrogram",
        loss="ctc",
    )
)
_register(
    ModelSpec(
        "qwen3_next",
        Qwen3Next,
        "tokens",
        (4096,),  # one sequence of token ids
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(qwen3_next.PRESETS),
    )
)
_register(
    ModelSpec(
        "keye_vl2",
        KeyeVL2,
        "tokens",
        (16384,),  # one sequence of token ids
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(keye_vl2.PRESETS),
    )
)
_register(
    ModelSpec(
        "trinity_mini",
        TrinityMini,
        "tokens",
        (16384,),  # one sequence of token ids
        # Its router's balancing bias rides in ``batch_stats``; there is no
        # BatchNorm in it.
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(trinity_mini.PRESETS),
    )
)
_register(
    ModelSpec(
        "kanana2",
        Kanana2,
        "tokens",
        (8192,),  # one sequence of token ids
        # As trinity_mini: the balancing bias rides in ``batch_stats``.
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(kanana2.PRESETS),
    )
)
_register(
    ModelSpec(
        "ouro",
        Ouro,
        "tokens",
        (4096,),  # one sequence of token ids
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(ouro.PRESETS),
    )
)
_register(
    ModelSpec(
        "sdar",
        SDAR,
        "tokens",
        (8192,),  # one sequence of token ids; the model runs it as 2 x 8,192 rows
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(sdar.PRESETS),
    )
)
_register(
    ModelSpec(
        "kimi_linear",
        KimiLinear,
        "tokens",
        (8192,),  # one sequence of token ids
        # As kanana2: the balancing bias rides in ``batch_stats``.
        has_batchnorm=False,
        input_key="tokens",
        loss="own",
        presets=tuple(kimi_linear.PRESETS),
    )
)


def get_model(dnn: str, **kwargs: Any) -> Tuple[nn.Module, ModelSpec]:
    """Build a zoo model by its reference ``--dnn`` flag string.

    ``space_to_depth`` is accepted for every model so an entry point can
    forward its flag unconditionally, but it
    is a resnet50-only stem transform: any other model rejects a truthy
    value with a clean error here rather than a constructor TypeError
    deep in flax."""
    try:
        spec = _ZOO[dnn]
    except KeyError:
        raise ValueError(
            f"unknown dnn {dnn!r}; available: {sorted(_ZOO)}"
        ) from None
    if not kwargs.get("space_to_depth", True):
        kwargs.pop("space_to_depth")  # falsy = default stem everywhere
    elif "space_to_depth" in kwargs and dnn != "resnet50":
        raise ValueError(
            f"--s2d is a resnet50 stem transform; --dnn {dnn} "
            "does not take it")
    if kwargs.get("preset") is None:
        kwargs.pop("preset", None)     # the model's own default
    elif kwargs["preset"] not in spec.presets:
        raise ValueError(
            f"--model-preset {kwargs['preset']!r}: --dnn {dnn} has "
            + (f"the presets {list(spec.presets)}" if spec.presets else
               "none (models with presets: "
               f"{sorted(n for n, m in _ZOO.items() if m.presets)})"))
    return spec.build(**kwargs), spec


def available_models():
    return sorted(_ZOO)


__all__ = [
    "get_model",
    "available_models",
    "ModelSpec",
    "VGG16",
    "ResNetCIFAR",
    "ResNetImageNet",
    "AlexNet",
    "PTBLSTM",
    "DeepSpeechAN4",
    "Qwen3Next",
    "KeyeVL2",
    "TrinityMini",
    "Kanana2",
    "Ouro",
    "SDAR",
    "KimiLinear",
]
