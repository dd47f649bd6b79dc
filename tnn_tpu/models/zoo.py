"""Model zoo registry.

Parity: ExampleModels registry (include/nn/example_models.hpp:19-46,
``load_or_create_model`` :49; creators registered in src/nn/example_models.cpp:531-558).
Same inventory, same names; "flash" variants select the pallas attention backend.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

from . import gpt2 as gpt2_lib
from . import llama as llama_lib
from . import resnet, vit

_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    def wrap(fn):
        _REGISTRY[name] = fn
        return fn

    return wrap


def create(name: str, **kw):
    """Instantiate a zoo model by name (parity: ExampleModels::create)."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)


def names() -> Sequence[str]:
    return sorted(_REGISTRY)


# -- vision (parity: example_models.cpp:21-335) ------------------------------

register("mnist_cnn")(lambda **kw: resnet.mnist_cnn(num_classes=10, **kw))
register("cifar10_vgg")(lambda **kw: resnet.vgg11(num_classes=10, **kw))
register("cifar10_resnet9")(lambda **kw: resnet.resnet9(num_classes=10, **kw))
register("cifar100_resnet18")(lambda **kw: resnet.resnet18(num_classes=100, **kw))
register("cifar100_wrn16_8")(lambda **kw: resnet.wrn16_8(num_classes=100, **kw))
# 10-class WRN-16-8 for the bundled real handwritten-digits set — the offline
# stand-in for the reference's CIFAR-100 convergence logs (data/datasets.py
# DigitsDataLoader; CIFAR binaries are not downloadable in this environment)
register("digits_wrn16_8")(lambda **kw: resnet.wrn16_8(num_classes=10, **kw))
register("tiny_imagenet_resnet18")(
    lambda **kw: resnet.resnet18(num_classes=200, **kw))
register("tiny_imagenet_wrn16_8")(
    lambda **kw: resnet.wrn16_8(num_classes=200, **kw))
register("tiny_imagenet_resnet50")(
    lambda **kw: resnet.resnet50(num_classes=200, small_input=True, **kw))
register("resnet50_imagenet")(
    lambda **kw: resnet.resnet50(num_classes=1000, small_input=False, **kw))
register("tiny_imagenet_vit")(
    lambda **kw: vit.ViT(num_classes=200, patch_size=8, **kw))
register("flash_vit")(
    lambda **kw: vit.ViT(num_classes=200, patch_size=8, backend="pallas", **kw))

# -- language (parity: example_models.cpp:384-504) ---------------------------

register("gpt2_tiny")(lambda **kw: gpt2_lib.gpt2_tiny(**kw))
register("gpt2_small")(lambda **kw: gpt2_lib.gpt2_small(**kw))
register("gpt2_small_hd128")(lambda **kw: gpt2_lib.gpt2_small_hd128(**kw))
register("flash_gpt2_small_hd128")(
    lambda **kw: gpt2_lib.gpt2_small_hd128(backend="pallas", **kw))
register("gpt2_small_gqa4")(lambda **kw: gpt2_lib.gpt2_small_gqa4(**kw))
register("flash_gpt2_small_gqa4")(
    lambda **kw: gpt2_lib.gpt2_small_gqa4(backend="pallas", **kw))
register("llama_small")(lambda **kw: llama_lib.llama_small(**kw))
register("flash_llama_small")(
    lambda **kw: llama_lib.llama_small(backend="pallas", **kw))
register("llama_1b")(lambda **kw: llama_lib.llama_1b(**kw))
register("evabyte")(lambda **kw: llama_lib.evabyte(**kw))
register("evabyte_tiny")(lambda **kw: llama_lib.evabyte_tiny(**kw))
register("mistral_small4")(lambda **kw: llama_lib.mistral_small4(**kw))
register("mistral_small4_tiny")(
    lambda **kw: llama_lib.mistral_small4_tiny(**kw))
register("trinity_large_ep8")(
    lambda **kw: llama_lib.trinity_large_ep8(**kw))
register("trinity_large_tiny")(
    lambda **kw: llama_lib.trinity_large_tiny(**kw))
register("longcat_flash_ep32")(
    lambda **kw: llama_lib.longcat_flash_ep32(**kw))
register("longcat_flash_tiny")(
    lambda **kw: llama_lib.longcat_flash_tiny(**kw))
register("qwen3_next_ep4")(
    lambda **kw: llama_lib.qwen3_next_ep4(**kw))
register("qwen3_next_tiny")(
    lambda **kw: llama_lib.qwen3_next_tiny(**kw))
register("granite4_h_micro")(
    lambda **kw: llama_lib.granite4_h_micro(**kw))
register("granite4_h_micro_cpu")(
    lambda **kw: llama_lib.granite4_h_micro_cpu(**kw))
register("gpt2_medium")(lambda **kw: gpt2_lib.gpt2_medium(**kw))
register("gpt2_large")(lambda **kw: gpt2_lib.gpt2_large(**kw))
register("flash_gpt2_small")(lambda **kw: gpt2_lib.gpt2_small(backend="pallas", **kw))
register("flash_gpt2_medium")(lambda **kw: gpt2_lib.gpt2_medium(backend="pallas", **kw))
register("flash_gpt2_large")(lambda **kw: gpt2_lib.gpt2_large(backend="pallas", **kw))
register("moe_gpt2_small")(lambda **kw: gpt2_lib.gpt2_small(moe_experts=8, **kw))
