"""Model registry: name -> (Config, init, apply, loss_fn, logical_axes).

The analog of the reference's per-framework job kinds (TFJob/PyTorchJob pick a
user image); here a JAXJob spec names a registered model + config overrides.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, NamedTuple


class ModelDef(NamedTuple):
    config_cls: type
    init: Callable
    apply: Callable
    loss_fn: Callable
    logical_axes: Callable


_REGISTRY: dict[str, ModelDef] = {}
_populated = False
_populate_lock = threading.Lock()


def register(name: str, model: ModelDef) -> None:
    _REGISTRY[name] = model


def get(name: str) -> ModelDef:
    if name not in _REGISTRY:
        _populate()
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def names() -> list[str]:
    _populate()
    return sorted(_REGISTRY)


def make_config(name: str, overrides: dict[str, Any] | None = None):
    model = get(name)
    return model.config_cls(**(overrides or {}))


def config_with(cfg, **overrides):
    return dataclasses.replace(cfg, **overrides)


def _populate() -> None:
    """Thread-safe lazy registration: concurrent trial pods hit get() at
    once, and the flag must only flip AFTER every built-in is registered
    (flag-first left a window where a second thread saw an empty
    registry)."""
    global _populated
    if _populated:
        return
    with _populate_lock:
        if _populated:
            return
        _do_populate()
        _populated = True


def _do_populate() -> None:
    from kubeflow_tpu.models import (bert, kimi_linear, laguna, llama, lora,
                                     mnist_cnn, moe_llama, nas_cnn,
                                     nemotron_h, pangu_ultra_moe, resnet,
                                     vit)

    register("llama", ModelDef(llama.LlamaConfig, llama.init, llama.apply,
                               llama.loss_fn, llama.logical_axes))
    register("llama_lora", ModelDef(lora.LoraLlamaConfig, lora.init,
                                    lora.apply, lora.loss_fn,
                                    lora.logical_axes))
    register("mixtral", ModelDef(moe_llama.MoELlamaConfig, moe_llama.init,
                                 moe_llama.apply, moe_llama.loss_fn,
                                 moe_llama.logical_axes))
    register("kimi_linear", ModelDef(
        kimi_linear.KimiLinearConfig, kimi_linear.init, kimi_linear.apply,
        kimi_linear.loss_fn, kimi_linear.logical_axes))
    register("laguna", ModelDef(
        laguna.LagunaConfig, laguna.init, laguna.apply, laguna.loss_fn,
        laguna.logical_axes))
    register("pangu_ultra_moe", ModelDef(
        pangu_ultra_moe.PanguUltraMoEConfig, pangu_ultra_moe.init,
        pangu_ultra_moe.apply, pangu_ultra_moe.loss_fn,
        pangu_ultra_moe.logical_axes))
    register("nemotron_h", ModelDef(
        nemotron_h.NemotronHConfig, nemotron_h.init, nemotron_h.apply,
        nemotron_h.loss_fn, nemotron_h.logical_axes))
    register("mnist_cnn", ModelDef(mnist_cnn.MnistConfig, mnist_cnn.init,
                                   mnist_cnn.apply, mnist_cnn.loss_fn,
                                   mnist_cnn.logical_axes))
    register("bert", ModelDef(bert.BertConfig, bert.init, bert.apply,
                              bert.loss_fn, bert.logical_axes))
    register("resnet", ModelDef(resnet.ResNetConfig, resnet.init, resnet.apply,
                                resnet.loss_fn, resnet.logical_axes))
    register("nas_cnn", ModelDef(nas_cnn.NasCnnConfig, nas_cnn.init,
                                 nas_cnn.apply, nas_cnn.loss_fn,
                                 nas_cnn.logical_axes))
    register("darts_supernet", ModelDef(
        nas_cnn.NasCnnConfig, nas_cnn.darts_init, nas_cnn.darts_apply,
        nas_cnn.darts_loss_fn, nas_cnn.darts_logical_axes))
    register("vit", ModelDef(vit.ViTConfig, vit.init, vit.apply,
                             vit.loss_fn, vit.logical_axes))
