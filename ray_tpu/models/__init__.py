"""Model family: Llama-style decoder transformers, dense and MoE.

Functional style (pure pytrees + apply fns), not a port of the reference's
torch models: parameters carry logical sharding axes so one model definition
lowers to DP/FSDP/TP/SP/EP via the rules table in ray_tpu.parallel.sharding.
"""

from ray_tpu.models import transformer
from ray_tpu.models.transformer import (
    ModelConfig,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.models import configs


def model_module(config: ModelConfig):
    """The module that implements `config`: nemotron_h where it has a
    `layer_pattern`, windowed where it has an `attn_pattern`, else by its
    `attention` field this package's transformer ("gqa") or deepseek_v2
    ("mla"). A looped stack (`loops` > 1) and a layer's two further norms
    (`post_norms`) are the transformer's alone: the others refuse them."""
    if config.layer_pattern:
        from ray_tpu.models import nemotron_h as module
    elif config.attn_pattern:
        from ray_tpu.models import windowed as module
    elif config.attention == "mla":
        from ray_tpu.models import deepseek_v2 as module
    elif config.attention != "gqa":
        raise ValueError(f"ModelConfig.attention={config.attention!r}: "
                         f"\"gqa\" or \"mla\"")
    else:
        return transformer
    if config.loops > 1 or config.post_norms:
        raise ValueError(
            f"ModelConfig.loops={config.loops}, post_norms="
            f"{config.post_norms}: {module.__name__} runs its layers once, "
            f"each with two norms; only models/transformer.py (attention="
            f"\"gqa\", no layer_pattern, no attn_pattern) loops")
    return module


def init_params(config: ModelConfig, key) -> dict:
    return model_module(config).init_params(config, key)


def forward(params, tokens, config: ModelConfig, mesh=None):
    return model_module(config).forward(params, tokens, config, mesh)


__all__ = ["ModelConfig", "init_params", "forward", "loss_fn",
           "param_logical_axes", "configs"]
