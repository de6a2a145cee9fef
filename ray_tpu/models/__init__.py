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
    `layer_pattern`, else by its `attention` field this package's
    transformer ("gqa") or deepseek_v2 ("mla")."""
    if config.layer_pattern:
        from ray_tpu.models import nemotron_h
        return nemotron_h
    if config.attention == "mla":
        from ray_tpu.models import deepseek_v2
        return deepseek_v2
    if config.attention != "gqa":
        raise ValueError(f"ModelConfig.attention={config.attention!r}: "
                         f"\"gqa\" or \"mla\"")
    return transformer


def init_params(config: ModelConfig, key) -> dict:
    return model_module(config).init_params(config, key)


def forward(params, tokens, config: ModelConfig, mesh=None):
    return model_module(config).forward(params, tokens, config, mesh)


__all__ = ["ModelConfig", "init_params", "forward", "loss_fn",
           "param_logical_axes", "configs"]
