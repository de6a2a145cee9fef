"""From a configuration file (configs/<name>.json, the source's own key
names) to the program's ModelConfig / EngineConfig, by a table of fields
that the file may extend, and the decoders' count of training operations.
What is written here about a model is the default table and that count,
both for the two decoders the benchmark began with; a configuration of
another architecture brings its own as files (perfbench/README.md)."""

from __future__ import annotations

import dataclasses

# Log-probabilities of two independent programs over the same bf16 weights
# (the engine: dense fp32-softmax prefill, Pallas paged decode, bf16 KV
# pool; the reference: plain float32 jnp at "highest" precision). What
# differs is where activations round to bf16 (2^-8 relative), which moves
# a unit-variance logit by ~1e-2 over a dozen layers; a wrong token or
# position is off by whole units. chip_smoke.py's tolerance and reason
# (PR 21 measured 0.029 on the chip); float32 is the CPU rehearsal's.
LOGPROB_TOL = {"bfloat16": 0.1, "float32": 1e-2}


# Field of ray_tpu.models.ModelConfig -> where its value comes from: a key
# of the configuration's file (the source's own key name) with its cast,
# and a default where the source may leave the key out. The table of the
# two decoders the benchmark began with; a configuration's own
# "model_fields" is laid over it (perfbench/README.md).
DEFAULT_MODEL_FIELDS = {
    "vocab": {"key": "vocab_size", "cast": "int"},
    "d_model": {"key": "hidden_size", "cast": "int"},
    "n_layers": {"key": "num_hidden_layers", "cast": "int"},
    "n_heads": {"key": "num_attention_heads", "cast": "int"},
    "n_kv_heads": {"key": "num_key_value_heads", "cast": "int"},
    "d_ff": {"key": "intermediate_size", "cast": "int"},
    "rope_theta": {"key": "rope_theta", "cast": "float"},
    "norm_eps": {"key": "rms_norm_eps", "cast": "float"},
    "moe_experts": {"key": "num_local_experts", "cast": "int", "default": 0},
    "moe_top_k": {"key": "num_experts_per_tok", "cast": "int", "default": 2},
    "dtype": {"key": "torch_dtype", "cast": "str"},
    "tie_embeddings": {"key": "tie_word_embeddings", "cast": "bool"},
    "remat": {"key": "remat", "cast": "bool", "default": False},
}
CASTS = {"int": int, "float": float, "str": str, "bool": bool}


def _merged(cfg: dict, kind: str, rehearsal: bool) -> dict:
    out = dict(cfg)
    out.update(cfg.get("by_kind", {}).get(kind, {}))
    if rehearsal:
        out.update(cfg["rehearsal"]["model"])
    return out


def _hashable(v):
    """JSON lists as tuples, objects (a rope_scaling) as tuples of (key,
    value) sorted by key: the program's configs are frozen dataclasses
    used as static arguments. dict(value) gives an object back."""
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def model_config(cfg: dict, kind: str, rehearsal: bool = False):
    """ModelConfig by the field table. An entry is {"key": <key of the
    file>, "cast": int|float|str|bool, "default": <literal>} (cast and
    default optional), {"value": <literal>}, or null, which drops a default
    line so that the dataclass's own default holds. Keys are looked up
    after by_kind and the rehearsal's sizes were laid over the file."""
    from ray_tpu.models import ModelConfig
    c = _merged(cfg, kind, rehearsal)
    file = cfg.get("_file", "the configuration")
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {}
    for field, how in {**DEFAULT_MODEL_FIELDS,
                       **c.get("model_fields", {})}.items():
        if field not in known:
            raise ValueError(f"{file}: model_fields names `{field}`, which "
                             f"ray_tpu.models.ModelConfig does not have")
        if how is None:
            continue
        if (not isinstance(how, dict) or ("key" in how) == ("value" in how)
                or ("cast" in how and how["cast"] not in CASTS)):
            raise ValueError(f"{file}: model_fields[`{field}`] is {how!r}; "
                             f"want a key (with a cast of {sorted(CASTS)}, "
                             f"a default) or a value")
        if "value" in how:
            v = how["value"]
        elif how["key"] in c:
            v = c[how["key"]]
        elif "default" in how:
            v = how["default"]
        else:
            raise KeyError(f"{file}: ModelConfig.{field} is read from key "
                           f"`{how['key']}`, which the file does not have")
        kw[field] = CASTS[how["cast"]](v) if "cast" in how else _hashable(v)
    return ModelConfig(**kw)


def engine_config(cfg: dict, cellp: dict, rehearsal: bool = False):
    """EngineConfig from every key of the file's `engine` object, then the
    rehearsal's, then the cell's own shape, which wins. `reckoning` is
    prose; a key EngineConfig does not have is an error."""
    from ray_tpu.llm import EngineConfig
    e = {"kv_layout": "paged", **cfg["engine"]}
    if rehearsal:
        e.update(cfg["rehearsal"].get("engine", {}))
    e.update(cellp.get("engine", {}))
    e.pop("reckoning", None)
    known = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(set(e) - known)
    if unknown:
        raise ValueError(
            f"{cfg.get('_file', 'the configuration')}: `engine` has "
            f"{unknown}, which ray_tpu.llm.EngineConfig does not have")
    return EngineConfig(**{k: _hashable(v) for k, v in e.items()})


def matmul_params(m) -> int:
    """Parameters that multiply activations in a step (the embedding table
    is a lookup): what 6 * params * tokens counts."""
    hd = m.head_dim
    attn = m.d_model * hd * (2 * m.n_heads + 2 * m.n_kv_heads)
    ff = 3 * m.d_model * m.d_ff
    if m.moe_experts:
        ff = ff * m.moe_experts + m.d_model * m.moe_experts
    return m.n_layers * (attn + ff) + m.d_model * m.vocab


def active_matmul_params(m) -> int:
    """As matmul_params, with only the experts a token is routed to."""
    hd = m.head_dim
    attn = m.d_model * hd * (2 * m.n_heads + 2 * m.n_kv_heads)
    ff = 3 * m.d_model * m.d_ff
    if m.moe_experts:
        ff = ff * m.moe_top_k + m.d_model * m.moe_experts
    return m.n_layers * (attn + ff) + m.d_model * m.vocab


def train_flops_per_token(m, seq: int) -> float:
    """Forward plus backward, no recomputation: 6 per matmul parameter,
    and causal attention's two score-sized matmuls (QK^T, PV) forward and
    four backward, each 2 * seq/2 * head_dim per head and token."""
    attn = 6 * 2 * (seq / 2) * m.head_dim * m.n_heads * m.n_layers
    return 6.0 * active_matmul_params(m) + attn
