"""From a configuration file (configs/<name>.json, the source's own key
names) to the program's ModelConfig / EngineConfig. Nothing about a model
is written in code: a new configuration is a new file."""

from __future__ import annotations

# Log-probabilities of two independent programs over the same bf16 weights
# (the engine: dense fp32-softmax prefill, Pallas paged decode, bf16 KV
# pool; the reference: plain float32 jnp at "highest" precision). What
# differs is where activations round to bf16 (2^-8 relative), which moves
# a unit-variance logit by ~1e-2 over a dozen layers; a wrong token or
# position is off by whole units. chip_smoke.py's tolerance and reason
# (PR 21 measured 0.029 on the chip); float32 is the CPU rehearsal's.
LOGPROB_TOL = {"bfloat16": 0.1, "float32": 1e-2}


def _merged(cfg: dict, kind: str, rehearsal: bool) -> dict:
    out = dict(cfg)
    out.update(cfg.get("by_kind", {}).get(kind, {}))
    if rehearsal:
        out.update(cfg["rehearsal"]["model"])
    return out


def model_config(cfg: dict, kind: str, rehearsal: bool = False):
    from ray_tpu.models import ModelConfig
    c = _merged(cfg, kind, rehearsal)
    return ModelConfig(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_layers=int(c["num_hidden_layers"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        moe_experts=int(c.get("num_local_experts", 0)),
        moe_top_k=int(c.get("num_experts_per_tok", 2)),
        dtype=str(c["torch_dtype"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        remat=bool(c.get("remat", False)))


def engine_config(cfg: dict, cellp: dict, rehearsal: bool = False):
    from ray_tpu.llm import EngineConfig
    e = dict(cfg["engine"])
    if rehearsal:
        e.update(cfg["rehearsal"].get("engine", {}))
    e.update(cellp.get("engine", {}))   # the cell's own shape wins
    return EngineConfig(
        max_slots=int(e["max_slots"]), max_len=int(e["max_len"]),
        prompt_buckets=tuple(e["prompt_buckets"]),
        page_size=int(e["page_size"]),
        prefix_cache=bool(e["prefix_cache"]),
        eos_token=int(e["eos_token"]), kv_layout="paged")


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
