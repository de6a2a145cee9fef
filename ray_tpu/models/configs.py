"""Named model configs (tiny test configs through Llama-3-8B class)."""

from __future__ import annotations

from ray_tpu.models.transformer import ModelConfig


def _preset(kw: dict, **fields) -> ModelConfig:
    """A preset's fields with the caller's overrides winning, so depth can
    be cut without touching a width: `configs.qwen2_7b(n_layers=2)`."""
    return ModelConfig(**(fields | kw))


def tiny(**kw) -> ModelConfig:
    """CPU-test scale."""
    return _preset(kw, vocab=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128)


def tiny_moe(**kw) -> ModelConfig:
    return _preset(kw, vocab=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=4, d_ff=128, moe_experts=4, moe_top_k=2)


def llama3_8b(**kw) -> ModelConfig:
    """Llama-3-8B geometry (BASELINE north-star FSDP config)."""
    return _preset(kw, vocab=128256, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, rope_theta=500000.0,
                   dtype="bfloat16", remat=True)


def llama3_1b(**kw) -> ModelConfig:
    return _preset(kw, vocab=128256, d_model=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, d_ff=8192, rope_theta=500000.0,
                   dtype="bfloat16")


def bench_125m(**kw) -> ModelConfig:
    """Single-chip bench scale (GPT-small geometry)."""
    return _preset(kw, vocab=32000, d_model=768, n_layers=12, n_heads=12,
                   n_kv_heads=12, d_ff=3072, dtype="bfloat16")


def llama_125m(**kw) -> ModelConfig:
    """Default serving scale (alias of the bench geometry)."""
    return bench_125m(**kw)


def llama3_70b(**kw) -> ModelConfig:
    """Llama-3-70B geometry (multi-slice FSDP+TP target)."""
    return _preset(kw, vocab=128256, d_model=8192, n_layers=80, n_heads=64,
                   n_kv_heads=8, d_ff=28672, rope_theta=500000.0,
                   dtype="bfloat16", remat=True)


def mixtral_8x7b(**kw) -> ModelConfig:
    """Mixtral-8x7B geometry: 8-expert top-2 MoE (the EP mesh-axis
    flagship)."""
    return _preset(kw, vocab=32000, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, rope_theta=1e6,
                   moe_experts=8, moe_top_k=2,
                   dtype="bfloat16", remat=True)


def qwen2_7b(**kw) -> ModelConfig:
    """Qwen-2-7B-class geometry (GQA, untied head)."""
    return _preset(kw, vocab=152064, d_model=3584, n_layers=28, n_heads=28,
                   n_kv_heads=4, d_ff=18944, rope_theta=1e6,
                   dtype="bfloat16", remat=True, tie_embeddings=False)


def deepseek_v2(**kw) -> ModelConfig:
    """DeepSeek-V2 (236B-A21B) at its published sizes: latent attention,
    one dense layer, then 160 routed experts (6 a token from 3 of 8
    groups) beside 2 shared experts; YaRN over 4096 positions. Every
    expert held: a serving replica names its share through `moe_experts`,
    `moe_router_experts` and `moe_held_group`."""
    return _preset(
        kw, vocab=102400, d_model=5120, n_layers=60, n_heads=128,
        n_kv_heads=128, d_ff=12288, rope_theta=10000.0, norm_eps=1e-6,
        dtype="bfloat16", tie_embeddings=False, attention="mla",
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
                      ("mscale", 0.707), ("mscale_all_dim", 0.707),
                      ("original_max_position_embeddings", 4096),
                      ("type", "yarn")),
        first_k_dense=1, moe_experts=160, moe_top_k=6, moe_d_ff=1536,
        moe_shared_experts=2, moe_router_experts=160, moe_n_group=8,
        moe_topk_group=3, moe_routed_scale=16.0, moe_norm_topk=False)


def tiny_mla(**kw) -> ModelConfig:
    """CPU-test scale of deepseek_v2's structure: 1 dense layer + 1 expert
    layer, 8 routed experts in 4 groups (2 of them a token), 1 shared."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, tie_embeddings=False, attention="mla", q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
                      ("mscale", 0.707), ("mscale_all_dim", 0.707),
                      ("original_max_position_embeddings", 64),
                      ("type", "yarn")),
        first_k_dense=1, moe_experts=8, moe_top_k=3, moe_d_ff=32,
        moe_shared_experts=1, moe_router_experts=8, moe_n_group=4,
        moe_topk_group=2, moe_routed_scale=4.0, moe_norm_topk=False)


def nemotron3_nano_30b(**kw) -> ModelConfig:
    """NVIDIA Nemotron-3-Nano-30B-A3B at its published sizes: 52 blocks of
    one mixer each (23 Mamba-2, 23 expert, 6 attention), 128 routed relu^2
    experts (6 a token, sigmoid scores) beside one shared expert. Every
    expert held: a serving replica names its share through `moe_experts`,
    `moe_router_experts` and `moe_held_group`."""
    return _preset(
        kw, vocab=131072, d_model=2688, n_layers=52, n_heads=32,
        n_kv_heads=2, head_size=128, d_ff=1856, norm_eps=1e-5,
        dtype="bfloat16", tie_embeddings=False, rotary=False,
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv_width=4, ssm_chunk=128, mlp_act="relu2",
        moe_score="sigmoid", moe_scale_normed=True, moe_experts=128,
        moe_router_experts=128, moe_top_k=6, moe_d_ff=1856,
        moe_shared_experts=1, moe_shared_d_ff=3712, moe_routed_scale=2.5,
        moe_norm_topk=True, moe_grouped="tiles")


def tiny_hybrid(**kw) -> ModelConfig:
    """CPU-test scale of nemotron3_nano_30b's structure: 7 blocks (3
    Mamba-2 of 8 heads x 8 over a state of 16 in 2 groups, 3 expert, 1
    attention), 8 routed experts (3 a token) beside one shared expert."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=7, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=32, norm_eps=1e-5, tie_embeddings=False,
        rotary=False, layer_pattern="MEM*EME", ssm_heads=8, ssm_head_dim=8,
        ssm_state=16, ssm_groups=2, ssm_conv_width=4, ssm_chunk=8,
        mlp_act="relu2", moe_score="sigmoid", moe_scale_normed=True,
        moe_experts=8, moe_router_experts=8, moe_top_k=3, moe_d_ff=32,
        moe_shared_experts=1, moe_shared_d_ff=48, moe_routed_scale=2.5,
        moe_norm_topk=True, moe_grouped="tiles")


def jamba_pattern(layers: int, period: int, offset: int) -> str:
    """Jamba's layers as blocks: a mixer then the dense MLP a layer, the
    mixer attention where layer % period == offset, Mamba-1 elsewhere."""
    return "".join(("*" if i % period == offset else "S") + "-"
                   for i in range(layers))


def jamba2_3b(**kw) -> ModelConfig:
    """AI21-Jamba2-3B at its published sizes: 28 layers of two blocks
    each (26 Mamba-1 and 2 multi-query attention mixers, layers 7 and 21,
    each followed by a dense gated MLP), a tied head, no positional
    embedding."""
    return _preset(
        kw, vocab=65536, d_model=2560, n_layers=56, n_heads=20, n_kv_heads=1,
        head_size=128, d_ff=8192, norm_eps=1e-6, dtype="bfloat16",
        tie_embeddings=True, rotary=False,
        layer_pattern=jamba_pattern(28, 14, 7), ssm_expand=2, ssm_state=16,
        ssm_conv_width=4, ssm_dt_rank=160, moe_top_k=1)


def tiny_jamba(**kw) -> ModelConfig:
    """CPU-test scale of jamba2_3b's structure: 5 layers (attention at
    layer 1 and 4), 128 channels over a state of 16, a 5 : 1 query group,
    a tied head."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=10, n_heads=5, n_kv_heads=1,
        head_size=16, d_ff=96, norm_eps=1e-6, tie_embeddings=True,
        rotary=False, layer_pattern=jamba_pattern(5, 3, 1), ssm_expand=2,
        ssm_state=16, ssm_conv_width=4, ssm_dt_rank=8)


def solar_pattern(layers: int, period: int) -> str:
    """Solar Open2's layers as blocks: a mixer then the expert layer a
    layer, the mixer attention where layer % period == 0, Kimi Delta
    Attention elsewhere."""
    return "".join(("*" if i % period == 0 else "K") + "E"
                   for i in range(layers))


def solar_open2_250b(**kw) -> ModelConfig:
    """upstage Solar-Open2-250B at its published sizes: 48 layers of two
    blocks each, the mixer of one in four gated GQA (64 query heads on 8
    K/V heads of 128, no rotary embedding, an output gate an element), of
    the rest Kimi Delta Attention (64 heads of 128, a convolution of 4
    taps, negative eigenvalues, low-rank decay and gate of 128); every
    layer 320 routed experts of 1280 (8 a token, sigmoid scores) beside
    one shared expert. Every expert held: a serving replica names its share
    through `moe_experts`, `moe_router_experts` and `moe_held_group`."""
    return _preset(
        kw, vocab=196608, d_model=4096, n_layers=96, n_heads=64,
        n_kv_heads=8, head_size=128, d_ff=10240, norm_eps=1e-5,
        dtype="bfloat16", tie_embeddings=False, rotary=False,
        layer_pattern=solar_pattern(48, 4), attn_gate="per_element",
        ssm_heads=64, ssm_head_dim=128, ssm_conv_width=4, ssm_chunk=64,
        kda_rank=128, kda_neg_eigval=True, moe_score="sigmoid",
        moe_experts=320, moe_router_experts=320, moe_top_k=8, moe_d_ff=1280,
        moe_shared_experts=1, moe_routed_scale=1.0,
        moe_norm_topk=True, moe_grouped="tiles")


def tiny_solar_open2(**kw) -> ModelConfig:
    """CPU-test scale of solar_open2_250b's structure: 4 layers (gated
    attention at layer 0, 4 query heads on 2 K/V heads of 16; then 3 Kimi
    Delta Attention layers of 4 heads of 16, low-rank pairs of 8), each
    followed by 8 routed experts (3 a token) beside one shared expert."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=96, norm_eps=1e-5, tie_embeddings=False,
        rotary=False, layer_pattern=solar_pattern(4, 4),
        attn_gate="per_element", ssm_heads=4, ssm_head_dim=16,
        ssm_conv_width=4, ssm_chunk=16, kda_rank=8, kda_neg_eigval=True,
        moe_score="sigmoid", moe_experts=8, moe_router_experts=8,
        moe_top_k=3, moe_d_ff=32, moe_shared_experts=1, moe_routed_scale=1.0,
        moe_norm_topk=True, moe_grouped="tiles")


def window_pattern(layers: int, period: int) -> str:
    """Attention kinds a layer: full ("F") where layer % period == 0, a
    window ("W") elsewhere."""
    return "".join("F" if i % period == 0 else "W" for i in range(layers))


def laguna_s_2_1(**kw) -> ModelConfig:
    """poolside Laguna-S-2.1 at its published sizes: 48 layers, one in four
    full attention (48 query heads, half of each head rotated, YaRN), the
    rest a window of 512 (72 query heads, plain rotary), 8 K/V heads of
    128 in both, a sigmoid gate a head; layer 0 a dense MLP, then 256
    routed experts (10 a token, sigmoid scores, x 2.5) beside one shared
    expert. Every expert held: a serving replica names its share through
    `moe_experts`, `moe_router_experts` and `moe_held_group`."""
    return _preset(
        kw, vocab=100352, d_model=3072, n_layers=48, n_heads=48,
        n_kv_heads=8, head_size=128, d_ff=12288, rope_theta=500000.0,
        norm_eps=1e-6, dtype="bfloat16", tie_embeddings=False,
        attn_pattern=window_pattern(48, 4), window=512, window_heads=72,
        window_rope_theta=10000.0, rotary_fraction=0.5,
        rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 128),
                      ("original_max_position_embeddings", 8192),
                      ("type", "yarn")),
        attn_gate="per_head", first_k_dense=1, moe_experts=256,
        moe_router_experts=256, moe_top_k=10, moe_d_ff=1024,
        moe_shared_experts=1, moe_shared_d_ff=1024, moe_score="sigmoid",
        moe_routed_scale=2.5, moe_norm_topk=True, moe_scale_normed=True,
        moe_grouped="tiles")


def tiny_laguna(**kw) -> ModelConfig:
    """CPU-test scale of laguna_s_2_1's structure: 5 layers (full at 0 and
    4: 4 query heads, half rotated, YaRN over 32 positions; a window of 20
    between: 6 query heads), 2 K/V heads of 16, a gate a head, one dense
    layer, then 8 routed experts (3 a token) beside one shared expert."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=96, rope_theta=500000.0, norm_eps=1e-6,
        tie_embeddings=False, attn_pattern=window_pattern(5, 4), window=20,
        window_heads=6, window_rope_theta=10000.0, rotary_fraction=0.5,
        rope_scaling=(("beta_fast", 32), ("beta_slow", 1), ("factor", 8),
                      ("original_max_position_embeddings", 32),
                      ("type", "yarn")),
        attn_gate="per_head", first_k_dense=1, moe_experts=8,
        moe_router_experts=8, moe_top_k=3, moe_d_ff=32,
        moe_shared_experts=1, moe_shared_d_ff=32, moe_score="sigmoid",
        moe_routed_scale=2.5, moe_norm_topk=True, moe_scale_normed=True,
        moe_grouped="tiles")


def mimo_v2_pattern(n_layers: int) -> str:
    """MiMo-V2.5's `hybrid_layer_pattern` (0 full, 1 window) as letters: a
    full layer, five window layers, the first period one window layer
    short (F W W W W, then F W W W W W ...), and the last layer full."""
    return "".join("F" if l == 0 or l % 6 == 5 else "W"
                   for l in range(n_layers))


def mimo_v2_5(**kw) -> ModelConfig:
    """Xiaomi MiMo-V2.5's language model at its published sizes: 48 layers,
    9 full attention (4 K/V heads, rotary base 1e7) and 39 a window of 128
    (8 K/V heads, base 1e4, a learned sink a head), 64 query heads in
    both, q and k 192 wide of which the first 64 rotate, v 128 wide and
    scaled by 0.707; layer 0 a dense MLP of 16384, then 256 routed experts
    of 2048 (8 a token, sigmoid scores, a bias in the choice, weights
    renormalised, none shared). Every expert held: a serving replica names
    its share through `moe_experts`, `moe_router_experts` and
    `moe_held_group`."""
    return _preset(
        kw, vocab=152576, d_model=4096, n_layers=48, n_heads=64,
        n_kv_heads=4, head_size=192, v_head_dim=128, d_ff=16384,
        rope_theta=1e7, norm_eps=1e-5, dtype="bfloat16",
        tie_embeddings=False, attn_pattern=mimo_v2_pattern(48), window=128,
        window_heads=64, window_kv_heads=8, window_rope_theta=10000.0,
        rotary_fraction=0.334, window_rotary_fraction=0.334, attn_sink="W",
        value_scale=0.707, first_k_dense=1, moe_experts=256,
        moe_router_experts=256, moe_top_k=8, moe_d_ff=2048,
        moe_score="sigmoid", moe_router_bias=True, moe_norm_topk=True,
        moe_grouped="tiles")


def tiny_mimo_v2(**kw) -> ModelConfig:
    """CPU-test scale of mimo_v2_5's structure: 7 layers (F W W W W F W),
    8 query heads on 2 ("F") and 4 ("W") K/V heads, q and k 24 wide (the
    first 8 rotate), v 16 wide and scaled, a window of 16 with a sink a
    head, one dense layer, then 8 routed experts (3 a token) with a bias
    in the choice."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=7, n_heads=8, n_kv_heads=2,
        head_size=24, v_head_dim=16, d_ff=96, rope_theta=1e7, norm_eps=1e-5,
        tie_embeddings=False, attn_pattern=mimo_v2_pattern(7), window=16,
        window_heads=8, window_kv_heads=4, window_rope_theta=10000.0,
        rotary_fraction=0.334, window_rotary_fraction=0.334, attn_sink="W",
        value_scale=0.707, first_k_dense=1, moe_experts=8,
        moe_router_experts=8, moe_top_k=3, moe_d_ff=32, moe_score="sigmoid",
        moe_router_bias=True, moe_norm_topk=True, moe_grouped="tiles")


def ouro_2_6b(**kw) -> ModelConfig:
    """ByteDance Ouro-2.6B at its published sizes: 48 layers run 4 times
    over one set of weights (192 cache layers), 16 heads of 128 on 16 K/V
    heads (a group of one), four norms a layer, the final norm closing
    every pass, an exit gate a pass (threshold 1: the last pass), an untied
    head."""
    return _preset(
        kw, vocab=49152, d_model=2048, n_layers=48, n_heads=16,
        n_kv_heads=16, head_size=128, d_ff=5632, rope_theta=1e6,
        norm_eps=1e-6, dtype="bfloat16", tie_embeddings=False, loops=4,
        exit_threshold=1.0, post_norms=True)


def tiny_ouro(**kw) -> ModelConfig:
    """CPU-test scale of ouro_2_6b's structure: 3 layers run 4 times, 4
    heads of 16 on 4 K/V heads, four norms a layer, an untied head."""
    return _preset(
        kw, vocab=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        head_size=16, d_ff=96, rope_theta=1e6, norm_eps=1e-6,
        tie_embeddings=False, loops=4, exit_threshold=1.0, post_norms=True)
