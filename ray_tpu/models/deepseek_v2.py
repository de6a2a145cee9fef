"""DeepSeek-V2's decoder (arXiv:2405.04434): latent attention (MLA), one or
more leading dense layers, then layers of routed experts beside shared
experts, chosen by a group-limited router. `ModelConfig.attention == "mla"`
selects this module (models.init_params, the serving engine).

The equations (x after the layer's input RMSNorm; h heads):

  Attention, every layer.
    c_q = RMSNorm(x W_qa)                         [q_lora_rank]
    [q_nope_h | q_pe_h] = c_q W_qb   per head     [nope | rope]
    [c_kv | k_pe] = x W_kva                       [kv_lora_rank | rope]
    c_kv = RMSNorm(c_kv);  k_pe = RoPE(k_pe), one for all heads
    q_pe_h = RoPE(q_pe_h)
    [k_nope_h | v_h] = c_kv W_kvb    per head     [nope | v]
      (W_kvb is kept as its halves W_UK [h, rank, nope], W_UV [h, rank, v])
    s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * (nope + rope)^-0.5 * m^2
    m = 0.1 * mscale_all_dim * ln(factor) + 1     (YaRN; 1 without it)
    o = concat_h(softmax_causal_fp32(s_h) v_h) W_o
  RoPE is YaRN (ops/layers.yarn_frequencies) in the half-split pair layout.
  The cache holds (c_kv, k_pe) after norm and rotation: the LATENT,
  kv_lora_rank + rope numbers a token and layer, shared by all heads.

  Two forms of the same function:
    up-projected (prefill): k_h, v_h as above, a flash kernel over them;
    absorbed (decode): q~_h = q_nope_h W_UK,h^T  [kv_lora_rank],
      s_h = (q~_h . c_kv + q_pe_h . k_pe) * scale,
      o_h = (sum_t p_t c_kv,t) W_UV,h, with W_UK | W_UV the two halves of
      W_kvb: attention in the latent, a page read once for all heads.

  Feed-forward. Layers < first_k_dense: SwiGLU of width d_ff. The others:
    g = softmax_fp32(x W_g) over moe_router_experts
    group score = max of g in each of moe_n_group groups; the
    moe_topk_group best groups stay, the rest is zeroed; top-k of what is
    left; weights are those g values, renormalised if moe_norm_topk, else
    times moe_routed_scale (the source scales only in that branch)
    y = sum_k w_k SwiGLU^(e_k)(x) + SwiGLU^shared(x)

The expert layer (models/experts.py, shared with models/nemotron_h.py) is
told which experts it HOLDS and leaves out what the absent experts would
add: nothing stands in for the absent chips.

Parameters: {"embed" [V, d], "layers": [one tree a layer; kinds differ],
"final_norm" [d], "lm_head" [d, V]}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.experts import (N_STATS, expert_layer,  # noqa: F401
                                    init_expert_weights, route, stats_zero)
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.ops.latent_attention import (mla_prefill_attention,
                                          paged_latent_decode_attention)
from ray_tpu.ops.layers import (apply_rope, last_rows, rmsnorm, rope, swiglu,
                                yarn_mscale)


def latent_width(c: ModelConfig) -> int:
    return c.kv_lora_rank + c.qk_rope_head_dim


def softmax_scale(c: ModelConfig) -> float:
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    if c.rope_scaling:
        s = dict(c.rope_scaling)
        m = yarn_mscale(float(s["factor"]), float(s.get("mscale_all_dim", 0)))
        scale *= m * m
    return scale


def _is_dense(c: ModelConfig, li: int) -> bool:
    return li < c.first_k_dense or not c.moe_experts


# ---------------------------------------------------------------- params


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _seeded(key, shape: tuple, scale: float, dtype: str):
    """One weight, made under jit straight into its dtype: the float32
    draw never outlives this call (eagerly, the three tensors of one
    expert layer are 5.7 GB of float32 beside what already exists)."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _init_layer(key, config: ModelConfig, dense: bool) -> dict:
    c, dt = config, config.jdtype
    d, h = c.d_model, c.n_heads
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return _seeded(next(ks), shape, fan_in ** -0.5, c.dtype)

    lp = {
        "attn_norm": jnp.ones((d,), dt),
        "wq_a": w((d, c.q_lora_rank), d),
        "q_norm": jnp.ones((c.q_lora_rank,), dt),
        "wq_b": w((c.q_lora_rank, h * (c.qk_nope_head_dim
                                       + c.qk_rope_head_dim)),
                  c.q_lora_rank),
        "wkv_a": w((d, latent_width(c)), d),
        "kv_norm": jnp.ones((c.kv_lora_rank,), dt),
        # W_kvb as its two halves, heads first: both forms contract them
        # head by head (a batched product over h) where they lie
        "w_uk": w((h, c.kv_lora_rank, c.qk_nope_head_dim), c.kv_lora_rank),
        "w_uv": w((h, c.kv_lora_rank, c.v_head_dim), c.kv_lora_rank),
        "wo": w((h * c.v_head_dim, d), h * c.v_head_dim),
        "mlp_norm": jnp.ones((d,), dt),
    }
    if dense:
        lp.update(wg=w((d, c.d_ff), d), wu=w((d, c.d_ff), d),
                  wd=w((c.d_ff, d), c.d_ff))
        return lp
    lp.update(init_expert_weights(
        w, c, lambda shape, scale: _seeded(next(ks), shape, scale, c.dtype)))
    return lp


def init_params(config: ModelConfig, key) -> dict:
    """Seeded weights in the configuration's dtype, no float32 leaf."""
    c = config
    if c.tie_embeddings:
        raise ValueError("ModelConfig.attention=\"mla\": the head is untied "
                         "(tie_embeddings must be False)")
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    return {
        "embed": _seeded(k_embed, (c.vocab, c.d_model), 0.02, c.dtype),
        "layers": [_init_layer(jax.random.fold_in(k_layers, li), c,
                               _is_dense(c, li))
                   for li in range(c.n_layers)],
        "final_norm": jnp.ones((c.d_model,), c.jdtype),
        "lm_head": _seeded(k_head, (c.d_model, c.vocab), c.d_model ** -0.5,
                           c.dtype),
    }


# ------------------------------------------------------------- attention


def _mla_project(x, lp, c: ModelConfig, sin, cos, fence: bool = False):
    """x [b, s, d] (normed) -> q_nope [b, s, h, nope], q_pe [b, s, h, rope]
    (rotated), latent [b, s, kv_lora_rank + rope] as the cache holds it.
    `fence` (decode) as llm/engine._qkv's: at a handful of tokens XLA
    otherwise folds the head split into the projection and re-lays out
    the weight on every step."""
    b, s, _ = x.shape
    rank, nope = c.kv_lora_rank, c.qk_nope_head_dim
    hold = jax.lax.optimization_barrier if fence else (lambda a: a)
    c_q = rmsnorm(hold(jnp.einsum("bsd,dr->bsr", x, lp["wq_a"])),
                  lp["q_norm"], c.norm_eps)
    q = hold(jnp.einsum("bsr,rk->bsk", c_q, lp["wq_b"])).reshape(
        b, s, c.n_heads, nope + c.qk_rope_head_dim)
    kva = hold(jnp.einsum("bsd,dr->bsr", x, lp["wkv_a"]))
    c_kv = rmsnorm(kva[..., :rank], lp["kv_norm"], c.norm_eps)
    k_pe = apply_rope(kva[:, :, None, rank:], sin, cos)[:, :, 0]
    return (q[..., :nope], apply_rope(q[..., nope:], sin, cos),
            jnp.concatenate([c_kv, k_pe], axis=-1))


def _attend_up_projected(q_nope, q_pe, keys, prefix_len, lengths, lp,
                         c: ModelConfig, pre_t: int):
    """The up-projected form, one request at a time (the 192- and 128-wide
    per-head K and V of one request are 0.7 GB at 8192 keys; a burst's
    would not fit beside the weights). q_* [n, S, h, .], right-padded to
    `lengths`; keys [n, pre_t + S, latent] = cached prefix | this chunk;
    -> [n, S, h, v]."""
    rank = c.kv_lora_rank

    def one(args):
        qn, qp, lat, plen, rows = args
        k = jnp.concatenate(
            [jnp.einsum("tr,hrn->htn", lat[:, :rank], lp["w_uk"]),
             jnp.broadcast_to(lat[None, :, rank:],
                              (c.n_heads,) + lat[:, rank:].shape)],
            axis=-1)                                       # [h, T, nope+rope]
        v = jnp.einsum("tr,hrv->htv", lat[:, :rank], lp["w_uv"])
        q = jnp.concatenate([qn, qp], axis=-1).transpose(1, 0, 2)
        o = mla_prefill_attention(
            q[None], k[None], v[None], plen[None], pre_t=pre_t,
            scale=softmax_scale(c), lengths=rows[None])
        return o[0].transpose(1, 0, 2)                     # [S, h, v]

    with jax.named_scope("mla_prefill"):
        return jax.lax.map(one, (q_nope, q_pe, keys, prefix_len, lengths))


def _absorb_query(q_nope, q_pe, lp):
    """q~_h = q_nope_h W_UK,h^T beside q_pe_h: [..., h, latent]."""
    return jnp.concatenate(
        [jnp.einsum("...hn,hrn->...hr", q_nope, lp["w_uk"]), q_pe], axis=-1)


def _unabsorb_output(o_lat, lp):
    """o_h = (sum_t p_t c_kv,t) W_UV,h: [..., h, rank] -> [..., h, v]."""
    return jnp.einsum("...hr,hrv->...hv", o_lat, lp["w_uv"])


def attend_absorbed_dense(q_nope, q_pe, keys, lp, c: ModelConfig):
    """The absorbed form over an unpaged causal sequence, in plain jnp
    (tests: it must equal the up-projected form). q_* [n, S, h, .], keys
    [n, S, latent] -> [n, S, h, v]."""
    s = q_nope.shape[1]
    q_lat = _absorb_query(q_nope, q_pe, lp).astype(jnp.float32)
    lat = keys.astype(jnp.float32)
    sc = jnp.einsum("nqhc,nkc->nhqk", q_lat, lat) * softmax_scale(c)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o_lat = jnp.einsum("nhqk,nkr->nqhr", jax.nn.softmax(sc, axis=-1),
                       lat[..., :c.kv_lora_rank])
    return _unabsorb_output(o_lat.astype(keys.dtype), lp)


# ----------------------------------------------------------- feed-forward


def _mlp_block(h, lp, c: ModelConfig, li: int, valid, stats):
    """h [b, s, d] + feed-forward of layer li; valid [b, s]."""
    normed = rmsnorm(h, lp["mlp_norm"], c.norm_eps)
    if _is_dense(c, li):
        return h + swiglu(normed, lp["wg"], lp["wu"], lp["wd"]), stats
    b, s, d = h.shape
    y, st = expert_layer(normed.reshape(b * s, d), lp, c,
                         valid.reshape(b * s))
    return h + y.reshape(b, s, d), stats + st


def _head(x, params):
    """bf16 operands, float32 accumulation and logits."""
    return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------- whole-sequence forward


def _prefill(params, tokens, lengths, stats, c: ModelConfig, prefix=None):
    """The layers over tokens [n, S] at positions prefix_len + arange(S),
    keys = cached prefix pages (if any) | the chunk. Returns (final hidden
    states [n, S, d], latents [L, n, S, latent], stats)."""
    n, s = tokens.shape
    if prefix is None:
        prefix_len, pre_t = jnp.zeros((n,), jnp.int32), 0
    else:
        pool, prefix_pages, prefix_len = prefix
        pre_t = prefix_pages.shape[1] * pool.shape[3]
    x = jnp.take(params["embed"], tokens, axis=0)
    sin, cos = rope(prefix_len[:, None] + jnp.arange(s)[None],
                    c.qk_rope_head_dim, c.rope_theta, c.rope_scaling)
    valid = jnp.arange(s)[None] < lengths[:, None]
    latents = []
    for li, lp in enumerate(params["layers"]):
        normed = rmsnorm(x, lp["attn_norm"], c.norm_eps)
        q_nope, q_pe, lat = _mla_project(normed, lp, c, sin, cos)
        latents.append(lat)
        keys = lat
        if pre_t:
            # [n, Pp, latent, page] -> [n, Pp * page, latent]
            pre = pool[li][prefix_pages].transpose(0, 1, 3, 2).reshape(
                n, pre_t, -1)
            keys = jnp.concatenate([pre.astype(lat.dtype), lat], axis=1)
        o = _attend_up_projected(q_nope, q_pe, keys, prefix_len, lengths, lp,
                                 c, pre_t)
        h = x + jnp.einsum("bsk,kd->bsd", o.reshape(n, s, -1), lp["wo"])
        x, stats = _mlp_block(h, lp, c, li, valid, stats)
    return (rmsnorm(x, params["final_norm"], c.norm_eps),
            jnp.stack(latents), stats)


def forward(params, tokens, config: ModelConfig, mesh=None):
    """tokens [batch, seq] -> logits [batch, seq, vocab] float32 (CPU use
    and tests; the serving programs are below)."""
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            "ModelConfig.attention=\"mla\" runs on one device: no mesh")
    full = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _, _ = _prefill(params, tokens, full, stats_zero(config), config)
    return _head(x, params)


# ---------------------------------------- the serving engine's programs


def prefill_batch(params, tokens, lengths, stats, config: ModelConfig):
    """tokens [n, S] right-padded, lengths [n] -> (logits [n, vocab] at
    each request's last token, latents [L, n, S, latent], stats)."""
    x, latents, stats = _prefill(params, tokens, lengths, stats, config)
    return _head(last_rows(x, lengths), params), latents, stats


def prefill_with_prefix_batch(params, tokens, lengths, pool, prefix_pages,
                              prefix_len, stats, config: ModelConfig):
    """As prefill_batch for the SUFFIX of prompts whose first prefix_len
    tokens are cached in `pool` pages prefix_pages [n, Pp] (0-padded). The
    cached latents are up-projected like the chunk's own: at 640 against
    2176 operations a token pair and head the up-projection of a prefix
    (0.27 GFLOP a cached token and layer, once) is repaid after 1.4 query
    tokens, and one flash kernel then serves both prefill programs."""
    x, latents, stats = _prefill(params, tokens, lengths, stats, config,
                                 (pool, prefix_pages, prefix_len))
    return _head(last_rows(x, lengths), params), latents, stats


def insert_latent_pages_batch(pool, latents, page_ids, lengths):
    """engine.insert_pages_batch's twin. latents [L, n, S, latent];
    page_ids [n, n_tab] (0 = scratch); lengths [n]; the padded tail of a
    page is zeroed."""
    L, n, S, width = latents.shape
    page, n_tab = pool.shape[3], page_ids.shape[1]
    s_pad = n_tab * page
    if s_pad != S:
        latents = jnp.pad(latents, [(0, 0), (0, 0), (0, s_pad - S), (0, 0)])
    mask = (jnp.arange(s_pad)[None] < lengths[:, None])[None, :, :, None]
    pages = jnp.where(mask, latents, 0).reshape(
        L, n * n_tab, page, width).swapaxes(2, 3)
    return pool.at[:, page_ids.reshape(-1)].set(pages.astype(pool.dtype))


def decode_paged(params, pool, tokens, lengths, active, page_tables, stats,
                 config: ModelConfig):
    """One token for every slot against the latent pool [L, N, latent,
    page]: engine.decode_paged's twin (unrolled layers, donated pool, the
    new token's latent written before the kernel attends over it).
    Returns (logits [B, vocab] float32, pool, stats)."""
    c = config
    B, P = page_tables.shape
    page = pool.shape[3]
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]   # [B, 1, d]
    sin, cos = rope(lengths[:, None], c.qk_rope_head_dim, c.rope_theta,
                    c.rope_scaling)
    w_idx = jnp.clip(lengths // page, 0, P - 1)
    w_page = jnp.take_along_axis(page_tables, w_idx[:, None], 1)[:, 0]
    w_page = jnp.where((lengths // page >= P) | ~active, 0, w_page)
    w_off = lengths % page
    for li, lp in enumerate(params["layers"]):
        normed = rmsnorm(x, lp["attn_norm"], c.norm_eps)
        q_nope, q_pe, lat = _mla_project(normed, lp, c, sin, cos,
                                         fence=True)
        # one strided column a slot, in place: a scatter over the page
        # axis makes XLA re-lay-out the whole pool, twice a layer
        col = lat[:, 0, :, None].astype(pool.dtype)          # [B, latent, 1]
        for b in range(B):
            pool = jax.lax.dynamic_update_slice(
                pool, col[b][None, None], (li, w_page[b], 0, w_off[b]))
        with jax.named_scope("mla_decode"):
            o_lat = paged_latent_decode_attention(
                _absorb_query(q_nope[:, 0], q_pe[:, 0], lp), pool,
                lengths + 1, page_tables, layer=li, rank=c.kv_lora_rank,
                scale=softmax_scale(c))
            o = _unabsorb_output(o_lat.astype(x.dtype), lp)
        h = x + jnp.einsum("bk,kd->bd", o.reshape(B, -1), lp["wo"])[:, None]
        x, stats = _mlp_block(h, lp, c, li, active[:, None], stats)
    x = rmsnorm(x, params["final_norm"], c.norm_eps)
    logits = _head(x[:, 0], params)
    neg = jnp.full_like(logits, -1e30).at[:, 0].set(0.0)
    return jnp.where(active[:, None], logits, neg), pool, stats


def pool_shape(c: ModelConfig, num_pages: int, page: int) -> tuple:
    return (c.n_layers, num_pages, latent_width(c), page)


# ---- what the engine asks for by name (llm/engine._serving_of) ----


def page_pools(c: ModelConfig, num_pages: int, page: int) -> tuple:
    return (jax.ShapeDtypeStruct(pool_shape(c, num_pages, page), c.jdtype),)


insert_pages_batch = insert_latent_pages_batch
