"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434; the source's
modeling_deepseek.py): straight jax.numpy in float32 at "highest" matmul
precision, one layer at a time, no kernels, no cache, no dispatch, nothing
imported from the program. Attention in its NON-absorbed form only.

The equations (x after the layer's input RMSNorm, eps from the config):

  Attention, every layer.
    c_q = RMSNorm(x W_qa);  [q_nope_h | q_pe_h] = c_q W_qb  per head h
    [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv);  k_pe = RoPE(k_pe),
    one for all heads;  q_pe_h = RoPE(q_pe_h)
    [k_nope_h | v_h] = c_kv W_kvb
    s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * (nope + rope)^-0.5 * m^2
    m = 0.1 * mscale_all_dim * ln(factor) + 1
    causal softmax, o = concat_h(sum p_h v_h) W_o
  RoPE is YaRN: frequency i is a blend of 1/theta_i and 1/(factor theta_i)
  by the linear ramp between the correction dims of beta_fast and beta_slow
  rotations over original_max_position_embeddings; cos and sin carry
  mscale / mscale_all_dim.
  Feed-forward. Layer < first_k_dense: SwiGLU(x). Else:
    g = softmax(x W_g) over the router's published width; group score =
    max of g in each of n_group groups; the topk_group best groups stay,
    the rest is zeroed; top-k of what is left; weights are those g values
    times routed_scaling_factor (renormalised instead where norm_topk_prob)
    y = sum_k w_k SwiGLU^(e_k)(x) + SwiGLU^shared(x)

Departures, shared with the program and listed in the configuration's
`assumed`: rotary pairs are (j, j + rope/2) (half-split) where the
checkpoint interleaves them, a fixed permutation of W_qb's and W_kva's rope
columns; the model holds `moe_experts` of the router's `moe_router_experts`
experts (group `moe_held_group`) and computes THEIR part of the sum plus
the shared experts, as the chip of the stated deployment would; what the
absent experts add is left out here too.

Parameters are the program's tree (ray_tpu/models/deepseek_v2.py):
embed [V, d]; layers: a list, each {attn_norm, wq_a [d, rq], q_norm,
wq_b [rq, h * (nope+rope)], wkv_a [d, rkv+rope], kv_norm, W_kvb as its
halves w_uk [h, rkv, nope] and w_uv [h, rkv, v], wo [h * v, d], mlp_norm} and {wg, wu, wd} (dense) or {router
[d, X], wg/wu [E, d, f], wd [E, f, d], shared_wg, shared_wu, shared_wd};
final_norm [d]; lm_head [d, V].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512     # query rows and heads whose [heads, rows, keys] scores
HEAD_BLOCK = 16   # exist at once: 0.26 GB at 8064 keys


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(c, n: int):
    """(sin, cos) [n, rope/2] and the softmax scale."""
    dim, theta = c.qk_rope_head_dim, float(c.rope_theta)
    half = dim // 2
    base = theta ** (np.arange(half, dtype=np.float64) / half)
    scale = (c.qk_nope_head_dim + dim) ** -0.5
    freqs, amp = 1.0 / base, 1.0
    if c.rope_scaling:
        rs = dict(c.rope_scaling)
        factor = float(rs["factor"])
        orig = float(rs["original_max_position_embeddings"])

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
        high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
        freqs = ramp / (factor * base) + (1 - ramp) / base
        amp = (_mscale(factor, float(rs.get("mscale", 1.0)))
               / _mscale(factor, float(rs.get("mscale_all_dim", 0.0))))
        scale *= _mscale(factor, float(rs.get("mscale_all_dim", 0.0))) ** 2
    ang = np.arange(n, dtype=np.float64)[:, None] * freqs
    return (jnp.asarray(np.sin(ang) * amp, jnp.float32),
            jnp.asarray(np.cos(ang) * amp, jnp.float32), scale)


def _rotate(x, sin, cos):
    """x [s, ..., rope], pair (j, j + rope/2) rotated by position."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = sin.reshape(shape), cos.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(n, lp, c, sin, cos, scale):
    """n [s, d] normed -> attention output [s, d]."""
    s = n.shape[0]
    nope, rank = c.qk_nope_head_dim, c.kv_lora_rank
    c_q = _rmsnorm(n @ _f32(lp["wq_a"]), _f32(lp["q_norm"]), c.norm_eps)
    kva = n @ _f32(lp["wkv_a"])
    c_kv = _rmsnorm(kva[:, :rank], _f32(lp["kv_norm"]), c.norm_eps)
    k_pe = _rotate(kva[:, rank:], sin, cos)                      # [s, rope]
    wq_b = lp["wq_b"].reshape(lp["wq_b"].shape[0], c.n_heads, -1)
    wo = lp["wo"].reshape(c.n_heads, c.v_head_dim, -1)
    att = jnp.zeros_like(n)
    for h0 in range(0, c.n_heads, HEAD_BLOCK):   # a few heads at a time,
        hs = slice(h0, h0 + HEAD_BLOCK)          # so that it fits at 8k
        q = jnp.einsum("sr,rhe->she", c_q, _f32(wq_b[:, hs]))
        k_nope = jnp.einsum("sr,hre->she", c_kv, _f32(lp["w_uk"][hs]))
        v = jnp.einsum("sr,hre->she", c_kv, _f32(lp["w_uv"][hs]))
        q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], sin, cos)
        out = []
        for q0 in range(0, s, Q_BLOCK):  # and a block of queries at a time
            rows = slice(q0, min(q0 + Q_BLOCK, s))
            sc = (jnp.einsum("qhe,khe->hqk", q_nope[rows], k_nope)
                  + jnp.einsum("qhe,ke->hqk", q_pe[rows], k_pe)) * scale
            mask = (jnp.arange(s)[None, :]
                    <= jnp.arange(rows.start, rows.stop)[:, None])
            p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hqk,khv->qhv", p, v))
        att = att + jnp.einsum("shv,hvd->sd", jnp.concatenate(out, axis=0),
                               _f32(wo[hs]))
    return att


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def _experts(n, lp, c):
    """n [s, d] -> (held experts' part + shared experts [s, d], margin [s]:
    the least of the gap between the last expert chosen and the best left
    out and the gap between the last group kept and the best dropped, both
    in log g)."""
    k, E = c.moe_top_k, c.moe_experts
    g = jax.nn.softmax(n @ _f32(lp["router"]), axis=-1)          # [s, X]
    margin = jnp.full(n.shape[:1], jnp.inf)
    if c.moe_n_group > 1:
        per = g.shape[-1] // c.moe_n_group
        gs = g.reshape(-1, c.moe_n_group, per).max(-1)
        best, _ = jax.lax.top_k(gs, c.moe_topk_group + 1)
        margin = jnp.log(best[:, -2]) - jnp.log(best[:, -1])
        keep = gs >= best[:, -2:-1]
        g = jnp.where(jnp.repeat(keep, per, axis=1), g, 0.0)
    best, idx = jax.lax.top_k(g, k + 1)
    margin = jnp.minimum(margin,
                         jnp.log(best[:, -2]) - jnp.log(best[:, -1]))
    w, idx = best[:, :k], idx[:, :k]
    if k > 1 and c.moe_norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * c.moe_routed_scale
    y = jnp.zeros_like(n)
    for e in range(E):                    # every held expert, one at a time
        we = jnp.sum(jnp.where(idx == c.moe_held_group * E + e, w, 0.0), -1)
        y = y + we[:, None] * _swiglu(n, lp["wg"][e], lp["wu"][e],
                                      lp["wd"][e])
    if c.moe_shared_experts:
        y = y + _swiglu(n, lp["shared_wg"], lp["shared_wu"], lp["shared_wd"])
    return y, margin


def _layer(x, lp, c, li, sin, cos, scale):
    x = x + _attention(_rmsnorm(x, _f32(lp["attn_norm"]), c.norm_eps), lp, c,
                       sin, cos, scale)
    n = _rmsnorm(x, _f32(lp["mlp_norm"]), c.norm_eps)
    if li < c.first_k_dense or not c.moe_experts:
        return (x + _swiglu(n, lp["wg"], lp["wu"], lp["wd"]),
                jnp.full(x.shape[:1], jnp.inf))
    y, margin = _experts(n, lp, c)
    return x + y, margin


def hidden_of(params, c, tokens):
    """tokens [s] -> (final-norm hidden states [s, d] float32, each
    position's least router margin over the layers [s])."""
    with jax.default_matmul_precision("highest"):
        sin, cos, scale = rope_tables(c, len(tokens))
        x = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        margin = jnp.full(x.shape[:1], jnp.inf)
        for li, lp in enumerate(params["layers"]):
            x, m = _layer(x, lp, c, li, sin, cos, scale)
            margin = jnp.minimum(margin, m)
        return _rmsnorm(x, _f32(params["final_norm"]), c.norm_eps), margin


def logprobs_of(params, c, prompt: list, generated: list) -> tuple:
    """(log p(generated[i] | prompt + generated[:i]) for every i, the
    router margin at the position that predicts each)."""
    seq = list(prompt) + list(generated)
    x, margin = hidden_of(params, c, np.asarray(seq, np.int32))
    pos = np.arange(len(prompt) - 1, len(seq) - 1)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x[pos] @ _f32(params["lm_head"]), axis=-1)
    return ([float(v) for v in logp[np.arange(len(pos)),
                                    np.asarray(generated, np.int32)]],
            [float(v) for v in margin[pos]])


def mean_loss(params, c, tokens) -> float:
    """Next-token cross entropy of tokens [b, s + 1], mean over b * s."""
    host = np.asarray(tokens)
    total = 0.0
    for row in host:                      # one sequence at a time
        x, _ = hidden_of(params, c, row[:-1])
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)
        total += float(-jnp.mean(logp[np.arange(len(row) - 1), row[1:]]))
    return total / host.shape[0]
