"""Plain reference of the decoder both configurations are: RMSNorm, rotary
embedding (half-split pairing), grouped-query causal attention, SwiGLU or a
top-k mixture of SwiGLU experts, untied head. Straight jax.numpy in
float32 at "highest" matmul precision, one layer at a time: no kernels, no
cache, no batching tricks, nothing imported from the program.

Follows the published descriptions (Qwen2 technical report; Mixtral of
Experts, arXiv:2401.04088: softmax over the top-k router logits).
Departures, shared with the program under test and listed in each
configuration's `assumed`: no q/k/v biases (Qwen2 has them), the router
softmax is taken over all experts and the top-k weights renormalised —
the same numbers as a softmax over the top-k logits.

Parameters are the program's tree: embed [V, d], layers.* stacked on a
leading layer axis, final_norm [d], lm_head [d, V].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [b, s, heads, hd]; position i rotates pair (j, j + hd/2) by
    i * theta^(-2j/hd)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c):
    """One layer: (output, router margin [b, s]). The margin is the gap
    between the last chosen expert's router logit and the best one left
    out; infinite for a dense layer."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, d = x.shape
    h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    n = _rmsnorm(x, f32(lp["attn_norm"]), c.norm_eps)
    q = _rope((n @ f32(lp["wq"])).reshape(b, s, h, hd), c.rope_theta)
    k = _rope((n @ f32(lp["wk"])).reshape(b, s, hkv, hd), c.rope_theta)
    v = (n @ f32(lp["wv"])).reshape(b, s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * hd)
    x = x + att @ f32(lp["wo"])
    n = _rmsnorm(x, f32(lp["mlp_norm"]), c.norm_eps)
    if c.moe_experts:
        probs = jax.nn.softmax(n @ f32(lp["router"]), axis=-1)
        best, top_i = jax.lax.top_k(probs, c.moe_top_k + 1)
        margin = jnp.log(best[..., -2]) - jnp.log(best[..., -1])
        top_w, top_i = best[..., :-1], top_i[..., :-1]
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(c.moe_experts):   # every expert, one at a time
            w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)[..., None]
            act = jax.nn.silu(n @ f32(lp["wg"][e])) * (n @ f32(lp["wu"][e]))
            y = y + w * (act @ f32(lp["wd"][e]))
        return x + y, margin
    act = jax.nn.silu(n @ f32(lp["wg"])) * (n @ f32(lp["wu"]))
    return x + act @ f32(lp["wd"]), jnp.full((b, s), jnp.inf)


def logits_of(params, c, tokens, with_margin: bool = False):
    """tokens [b, s] -> float32 logits [b, s, V]; with_margin also gives
    each position's smallest router margin over the layers [b, s]."""
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp: _layer(x, lp, c))
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        margin = jnp.full(tokens.shape, jnp.inf)
        for li in range(c.n_layers):
            x, m = layer(x, jax.tree.map(lambda a: a[li], params["layers"]))
            margin = jnp.minimum(margin, m)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), c.norm_eps)
        head = (params["embed"].T if c.tie_embeddings
                else params["lm_head"])
        logits = jax.jit(lambda a, w: a @ w.astype(jnp.float32))(x, head)
        return (logits, margin) if with_margin else logits


def logprobs_of(params, c, prompt: list, generated: list) -> tuple:
    """(log p(generated[i] | prompt + generated[:i]) for every i, the
    router margin at the position that predicts each)."""
    seq = list(prompt) + list(generated)
    pad = -(-len(seq) // 128) * 128     # causal: right-padding is unseen
    tokens = jnp.zeros((1, pad), jnp.int32).at[0, :len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    logits, margin = logits_of(params, c, tokens, with_margin=True)
    logp = jax.nn.log_softmax(logits[0], axis=-1)
    pos = jnp.arange(len(prompt) - 1, len(seq) - 1)
    return ([float(x) for x in logp[pos, jnp.asarray(generated, jnp.int32)]],
            [float(x) for x in margin[0, pos]])


def mean_loss(params, c, tokens) -> float:
    """Next-token cross entropy of tokens [b, s + 1], mean over b * s."""
    host = np.asarray(tokens)            # sharded or not: one host copy
    inputs, targets = host[:, :-1], host[:, 1:]
    total = 0.0
    for i in range(inputs.shape[0]):     # one sequence at a time
        logp = jax.nn.log_softmax(
            logits_of(params, c, jnp.asarray(inputs[i:i + 1]))[0], axis=-1)
        total += float(-jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / inputs.shape[0]
