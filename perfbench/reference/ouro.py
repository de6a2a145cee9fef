"""Plain reference of a looped decoder (ByteDance Ouro, arXiv:2510.25741):
the layer stack runs `loops` times over ONE set of weights, every pass
with keys and values of its own; a layer has four RMS norms (two before
its sublayers, two on their outputs before the residual add); the model's
one final norm closes every pass and its output is the next pass's input;
an exit gate (one linear unit with a bias on the closed pass's state)
gives lambda_t = sigmoid(g_t), p_t = lambda_t * prod_{s<t}(1 - lambda_s)
(the last pass takes what is left), and the head reads, a position at a
time, the closed state of the first pass whose p add up to
`exit_threshold` (the last pass at the published 1.0). Every pass is
computed whatever the gate says.

Straight jax.numpy in float32 at "highest" matmul precision, one layer at
a time with its weights cast up as it is used: no kernels, no cache (a
pass attends the keys it computed itself, all positions at once), nothing
imported from the program. Plain multi-head attention where the config has
a K/V head a query head (grouped where it has fewer), rotary embedding over
the whole head (half-split pairing), SwiGLU, no biases, an untied head.

Parameters are the program's tree: embed [V, d], layers.* stacked on a
leading layer axis (attn_norm, wq, wk, wv, wo, attn_post_norm, mlp_norm,
wg, wu, wd, mlp_post_norm), final_norm [d], lm_head [d, V], exit_gate
{w [d], b []}.

`FAULTS` (tools/checkdist_ouro.py sets it; empty = the model): what a wrong
program would compute, for the comparison that decides `correct` to refuse:
  "shared_cache"  every pass attends pass 0's keys and values (a cache
                  indexed by layer alone)
  "open_stream"   the final norm is read by the gate and the head only;
                  the next pass takes the un-normed stream
  "kv_f8"         keys and values rounded through float8_e4m3fn
(fewer passes and two norms a layer are fields of the config: `loops`,
`post_norms`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

FAULTS: frozenset = frozenset()


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [b, s, heads, hd]; position i rotates pair (j, j + hd/2) by
    i * theta^(-2j/hd)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _keys_values(n, lp, c, faults):
    """The keys (rotated) and values a layer computes from its normed
    input n."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, _ = n.shape
    k = _rope((n @ f32(lp["wk"])).reshape(b, s, c.n_kv_heads, c.head_dim),
              c.rope_theta)
    v = (n @ f32(lp["wv"])).reshape(b, s, c.n_kv_heads, c.head_dim)
    if "kv_f8" in faults:
        k, v = (f32(a.astype(jnp.float8_e4m3fn)) for a in (k, v))
    return k, v


def _layer(x, lp, c, faults, kv=None):
    """One layer of one pass -> (output, the keys and values it used)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, _ = x.shape
    h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    n = _rmsnorm(x, f32(lp["attn_norm"]), c.norm_eps)
    q = _rope((n @ f32(lp["wq"])).reshape(b, s, h, hd), c.rope_theta)
    k, v = _keys_values(n, lp, c, faults) if kv is None else kv
    kr = jnp.repeat(k, h // hkv, axis=2)
    vr = jnp.repeat(v, h // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, vr).reshape(b, s, h * hd)
    a = att @ f32(lp["wo"])
    if c.post_norms:
        a = _rmsnorm(a, f32(lp["attn_post_norm"]), c.norm_eps)
    x = x + a
    n = _rmsnorm(x, f32(lp["mlp_norm"]), c.norm_eps)
    m = (jax.nn.silu(n @ f32(lp["wg"])) * (n @ f32(lp["wu"]))) @ f32(lp["wd"])
    if c.post_norms:
        m = _rmsnorm(m, f32(lp["mlp_post_norm"]), c.norm_eps)
    return x + m, (k, v)


@functools.lru_cache(maxsize=None)
def _jitted_layer(c, faults):
    """One compiled layer a (config, faults): every pass, layer and
    sequence of a process calls the same one."""
    return jax.jit(lambda x, lp, kv: _layer(x, lp, c, faults, kv))


def exit_pass(gates, threshold: float):
    """gates [T, ...] (g_t of every pass) -> the exit pass of every
    position [...] int32: the first t whose p_0 + .. + p_t >= threshold,
    the last pass where none is."""
    lam = jax.nn.sigmoid(gates)
    still = jnp.cumprod(1.0 - lam, axis=0)                 # prod_{s<=t}
    before = jnp.concatenate([jnp.ones_like(still[:1]), still[:-1]], 0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], 0)
    reached = jnp.cumsum(p, axis=0) >= threshold
    last = gates.shape[0] - 1
    return jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                     last).astype(jnp.int32)


def states_of(params, c, tokens, faults=None):
    """tokens [b, s] -> (every pass's closed state [T, b, s, d] float32,
    its gate g_t [T, b, s])."""
    faults = FAULTS if faults is None else frozenset(faults)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        layer = _jitted_layer(c, faults)
        x = f32(jnp.take(params["embed"], tokens, axis=0))
        gate = params["exit_gate"]
        closed, gates, first = [], [], []
        for t in range(c.loops):
            for li in range(c.n_layers):
                lp = jax.tree.map(lambda a: a[li], params["layers"])
                kv = first[li] if ("shared_cache" in faults and t) else None
                x, kv = layer(x, lp, kv)
                if t == 0:
                    first.append(kv)
            h = _rmsnorm(x, f32(params["final_norm"]), c.norm_eps)
            if "open_stream" not in faults:
                x = h
            closed.append(h)
            gates.append(h @ f32(gate["w"]) + f32(gate["b"]))
        return jnp.stack(closed), jnp.stack(gates)


def logits_of(params, c, tokens, faults=None):
    """tokens [b, s] -> float32 logits [b, s, V] of each position's exit
    pass."""
    closed, gates = states_of(params, c, tokens, faults)
    with jax.default_matmul_precision("highest"):
        at = exit_pass(gates, c.exit_threshold)            # [b, s]
        h = jnp.take_along_axis(closed, at[None, ..., None], axis=0)[0]
        head = params["embed"].T if c.tie_embeddings else params["lm_head"]
        return jax.jit(lambda a, w: a @ w.astype(jnp.float32))(h, head)


def logprobs_of(params, c, prompt: list, generated: list,
                faults=None) -> tuple:
    """(log p(generated[i] | prompt + generated[:i]) for every i, the
    router margin at the position that predicts each: nothing routes hard,
    so every margin is infinite)."""
    seq = list(prompt) + list(generated)
    pad = -(-len(seq) // 128) * 128     # causal: right-padding is unseen
    tokens = jnp.zeros((1, pad), jnp.int32).at[0, :len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    logp = jax.nn.log_softmax(logits_of(params, c, tokens, faults)[0], -1)
    pos = jnp.arange(len(prompt) - 1, len(seq) - 1)
    return ([float(x) for x in logp[pos, jnp.asarray(generated, jnp.int32)]],
            [math.inf] * len(generated))


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
