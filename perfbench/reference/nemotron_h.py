"""Plain reference of the Nemotron-H decoder (NVIDIA Nemotron-3-Nano-30B-A3B,
`model_type` "nemotron_h"; the Mamba-2 recurrence of arXiv:2405.21060):
straight jax.numpy in float32 at "highest" matmul precision, one block at
a time, the recurrence one position at a time — no chunks, no cache, no
kernels, no dispatch, nothing imported from the program.

Block i of the pattern (one letter a block):  h <- h + mixer_i(RMSNorm(h)),
eps from the config; then a final RMSNorm and an untied head. No bias
anywhere except the convolution's.

  "M", Mamba-2.  d_i = heads * head width; G groups; N state size.
    [z d_i | xBC d_i + 2 G N | dt heads] = u W_in
    xBC_t = silu(sum_{j<W} w[:, j] * xBC_{t-W+1+j} + b), zeros before the
      sequence (depthwise, causal, W taps)
    xBC -> x [heads, P] | B [G, N] | C [G, N]; head h reads group
      h // (heads / G)
    dt = softplus(dt + dt_bias) a head (no limits);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      S [P, N] a head
    y_t = S_t C_t + D x_t
    y <- y * silu(z); RMS norm over groups of d_i / G, times a weight [d_i]
    out = y W_out
  "*", attention.  q [h, hd], k and v [hkv, hd] (query head j reads K/V
    head j // (h / hkv)), scale hd^-0.5, causal softmax, o W_o. NO rotary
    embedding: this family's attention layers apply none.
  "E", experts.  s = sigmoid(u W_r) over the router's published width;
    the top-k of s + b (b: e_score_correction_bias) are chosen; the
    weights are s (WITHOUT b) at the chosen, divided by their sum + 1e-20
    where norm_topk_prob, THEN times routed_scaling_factor.
    Expert: W_down relu(W_up u)^2, no gate matrix. One shared expert of the
    same form for every token.

Departure, shared with the program and listed in the configuration's
`assumed` and `deployment`: the model holds `moe_experts` of the router's
`moe_router_experts` experts (group `moe_held_group`) and computes THEIR
part of the sum plus the shared expert, as one chip of the stated
deployment would; what the absent experts add is left out here too.

Parameters are the program's tree (ray_tpu/models/nemotron_h.py): embed
[V, d]; layers: a list, a block each, {norm [d]} and, by the block's
letter, "M": {in_proj [d, 2 d_i + 2 G N + heads], conv_w [d_i + 2 G N, W],
conv_b, dt_bias [heads], a_log [heads], d [heads], gate_norm [d_i],
out_proj [d_i, d]}; "*": {wq [d, h hd], wk, wv [d, hkv hd], wo [h hd, d]};
"E": {router [d, X], router_bias [X], wu [E, d, f], wd [E, f, d],
shared_wu [d, fs], shared_wd [fs, d]}; final_norm [d]; lm_head [d, V].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 8    # query heads whose [heads, s, s] scores exist at once

# tools/checkdist_faults.py only: (a, b) makes every Mamba layer compute
# positions >= b as a program would that resumed at b from the state and
# window of boundary a < b (a snapshot of the wrong boundary). None here.
MAMBA_BLIND = None

# (exponent, mantissa) bits of what `ssm_state_dtype` may name
STATE_BITS = {"float32": (8, 23), "bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


def _f32(a):
    return a.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(u, lp, c, keep=()):
    """u [s, d] normed -> (the mixer's output [s, d], the state [H, P, N]
    after each of the `keep` positions' worth of tokens)."""
    if MAMBA_BLIND is None:
        return _mamba_over(u, lp, c, keep)
    a, b = MAMBA_BLIND
    short, _ = _mamba_over(jnp.concatenate([u[:a], u[b:]]), lp, c)
    whole, kept = _mamba_over(u, lp, c, keep)
    return jnp.concatenate([whole[:b], short[a:]]), kept


def _mamba_over(u, lp, c, keep=()):
    s = u.shape[0]
    H, P, N, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    W = c.ssm_conv_width
    di, gn = H * P, G * N
    proj = u @ _f32(lp["in_proj"])
    cd = di + 2 * gn
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    w = _f32(lp["conv_w"])[:, -W:]      # the taps nearest the position
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
    conv = _f32(lp["conv_b"])[None] + sum(
        padded[j:j + s] * w[None, :, j] for j in range(W))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(s, H, P)
    b = jnp.repeat(xbc[:, di:di + gn].reshape(s, G, N), H // G, axis=1)
    cc = jnp.repeat(xbc[:, di + gn:].reshape(s, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"])[None])        # [s, H]
    a = -jnp.exp(_f32(lp["a_log"]))

    def step(state, t):
        x_t, b_t, c_t, dt_t = t                  # [H, P], [H, N], [H, N], [H]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        # kept between positions in the precision the configuration states
        # (reduce_precision: a convert there and back is removed by XLA)
        state = jax.lax.reduce_precision(state, *STATE_BITS[c.ssm_state_dtype])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    # the recurrence in runs that end where a state is asked for
    state, ys, kept = jnp.zeros((H, P, N)), [], []
    cuts = sorted(set(keep) | {0, s})
    for lo, hi in zip(cuts, cuts[1:]):
        state, y = jax.lax.scan(step, state, (x[lo:hi], b[lo:hi], cc[lo:hi],
                                              dt[lo:hi]))
        ys.append(y)
        kept.append(state)
    kept = [kept[cuts.index(k) - 1] for k in keep]
    y = jnp.concatenate(ys) + _f32(lp["d"])[None, :, None] * x
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = y.reshape(s, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c.norm_eps)
    return ((y.reshape(s, di) * _f32(lp["gate_norm"]))
            @ _f32(lp["out_proj"]), kept)


def _attention(u, lp, c):
    """u [s, d] normed -> attention output [s, d]; no rotary embedding."""
    s = u.shape[0]
    h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    q = (u @ _f32(lp["wq"])).reshape(s, h, hd)
    k = (u @ _f32(lp["wk"])).reshape(s, hkv, hd)
    v = (u @ _f32(lp["wv"])).reshape(s, hkv, hd)
    wo = _f32(lp["wo"]).reshape(h, hd, -1)
    mask = jnp.tril(jnp.ones((s, s), bool))
    out = jnp.zeros_like(u)
    for h0 in range(0, h, HEAD_BLOCK):
        hs = np.arange(h0, min(h0 + HEAD_BLOCK, h))
        kv = hs // (h // hkv)
        sc = jnp.einsum("qhe,khe->hqk", q[:, hs], k[:, kv]) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        out = out + jnp.einsum("hqk,khe,hed->qd", p, v[:, kv], wo[hs])
    return out


def _relu2(x, w_up, w_down):
    r = jax.nn.relu(x @ _f32(w_up))
    return (r * r) @ _f32(w_down)


def _experts(u, lp, c):
    """u [s, d] -> (held experts' part + the shared expert [s, d], margin
    [s]: the choice score (s + b) of the last expert chosen minus that of
    the best one left out)."""
    k, E = c.moe_top_k, c.moe_experts
    score = jax.nn.sigmoid(u @ _f32(lp["router"]))               # [s, X]
    best, idx = jax.lax.top_k(score + _f32(lp["router_bias"])[None], k + 1)
    margin = best[:, -2] - best[:, -1]
    idx = idx[:, :k]
    w = jnp.take_along_axis(score, idx, axis=1)                  # without b
    if k > 1 and c.moe_norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * c.moe_routed_scale
    y = jnp.zeros_like(u)
    for e in range(E):                    # every held expert, one at a time
        we = jnp.sum(jnp.where(idx == c.moe_held_group * E + e, w, 0.0), -1)
        y = y + we[:, None] * _relu2(u, lp["wu"][e], lp["wd"][e])
    if c.moe_shared_experts:
        y = y + _relu2(u, lp["shared_wu"], lp["shared_wd"])
    return y, margin


def hidden_of(params, c, tokens, states=None):
    """tokens [s] -> (final-norm hidden states [s, d] float32, each
    position's least router margin over the expert layers [s]). `states`:
    {n: []} gets every Mamba layer's state after the first n tokens
    appended to its list, in the layers' order (states_of)."""
    keep = tuple(sorted(states or ()))
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        margin = jnp.full(x.shape[:1], jnp.inf)
        for kind, lp in zip(c.layer_pattern, params["layers"]):
            u = _rmsnorm(x, _f32(lp["norm"]), c.norm_eps)
            if kind == "M":
                y, kept = _mamba(u, lp, c, keep)
                x = x + y
                for n, state in zip(keep, kept):
                    states[n].append(state)
            elif kind == "*":
                x = x + _attention(u, lp, c)
            else:
                y, m = _experts(u, lp, c)
                x, margin = x + y, jnp.minimum(margin, m)
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


def states_of(params, c, tokens, at: list) -> np.ndarray:
    """The recurrent state of every Mamba layer after the first n tokens
    of `tokens`, for each n of `at` (0 < n <= len(tokens)): [len(at), LM,
    H, P, N] float32, by the recurrence above, a position at a time. What
    a serving engine's row of state is compared with directly
    (tools/checkstate.py)."""
    states = {int(n): [] for n in at}
    hidden_of(params, c, np.asarray(tokens, np.int32), states)
    return np.stack([np.stack([np.asarray(s) for s in states[int(n)]])
                     for n in at])


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
