"""Plain reference of the MiMo-V2 decoder (Xiaomi MiMo-V2.5, `model_type`
"mimo_v2", the language model alone): straight jax.numpy in float32 at
"highest" matmul precision, one layer at a time, the window as a mask on a
[rows, keys] score matrix and the sink as one more column of it — no cache,
no pages, no kernels, no dispatch, nothing imported from the program.

Layer l, u = RMSNorm(x, eps from the config), no bias in any projection:
  x <- x + Attn_l(u);  x <- x + FFN_l(RMSNorm(x));  final RMSNorm, untied head.

  Attention, by the layer's kind (attn_pattern: "F" full, "W" window):
    H query heads (n_heads "F", window_heads "W": 64 in both as published);
    K/V heads hkv n_kv_heads ("F") or window_kv_heads ("W"); query head j
    reads K/V head j // (H / hkv).
    q = u W_q [H, hd], k = u W_k [hkv, hd], v = value_scale * (u W_v)
    [hkv, dv] (hd = head_dim 192, dv = v_head_dim 128). No norm on q or k,
    no gate.
    Rotary, half-split pairs (i, i + r / 2) over the FIRST r = int(hd *
    fraction) dims of q and k (64 of 192), the rest passed through, base
    rope_theta ("F") or window_rope_theta ("W"), unscaled.
    Scores s_ij = q_i . k_j * hd^-0.5 in float32 over keys j <= i, and in a
    window layer only i - window < j.
    A kind named in `attn_sink` ("W" as published) has a learned logit b_h
    a query head: m_i = max(b_h, max_j s_ij), p_ij = exp(s_ij - m_i) /
    (exp(b_h - m_i) + sum_j exp(s_ij - m_i)): a softmax over the keys and
    one more column that gives no value. Otherwise p = softmax(s).
    o_i = sum_j p_ij v_j [H, dv], then W_o [H dv, d].
  FFN: layer < first_k_dense: W_down(silu(W_gate u) * W_up u). Else experts:
    g = sigmoid(u W_r) in float32 over the router's published width; the
    top-k of g + e_bias are chosen (`topk_method` noaux_tc; one group: no
    group limit); the weights are g at the chosen, divided by their sum +
    1e-20 (norm_topk_prob), times moe_routed_scale (1: the source's
    routed_scaling_factor is null); applied to the experts' OUTPUTS.
    Expert: SwiGLU of moe_d_ff. No shared expert.

Departures from the published description, shared with the program and
listed in the configuration's `assumed` and `deployment`:
  - the model holds `moe_experts` of the router's `moe_router_experts`
    experts (group `moe_held_group`) and computes THEIR part of the sum, as
    one chip of the stated deployment would; what the absent experts add
    is left out here too;
  - layers 0..n_layers - 1 of the 48 and rows [0, vocab) of embedding and
    head;
  - `attention_chunk_size` (128, equal to the window) is no part of the
    mask: a full layer is global;
  - the value scale is applied to v before the weighted sum (after it is
    the same function);
  - the three multi-token-prediction layers and the vision and audio
    towers are not here: the catalog's config has no key for them.

Parameters are the program's tree (ray_tpu/models/windowed.py): embed
[V, d]; layers: a list, a layer each: {attn_norm [d], wq [d, H hd], wk [d,
hkv hd], wv [d, hkv dv], wo [H dv, d], mlp_norm [d], sink [H] float32 where
the kind has one} and {w_gate, w_up [d, f], w_down [f, d]} or {router [d,
X], router_bias [X], wg, wu [E, d, fe], wd [E, fe, d]}; final_norm [d];
lm_head [d, V].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 8      # query heads whose [heads, rows, S] scores exist at once
ROW_BLOCK = 512     # query rows of them, and rows of a feed-forward pass

# tools/checkwindow_mimo_v2.py only, each what a wrong program would read;
# None / False here.
KV_DTYPE = None         # a dtype name: K and the scaled V rounded through it
ROUTER_DTYPE = None     # the router's logits computed in it
SCORES_DTYPE = None     # the attention scores computed in it
SINK_DTYPE = None       # the sinks rounded through it
SINK_WHERE_ABSENT = None    # a float: the sink of a layer whose kind
#                             `attn_sink` names and whose tree has none
WEIGHTS_DTYPE = None    # every weight matrix rounded through it


def _f32(a):
    return a.astype(jnp.float32)


def _w(a):
    """A weight matrix in float32 (through WEIGHTS_DTYPE where the tool
    sets it), taken where it is used: no second copy of the tree."""
    if WEIGHTS_DTYPE is not None:
        a = a.astype(jnp.dtype(WEIGHTS_DTYPE))
    return _f32(a)


def _through(dtype, fn, *operands):
    """fn(*operands); with a dtype name, of the operands rounded to it and
    with its result in it, back in float32."""
    if dtype is None:
        return fn(*operands)
    return _f32(fn(*(a.astype(jnp.dtype(dtype)) for a in operands)))


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(c, kind: str, s: int):
    """(cos, sin) [s, r / 2] float32 of a layer kind, r its rotated dims."""
    fraction, base = ((c.window_rotary_fraction, c.window_rope_theta)
                      if kind == "W" else (c.rotary_fraction, c.rope_theta))
    r = int(c.head_dim * fraction)
    inv_freq = 1.0 / base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    angles = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def _rotate(t, cos, sin):
    """t [s, heads, hd]: pairs (i, i + r / 2) of its first r dims."""
    half = cos.shape[-1]
    a, b, rest = t[..., :half], t[..., half:2 * half], t[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _rows(fn, u):
    """fn over u [s, d] in blocks of ROW_BLOCK rows."""
    return jnp.concatenate([fn(u[i:i + ROW_BLOCK])
                            for i in range(0, u.shape[0], ROW_BLOCK)])


def _attention(u, lp, c, kind: str):
    s = u.shape[0]
    window = kind == "W"
    h = c.window_heads if window else c.n_heads
    hkv = (c.window_kv_heads if window else 0) or c.n_kv_heads
    hd, dv = c.head_dim, c.v_head_dim or c.head_dim
    cos, sin = _rotary(c, kind, s)
    k = _rotate((u @ _w(lp["wk"])).reshape(s, hkv, hd), cos, sin)
    v = c.value_scale * (u @ _w(lp["wv"])).reshape(s, hkv, dv)
    if KV_DTYPE is not None:
        k, v = (_f32(a.astype(jnp.dtype(KV_DTYPE))) for a in (k, v))
    sink = None
    if kind in c.attn_sink:
        sink = (_f32(lp["sink"]) if "sink" in lp                  # [H]
                else jnp.full((h,), SINK_WHERE_ABSENT, jnp.float32))
        if SINK_DTYPE is not None:
            sink = _f32(sink.astype(jnp.dtype(SINK_DTYPE)))
    wq, wo = _w(lp["wq"]), _w(lp["wo"]).reshape(h, dv, -1)
    out = []
    for r0 in range(0, s, ROW_BLOCK):
        r1 = min(r0 + ROW_BLOCK, s)
        rows = jnp.arange(r0, r1)[:, None]
        # a block's queries alone: 64 heads of 192 over every row at once
        # would be 0.7 GB at 15,000 rows beside the engine's pools
        q = _rotate((u[r0:r1] @ wq).reshape(r1 - r0, h, hd), cos[r0:r1],
                    sin[r0:r1])
        # the block of keys some row of this block can see; the mask
        # decides within it
        k0 = max(r0 - c.window + 1, 0) if window else 0
        cols = jnp.arange(k0, r1)[None]
        mask = cols <= rows
        if window:
            mask &= cols > rows - c.window
        acc = jnp.zeros((rows.shape[0], u.shape[1]), jnp.float32)
        for h0 in range(0, h, HEAD_BLOCK):
            hs = np.arange(h0, min(h0 + HEAD_BLOCK, h))
            kv = hs // (h // hkv)
            sc = _through(SCORES_DTYPE, lambda a, b: jnp.einsum(
                "qhe,khe->hqk", a, b), q[:, hs], k[k0:r1][:, kv]) * hd ** -0.5
            sc = jnp.where(mask[None], sc, -jnp.inf)
            if sink is not None:        # one more column, which has no v
                sc = jnp.concatenate([sc, jnp.broadcast_to(
                    sink[hs][:, None, None], sc.shape[:2] + (1,))], -1)
            p = jax.nn.softmax(sc, axis=-1)[..., :r1 - k0]
            o = jnp.einsum("hqk,khe->qhe", p, v[k0:r1][:, kv])
            acc = acc + jnp.einsum("qhe,hed->qd", o, wo[hs])
        out.append(acc)
    return jnp.concatenate(out)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _w(w_gate)) * (x @ _w(w_up))) @ _w(w_down)


def _experts(u, lp, c):
    """u [s, d] -> (held experts' part of the sum [s, d], margin [s]: the
    biased score of the last expert chosen minus the best left out)."""
    k, E = c.moe_top_k, c.moe_experts
    logits = _through(ROUTER_DTYPE, jnp.matmul, u, _w(lp["router"]))
    score = jax.nn.sigmoid(logits)                                # [s, X]
    pick = score + _f32(lp["router_bias"]) if "router_bias" in lp else score
    best, idx = jax.lax.top_k(pick, k + 1)
    margin = best[:, -2] - best[:, -1]
    idx = idx[:, :k]
    w = jnp.take_along_axis(score, idx, axis=1)
    if k > 1 and c.moe_norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    else:
        w = w * c.moe_routed_scale
    y = jnp.zeros_like(u)
    for e in range(E):                    # every held expert, one at a time
        we = jnp.sum(jnp.where(idx == c.moe_held_group * E + e, w, 0.0), -1)
        y = y + we[:, None] * _swiglu(u, lp["wg"][e], lp["wu"][e],
                                      lp["wd"][e])
    return y, margin


def hidden_of(params, c, tokens):
    """tokens [s] -> (final-norm hidden states [s, d] float32, each
    position's least router margin over the expert layers [s])."""
    with jax.default_matmul_precision("highest"):
        x = _w(jnp.take(params["embed"], jnp.asarray(tokens), axis=0))
        margin = jnp.full(x.shape[:1], jnp.inf)
        for li, (kind, lp) in enumerate(zip(c.attn_pattern,
                                            params["layers"])):
            x = x + _attention(
                _rmsnorm(x, _f32(lp["attn_norm"]), c.norm_eps), lp, c, kind)
            u = _rmsnorm(x, _f32(lp["mlp_norm"]), c.norm_eps)
            if li < c.first_k_dense:
                x = x + _rows(lambda r: _swiglu(
                    r, lp["w_gate"], lp["w_up"], lp["w_down"]), u)
            else:
                ys, ms = zip(*(_experts(u[i:i + ROW_BLOCK], lp, c)
                               for i in range(0, u.shape[0], ROW_BLOCK)))
                x = x + jnp.concatenate(ys)
                margin = jnp.minimum(margin, jnp.concatenate(ms))
        return _rmsnorm(x, _f32(params["final_norm"]), c.norm_eps), margin


def logits_of(params, c, tokens):
    """tokens [s] -> logits [s, vocab] float32 (the tier-1 tests')."""
    x, _ = hidden_of(params, c, np.asarray(tokens, np.int32))
    with jax.default_matmul_precision("highest"):
        return x @ _w(params["lm_head"])


def logprobs_of(params, c, prompt: list, generated: list) -> tuple:
    """(log p(generated[i] | prompt + generated[:i]) for every i, the
    router margin at the position that predicts each)."""
    seq = list(prompt) + list(generated)
    x, margin = hidden_of(params, c, np.asarray(seq, np.int32))
    pos = np.arange(len(prompt) - 1, len(seq) - 1)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x[pos] @ _w(params["lm_head"]), axis=-1)
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
            logp = jax.nn.log_softmax(x @ _w(params["lm_head"]), axis=-1)
        total += float(-jnp.mean(logp[np.arange(len(row) - 1), row[1:]]))
    return total / host.shape[0]
