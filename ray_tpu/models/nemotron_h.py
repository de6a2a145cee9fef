"""The hybrid state-space decoders: blocks of ONE sublayer each, by a pattern
of letters (`ModelConfig.layer_pattern` selects this module: models.
init_params, the serving engine). Two published families spell their
layers with it:

- Nemotron-H (NVIDIA Nemotron-3-Nano, `model_type` "nemotron_h"): "M" a
  Mamba-2 mixer, "E" an expert layer, "*" attention; an untied head.
- Jamba (AI21 Jamba2, `model_type` "jamba"): a layer is TWO blocks, a
  mixer then "-" the dense gated MLP, the mixer "S" Mamba-1 or "*"
  attention; a tied head (`tie_embeddings`: logits through the embedding's
  transpose).

Block i:  h <- h + block_i(RMSNorm(h)); then a final RMSNorm and the head.
No bias anywhere except the convolution's and Mamba-1's step.

  "M", Mamba-2 (d_i = ssm_heads * ssm_head_dim, G = ssm_groups, N = ssm_state):
    [z d_i | xBC d_i + 2 G N | dt H] = u W_in
    xBC_t = silu(sum_j w[:, j] * xBC_{t-W+1+j} + b)   depthwise, causal; the
      W - 1 inputs before a call are the carried WINDOW (zeros at the start)
    xBC -> x [H, P] | B [G, N] | C [G, N]; head h reads group h // (H / G)
    dt = softplus(dt + dt_bias), A = -exp(A_log)      a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    y <- y * silu(z); RMS norm over groups of d_i / G with a weight [d_i];
    out = y W_out.        S [H, P, N] is the recurrent STATE, float32.
  "S", Mamba-1 (D = ssm_expand * d_model channels, N = ssm_state, R =
    ssm_dt_rank):
    [x | z] = u W_in;  x_t = silu(conv over x only, as above)
    [delta R | B N | C N] = x W_x, each through an RMS norm of its own
    dt = softplus(delta W_dt + b_dt) [D] float32;  A = -exp(A_log) [N, D]
    S_t = exp(dt_t (x) A) * S_{t-1} + B_t (x) (dt_t * x_t);  y_t = C_t S_t
      + D * x_t;  out = (y * silu(z)) W_out.   S [N, D] float32 (channels
      last: on the chip's lanes).
  "*", attention: q [h, hd], k and v [hkv, hd], scale hd^-0.5, causal,
    NO rotary embedding where `rotary` is False (the Mamba layers carry
    position); the kernels the per-head engine programs call.
  "E", experts: models/experts.py (sigmoid scores with a bias in the
    choice, relu^2 experts, one shared expert, this chip's share held).
  "-", the dense MLP: W_down(silu(W_gate u) * W_up u).

What a sequence keeps in the serving engine (`ModelConfig.kv_cache ==
"recurrent"`): pages of K and V for the "*" layers, [L*, hkv, N, hd, page]
as the per-head models have them, and ONE ROW of the two row pools for the
recurrent layers ("M" or "S": a pattern holds one of the two kinds): state
[LM, rows, H, P, N] or [LS, rows, N, D] float32, and window [L, W - 1,
rows, channels] (taps before rows: a [rows, channels] tile a tap, which
the chip's tiling does not pad). Row b is slot b's; the engine keeps
further rows as snapshots of the state at a chunk's end (llm/engine.py). A
prefill program reads its initial rows and writes its final rows itself;
padding advances neither (dt = 0 there, and the window is taken at each
sequence's own length).

Parameters: {"embed" [V, d], "layers": [one tree a block; kinds differ],
"final_norm" [d], "lm_head" [d, V] unless tied}, every leaf in the
configuration's dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.models.experts import (N_STATS, expert_layer,  # noqa: F401
                                    init_expert_weights, stats_zero)
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.ops.attention import prefill_attention
from ray_tpu.ops.layers import last_rows, rmsnorm
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.ops.ssm import (causal_conv, conv_step, selective_scan,
                             selective_state_update, ssd_chunk_scan,
                             ssm_state_update)


def pattern(c: ModelConfig) -> str:
    p = c.layer_pattern
    if len(p) != c.n_layers or set(p) - set("MSE*-") or (
            "M" in p and "S" in p):
        raise ValueError(
            f"ModelConfig.layer_pattern={p!r}: want n_layers={c.n_layers} "
            f"letters of \"M\" (Mamba-2) or \"S\" (Mamba-1), not both, "
            f"\"E\" (experts), \"-\" (dense MLP), \"*\" (attention)")
    return p


def recurrent_kind(c: ModelConfig) -> str:
    """The letter of the pattern's recurrent blocks: the row pools are
    theirs."""
    return "S" if "S" in pattern(c) else "M"


def d_inner(c: ModelConfig) -> int:
    if recurrent_kind(c) == "S":
        return c.ssm_expand * c.d_model
    return c.ssm_heads * c.ssm_head_dim


def conv_dim(c: ModelConfig) -> int:
    """Channels the convolution runs over (the window's width)."""
    if recurrent_kind(c) == "S":
        return d_inner(c)
    return d_inner(c) + 2 * c.ssm_groups * c.ssm_state


def state_shape(c: ModelConfig) -> tuple:
    """One layer's recurrent state a sequence."""
    if recurrent_kind(c) == "S":
        return (c.ssm_state, d_inner(c))
    return (c.ssm_heads, c.ssm_head_dim, c.ssm_state)


def _index_of_kind(c: ModelConfig, li: int) -> int:
    """Block li's index among the blocks of its own kind: its layer in
    the page pools ("*") or the row pools ("M")."""
    p = pattern(c)
    return p[:li].count(p[li])


# ---------------------------------------------------------------- params


@functools.partial(jax.jit, static_argnames=("shape", "kind", "scale",
                                             "dtype"))
def _made(key, shape: tuple, kind: str, scale: float, dtype: str):
    """One leaf, made under jit straight into its dtype (no float32 copy
    outlives the call). kind: "normal" (* scale); "a_log": log of a
    uniform draw over [1, 16]; "a_log_rows": log(1..N) down the rows of
    [N, D], every channel alike (Mamba-1's); "dt_bias": the inverse
    softplus of a log-uniform step over [0.001, 0.1] (time_step_min,
    time_step_max)."""
    if kind == "normal":
        v = jax.random.normal(key, shape, jnp.float32) * scale
    elif kind == "a_log":
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "a_log_rows":
        v = jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    else:
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    return v.astype(dtype)


def _init_block(key, c: ModelConfig, kind: str) -> dict:
    dt, d = c.jdtype, c.d_model
    ks = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return _made(next(ks), shape, "normal", fan_in ** -0.5, c.dtype)

    lp = {"norm": jnp.ones((d,), dt)}
    if kind == "M":
        di, cd, H = d_inner(c), conv_dim(c), c.ssm_heads
        lp.update(
            in_proj=w((d, di + cd + H), d),
            conv_w=w((cd, c.ssm_conv_width), c.ssm_conv_width),
            conv_b=_made(next(ks), (cd,), "normal", 0.1, c.dtype),
            dt_bias=_made(next(ks), (H,), "dt_bias", 1.0, c.dtype),
            a_log=_made(next(ks), (H,), "a_log", 1.0, c.dtype),
            d=jnp.ones((H,), dt), gate_norm=jnp.ones((di,), dt),
            out_proj=w((di, d), di))
    elif kind == "S":
        di, N, R = d_inner(c), c.ssm_state, c.ssm_dt_rank
        lp.update(
            in_proj=w((d, 2 * di), d),
            conv_w=w((di, c.ssm_conv_width), c.ssm_conv_width),
            conv_b=_made(next(ks), (di,), "normal", 0.1, c.dtype),
            x_proj=w((di, R + 2 * N), di),
            dt_norm=jnp.ones((R,), dt), b_norm=jnp.ones((N,), dt),
            c_norm=jnp.ones((N,), dt), dt_proj=w((R, di), R),
            dt_bias=_made(next(ks), (di,), "dt_bias", 1.0, c.dtype),
            a_log=_made(next(ks), (N, di), "a_log_rows", 1.0, c.dtype),
            d=jnp.ones((di,), dt), out_proj=w((di, d), di))
    elif kind == "*":
        h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
        lp.update(wq=w((d, h * hd), d), wk=w((d, hkv * hd), d),
                  wv=w((d, hkv * hd), d), wo=w((h * hd, d), h * hd))
    elif kind == "-":
        lp.update(w_gate=w((d, c.d_ff), d), w_up=w((d, c.d_ff), d),
                  w_down=w((c.d_ff, d), c.d_ff))
    else:
        lp.update(init_expert_weights(
            w, c, lambda shape, scale: _made(next(ks), shape, "normal",
                                             scale, c.dtype)))
    return lp


def init_params(config: ModelConfig, key) -> dict:
    """Seeded weights in the configuration's dtype, no float32 leaf."""
    c = config
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params = {
        "embed": _made(k_embed, (c.vocab, c.d_model), "normal", 0.02,
                       c.dtype),
        "layers": [_init_block(jax.random.fold_in(k_layers, li), c, kind)
                   for li, kind in enumerate(pattern(c))],
        "final_norm": jnp.ones((c.d_model,), c.jdtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = _made(k_head, (c.d_model, c.vocab), "normal",
                                  c.d_model ** -0.5, c.dtype)
    return params


# ----------------------------------------------------------------- mixers


def _mamba_split(zxbcdt, c: ModelConfig):
    di, cd = d_inner(c), conv_dim(c)
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _ssm_inputs(xbc, dt_raw, lp, c: ModelConfig):
    """Convolved xBC [..., cd] and raw steps [..., H] -> x [..., H, P], B
    and C [..., G, N], dt [..., H] float32, A [H] float32."""
    di, gn = d_inner(c), c.ssm_groups * c.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(lead + (c.ssm_heads, c.ssm_head_dim))
    b = xbc[..., di:di + gn].reshape(lead + (c.ssm_groups, c.ssm_state))
    cc = xbc[..., di + gn:].reshape(lead + (c.ssm_groups, c.ssm_state))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    return x, b, cc, dt, -jnp.exp(lp["a_log"].astype(jnp.float32))


def _mamba_out(y, x, z, lp, c: ModelConfig, hold=lambda a: a):
    """y [..., H, P] float32 (S C) -> the mixer's output [..., d]: + D x,
    the gate, the norm over groups, the output projection."""
    lead = z.shape[:-1]
    y = y + lp["d"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(lead + (-1,)) * jax.nn.silu(z.astype(jnp.float32))
    per = d_inner(c) // c.ssm_groups
    grouped = y.reshape(lead + (c.ssm_groups, per))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + c.norm_eps)
    y = (grouped.reshape(lead + (-1,))
         * lp["gate_norm"].astype(jnp.float32)).astype(z.dtype)
    return hold(jnp.einsum("...k,kd->...d", y, lp["out_proj"]))


def _mamba_prefill(u, lp, c: ModelConfig, lengths, state, window):
    """u [n, S, d] normed, right-padded to `lengths`; state [n, H, P, N]
    float32 and window [n, W - 1, cd] before the call -> (out [n, S, d],
    state, window after each sequence's last real token)."""
    with jax.named_scope("mamba_mixer"):
        z, xbc, dt_raw = _mamba_split(
            jnp.einsum("nsd,dk->nsk", u, lp["in_proj"]), c)
        xbc, window = causal_conv(xbc, window, lp["conv_w"], lp["conv_b"],
                                  lengths)
        x, b, cc, dt, a = _ssm_inputs(xbc, dt_raw, lp, c)
        valid = jnp.arange(u.shape[1])[None] < lengths[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)   # padding: S unmoved
        y, state = ssd_chunk_scan(x, dt, a, b, cc, state, chunk=c.ssm_chunk)
        return (_mamba_out(y.astype(jnp.float32), x, z, lp, c), state,
                window)


def _mamba_decode(u, lp, c: ModelConfig, layer: int, ssm, conv, active):
    """u [B, d] normed, one token a slot; the pools' rows 0..B-1 of layer
    `layer` are the slots' -> (out [B, d], ssm, conv); inactive slots'
    rows stay as they were."""
    B = u.shape[0]
    hold = jax.lax.optimization_barrier   # as llm/engine._qkv's fence
    with jax.named_scope("mamba_mixer"):
        z, xbc, dt_raw = _mamba_split(
            hold(jnp.einsum("bd,dk->bk", u, lp["in_proj"])), c)
        window = jax.lax.slice_in_dim(conv[layer], 0, B, axis=1)
        xbc, shifted = conv_step(xbc, window, lp["conv_w"], lp["conv_b"])
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.where(active[None, :, None], shifted.astype(conv.dtype),
                            window)[None], (layer, 0, 0, 0))
        x, b, cc, dt, a = _ssm_inputs(xbc, dt_raw, lp, c)
        y, ssm = ssm_state_update(ssm, x, dt, a, b, cc, active, layer=layer)
        return _mamba_out(y, x, z, lp, c, hold), ssm, conv


def _selective_inputs(x, lp, c: ModelConfig, hold=lambda a: a):
    """Convolved x [..., D] -> dt [..., D] float32, A [N, D] float32, B
    and C [..., N] in x.dtype (each of delta, B, C through its own norm)."""
    N, R = c.ssm_state, c.ssm_dt_rank
    dbc = hold(jnp.einsum("...k,kr->...r", x, lp["x_proj"]))
    delta = rmsnorm(dbc[..., :R], lp["dt_norm"], c.norm_eps)
    b = rmsnorm(dbc[..., R:R + N], lp["b_norm"], c.norm_eps)
    cc = rmsnorm(dbc[..., R + N:], lp["c_norm"], c.norm_eps)
    dt = jax.nn.softplus(
        hold(jnp.einsum("...r,rk->...k", delta, lp["dt_proj"],
                        preferred_element_type=jnp.float32))
        + lp["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(lp["a_log"].astype(jnp.float32)), b, cc


def _selective_out(y, x, z, lp, hold=lambda a: a):
    """y [..., D] = C S -> the mixer's output [..., d]: + D x, the gate,
    the output projection."""
    y = y.astype(jnp.float32) + (lp["d"].astype(jnp.float32)
                                 * x.astype(jnp.float32))
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return hold(jnp.einsum("...k,kd->...d", y, lp["out_proj"]))


def _selective_prefill(u, lp, c: ModelConfig, lengths, state, window):
    """_mamba_prefill's twin for Mamba-1: state [n, N, D] float32, window
    [n, W - 1, D]."""
    di = d_inner(c)
    with jax.named_scope("mamba1_mixer"):
        xz = jnp.einsum("nsd,dk->nsk", u, lp["in_proj"])
        x, z = xz[..., :di], xz[..., di:]
        x, window = causal_conv(x, window, lp["conv_w"], lp["conv_b"],
                                lengths)
        dt, a, b, cc = _selective_inputs(x, lp, c)
        valid = jnp.arange(u.shape[1])[None] < lengths[:, None]
        dt = jnp.where(valid[..., None], dt, 0.0)   # padding: S unmoved
        y, state = selective_scan(x, dt, a, b, cc, state)
        return _selective_out(y, x, z, lp), state, window


def _selective_decode(u, lp, c: ModelConfig, layer: int, ssm, conv, active):
    """_mamba_decode's twin for Mamba-1."""
    B, di = u.shape[0], d_inner(c)
    hold = jax.lax.optimization_barrier
    with jax.named_scope("mamba1_mixer"):
        xz = hold(jnp.einsum("bd,dk->bk", u, lp["in_proj"]))
        x, z = xz[:, :di], xz[:, di:]
        window = jax.lax.slice_in_dim(conv[layer], 0, B, axis=1)
        x, shifted = conv_step(x, window, lp["conv_w"], lp["conv_b"])
        conv = jax.lax.dynamic_update_slice(
            conv, jnp.where(active[None, :, None], shifted.astype(conv.dtype),
                            window)[None], (layer, 0, 0, 0))
        dt, a, b, cc = _selective_inputs(x, lp, c, hold)
        y, ssm = selective_state_update(ssm, x, dt, a, b, cc, active,
                                        layer=layer)
        return _selective_out(y, x, z, lp, hold), ssm, conv


def _mlp(u, lp, hold=lambda a: a):
    """The dense gated MLP, u [..., d]."""
    with jax.named_scope("mlp"):
        g = hold(jnp.einsum("...d,df->...f", u, lp["w_gate"]))
        up = hold(jnp.einsum("...d,df->...f", u, lp["w_up"]))
        return hold(jnp.einsum("...f,fd->...d", jax.nn.silu(g) * up,
                               lp["w_down"]))


def _qkv(u, lp, c: ModelConfig, hold=lambda a: a):
    lead = u.shape[:-1]
    q = hold(jnp.einsum("...d,dq->...q", u, lp["wq"]))
    k = hold(jnp.einsum("...d,dk->...k", u, lp["wk"]))
    v = hold(jnp.einsum("...d,dk->...k", u, lp["wv"]))
    return (q.reshape(lead + (c.n_heads, c.head_dim)),
            k.reshape(lead + (c.n_kv_heads, c.head_dim)),
            v.reshape(lead + (c.n_kv_heads, c.head_dim)))


def _attention_prefill(u, lp, c: ModelConfig, prefix, layer: int, lengths):
    """u [n, S, d], right-padded to `lengths` -> (out [n, S, d], this
    chunk's k, v [n, S, hkv, hd]); keys = the cached prefix pages of pool
    layer `layer` | the chunk."""
    n, s, _ = u.shape
    with jax.named_scope("attention"):
        q, k, v = _qkv(u, lp, c)
        keys, values = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if prefix is None:
            prefix_len, pre_t = jnp.zeros((n,), jnp.int32), 0
        else:
            pool_k, pool_v, pages, prefix_len = prefix
            pre_t = pages.shape[1] * pool_k.shape[4]

            def behind(pool, new):  # [hkv, N, hd, page] -> [n, hkv, T, hd]
                cached = pool[layer][:, pages].transpose(
                    1, 0, 2, 4, 3).reshape(n, c.n_kv_heads, pre_t, -1)
                return jnp.concatenate([cached.astype(new.dtype), new], 2)

            keys, values = behind(pool_k, keys), behind(pool_v, values)
        o = prefill_attention(
            q.transpose(0, 2, 1, 3), keys, values, prefix_len, pre_t=pre_t,
            scale=c.head_dim ** -0.5, name="gqa_prefill_attention",
            lengths=lengths)
        o = o.transpose(0, 2, 1, 3).reshape(n, s, -1).astype(u.dtype)
        return jnp.einsum("nsq,qd->nsd", o, lp["wo"]), k, v


def _attention_decode(u, lp, c: ModelConfig, layer: int, pool_k, pool_v,
                      lengths, page_tables, w_at):
    """u [B, d], one token a slot: its K and V columns written where the
    pools lie (one dynamic_update_slice a slot, as llm/engine.decode_paged
    did until PR 46; ROADMAP S15), then the paged kernel over its pages."""
    B = u.shape[0]
    hold = jax.lax.optimization_barrier
    zero = jnp.zeros((), jnp.int32)
    at_layer = jnp.full((), layer, jnp.int32)

    def write(pool, new):
        cols = new.astype(pool.dtype).transpose(1, 2, 0).reshape(
            1, c.n_kv_heads, 1, c.head_dim, B)
        for b, (pg, off) in enumerate(w_at):
            pool = jax.lax.dynamic_update_slice(
                pool, jax.lax.slice_in_dim(cols, b, b + 1, axis=4),
                (at_layer, zero, pg, zero, off), allow_negative_indices=False)
        return pool

    with jax.named_scope("attention"):
        q, k, v = _qkv(u, lp, c, hold)
        pool_k, pool_v = write(pool_k, k), write(pool_v, v)
        o = paged_decode_attention(q, pool_k, pool_v, lengths + 1,
                                   page_tables, layer=layer)
        out = hold(jnp.einsum("bq,qd->bd", o.reshape(B, -1).astype(u.dtype),
                              lp["wo"]))
        return out, pool_k, pool_v


def _require_plain_attention(c: ModelConfig):
    if c.rotary:
        raise ValueError(
            "ModelConfig.rotary=True with a layer_pattern: this family's "
            "attention layers rotate nothing (set rotary=False)")


def _head(x, params):
    """bf16 operands, float32 accumulation and logits; a tied head reads
    the embedding's transpose where it lies."""
    if "lm_head" not in params:
        return jnp.einsum("...d,vd->...v", x, params["embed"],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...d,dv->...v", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------- whole-sequence forward


def _prefill(params, tokens, lengths, stats, c: ModelConfig, prefix=None,
             carried=None):
    """The blocks over tokens [n, S], right-padded to `lengths`. prefix:
    (pool_k, pool_v, prefix_pages [n, Pp], prefix_len [n]) or None;
    carried: (states [LM, n, *state_shape], windows [LM, n, W - 1, cd]) before
    the chunk, or None at a sequence's start. Returns (final hidden states
    [n, S, d], ks, vs [L*, n, S, hkv, hd], (states, windows) after each
    sequence's last real token, stats)."""
    _require_plain_attention(c)
    n, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = (jnp.arange(s)[None] < lengths[:, None]).reshape(n * s)
    ks, vs, states, windows = [], [], [], []
    for li, (kind, lp) in enumerate(zip(pattern(c), params["layers"])):
        u = rmsnorm(x, lp["norm"], c.norm_eps)
        at = _index_of_kind(c, li)
        if kind in "MS":
            if carried is None:
                state = jnp.zeros((n,) + state_shape(c), jnp.float32)
                window = jnp.zeros((n, c.ssm_conv_width - 1, conv_dim(c)),
                                   x.dtype)
            else:
                state, window = carried[0][at], carried[1][at]
            mixer = _mamba_prefill if kind == "M" else _selective_prefill
            out, state, window = mixer(u, lp, c, lengths, state, window)
            states.append(state)
            windows.append(window)
        elif kind == "*":
            out, k, v = _attention_prefill(u, lp, c, prefix, at, lengths)
            ks.append(k)
            vs.append(v)
        elif kind == "-":
            out = _mlp(u, lp)
        else:
            out, st = expert_layer(u.reshape(n * s, -1), lp, c, valid)
            out, stats = out.reshape(n, s, -1), stats + st
        x = x + out
    return (rmsnorm(x, params["final_norm"], c.norm_eps), jnp.stack(ks),
            jnp.stack(vs), (jnp.stack(states), jnp.stack(windows)), stats)


def forward(params, tokens, config: ModelConfig, mesh=None):
    """tokens [batch, seq] -> logits [batch, seq, vocab] float32 (CPU use
    and tests; the serving programs are below)."""
    if mesh is not None and mesh.devices.size > 1:
        raise NotImplementedError(
            "ModelConfig.layer_pattern runs on one device: no mesh")
    full = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x = _prefill(params, tokens, full, stats_zero(config), config)[0]
    return _head(x, params)


# ---------------------------------------- the serving engine's programs


def page_pools(c: ModelConfig, num_pages: int, page: int) -> tuple:
    shape = (pattern(c).count("*"), c.n_kv_heads, num_pages, c.head_dim, page)
    return (jax.ShapeDtypeStruct(shape, c.jdtype),) * 2


def row_pools(c: ModelConfig, rows: int) -> tuple:
    """(state, window) pools of `rows` rows a recurrent layer."""
    lm = pattern(c).count(recurrent_kind(c))
    return (jax.ShapeDtypeStruct((lm, rows) + state_shape(c),
                                 jnp.dtype(c.ssm_state_dtype)),
            jax.ShapeDtypeStruct((lm, c.ssm_conv_width - 1, rows,
                                  conv_dim(c)), c.jdtype))


# A request's rows of the two pools are read and written ONE ROW AT A
# TIME, a dynamic slice each (at most four requests a program, 12 layers a
# slice). As one gather `ssm[:, src_rows]` and one scatter
# `ssm.at[:, dst_rows].set(...)` the 27-block prefill program over a cached
# prefix HUNG the v5e at four requests, every run, at 512 and at 1024 rows,
# with either grouped product and either attention form, while the same
# program ran at one and two requests (chip probes of PR 33, PERF.md
# section 6); with slices it runs: 65 ms for 4 x 512 rows.


def _load_rows(ssm, conv, src_rows):
    """Rows src_rows [n] of the pools -> (states [LM, n, *state_shape]
    float32, windows [LM, n, W - 1, cd])."""
    n = src_rows.shape[0]
    states = jnp.stack([jax.lax.dynamic_slice_in_dim(ssm, src_rows[i], 1, 1)
                        [:, 0] for i in range(n)], axis=1)
    windows = jnp.stack([jax.lax.dynamic_slice_in_dim(conv, src_rows[i], 1, 2)
                         [:, :, 0] for i in range(n)], axis=1)
    return states.astype(jnp.float32), windows


def _store_rows(ssm, conv, carried, dst_rows):
    states, windows = carried            # windows [LM, n, W - 1, cd]
    zero = jnp.zeros((), jnp.int32)
    for i in range(dst_rows.shape[0]):
        ssm = jax.lax.dynamic_update_slice(
            ssm, states[:, i:i + 1].astype(ssm.dtype),
            (zero, dst_rows[i]) + (zero,) * (ssm.ndim - 2))
        conv = jax.lax.dynamic_update_slice(
            conv, windows[:, i][:, :, None].astype(conv.dtype),
            (zero, zero, dst_rows[i], zero))
    return ssm, conv


def prefill_batch(params, tokens, lengths, ssm, conv, src_rows, dst_rows,
                  stats, config: ModelConfig):
    """tokens [n, S] right-padded, lengths [n], from a sequence's start
    (src_rows is not read) -> (logits [n, vocab] at each request's last
    token, ks, vs [L*, n, S, hkv, hd], the row pools with request i's
    final state and window in row dst_rows[i], stats)."""
    x, ks, vs, carried, stats = _prefill(params, tokens, lengths, stats,
                                         config)
    ssm, conv = _store_rows(ssm, conv, carried, dst_rows)
    return _head(last_rows(x, lengths), params), ks, vs, ssm, conv, stats


def prefill_with_prefix_batch(params, tokens, lengths, pool_k, pool_v,
                              prefix_pages, prefix_len, ssm, conv, src_rows,
                              dst_rows, stats, config: ModelConfig):
    """As prefill_batch for the SUFFIX of prompts whose first prefix_len
    tokens are cached: their K and V in pages prefix_pages [n, Pp] of the
    page pools, and the state and window AT that boundary in row
    src_rows[i] of the row pools."""
    carried = _load_rows(ssm, conv, src_rows)
    x, ks, vs, carried, stats = _prefill(
        params, tokens, lengths, stats, config,
        (pool_k, pool_v, prefix_pages, prefix_len), carried)
    ssm, conv = _store_rows(ssm, conv, carried, dst_rows)
    return _head(last_rows(x, lengths), params), ks, vs, ssm, conv, stats


def decode_paged(params, pool_k, pool_v, ssm, conv, tokens, lengths, active,
                 page_tables, stats, config: ModelConfig):
    """One token for every slot: llm/engine.decode_paged's twin over both
    kinds of pool (unrolled blocks, donated pools, each touched where it
    lies). Returns (logits [B, vocab] float32, pool_k, pool_v, ssm, conv,
    stats)."""
    c = config
    _require_plain_attention(c)
    B, P = page_tables.shape
    page = pool_k.shape[4]
    x = jnp.take(params["embed"], tokens, axis=0)               # [B, d]
    w_idx = jnp.clip(lengths // page, 0, P - 1)
    w_page = jnp.take_along_axis(page_tables, w_idx[:, None], 1)[:, 0]
    w_page = jnp.where((lengths // page >= P) | ~active, 0, w_page)
    w_off = lengths % page
    w_at = [(w_page[b], w_off[b]) for b in range(B)]
    for li, (kind, lp) in enumerate(zip(pattern(c), params["layers"])):
        u = rmsnorm(x, lp["norm"], c.norm_eps)
        at = _index_of_kind(c, li)
        if kind in "MS":
            mixer = _mamba_decode if kind == "M" else _selective_decode
            out, ssm, conv = mixer(u, lp, c, at, ssm, conv, active)
        elif kind == "*":
            out, pool_k, pool_v = _attention_decode(
                u, lp, c, at, pool_k, pool_v, lengths, page_tables, w_at)
        elif kind == "-":
            out = _mlp(u, lp, jax.lax.optimization_barrier)
        else:
            out, st = expert_layer(u, lp, c, active)
            stats = stats + st
        x = x + out
    logits = _head(rmsnorm(x, params["final_norm"], c.norm_eps), params)
    neg = jnp.full_like(logits, -1e30).at[:, 0].set(0.0)
    return (jnp.where(active[:, None], logits, neg), pool_k, pool_v, ssm,
            conv, stats)
