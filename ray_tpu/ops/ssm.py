"""Mamba-2's state-space recurrence (arXiv:2405.21060), the two forms a
serving engine needs, and the causal convolution in front of it.

A head h keeps a state S [P, N] (P = head width, N = state size):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      y_t = S_t C_t

with x_t [P], B_t and C_t [N] (shared by the heads of a group), dt_t > 0
and A < 0 scalars a head. D x_t, the gate and the norm are the caller's.

- `ssd_chunk_scan` (prefill): the same function over a whole sequence in
  chunks of `chunk` positions, an initial state in and the final state
  out. Inside a chunk the outputs are three matrix products (decay-masked
  C B^T against dt x; C against the carried state); between chunks only
  the state is carried. Plain jnp: XLA's einsums on the chip and on the
  CPU alike. A position with dt = 0 leaves the state as it was, which is
  how padding is kept out of it.
- `ssm_state_update` (decode): one token for the ACTIVE rows of the state
  pool [layers, rows, H, P, N], updated where they lie by a Pallas kernel
  aliased in and out on the pool (`name="ssm_state_update"`); rows of
  inactive slots are neither read nor written. Memory-bound: a row is read
  and written once (2 * H * P * N * 4 B) for 6 H P N operations.
- `causal_conv` / `conv_step`: the depthwise causal convolution over the
  carried window of the last `width - 1` inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------ convolution


def causal_conv(u, window, weight, bias, lengths):
    """u [n, S, C] (right-padded to S, `lengths` [n] real), window
    [n, W - 1, C] = the W - 1 inputs before u (zeros before a sequence),
    weight [C, W], bias [C] -> (silu(conv) [n, S, C], the window after
    each sequence's last real input [n, W - 1, C])."""
    w = weight.shape[1]
    cat = jnp.concatenate([window.astype(u.dtype), u], axis=1)
    s = u.shape[1]
    out = bias.astype(jnp.float32)
    for j in range(w):
        out = out + (cat[:, j:j + s].astype(jnp.float32)
                     * weight[:, j].astype(jnp.float32))
    # input t sits at cat[t + W - 1]: the last W - 1 real inputs are
    # cat[len : len + W - 1] (the old window's tail where len < W - 1)
    take = lengths[:, None] + jnp.arange(w - 1)[None]
    new_window = jnp.take_along_axis(cat, take[:, :, None], axis=1)
    return jax.nn.silu(out).astype(u.dtype), new_window


def conv_step(u, window, weight, bias):
    """One position: u [B, C], window [W - 1, B, C] (taps outermost, as
    the engine's pool keeps it: a [rows, C] tile a tap, nothing padded)
    -> (silu(conv) [B, C], the window shifted by u)."""
    cat = jnp.concatenate([window.astype(u.dtype), u[None]], axis=0)
    out = jnp.einsum("wbc,cw->bc", cat.astype(jnp.float32),
                     weight.astype(jnp.float32)) + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(u.dtype), cat[1:]


# --------------------------------------------------------- prefill: chunks


def ssd_chunk_scan(x, dt, a, b, c, state, *, chunk: int):
    """x [n, S, H, P], dt [n, S, H] float32 (0 where padding), a [H]
    float32 (negative), b and c [n, S, G, N], state [n, H, P, N] float32
    -> (y [n, S, H, P] in x.dtype, final state float32). Matrix products
    take their inputs in x.dtype and accumulate in float32; decays and the
    carried state stay float32."""
    n, s, h, p = x.shape
    g = b.shape[2]
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    dtype = x.dtype
    f32 = jnp.float32

    def chunks(t):       # [n, nc * Q, ...] -> [nc, n, Q, ...]
        return jnp.moveaxis(t.reshape((n, nc, chunk) + t.shape[2:]), 1, 0)

    tril = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(st, xs):
        xq, dtq, bq, cq = xs           # [n, Q, H, P], [n, Q, H], [n, Q, G, N]
        cs = jnp.cumsum(dtq * a, axis=1)                     # [n, Q, H] <= 0
        # exp(cs_i - cs_j) for j <= i: every factor is at most 1
        decay = jnp.exp(jnp.where(
            tril[None, :, :, None], cs[:, :, None] - cs[:, None], -jnp.inf))
        cb = jnp.einsum("nigs,njgs->nijg", cq, bq,
                        preferred_element_type=f32)          # [n, Q, Q, G]
        m = decay * jnp.repeat(cb, h // g, axis=3)           # [n, Q, Q, H]
        dtx = (dtq[..., None] * xq.astype(f32)).astype(dtype)
        y = jnp.einsum("nijh,njhp->nihp", m.astype(dtype), dtx,
                       preferred_element_type=f32)
        # the carried state, decayed to each position
        ch = jnp.repeat(cq, h // g, axis=2)                  # [n, Q, H, N]
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "nihs,nhps->nihp", ch.astype(f32), st,
            preferred_element_type=f32)
        last = cs[:, -1]                                     # [n, H]
        to_end = jnp.exp(last[:, None] - cs)                 # [n, Q, H]
        bh = jnp.repeat(bq, h // g, axis=2)                  # [n, Q, H, N]
        st = (jnp.exp(last)[:, :, None, None] * st
              + jnp.einsum("njhp,njhs->nhps",
                           dtx.astype(f32) * to_end[..., None],
                           bh.astype(f32), preferred_element_type=f32))
        return st, y.astype(dtype)

    state, y = jax.lax.scan(
        step, state.astype(f32),
        (chunks(x), chunks(dt.astype(f32)), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(n, nc * chunk, h, p)
    return y[:, :s], state


def ssd_sequential(x, dt, a, b, c, state):
    """The recurrence one position at a time, float32 (tests: what
    ssd_chunk_scan must equal). Same arguments, no chunk."""
    h, g = x.shape[2], b.shape[2]
    f32 = jnp.float32

    def step(st, xs):
        xt, dtt, bt, ct = xs                    # [n, H, P], [n, H], [n, G, N]
        bt = jnp.repeat(bt, h // g, axis=1).astype(f32)
        ct = jnp.repeat(ct, h // g, axis=1).astype(f32)
        st = (jnp.exp(dtt * a)[..., None, None] * st
              + (dtt[..., None] * xt.astype(f32))[..., None]
              * bt[:, :, None, :])
        return st, jnp.einsum("nhps,nhs->nhp", st, ct)

    state, y = jax.lax.scan(
        step, state.astype(f32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt.astype(f32), b, c)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), state


# ------------------------------------------------------ decode: one token


def _update_kernel(layer_ref, n_ref, rows_ref,  # scalar prefetch (SMEM)
                   s_ref, dec_ref, dtx_ref, b_ref, c_ref, o_ref, y_ref, *,
                   heads: int, per_group: int):
    """One grid step a listed row: every head's [P, N] state, N along the
    lanes. x and the decay arrive transposed ([P, H]) so that a head's
    column is a lane of the tile, spread over the lanes of the state by a
    masked sum (a static lane slice `tile[:, h:h + 1]` compiles too and
    ran slower: 6.39 against 5.69 ms for 12 layers of 64 rows, kernel
    bench on the v5e, PR 33); B and C are rows [G, N], spread over the
    sublanes."""
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _():
        dec, dtx = dec_ref[0], dtx_ref[0]                  # [P, H] float32
        lane = jax.lax.broadcasted_iota(jnp.int32, dec.shape, 1)
        y = jnp.zeros(dec.shape, jnp.float32)
        for h in range(heads):
            grp = h // per_group
            at = lane == h
            d_col = jnp.sum(jnp.where(at, dec, 0.0), axis=1, keepdims=True)
            x_col = jnp.sum(jnp.where(at, dtx, 0.0), axis=1, keepdims=True)
            s = (s_ref[0, 0, h].astype(jnp.float32) * d_col
                 + x_col * b_ref[0, grp:grp + 1, :])        # [P, N]
            o_ref[0, 0, h] = s.astype(o_ref.dtype)
            y_col = jnp.sum(s * c_ref[0, grp:grp + 1, :], axis=1,
                            keepdims=True)                   # [P, 1]
            y = jnp.where(at, y_col, y)
        y_ref[0] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_update_call(pool, dec_t, dtx_t, b, c, layer, n_rows, rows, *,
                       interpret: bool):
    _, _, H, P, N = pool.shape
    B, G = b.shape[0], b.shape[1]
    kernel = functools.partial(_update_kernel, heads=H, per_group=H // G)

    def row(i, lyr, n, rows):
        return (rows[i], 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, H, P, N),
                             lambda i, lyr, n, rows: (lyr[0], rows[i], 0, 0,
                                                      0)),
                pl.BlockSpec((1, P, H), row),
                pl.BlockSpec((1, P, H), row),
                pl.BlockSpec((1, G, N), row),
                pl.BlockSpec((1, G, N), row),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, H, P, N),
                             lambda i, lyr, n, rows: (lyr[0], rows[i], 0, 0,
                                                      0)),
                pl.BlockSpec((1, P, H), row),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, P, H), jnp.float32)],
        # operand 3 counts the scalar-prefetch arguments: the pool
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=40 * 2**20),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), n_rows.reshape(1), rows,
      pool, dec_t, dtx_t, b, c)


def _active_rows(active):
    """active [B] bool -> (how many, their indices first in order and the
    last of them repeated after: a grid step past the count then names the
    block the step before it held, so nothing moves for it)."""
    B = active.shape[0]
    n = jnp.sum(active, dtype=jnp.int32)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    return n, jnp.where(jnp.arange(B) < n, order, last)


def ssm_state_update(pool, x, dt, a, b, c, active, *, layer,
                     impl: str = "auto"):
    """One token for the active rows of layer `layer` of the state pool.
    pool [L, rows >= B, H, P, N] float32 (row i is slot i's); x [B, H, P];
    dt [B, H] float32; a [H]; b, c [B, G, N]; active [B] bool -> (y
    [B, H, P] float32 = S_new C, zeros for inactive slots; the pool, whose
    inactive rows did not move). impl: "auto" (the kernel on a TPU, jnp
    elsewhere), "pallas", "interpret", "reference"."""
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu" else "reference")
    if impl == "reference":
        return ssm_state_update_reference(pool, x, dt, a, b, c, active,
                                          layer=layer)
    f32 = jnp.float32
    P = x.shape[2]
    dec_t = jnp.broadcast_to(jnp.exp(dt * a)[:, None, :],
                             (x.shape[0], P, x.shape[1]))
    dtx_t = (dt[:, :, None] * x.astype(f32)).transpose(0, 2, 1)
    n_rows, rows = _active_rows(active)
    pool, y_t = _state_update_call(
        pool, dec_t, dtx_t, b.astype(f32), c.astype(f32), layer, n_rows,
        rows, interpret=(impl == "interpret"))
    y = jnp.where(active[:, None, None], y_t.transpose(0, 2, 1), 0.0)
    return y, pool


def ssm_state_update_reference(pool, x, dt, a, b, c, active, *, layer):
    """The same in plain jnp (the CPU's form, and the kernel's oracle)."""
    f32 = jnp.float32
    B, H = dt.shape
    g = b.shape[1]
    st = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[:B]
    bh = jnp.repeat(b.astype(f32), H // g, axis=1)          # [B, H, N]
    ch = jnp.repeat(c.astype(f32), H // g, axis=1)
    new = (jnp.exp(dt * a)[..., None, None] * st
           + (dt[..., None] * x.astype(f32))[..., None] * bh[:, :, None, :])
    new = jnp.where(active[:, None, None, None], new, st)
    y = jnp.where(active[:, None, None],
                  jnp.einsum("bhpn,bhn->bhp", new, ch), 0.0)
    pool = jax.lax.dynamic_update_slice(
        pool, new[None].astype(pool.dtype),
        (jnp.asarray(layer, jnp.int32), 0, 0, 0, 0))
    return y, pool
