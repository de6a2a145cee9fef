"""Attention kernels of latent (MLA) attention (TPU Pallas).

Two kernels, one for each form of the same function
(models/deepseek_v2.py has the equations):

- `paged_latent_decode_attention`: the ABSORBED form over the paged latent
  pool. A cached token is 576 numbers shared by every head (512 normed
  latent | 64 rotated key); the query arrives as q~|q_pe with W_UK folded
  in, so a page is read once for all heads. The DMA scheme is
  ops/paged_attention.py's: one grid step a slot, the slot's pages
  streamed HBM -> VMEM two deep, flash accumulation on the way.
- `mla_prefill_attention`: the UP-PROJECTED form for prefill, a forward
  flash kernel with a 192-wide q.k and a 128-wide p.v, over
  [cached prefix | this chunk] keys: no score tensor ever lives in HBM.

Layouts:
  pool         [L, num_pages, 576, page]   (latent BEFORE page: a page's
               DMA slice has trailing dims (576, 128), which Mosaic tiles)
  q_lat        [B, n_heads, 576]           q~ (512) | q_pe (64)
  lengths      [B]  attend positions < lengths
  page_tables  [B, P]  page ids in position order (entry 0 = scratch)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------- decode


def _latent_decode_kernel(lengths_ref, tables_ref,  # scalar prefetch (SMEM)
                          q_ref, pool_hbm, o_ref,
                          buf, m_ref, l_ref, acc_ref, sem, *, layer: int,
                          page: int, rank: int, scale: float,
                          pages_per_seq: int):
    """One grid step per slot: [h, 576] @ [576, page] scores, running
    softmax, [h, page] @ [page, rank] accumulate. Matmul inputs stay in
    the pool's type (bf16 straight into the MXU), accumulators float32."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    npg = jnp.minimum(jax.lax.div(length + page - 1, page), pages_per_seq)

    def copy(i, slot):
        return pltpu.make_async_copy(
            pool_hbm.at[layer, tables_ref[b, i]], buf.at[slot], sem.at[slot])

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(npg > 0)
    def _first():
        copy(0, 0).start()

    q = q_ref[0]                                       # [h, 576]

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < npg)
        def _prefetch():
            copy(i + 1, 1 - slot).start()

        copy(i, slot).wait()
        kv = buf[slot]                                 # [576, page]
        s = jnp.dot(q, kv, preferred_element_type=jnp.float32) * scale
        pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG)           # [h, page]
        m_old = m_ref[...]                             # [h, 128]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [h, rank]
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, npg, body, 0)
    l = l_ref[...][:, :1]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "rank", "scale",
                                             "interpret"))
def _paged_latent_decode_dma(q_lat, pool, lengths, page_tables, *,
                             layer: int, rank: int, scale: float,
                             interpret: bool):
    B, h, width = q_lat.shape
    page = pool.shape[3]
    kernel = functools.partial(
        _latent_decode_kernel, layer=layer, page=page, rank=rank,
        scale=scale, pages_per_seq=page_tables.shape[1])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, h, width), lambda b, lens, tbl: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
            ],
            out_specs=pl.BlockSpec((1, h, rank),
                                   lambda b, lens, tbl: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, width, page), pool.dtype),
                pltpu.VMEM((h, 128), jnp.float32),        # m
                pltpu.VMEM((h, 128), jnp.float32),        # l
                pltpu.VMEM((h, rank), jnp.float32),       # acc
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_decode",
    )(lengths, page_tables, q_lat.astype(pool.dtype), pool)


def paged_latent_decode_attention(q_lat, pool, lengths, page_tables, *,
                                  layer: int, rank: int, scale: float,
                                  interpret: bool | None = None):
    """Absorbed-form decode attention of one layer over the latent pool:
    q_lat [B, h, 576] -> sum_t softmax_t(q_lat . pool_t * scale) c_kv_t,
    [B, h, rank] (W_UV is the caller's). See the module docstring for
    layouts. interpret=None: the kernel's interpreter off the chip."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _paged_latent_decode_dma(q_lat, pool, lengths, page_tables,
                                    layer=layer, rank=rank, scale=scale,
                                    interpret=interpret)


def paged_latent_decode_reference(q_lat, pool, lengths, page_tables, *,
                                  layer: int, rank: int, scale: float):
    """Plain gather-then-attend over the same layouts (tests)."""
    B, P = page_tables.shape
    page = pool.shape[3]
    lat = pool[layer][page_tables]                    # [B, P, 576, page]
    lat = lat.transpose(0, 1, 3, 2).reshape(B, P * page, -1).astype(
        jnp.float32)
    s = jnp.einsum("bhc,btc->bht", q_lat.astype(jnp.float32), lat) * scale
    s = jnp.where(jnp.arange(P * page)[None, None] < lengths[:, None, None],
                  s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, lat[..., :rank]).astype(q_lat.dtype)


# --------------------------------------------------------------- prefill


def _prefill_kernel(plen_ref,  # scalar prefetch (SMEM): [n] prefix lengths
                    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                    scale: float, bq: int, bk: int, nk: int, pre_t: int,
                    heads: int):
    """Keys are [pre_t cached-prefix positions | the chunk]: prefix key j
    counts where j < plen of the request, chunk key c where c <= the query
    row. Blocks with nothing to count are predicated out."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    plen = plen_ref[jax.lax.div(pl.program_id(0), heads)]
    k0 = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    has_prefix = k0 < jnp.minimum(plen, pre_t)
    has_chunk = (k0 + bk > pre_t) & (
        jnp.maximum(k0, pre_t) - pre_t <= qi * bq + bq - 1)

    @pl.when(has_prefix | has_chunk)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k0
        counts = (cols < jnp.minimum(plen, pre_t)) | (
            (cols >= pre_t) & (cols - pre_t <= rows))
        s = jnp.where(counts, s, _NEG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(
            o_ref.dtype)


_BLOCK_Q = 1024   # query and key rows a grid cell. On the chip, 128 heads x
_BLOCK_K = 1024   # 4096 queries (PR 28): 512/512 16.4 ms, 1024/512 15.8,
#                   512/1024 11.9, 1024/1024 10.0 (68 TFLOP/s of causal
#                   work; 95 over a 4096-token prefix); the [1024, 1024]
#                   float32 scores still fit the 16 MB of scoped VMEM


@functools.partial(jax.jit, static_argnames=("pre_t", "scale", "interpret"))
def _mla_prefill(q, k, v, prefix_len, *, pre_t: int, scale: float,
                 interpret: bool):
    n, h, s, dq = q.shape
    t, dv = k.shape[2], v.shape[3]
    bq, bk = min(_BLOCK_Q, s), min(_BLOCK_K, s)
    s_pad, t_pad = -(-s // bq) * bq, -(-t // bk) * bk
    if s_pad != s:      # padded query rows: garbage the caller slices off
        q = jnp.pad(q, [(0, 0), (0, 0), (0, s_pad - s), (0, 0)])
    if t_pad != t:      # padded keys sit past every row's diagonal
        k = jnp.pad(k, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])
    nk = t_pad // bk
    kernel = functools.partial(_prefill_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, pre_t=pre_t, heads=h)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n * h, s_pad // bq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, dq), lambda b, i, j, pl_: (b, i, 0)),
                pl.BlockSpec((1, bk, dq), lambda b, i, j, pl_: (b, j, 0)),
                pl.BlockSpec((1, bk, dv), lambda b, i, j, pl_: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, dv),
                                   lambda b, i, j, pl_: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running max
                pltpu.VMEM((bq, 128), jnp.float32),   # running sum
                pltpu.VMEM((bq, dv), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n * h, s_pad, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_prefill_attention",
    )(prefix_len, q.reshape(n * h, s_pad, dq), k.reshape(n * h, t_pad, dq),
      v.reshape(n * h, t_pad, dv))
    return out.reshape(n, h, s_pad, dv)[:, :, :s]


def mla_prefill_attention(q, k, v, prefix_len, *, pre_t: int, scale: float,
                          interpret: bool | None = None):
    """q [n, h, S, dq]; k [n, h, pre_t + S, dq], v [n, h, pre_t + S, dv]:
    the first pre_t keys are a cached prefix of which request i has
    prefix_len[i] (the rest is padding), the last S the chunk itself,
    causal. Query row r sits at position prefix_len + r. -> [n, h, S, dv]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _mla_prefill(q, k, v, prefix_len, pre_t=pre_t, scale=scale,
                        interpret=interpret)


def mla_prefill_reference(q, k, v, prefix_len, *, pre_t: int, scale: float):
    """The same function with the whole score tensor (tests)."""
    s, t = q.shape[2], k.shape[2]
    sc = jnp.einsum("nhqd,nhkd->nhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    cols, rows = jnp.arange(t)[None, None, :], jnp.arange(s)[None, :, None]
    ok = jnp.where(cols < pre_t, cols < prefix_len[:, None, None],
                   cols - pre_t <= rows)
    p = jax.nn.softmax(jnp.where(ok[:, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", p, v.astype(jnp.float32)).astype(
        q.dtype)
