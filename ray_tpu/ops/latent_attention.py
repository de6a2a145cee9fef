"""Attention kernels of latent (MLA) attention (TPU Pallas).

Two kernels, one for each form of the same function
(models/deepseek_v2.py has the equations):

- `paged_latent_decode_attention`: the ABSORBED form over the paged latent
  pool. A cached token is 576 numbers shared by every head (512 normed
  latent | 64 rotated key); the query arrives as q~|q_pe with W_UK folded
  in, so a page is read once for all heads. The DMA scheme is
  ops/paged_attention.py's: one grid step a slot, the slot's pages
  streamed HBM -> VMEM two deep, flash accumulation on the way.
- `mla_prefill_attention`: the UP-PROJECTED form for prefill: the
  serving prefill kernel of ops/attention.py (`prefill_attention`, a
  forward flash kernel over [cached prefix | this chunk] keys, no score
  tensor in HBM) with a 192-wide q.k and a 128-wide p.v.

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

from ray_tpu.ops.attention import prefill_attention

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


def mla_prefill_attention(q, k, v, prefix_len, *, pre_t: int, scale: float,
                          interpret: bool | None = None, lengths=None):
    """ops/attention.prefill_attention at the up-projected form's widths:
    every head has a K and V of its own (q, k [n, h, ., 192], v [n, h, .,
    128] at DeepSeek-V2's). interpret=None: the kernel's interpreter off
    the chip. `lengths` [n]: the real rows of each request, as there."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return prefill_attention(
        q, k, v, prefix_len, pre_t=pre_t, scale=scale,
        name="mla_prefill_attention", lengths=lengths,
        impl="interpret" if interpret else "pallas")
