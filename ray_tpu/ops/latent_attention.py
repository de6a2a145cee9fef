"""Attention kernels of latent (MLA) attention (TPU Pallas).

Two kernels, one for each form of the same function
(models/deepseek_v2.py has the equations):

- `paged_latent_decode_attention`: the ABSORBED form over the paged latent
  pool. A cached token is 576 numbers shared by every head (512 normed
  latent | 64 rotated key); the query arrives as q~|q_pe with W_UK folded
  in, so a page is read once for all heads. One grid step a slot, and
  one turn of its loop a BLOCK of G pages: while a block is attended the
  G copies of the next one (the next slot's first, at a slot's end) are
  all in flight, HBM -> VMEM, side by side in the other half of a
  two-block buffer; a turn is one [h, 576] @ [576, G * page] score
  product, one softmax update (one row maximum, one rescale of the
  [h, 512] accumulator) and one p . c_kv product over G * page tokens.
  A slot's last block fetches only the pages the slot holds; what else
  lies in that block's buffer is masked to p = 0 and is finite by
  construction (`_latent_decode_kernel` says why). G follows the shapes
  the kernel is handed, under `_BLOCK_BYTES` (`_block_pages`): 8 for
  DeepSeek-V2's bfloat16 pages, 4 in float32, never more than a table
  is wide.
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
# VMEM the decode kernel's two blocks of pages may take together: 2.25 MiB,
# eight [576, 128] bfloat16 pages a block (PERF.md section 5 has the sweep)
_BLOCK_BYTES = 9 << 18


# ---------------------------------------------------------------- decode


def _block_pages(width: int, page: int, itemsize: int,
                 pages_per_seq: int) -> int:
    """G, the pages one turn of the decode kernel attends: as many as
    `_BLOCK_BYTES` holds twice over (a block attended, a block in flight),
    a power of two, and no more than a slot's table can name."""
    fit = max(1, _BLOCK_BYTES // (2 * width * page * itemsize))
    return min(1 << (fit.bit_length() - 1), pages_per_seq)


def _latent_decode_kernel(lengths_ref, tables_ref,  # scalar prefetch (SMEM)
                          q_ref, pool_hbm, o_ref,
                          buf, m_ref, l_ref, acc_ref, sem, first_ref, *,
                          layer: int, page: int, rank: int, scale: float,
                          pages_per_seq: int, block: int):
    """One grid step per slot, one turn of its loop per BLOCK of `block`
    pages: [h, 576] @ [576, block * page] scores, one running-softmax
    update, [h, block * page] @ [block * page, rank] accumulate. Matmul
    inputs stay in the pool's type (bf16 straight into the MXU),
    accumulators float32.

    `buf` is two blocks; a block's pages land side by side along its last
    axis, each by a copy of its own on the block's semaphore. While block
    i is attended every copy of block i + 1 is in flight, and a slot's
    last turn starts the NEXT slot's first block (the grid is sequential:
    `first_ref` carries the half that block lands in), so only the call's
    first block is waited for with nothing to compute.

    The ragged end: a slot's last block fetches the pages the slot holds
    and no other (the copies' trip count is min(block, pages left): no
    table entry past them is read). The rest of that block's buffer is
    attended all the same, masked: its scores are `_NEG`, so its p is
    exactly 0 (the block holds a real position, so the maximum is finite),
    and 0 times what lies there is 0 because nothing non-finite can lie
    there: both blocks are zeroed on the first grid step, and every copy
    since brought a page that a slot of this call holds, the same pages
    whose own tail past `length` has always been attended at p = 0."""
    b, nb = pl.program_id(0), pl.num_programs(0)
    span = block * page
    # a length past the table's end attends what the table names
    length = jnp.minimum(lengths_ref[b], pages_per_seq * page)

    def pages_of(slot):
        return jnp.minimum(
            jax.lax.div(lengths_ref[slot] + page - 1, page), pages_per_seq)

    def copies(slot, i, half, wait=False):
        """Start, or wait for, the copies of block i of `slot` into `half`:
        of the pages the slot holds there, and of no other."""
        def one(g, _):
            copy = pltpu.make_async_copy(
                pool_hbm.at[layer, tables_ref[slot, i * block + g]],
                buf.at[half, :, pl.ds(pl.multiple_of(g * page, page), page)],
                sem.at[half])
            copy.wait() if wait else copy.start()
            return 0
        jax.lax.fori_loop(
            0, jnp.minimum(pages_of(slot) - i * block, block), one, 0)

    npg = pages_of(b)
    nblk = jax.lax.div(npg + block - 1, block)

    @pl.when(b == 0)
    def _clean():
        buf[...] = jnp.zeros_like(buf)
        first_ref[0] = 0

    first = first_ref[0]          # the half this slot's block 0 lands in

    # the slot before started this slot's first block in its last turn,
    # unless it had no turn (or there is no slot before)
    @pl.when((npg > 0)
             & ((b == 0) | (pages_of(jnp.maximum(b - 1, 0)) == 0)))
    def _first():
        copies(b, 0, first)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                       # [h, 576]

    def body(i, _):
        half = jax.lax.rem(first + i, 2)

        # the copies of this slot's next block or, in its last turn, of
        # the next slot's first (none where that slot holds no page)
        last = i + 1 == nblk

        @pl.when(~last | (b + 1 < nb))
        def _prefetch():
            copies(jnp.where(last, jnp.minimum(b + 1, nb - 1), b),
                   jnp.where(last, 0, i + 1), 1 - half)

        copies(b, i, half, wait=True)
        s = jnp.dot(q, buf[half],
                    preferred_element_type=jnp.float32) * scale
        pos = i * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG)           # [h, span]
        m_old = m_ref[...]                             # [h, 128]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(buf.dtype), buf[half, :rank],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [h, rank]
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    first_ref[0] = jax.lax.rem(first + nblk, 2)
    l = l_ref[...][:, :1]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "rank", "scale",
                                             "block", "interpret"))
def _paged_latent_decode_dma(q_lat, pool, lengths, page_tables, *,
                             layer: int, rank: int, scale: float,
                             block: int, interpret: bool):
    B, h, width = q_lat.shape
    page = pool.shape[3]
    kernel = functools.partial(
        _latent_decode_kernel, layer=layer, page=page, rank=rank,
        scale=scale, pages_per_seq=page_tables.shape[1], block=block)
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
                pltpu.VMEM((2, width, block * page), pool.dtype),
                pltpu.VMEM((h, 128), jnp.float32),        # m
                pltpu.VMEM((h, 128), jnp.float32),        # l
                pltpu.VMEM((h, rank), jnp.float32),       # acc
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),              # first block's half
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
    block = _block_pages(q_lat.shape[2], pool.shape[3], pool.dtype.itemsize,
                         page_tables.shape[1])
    return _paged_latent_decode_dma(q_lat, pool, lengths, page_tables,
                                    layer=layer, rank=rank, scale=scale,
                                    block=block, interpret=interpret)


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
