"""Paged-KV decode attention kernel (TPU Pallas).

The decode hot loop attends one query token per slot against that slot's
paged KV history. XLA lowers the naive formulation (gather pages into a
contiguous [B, T] cache, then attend) at ~10% of HBM bandwidth — the page
gather dominated the whole decode step. These kernels instead walk each
slot's page table and DMA exactly the pages it owns, flash-accumulating
on the fly, so per-step traffic is the true KV working set.

Two kernels. `paged_decode_attention` (`_dma_kernel`: the hybrid and the
window modules' decode, which write the token's K and V themselves) reads
only, a BLOCK of G pages a turn of its loop: while a block is attended
the G copies of the next one (the next slot's first, at a slot's end) are
all in flight, side by side in the other half of a two-block buffer, and
a turn is one score product, one softmax update and one p . v over
G * page positions. G follows the shapes the call is handed, by
`latent_attention._block_pages` over K and V of a page together: 8 at two
K/V heads of 128, 16 at one, 2 at eight, never more than a table is wide.
`paged_decode_insert_attention` (`_fused_kernel`: every per-head decode
step) also writes the token's K and V, and still walks a page a turn
through a two-deep pipeline (page i+1 in flight while page i is in the
flash update); `_block_copies` and `_block_update` are written to be
called from there next (ROADMAP S15).

Parity: the role of vLLM's paged attention CUDA kernel inside the
reference's LLM serving stack (`python/ray/llm/_internal/serve/deployments/
llm/vllm/`); the TPU shape follows the public JetStream/MaxText paged
decode pattern (scalar-prefetched page tables + manual double-buffered
page DMA).

Layouts:
  q            [B, n_heads, head_dim]
  pool_k, pool_v   [n_layers, n_kv_heads, num_pages, head_dim, page_size]
      (`paged_decode_attention` alone: pool_v's width may be another than
      pool_k's, keys of q's width and values of their own, which is the
      output's then)
      the engine's STACKED pools, with the `layer` to read: the kernels
      index `pool.at[layer, :, page_id]` themselves. A caller that hands
      over `pool[layer]` makes XLA slice 1/L of the pool out and re-tile
      it for the call on every layer of every token (1.0 ms of slices in
      qwen2_7b's 18.9 ms decode step on the v5e, beside the whole-pool
      copies of the scatter that fed it; PERF.md section 5, PR 29).
      (head_dim BEFORE page: a page's DMA slice then has trailing dims
      (head_dim, page) = (64|128, 128), which Mosaic can tile — with page
      last-minor the 64-wide head_dim would land on the 128-lane axis and
      the per-page slice fails to lower)
  lengths      [B]  number of valid tokens (attend positions < lengths)
  page_tables  [B, P]  page ids in position order (entry 0 = scratch page)

`paged_decode_attention_reference` takes ONE layer's pages
[n_kv_heads, num_pages, head_dim, page_size].

Returns [B, n_heads, head_dim].
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.latent_attention import _block_pages

_NEG = -0.7 * float(np.finfo(np.float32).max)


def _mosaic_tiles(page: int, hd: int) -> bool:
    """Mosaic can only DMA page slices whose trailing dims tile to
    (8, 128). Off-size pages (toy/test configs) take the XLA
    gather-attend formulation — slower, always correct. Only the compiled
    (on-chip) path asks, and there no dispatcher leaves the kernel
    without saying so."""
    if page % 128 == 0 and hd % 8 == 0:
        return True
    warnings.warn(
        f"paged attention: page_size={page}, head_dim={hd} do not tile to "
        "(8, 128); running the XLA gather formulation instead of the "
        "Pallas kernel", RuntimeWarning, stacklevel=3)
    return False


def paged_decode_attention(q, pool_k, pool_v, lengths, page_tables, *,
                           layer: int, interpret: bool | None = None,
                           lows=None, name: str | None = None, sink=None):
    """Flash decode over layer `layer` of the stacked paged pools; see
    module docstring for layouts.

    `lows` [B] (a window layer's call): a slot attends positions lows <=
    p < lengths only, positions counted from the first token of the
    table's first page, which need not be the sequence's first: the
    caller hands over the window's pages alone, and the kernel starts
    there. `name`: the call's name in a device trace. `sink` [h] float32
    (with `lows`): a learned logit a query head that joins its softmax's
    denominator and gives no value.

    interpret=None auto-selects: the Mosaic lowering needs a real TPU
    backend; everywhere else (CPU tests, multichip dryrun) the kernel
    runs in interpret mode. RAY_TPU_PAGED_ATTN_IMPL=xla forces the plain
    XLA gather-attend formulation — the fallback path the tp>1 virtual-
    mesh dryrun uses (GSPMD shards it like any einsum; Pallas interpret
    mode is also ~100x slower than XLA on CPU)."""
    import os
    if os.environ.get("RAY_TPU_PAGED_ATTN_IMPL") == "xla":
        return _paged_decode_gather(q, pool_k, pool_v, lengths, page_tables,
                                    lows, layer, sink)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    page, hd = pool_k.shape[4], pool_k.shape[3]
    if not interpret and not (_mosaic_tiles(page, hd)
                              and _mosaic_tiles(page, pool_v.shape[3])):
        return _paged_decode_gather(q, pool_k, pool_v, lengths, page_tables,
                                    lows, layer, sink)
    # G, the pages a turn of the kernel attends: K and V of a page together
    # are what the latent kernel's one pool is to its rule
    block = _block_pages(
        pool_k.shape[1] * (hd + pool_v.shape[3]), page,
        pool_k.dtype.itemsize, page_tables.shape[1])
    return _paged_decode_dma(q, pool_k, pool_v, lengths, page_tables, layer,
                             lows, sink, block=block, interpret=interpret,
                             name=name)


@functools.partial(jax.jit, static_argnames=("layer",))
def _paged_decode_xla(q, pool_k, pool_v, lengths, page_tables, lows=None,
                      sink=None, *, layer: int):
    return paged_decode_attention_reference(
        q, pool_k[layer], pool_v[layer], lengths, page_tables, lows, sink)


def _paged_decode_gather(q, pool_k, pool_v, lengths, page_tables, lows,
                         layer, sink=None):
    """The XLA gather formulation at `layer`, an int or a traced scalar (a
    looped stack's cache layer, inside its loop over passes)."""
    if isinstance(layer, int):
        return _paged_decode_xla(q, pool_k, pool_v, lengths, page_tables,
                                 lows, sink, layer=layer)
    return paged_decode_attention_reference(
        q, *(jax.lax.dynamic_index_in_dim(p, layer, keepdims=False)
             for p in (pool_k, pool_v)), lengths, page_tables, lows, sink)


def _block_copies(tables_ref, k_hbm, v_hbm, kbuf, vbuf, sem, *, layer, slot,
                  first, count, half, page: int, wait: bool = False):
    """Start, or wait for, the copies of `count` pages of `slot` (its
    table's entries `first`, `first` + 1, ...) into block `half` of `kbuf`
    and `vbuf` [2, hkv, hd | dv, G * page]: page j of them lands in lanes
    [j * page, (j + 1) * page), by a copy of its own on the block's two
    semaphores (`sem` [2, 2]: block, K | V). The trip count is `count`: no
    table entry past it is read, and nothing is unrolled."""
    def one(j, _):
        pid = tables_ref[slot, first + j]
        at = pl.ds(pl.multiple_of(j * page, page), page)
        for which, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
            copy = pltpu.make_async_copy(
                hbm.at[layer, :, pid], buf.at[half, :, :, at],
                sem.at[half, which])
            copy.wait() if wait else copy.start()
        return 0
    jax.lax.fori_loop(0, count, one, 0)


def _block_update(q, k, v, m_ref, l_ref, acc_ref, *, start, limit, low=None,
                  scale: float):
    """One running-softmax update over a block of keys: q [hkv, g, hd], k
    [hkv, hd, T] and v [hkv, dv, T] float32, T the block's G * page
    positions `start`, `start` + 1, ...; those outside [`low`, `limit`)
    score `_NEG`, so their p is exactly 0 once the running maximum is a
    real score's (or a sink's). m_ref / l_ref [hkv * g, 128], acc_ref
    [hkv, g, dv]."""
    hkv, g, _ = q.shape
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale       # [hkv, g, T]
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, dimension=2)
    s = jnp.where(pos < limit, s, _NEG)
    if low is not None:         # a window layer: nothing before `low`
        s = jnp.where(pos >= low, s, _NEG)
    m_old = m_ref[...]                                    # [hkv*g, 128]
    s2 = s.reshape(hkv * g, s.shape[2])
    m_cur = jnp.max(s2, axis=1, keepdims=True)
    m_new = jnp.maximum(m_old, jnp.broadcast_to(m_cur, m_old.shape))
    alpha = jnp.exp(m_old[:, :1] - m_new[:, :1])
    p_exp = jnp.exp(s2 - m_new[:, :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p_exp, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p_exp.reshape(s.shape), v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)               # [hkv, g, dv]
    acc_ref[...] = acc_ref[...] * alpha[:, None].reshape(hkv, g, 1) + pv
    m_ref[...] = m_new


def _dma_kernel(layer_ref, lengths_ref, tables_ref,  # scalar prefetch (SMEM)
                q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, m_ref, l_ref, acc_ref, sem, first_ref, *,
                page: int, scale: float, pages_per_seq: int, block: int,
                lows_ref=None, sink_ref=None):
    """k_hbm / v_hbm are the stacked pools [L, hkv, N, hd | dv, page] (V's
    width its own: `vbuf`, the accumulator and the output have it), read at
    layer `layer_ref[0]`: a prefetched scalar, not a static, so that the
    L calls of a decode program share ONE traced, lowered and compiled
    kernel (a static layer made twelve of each on qwen2_7b: 0.5 s more
    of tracing and lowering whenever a decode program is looked up, 7 s
    of the chat cell's warm-up on the chip machine; PERF.md section 6).

    One grid step per slot, one turn of its loop per BLOCK of `block`
    pages (`_block_copies`, `_block_update`): one [hkv, g, hd] .
    [hkv, hd, block * page] score product, one running-softmax update, one
    p . v. A page a turn paid the turn's dependency chain (wait, scores,
    maximum, rescale, p . v, each behind the one before) once a page: 0.2
    to 0.35 us over the page's bytes whether it held 128 KB or 512 (PERF.md
    section 5, PR 61). One grid step per slot keeps grid overhead off the
    hot path: a BlockSpec-per-page variant spends more time stepping the
    grid than computing (measured ~0.8ms per layer call vs ~0.2ms for
    this shape).

    `kbuf` / `vbuf` are two blocks. While block i is attended every copy
    of block i + 1 is in flight, and a slot's last turn starts the NEXT
    slot's first block (the grid is sequential: `first_ref` carries the
    half that block lands in), so only the call's first block is waited
    for with nothing to compute: what a window layer's call gains, whose
    tables hold 2 to 5 pages a slot.

    The ragged end: a slot's last block fetches the pages the slot holds
    and no other. The rest of that block's buffer is attended all the
    same, at a score of `_NEG`, and 0 times what lies there is 0 because
    nothing non-finite can lie there: both blocks are zeroed on the first
    grid step, and every copy since brought a page that a slot of this
    call holds, the same pages whose own tail past `length` has always
    been attended at p = 0.

    `sink_ref` [hkv * g, 128] float32 (`_dma_sink_kernel`), a query head's
    learned sink in every lane of its row: the flash update's starting
    state is then running max the sink, sum 1, accumulator 0, and the
    maximum is finite whether or not a block holds a real position."""
    b, nb = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    span = block * page
    # a length past the table's end attends what the table names
    length = jnp.minimum(lengths_ref[b], pages_per_seq * page)

    def pages_of(slot):
        return jnp.minimum(
            jax.lax.div(lengths_ref[slot] + page - 1, page), pages_per_seq)

    def copies(slot, i, half, wait=False):
        """Block i of `slot`: the pages the slot holds there."""
        _block_copies(
            tables_ref, k_hbm, v_hbm, kbuf, vbuf, sem, layer=layer,
            slot=slot, first=i * block, half=half, page=page, wait=wait,
            count=jnp.minimum(pages_of(slot) - i * block, block))

    npg = pages_of(b)
    nblk = jax.lax.div(npg + block - 1, block)

    @pl.when(b == 0)
    def _clean():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        first_ref[0] = 0

    first = first_ref[0]          # the half this slot's block 0 lands in

    # the slot before started this slot's first block in its last turn,
    # unless it had no turn (or there is no slot before)
    @pl.when((npg > 0)
             & ((b == 0) | (pages_of(jnp.maximum(b - 1, 0)) == 0)))
    def _first():
        copies(b, 0, first)

    if sink_ref is None:
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
    else:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32)                   # [hkv, g, hd]
    hkv, g, _ = q.shape

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
        _block_update(
            q, kbuf[half].astype(jnp.float32), vbuf[half].astype(jnp.float32),
            m_ref, l_ref, acc_ref, start=i * span, limit=length,
            low=None if lows_ref is None else lows_ref[b], scale=scale)
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    first_ref[0] = jax.lax.rem(first + nblk, 2)
    l = l_ref[...][:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l.reshape(hkv, g, 1)).astype(o_ref.dtype)


def _dma_window_kernel(layer_ref, lengths_ref, tables_ref, lows_ref, *refs,
                       **statics):
    """`_dma_kernel` with one more prefetched scalar a slot, the first
    position that counts."""
    _dma_kernel(layer_ref, lengths_ref, tables_ref, *refs, lows_ref=lows_ref,
                **statics)


def _dma_sink_kernel(layer_ref, lengths_ref, tables_ref, lows_ref, q_ref,
                     k_hbm, v_hbm, sink_ref, *refs, **statics):
    """`_dma_window_kernel` with one more input, the heads' sinks."""
    _dma_kernel(layer_ref, lengths_ref, tables_ref, q_ref, k_hbm, v_hbm,
                *refs, lows_ref=lows_ref, sink_ref=sink_ref, **statics)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "name"))
def _paged_decode_dma(q, k_pages, v_pages, lengths, page_tables, layer,
                      lows=None, sink=None, *, block: int,
                      interpret: bool = False, name: str | None = None):
    """With `lows` (a window layer's pages): one more prefetched scalar a
    slot, below which nothing counts; with a `sink` besides, one more
    input, resident over the grid. `block`: the pages a turn attends."""
    B, h, hd = q.shape
    _, hkv, N, _, page = k_pages.shape
    dv = v_pages.shape[3]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    P = page_tables.shape[1]
    q4 = q.reshape(B, hkv, g, hd)
    scale = 1.0 / float(np.sqrt(hd))
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), lengths, page_tables)
    if lows is not None:
        scalars += (lows,)
    kernel = functools.partial(
        _dma_kernel if lows is None else _dma_window_kernel, page=page,
        scale=scale, pages_per_seq=P, block=block)

    def slot(b, *_scalars):
        return (b, 0, 0, 0)

    operands = (q4, k_pages, v_pages)
    in_specs = [
        pl.BlockSpec((1, hkv, g, hd), slot),
        pl.BlockSpec(memory_space=pl.ANY),   # k_pages in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # v_pages in HBM
    ]
    if sink is not None:    # [h] float32 -> a row a head, every lane
        assert lows is not None, "a sink comes with a window's `lows`"
        kernel = functools.partial(_dma_sink_kernel, **kernel.keywords)
        operands += (jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (h, 128)),)
        in_specs.append(pl.BlockSpec((h, 128), lambda b, *_scalars: (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, g, dv), slot),
            scratch_shapes=[
                pltpu.VMEM((2, hkv, hd, block * page), k_pages.dtype),
                pltpu.VMEM((2, hkv, dv, block * page), v_pages.dtype),
                pltpu.VMEM((hkv * g, 128), jnp.float32),        # m
                pltpu.VMEM((hkv * g, 128), jnp.float32),        # l
                pltpu.VMEM((hkv, g, dv), jnp.float32),          # acc
                pltpu.SemaphoreType.DMA((2, 2)),         # block, K | V
                pltpu.SMEM((1,), jnp.int32),     # first block's half
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, hkv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, *operands)
    return out.reshape(B, h, dv)


def _fused_kernel(lengths_ref, tables_ref,  # scalar prefetch (SMEM)
                  q_ref, knew_ref, vnew_ref, k_hbm, v_hbm,
                  o_ref, ko_ref, vo_ref,
                  kbuf, vbuf, m_ref, l_ref, acc_ref, sem, wsem, *,
                  page: int, scale: float, pages_per_seq: int, n_q: int,
                  layer: int):
    """Attention with the KV INSERT fused in (JetStream-style): the kernel
    already streams every page of the slot; when the page holding the n_q
    new tokens passes through VMEM, their K/V columns are merged in and
    the merged page is DMAd back to the pool, which is input/output-
    aliased. Token-granular XLA scatters serialized at ~2us/row and cost
    more than the whole forward; here the write rides the DMA pipeline the
    attend already pays for. The written values are BITWISE the new K and
    V (a select, no product through the MXU).

    The new tokens' blocks differ with the static n_q, a shape: n_q > 1
    (several tokens a slot at consecutive positions, query j attending
    positions < lengths + j: what a draft source inside step() would
    call; nothing does today) [hkv*hd, n_q], rolled along the page's
    lanes to where the tokens land; n_q == 1 (every decode step) the
    token's [hkv, hd] as the projections leave them, copied into every
    column, so that no re-lay-out runs before the call (it was 14 us a
    call on ouro_2_6b, PERF.md section 6, PR 46)."""
    b = pl.program_id(0)
    length = lengths_ref[b]          # = base + 1 (limit of query 0)
    base = length - 1                # position of the first new token
    npg = jnp.minimum(
        jax.lax.div(length + (n_q - 1) + page - 1, page), pages_per_seq)

    def start_copy(i, slot):
        pid = tables_ref[b, i]
        pltpu.make_async_copy(
            k_hbm.at[layer, :, pid], kbuf.at[slot], sem.at[slot, 0]).start()
        pltpu.make_async_copy(
            v_hbm.at[layer, :, pid], vbuf.at[slot], sem.at[slot, 1]).start()

    def wait_copy(slot):
        pltpu.make_async_copy(
            k_hbm.at[layer, :, 0], kbuf.at[slot], sem.at[slot, 0]).wait()
        pltpu.make_async_copy(
            v_hbm.at[layer, :, 0], vbuf.at[slot], sem.at[slot, 1]).wait()

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(npg > 0)
    def _first():
        start_copy(0, 0)

    q = q_ref[0].astype(jnp.float32)               # [hkv, g*n_q, hd]
    hkv, gq, hd = q.shape

    def landed(new_ref, at):
        """The new tokens' block as a page [hkv, hd, page] whose column
        `at` + j holds token j, `at` in (-n_q, page) (float32: roll and
        the transpose only lower for 32-bit lanes, and bf16 -> f32 -> bf16
        is exact, so the write stays bitwise)."""
        new = new_ref[0].astype(jnp.float32)
        if n_q == 1:  # [hkv, hd] down the sublanes, then hd under the page:
            # the token in every column
            return jnp.swapaxes(jnp.broadcast_to(
                new[:, None, :], (hkv, page, hd)), 1, 2)
        pad = jnp.zeros((new.shape[0], page - n_q), new.dtype)
        return pltpu.roll(jnp.concatenate([new, pad], axis=1),  # [hkv*hd, n_q]
                          jax.lax.rem(at + page, page), 1).reshape(
                              hkv, hd, page)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < npg)
        def _prefetch():
            start_copy(i + 1, 1 - slot)

        wait_copy(slot)

        # ---- fused insert: this page holds new-token positions? ----
        lo, hi = i * page, (i + 1) * page
        overlaps = (lo <= base + n_q - 1) & (hi > base)

        @pl.when(overlaps)
        def _merge():
            pid = tables_ref[b, i]
            # Token j lands at column base+j-lo: select the covered
            # columns of the block shifted there.
            at = base - lo
            idx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2) - at
            sel = (idx >= 0) & (idx < n_q)
            kbuf[slot] = jnp.where(
                sel, landed(knew_ref, at).astype(kbuf.dtype), kbuf[slot])
            vbuf[slot] = jnp.where(
                sel, landed(vnew_ref, at).astype(vbuf.dtype), vbuf[slot])
            # write the merged page back to the (aliased) pool
            pltpu.make_async_copy(
                kbuf.at[slot], k_hbm.at[layer, :, pid], wsem.at[0]).start()
            pltpu.make_async_copy(
                vbuf.at[slot], v_hbm.at[layer, :, pid], wsem.at[1]).start()

        k = kbuf[slot].astype(jnp.float32)             # [hkv, hd, page]
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [hkv, gq, page]
        pos = i * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=2)
        limit = length + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, dimension=1),
            n_q)
        s = jnp.where(pos < limit, s, _NEG)
        m_old = m_ref[...]
        s2 = s.reshape(hkv * gq, page)
        m_cur = jnp.max(s2, axis=1, keepdims=True)
        m_new = jnp.maximum(m_old, jnp.broadcast_to(m_cur, m_old.shape))
        alpha = jnp.exp(m_old[:, :1] - m_new[:, :1])
        p_exp = jnp.exp(s2 - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            p_exp, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p_exp.reshape(hkv, gq, page), v,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None].reshape(
            hkv, gq, 1) + pv
        m_ref[...] = m_new

        @pl.when(overlaps)
        def _written():
            # the write-back flew under the page's flash update (5 % of a
            # call at 16 K/V heads, PERF.md section 6, PR 46); nothing
            # lands in kbuf[slot] before the next turn's prefetch
            pid = tables_ref[b, i]
            pltpu.make_async_copy(
                kbuf.at[slot], k_hbm.at[layer, :, pid], wsem.at[0]).wait()
            pltpu.make_async_copy(
                vbuf.at[slot], v_hbm.at[layer, :, pid], wsem.at[1]).wait()

        return 0

    jax.lax.fori_loop(0, npg, body, 0)
    l = l_ref[...][:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l.reshape(hkv, gq, 1)).astype(o_ref.dtype)


def _fused_layer_kernel(layer_ref, lengths_ref, tables_ref, *refs,
                        **statics):
    """`_fused_kernel` at layer `layer_ref[0]`, one more prefetched scalar
    (a looped stack's cache layer, traced inside its loop over passes)."""
    _fused_kernel(lengths_ref, tables_ref, *refs, layer=layer_ref[0],
                  **statics)


def paged_decode_insert_attention(q, pool_k, pool_v, knew, vnew, lengths,
                                  page_tables, *, layer, name: str,
                                  interpret: bool | None = None):
    """One decode token a slot with its K/V insert FUSED into the
    attention kernel (`_fused_kernel` at one query a slot): q [B, h, hd],
    knew / vnew [B, hkv, hd] the token's own K and V, written at position
    lengths - 1 of cache layer `layer` (an int or a traced scalar) as the
    page that holds it streams through VMEM; the slot attends positions <
    lengths. A slot of length 0 moves nothing (no page read, none written;
    its row of the output is 0), and one whose position lies past its
    table writes nothing. -> (attn [B, h, hd], pool_k, pool_v), the pools
    aliased.

    Why every per-head decode step takes it: a column written by
    `dynamic_update_slice` is read-modified-written a tile at a time (0.57
    us + 0.051 us a tile on the v5e: 2.2 us at qwen2_7b's 4 K/V heads, 384
    updates and 0.86 ms of its 10.5 ms step; 7.1 us at ouro_2_6b's 16,
    2,688 updates and 19.7 ms of its 58 ms step), whatever the slot holds;
    here the write is one more page DMA of a page the kernel holds
    already, and an idle slot costs nothing (PERF.md sections 5 and 6, PR
    45 and PR 46). Off the chip (interpret mode does not carry the
    kernel's in-place HBM write-back through the input/output aliasing:
    the aliased outputs come back unmodified) and for pages Mosaic cannot
    tile: the XLA column insert, then `paged_decode_attention`.

    So tier-1 EXECUTES only the fallback, and test_chip_compile only
    compiles the fused kernel: its numbers are checked on the chip alone,
    by the cells' `correct` and by `perfbench/tools/checkdist.py` and
    `checkdist_ouro.py` (the verify skill has the commands): run those
    after any edit to `_fused_kernel` or `_fused_insert_call`."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    page, hd = pool_k.shape[4], pool_k.shape[3]
    if interpret or not _mosaic_tiles(page, hd):
        pool_k, pool_v = _insert_tokens_xla(
            pool_k, pool_v, knew[:, None], vnew[:, None], lengths,
            page_tables, layer)
        return paged_decode_attention(
            q, pool_k, pool_v, lengths, page_tables, layer=layer,
            interpret=interpret, name=name), pool_k, pool_v
    return _decode_insert_dma(q, pool_k, pool_v, knew, vnew, lengths,
                              page_tables, jnp.asarray(layer, jnp.int32),
                              name=name)


@functools.partial(jax.jit, static_argnames=("name",))
def _decode_insert_dma(q, k_pages, v_pages, knew, vnew, lengths, page_tables,
                       layer, *, name: str):
    """The layer a prefetched scalar whether the program unrolls its
    layers or loops over passes, and the call a jit of its own: the L
    calls of a decode program share ONE traced, lowered and compiled
    kernel, as `_dma_kernel`'s do."""
    out, k_pages, v_pages = _fused_insert_call(
        q[:, None], k_pages, v_pages, knew[:, None], vnew[:, None], lengths,
        page_tables, layer, name=name)
    return out[:, 0], k_pages, v_pages


def _insert_tokens_xla(pool_k, pool_v, knew, vnew, lengths,
                       page_tables, layer):
    """Token-scatter fallback insert (CPU tests / odd shapes)."""
    B, S = knew.shape[:2]
    hkv = pool_k.shape[1]
    page = pool_k.shape[4]
    P = page_tables.shape[1]
    positions = (lengths - 1)[:, None] + jnp.arange(S)[None]
    w_idx = jnp.clip(positions // page, 0, P - 1)
    w_page = jnp.take_along_axis(page_tables, w_idx, 1)
    w_page = jnp.where(positions // page >= P, 0, w_page)
    w_off = positions % page
    hkv_idx = jnp.arange(hkv)[:, None, None]
    pool_k = pool_k.at[layer, hkv_idx, w_page[None], :, w_off[None]].set(
        knew.transpose(2, 0, 1, 3).astype(pool_k.dtype))
    pool_v = pool_v.at[layer, hkv_idx, w_page[None], :, w_off[None]].set(
        vnew.transpose(2, 0, 1, 3).astype(pool_v.dtype))
    return pool_k, pool_v


def _fused_insert_call(q, k_pages, v_pages, knew, vnew, lengths,
                        page_tables, layer, *, interpret: bool = False,
                        name: str | None = None):
    """The fused kernel's call: q [B, S, h, hd], knew / vnew
    [B, S, hkv, hd], S = 1 from `_decode_insert_dma`. `layer` an int: a
    static of the kernel; a traced scalar (the decode programs'):
    prefetched before the two the kernel takes anyway."""
    B, S, h, hd = q.shape
    L, hkv, N, _, page = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    P = page_tables.shape[1]
    q4 = q.reshape(B, S, hkv, g, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, hkv, g * S, hd)
    if S == 1:   # one token a slot: its K and V as they come, [B, hkv, hd]
        kn, vn = knew[:, 0], vnew[:, 0]
    else:        # [B, S, hkv, hd] -> [B, hkv*hd, S]: tokens along the lanes
        kn = knew.transpose(0, 2, 3, 1).reshape(B, hkv * hd, S)
        vn = vnew.transpose(0, 2, 3, 1).reshape(B, hkv * hd, S)
    new_spec = pl.BlockSpec((1,) + kn.shape[1:],
                            lambda b, *_scalars: (b,) + (0,) * (kn.ndim - 1))
    scale = 1.0 / float(np.sqrt(hd))
    statics = dict(page=page, scale=scale, pages_per_seq=P, n_q=S)
    if isinstance(layer, int):
        kernel = functools.partial(_fused_kernel, layer=layer, **statics)
        scalars = (lengths, page_tables)
    else:
        kernel = functools.partial(_fused_layer_kernel, **statics)
        scalars = (jnp.asarray(layer, jnp.int32).reshape(1), lengths,
                   page_tables)
    n_sc = len(scalars)
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_sc,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, hkv, g * S, hd),
                             lambda b, *_scalars: (b, 0, 0, 0)),
                new_spec,
                new_spec,
                pl.BlockSpec(memory_space=pl.ANY),      # k_pages in HBM
                pl.BlockSpec(memory_space=pl.ANY),      # v_pages in HBM
            ],
            out_specs=[
                pl.BlockSpec((1, hkv, g * S, hd),
                             lambda b, *_scalars: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # aliased k_pages
                pl.BlockSpec(memory_space=pl.ANY),      # aliased v_pages
            ],
            scratch_shapes=[
                pltpu.VMEM((2, hkv, hd, page), k_pages.dtype),  # kbuf
                pltpu.VMEM((2, hkv, hd, page), v_pages.dtype),  # vbuf
                pltpu.VMEM((hkv * g * S, 128), jnp.float32),    # m
                pltpu.VMEM((hkv * g * S, 128), jnp.float32),    # l
                pltpu.VMEM((hkv, g * S, hd), jnp.float32),      # acc
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),                  # writeback
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, hkv, g * S, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand indices count the scalar-prefetch args first:
        # [layer] lengths tables | q knew vnew k_pages v_pages
        input_output_aliases={n_sc + 3: 1, n_sc + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, q4, kn, vn, k_pages, v_pages)
    out = out.reshape(B, hkv, g, S, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, S, h, hd)
    return out, k_pages, v_pages


def paged_decode_attention_reference(q, k_pages, v_pages, lengths,
                                     page_tables, lows=None, sink=None):
    """Dense reference for tests: gather pages, mask, softmax (with a
    `sink` [h]: over one more column a head, which gives no value)."""
    B, h, hd = q.shape
    hkv, N, _, page = k_pages.shape
    g = h // hkv
    P = page_tables.shape[1]
    T = P * page
    ck = k_pages[:, page_tables]          # [hkv, B, P, hd, page]
    cv = v_pages[:, page_tables]
    # -> [B, hkv, T, hd]
    ck = jnp.moveaxis(ck, 0, 1).transpose(0, 1, 2, 4, 3).reshape(
        B, hkv, T, hd)
    cv = jnp.moveaxis(cv, 0, 1).transpose(0, 1, 2, 4, 3).reshape(
        B, hkv, T, -1)
    q4 = q.reshape(B, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", q4, ck.astype(jnp.float32))
    s = s / np.sqrt(hd)
    mask = jnp.arange(T)[None, None, None] < lengths[:, None, None, None]
    if lows is not None:
        mask &= jnp.arange(T)[None, None, None] >= lows[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    if sink is not None:
        col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, hkv, g, 1), (B, hkv, g, 1))
        s = jnp.concatenate([s, col], -1)
        mask = jnp.concatenate([jnp.broadcast_to(mask, (B, 1, 1, T)),
                                jnp.zeros((B, 1, 1, 1), bool)], -1)
    # a slot of length 0 attends nothing: a row of 0, as the kernels give
    pr = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    if sink is not None:
        pr = pr[..., :T]
    out = jnp.einsum("bkgt,bktd->bkgd", pr, cv.astype(jnp.float32))
    return out.reshape(B, h, -1).astype(q.dtype)
