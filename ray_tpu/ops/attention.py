"""Flash attention: Pallas TPU kernels (forward + backward) with online
softmax.

The hot op of the model family (SURVEY §2.4 / pallas_guide.md). Tiled for the
MXU: grid = (batch*heads, q_blocks, k_blocks), fp32 accumulators in VMEM
scratch that persist across the innermost grid dimension, causal blocks
predicated with @pl.when so fully-masked tiles cost nothing. Falls back to a
jnp reference off-TPU (tests run the kernels in interpret mode to check the
exact same code path).

Backward: flash-style recompute in two Pallas kernels (dq; dkv), bf16 matmul
inputs with fp32 MXU accumulation. The forward saves the per-row logsumexp
(replicated along a 128-lane minor dim so both backward kernels read it in
their natural layout without in-kernel relayouts). A jnp recompute backward
(`impl="reference"`) remains as the numerics oracle.

Serving prefill (`prefill_attention`, at the end): a forward-only kernel of
its own over [cached prefix | chunk] keys with per-request prefix lengths,
general in the q.k and p.v widths and in the number of query heads that
share a K/V head. llm/engine.py's two prefill programs call it as
`gqa_prefill_attention`, models/deepseek_v2.py's as
`mla_prefill_attention` (ops/latent_attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, causal: bool,
                bq: int, bk: int, nk: int, with_lse: bool,
                kv_len: int | None):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # For causal attention, blocks strictly above the diagonal contribute
    # nothing; predicate them out entirely.
    run = True if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _compute():
        # bf16 straight into the MXU; fp32 comes out via preferred_element_type.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk] f32
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len is not None:
            # Sequence padded to the block multiple: hide the padded keys
            # (padded QUERY rows produce garbage and are sliced off by the
            # caller; under causal masking the padded keys sit above every
            # real row's diagonal already, but non-causal needs this).
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_scr[:, :1]                                  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)             # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                 # [bq, bk] f32
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if with_lse:
            # logsumexp per row, replicated along the 128-lane minor dim so
            # the backward kernels read it without relayouts.
            lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l),
                                          lse_ref.shape[1:])


def _flash_fwd(q, k, v, scale, causal, bq, bk, interpret, with_lse=True,
               kv_len=None):
    """q,k,v: [BH, S, D] -> (out [BH, S, D], lse [BH, S, 128] f32) when
    with_lse, else out alone (primal-only path: a pallas_call output cannot
    be DCE'd, so the inference path must not emit the lse at all)."""
    bh, s, d = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(s, bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, with_lse=with_lse,
                               kv_len=kv_len)
    out_shape = jax.ShapeDtypeStruct((bh, s, d), q.dtype)
    out_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    if with_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((bh, s, 128), jnp.float32))
        out_spec = (out_spec,
                    pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)))
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * s * s * d // (2 if causal else 1),
            bytes_accessed=3 * bh * s * d * q.dtype.itemsize,
            transcendentals=bh * s * s),
    )(q, k, v)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, bq: int, bk: int,
                   nk: int, kv_len: int | None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True if not causal else (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len is not None:
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                     # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, d]

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, bq: int, bk: int, nq: int,
                    kv_len: int | None):
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # q blocks entirely before this k block contribute nothing under causal.
    run = True if not causal else (qj * bq + bq - 1 >= ki * bk)

    @pl.when(run)
    def _compute():
        # Work in the transposed orientation [bk, bq]: the per-q-row lse and
        # delta then broadcast along sublanes, which is free on TPU.
        st = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [bk, bq]
        if causal:
            krows = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) + ki * bk
            qcols = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) + qj * bq
            st = jnp.where(qcols >= krows, st, NEG_INF)
        if kv_len is not None:
            krows = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) + ki * bk
            st = jnp.where(krows < kv_len, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0][:1])                      # [bk, bq]
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, bq]
        dst = pt * (dpt - delta_ref[0][:1]) * scale
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, scale, causal, bq, bk, interpret,
               kv_len=None):
    """Backward via flash-style recompute. lse: flat [BH, S] from forward."""
    bh, s, d = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(s, bk)
    # delta_i = rowsum(dO_i * O_i). Both lse and delta are fed to the dq
    # kernel lane-replicated [BH, S, 128] and to the dkv kernel transposed
    # [BH, 8, S] (seq along lanes) — each kernel reads its natural layout.
    delta_flat = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                         axis=-1)                              # [BH, S]
    delta = jnp.broadcast_to(delta_flat[..., None], (bh, s, 128))
    lse_rep = jnp.broadcast_to(lse[..., None], (bh, s, 128))
    lse_t = jnp.broadcast_to(lse[:, None, :], (bh, 8, s))
    delta_t = jnp.broadcast_to(delta_flat[:, None, :], (bh, 8, s))
    g = g.astype(q.dtype)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, kv_len=kv_len),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),    # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),    # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),    # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),    # do
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),  # lse
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * bh * s * s * d // (2 if causal else 1),
            bytes_accessed=4 * bh * s * d * q.dtype.itemsize,
            transcendentals=bh * s * s),
    )(q, k, v, g, lse_rep, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, kv_len=kv_len),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),    # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),    # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0)),    # q
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0)),    # do
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, j)),    # lse_t
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, j)),    # delta_t
        ],
        out_specs=(pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * bh * s * s * d // (2 if causal else 1),
            bytes_accessed=4 * bh * s * d * q.dtype.itemsize,
            transcendentals=bh * s * s),
    )(k, v, q, g, lse_t, delta_t)
    return dq, dk, dv


def _reference(q, k, v, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _on_tpu() -> bool:
    # No catch: a chip JAX could not initialise must raise here, not turn
    # into "use the dense reference".
    return jax.default_backend() == "tpu"


# 1024-row tiles: ~25-30% faster than 512 at S in [1k, 4k] on v5e (fewer
# grid cells, better MXU occupancy per cell) and still inside the 16MB
# scoped-vmem budget at D=64..128; 2048 blows scoped vmem. Shorter or
# misaligned sequences shrink via min/gcd below.
_BQ = 1024
_BK = 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, impl, kv_len=None, blk=_BQ):
    if impl == "reference":
        return _reference(q, k, v, scale, causal)
    return _flash_fwd(q, k, v, scale, causal, bq=blk, bk=blk,
                      interpret=(impl == "interpret"), with_lse=False,
                      kv_len=kv_len)


def _flash_vjp_fwd(q, k, v, scale, causal, impl, kv_len=None, blk=_BQ):
    if impl == "reference":
        return _reference(q, k, v, scale, causal), (q, k, v, None, None)
    out, lse = _flash_fwd(q, k, v, scale, causal, bq=blk, bk=blk,
                          interpret=(impl == "interpret"), kv_len=kv_len)
    # Save the flat [BH, S] logsumexp — the lane-replicated form would
    # multiply the per-layer residual footprint by 128.
    return out, (q, k, v, out, lse[:, :, 0])


def _flash_vjp_bwd(scale, causal, impl, kv_len, blk, res, g):
    q, k, v, o, lse = res
    if impl == "reference":
        # jnp recompute backward — the numerics oracle.
        _, vjp = jax.vjp(lambda q, k, v: _reference(q, k, v, scale, causal),
                         q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, o, lse, g, scale, causal, bq=blk, bk=blk,
                      interpret=(impl == "interpret"), kv_len=kv_len)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_per_shard(q, k, v, causal, scale, impl, mesh):
    """The kernel under shard_map: batch over the mesh's data axes, heads
    over "tp", every other axis replicated. A Mosaic kernel cannot be
    partitioned by GSPMD ("wrap the call in a shard_map"), so inside a jit
    over more than one device this is the only way the kernel lowers."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import data_axes, shard_map_compat
    tp = mesh.shape.get("tp", 1)
    if k.shape[2] % tp:  # fewer KV heads than tp shards: broadcast first
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    spec = P(data_axes(mesh) or None, None, "tp" if tp > 1 else None, None)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           impl=impl)
    return shard_map_compat(fn, mesh, (spec, spec, spec), spec)(q, k, v)


def flash_attention(q, k, v, causal: bool = True, scale: float | None = None,
                    impl: str = "auto", mesh=None):
    """q: [B, S, H, D], k/v: [B, S, Hkv, D] (GQA broadcast inside).

    impl: "auto" (pallas on TPU, reference elsewhere), "pallas",
    "interpret" (pallas interpreter — used by CPU tests), "reference".
    mesh: the mesh the surrounding jit is partitioned over; with more than
    one device the kernel runs per shard (see _flash_per_shard), the
    reference stays plain GSPMD.
    """
    b, s, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if (mesh is not None and mesh.devices.size > 1
            and impl in ("pallas", "interpret")):
        return _flash_per_shard(q, k, v, causal, scale, impl, mesh)
    hkv = k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    kv_len = None
    s_pad = s
    blk = _BQ
    if impl in ("pallas", "interpret"):
        # The kernels assume the sequence tiles exactly into the block size
        # (partial pallas blocks carry undefined values that the dkv
        # accumulation would fold into valid rows). Pad to the next
        # 128-lane multiple and mask the padded keys statically via kv_len
        # instead of falling back to the O(S^2)-memory dense reference —
        # at the lengths the kernel exists for, the fallback OOMs. The
        # tile shrinks to whatever still divides the padded length (at
        # most one 128-row tile of overhead, not a 512-multiple round-up).
        import math as _math
        blk = min(_BQ, s)
        if s % blk or blk % 8:  # untileable or sublane-misaligned
            s_pad = max(128, -(-s // 128) * 128)
            blk = _math.gcd(s_pad, _BQ)
            pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
            q = jnp.pad(q, pad)
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
            kv_len = s
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)
    out = _flash(qt, kt, vt, scale, causal, impl, kv_len, blk)
    out = out.reshape(b, h, s_pad, d).transpose(0, 2, 1, 3)
    return out[:, :s] if s_pad != s else out


# ------------------------------------------- serving prefill (forward only)


def _prefill_kernel(plen_ref,  # scalar prefetch (SMEM): [n] prefix lengths,
                    real_ref,  # [n * heads] query blocks that hold a token
                    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                    scale: float, bq: int, bk: int, nk: int, pre_t: int,
                    heads: int, window: int = 0, sink_ref=None):
    """Keys are [pre_t cached-prefix positions | the chunk]: prefix key j
    counts where j < plen of the request, chunk key c where c <= the query
    row. Blocks with nothing to count are predicated out, and so is every
    key block of a query block that lies past its request's last real row:
    nothing accumulates there, and what `_finalize` writes of it is zeros
    (the index maps of `_prefill_flash` fetch nothing for such a cell).

    With a `window` a query sees only the last `window` keys, and the
    cached prefix is RIGHT-aligned (request i's last plen positions before
    the chunk end at pre_t), so a key's index is its position up to a
    constant: query row r (index pre_t + r) sees the indices in (pre_t + r
    - window, pre_t + r] that are at least pre_t - plen. A grid cell then
    walks only the `nk` key blocks its query block can see, from
    `_window_first_block` on, not every block.

    `sink_ref` (`_prefill_sink_kernel`): the head's learned sink, a logit
    that joins every row's denominator and gives no value. It is the
    online softmax's starting state: running max the sink, sum 1,
    accumulator 0."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    plen = plen_ref[jax.lax.div(pl.program_id(0), heads)]
    if window:
        k0 = (_window_first_block(qi, bq=bq, bk=bk, pre_t=pre_t,
                                  window=window) + ki) * bk
    else:
        k0 = ki * bk

    @pl.when(ki == 0)
    def _init():
        if sink_ref is None:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        else:
            m_scr[...] = jnp.broadcast_to(sink_ref[0][:1], m_scr.shape)
            l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if window:
        # blocks past the query block's last row repeat the last block
        # (their index is clamped, so nothing is fetched again) and count
        # nothing
        visit = (k0 <= pre_t + qi * bq + bq - 1) & (k0 + bk > pre_t - plen)
    else:
        has_prefix = k0 < jnp.minimum(plen, pre_t)
        has_chunk = (k0 + bk > pre_t) & (
            jnp.maximum(k0, pre_t) - pre_t <= qi * bq + bq - 1)
        visit = has_prefix | has_chunk

    @pl.when(visit & (qi < real_ref[pl.program_id(0)]))
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
        if window:      # as a key index: row r sits at pre_t + r
            rows = rows + pre_t
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + k0
        if window:
            counts = ((cols <= rows) & (cols > rows - window)
                      & (cols >= pre_t - plen))
        else:
            counts = (cols < jnp.minimum(plen, pre_t)) | (
                (cols >= pre_t) & (cols - pre_t <= rows))
        s = jnp.where(counts, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if window:
            # a row that has counted nothing yet keeps m at NEG_INF: its p
            # is exp(0) where nothing counts, so mask p too
            p = jnp.where(counts, p, 0.0)
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


def _prefill_sink_kernel(plen_ref, real_ref, q_ref, k_ref, v_ref, sink_ref,
                         *refs, **statics):
    """`_prefill_kernel` with one more input, the heads' sinks: a grid
    row's [8, 128] float32 block holds its head's sink in every cell."""
    _prefill_kernel(plen_ref, real_ref, q_ref, k_ref, v_ref, *refs,
                    sink_ref=sink_ref, **statics)


def _window_first_block(qi, *, bq: int, bk: int, pre_t: int, window: int):
    """The first key block query block `qi` of a windowed call can see."""
    return jax.lax.div(jnp.maximum(pre_t + qi * bq - window + 1, 0), bk)


def _prefill_index_maps(*, h: int, group: int, bq: int, bk: int, nk: int,
                        pre_t: int, window: int):
    """(q's index map, K's and V's) over `_prefill_flash`'s grid (row b = a
    request's head, query block i, key step j), beside the two prefetched
    scalars. A cell that counts nothing fetches nothing: its index is that
    of the block the cell before it left resident, or of the next one that
    counts, so the pipeline issues no copy for it."""
    def last(i):        # the key block that holds query block i's last row
        return jax.lax.div(pre_t + i * bq + bq - 1, bk)

    if window:
        def kv_block(b, i, j, plen_ref):
            return jnp.minimum(_window_first_block(
                i, bq=bq, bk=bk, pre_t=pre_t, window=window) + j, last(i))
    else:
        def kv_block(b, i, j, plen_ref):
            if pre_t >= bk:
                # whole blocks of prefix: past those the request has, on
                # to the chunk's first block
                held = jax.lax.div(
                    jnp.minimum(plen_ref[jax.lax.div(b, h)], pre_t) + bk - 1,
                    bk)
                j = jnp.where((j >= held) & (j < pre_t // bk), pre_t // bk, j)
            return jnp.minimum(j, last(i))  # past the diagonal: its block

    def q_block(b, i, real_ref):
        # past the request's last real row: its last real block, resident
        return jnp.minimum(i, jnp.maximum(real_ref[b] - 1, 0))

    def q_map(b, i, j, plen_ref, real_ref):
        return b, q_block(b, i, real_ref), 0

    def kv_map(b, i, j, plen_ref, real_ref):
        # ... and of that block's key blocks the last, resident too
        j = jnp.where(i < real_ref[b], j, nk - 1)
        return jax.lax.div(b, group), kv_block(b, q_block(b, i, real_ref), j,
                                               plen_ref), 0

    return q_map, kv_map


# Query rows and key rows a grid cell: min(1024, S) and min(1024, keys).
# Kernel benches on the chip (v5e), ms a call, query/key rows:
# - PR 28, 128 heads x 4096 queries, 192/128 wide: 512/512 16.4, 1024/512
#   15.8, 512/1024 11.9, 1024/1024 10.0 (68 TFLOP/s of causal work; 95 over
#   a 4096-token prefix); the [1024, 1024] float32 scores still fit the
#   16 MB of scoped VMEM.
# - PR 31, 28 query / 4 KV heads of 128 (32 / 8 in brackets), n 8 x S 1024:
#   256/256 3.38, 512/512 1.84, 1024/512 2.05, 512/1024 1.26, 1024/1024 1.16
#   (1.41); over a 1024-token prefix 512/512 3.84, 512/1024 2.36, 1024/1024
#   2.05 (2.42); n 1: 0.19 and 0.30 (0.21, 0.35). 1024/1024 was first at
#   every n, S and prefix tried.
# - Key blocks do not shrink with the chunk: n 8 x S 64 over a 1024-token
#   prefix takes 2.11 ms with 64-key blocks (17 steps a head), 0.42 with one
#   of 1024; n 8 x S 256: 1.39 against 0.83.
# - Below 1024 rows a cell is one head's whole [S, S] block and the grid
#   step is what costs: n 8 x S 256, no prefix, 0.35 ms (n 1: 0.09; S 64:
#   0.18 and 0.07) where XLA's fused softmax over the small score array
#   took 0.13 (0.06; 0.06 and 0.04). Several heads a cell would mend that.
_PREFILL_BQ = 1024
_PREFILL_BK = 1024


@functools.partial(jax.jit, static_argnames=("pre_t", "scale", "name", "bq",
                                             "bk", "window", "interpret"))
def _prefill_flash(q, k, v, prefix_len, lengths=None, sink=None, *,
                   pre_t: int, scale: float, name: str, bq: int, bk: int,
                   interpret: bool, window: int = 0):
    n, h, s, dq = q.shape
    if lengths is None:     # every row is real
        lengths = jnp.full((n,), s, jnp.int32)
    hkv, t, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv    # query heads that read one K/V head: the index
    #                     maps hand a grid cell its head's K and V, so a
    #                     grouped-query model's K and V are never repeated
    bq, bk = min(bq, s), min(bk, t)
    s_pad, t_pad = -(-s // bq) * bq, -(-t // bk) * bk
    if s_pad != s:      # padded query rows: garbage the caller slices off
        q = jnp.pad(q, [(0, 0), (0, 0), (0, s_pad - s), (0, 0)])
    if t_pad != t:      # padded keys sit past every row's diagonal
        k = jnp.pad(k, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, 0), (0, t_pad - t), (0, 0)])
    nk = t_pad // bk
    if window:
        # key blocks the widest query block's bq + window - 1 indices
        # straddle
        nk = max((pre_t + i * bq + bq - 1) // bk
                 - max(pre_t + i * bq - window + 1, 0) // bk + 1
                 for i in range(s_pad // bq))
    # The query blocks of each grid row (a request's head) that hold a
    # token, reckoned here once: the index maps run a grid step each, on
    # the scalar core, and a division there is time the step waits for.
    real = jnp.repeat(-(-lengths // bq), h).astype(jnp.int32)
    q_map, kv_map = _prefill_index_maps(h=h, group=group, bq=bq, bk=bk,
                                        nk=nk, pre_t=pre_t, window=window)
    kernel = functools.partial(_prefill_kernel, scale=scale, bq=bq, bk=bk,
                               nk=nk, pre_t=pre_t, heads=h, window=window)
    operands = (q.reshape(n * h, s_pad, dq), k.reshape(n * hkv, t_pad, dq),
                v.reshape(n * hkv, t_pad, dv))
    in_specs = [pl.BlockSpec((1, bq, dq), q_map),
                pl.BlockSpec((1, bk, dq), kv_map),
                pl.BlockSpec((1, bk, dv), kv_map)]
    if sink is not None:    # [h] float32 -> a tile a grid row
        kernel = functools.partial(_prefill_sink_kernel, **kernel.keywords)
        operands += (jnp.broadcast_to(jnp.tile(sink.astype(
            jnp.float32), n)[:, None, None], (n * h, 8, 128)),)
        in_specs.append(pl.BlockSpec((1, 8, 128),
                                     lambda b, i, j, *_: (b, 0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n * h, s_pad // bq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, dv),
                                   lambda b, i, j, *_: (b, i, 0)),
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
        name=name,
    )(prefix_len, real, *operands)
    return out.reshape(n, h, s_pad, dv)[:, :, :s]


def window_block(window: int) -> int:
    """Query and key rows a grid cell of a windowed call: the window itself
    (a query block then walks its own key block and the one before it,
    2 x window keys a row where it needs window), within [128, 1024]."""
    return max(128, min(_PREFILL_BQ, 1 << (max(window, 1) - 1).bit_length()))


def prefill_blocks(lengths, s: int, window: int = 0) -> tuple[int, int]:
    """The query blocks a head of one `prefill_attention` call of
    [len(lengths), s] rows has in its grid, and those of them that hold a
    token, which are the ones run; `lengths` on the host (numpy)."""
    bq = min(window_block(window) if window else _PREFILL_BQ, s)
    return len(lengths) * -(-s // bq), int((-(-lengths // bq)).sum())


def prefill_attention(q, k, v, prefix_len, *, pre_t: int, scale: float,
                      name: str, impl: str = "auto", window: int = 0,
                      lengths=None, sink=None):
    """The serving prefill programs' attention, forward only, no score
    tensor in HBM. q [n, h, S, dq]; k [n, hkv, pre_t + S, dq], v [n, hkv,
    pre_t + S, dv], h a multiple of hkv: the first pre_t keys are a cached
    prefix of which request i has prefix_len[i] (the rest is padding), the
    last S the chunk itself, causal. Query row r sits at position
    prefix_len + r. -> [n, h, S, dv]. bf16 goes into the MXU as it is,
    scores and accumulators are float32.

    `window` > 0: a query sees only the keys at the last `window`
    positions up to its own, and the cached prefix is RIGHT-aligned:
    request i's min(prefix_len[i], pre_t) positions just before the chunk
    lie at the END of the first pre_t keys (padding before them), so that
    a key's index is its position up to a constant. Key blocks wholly
    before a query block's window are not visited.

    `lengths` [n]: the real rows of each right-padded request (None: every
    row is real). A whole query block (`_PREFILL_BQ` rows, `window_block`
    with a window) past a request's last real row is not run: the kernel
    computes nothing and fetches nothing there and its rows come out as
    zeros; a padded row of a block that holds a token comes out as
    whatever it attends to, as every padded row does without `lengths` and
    in the reference.

    `sink` [h] float32: a learned logit a query head that joins the
    denominator of every row of that head and gives no value (p_ij =
    exp(s_ij - m_i) / (exp(sink - m_i) + sum_j exp(s_ij - m_i)), m_i the
    larger of the sink and the row's largest score).

    `name` is the kernel's in a device trace. impl: "auto" (the kernel on
    the TPU, the jnp reference elsewhere), "pallas", "interpret" (the
    kernel's interpreter), "reference"."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "reference"
    if impl == "reference":
        return prefill_attention_reference(q, k, v, prefix_len, pre_t=pre_t,
                                           scale=scale, window=window,
                                           sink=sink)
    bq, bk = ((window_block(window),) * 2 if window
              else (_PREFILL_BQ, _PREFILL_BK))
    return _prefill_flash(q, k, v, prefix_len, lengths, sink, pre_t=pre_t,
                          scale=scale, name=name, bq=bq, bk=bk, window=window,
                          interpret=(impl == "interpret"))


def prefill_attention_reference(q, k, v, prefix_len, *, pre_t: int,
                                scale: float, window: int = 0, sink=None):
    """The same function with the whole score tensor: the tests' oracle,
    and what the prefill programs run where no chip is."""
    n, h, s, _ = q.shape
    hkv, t = k.shape[1], k.shape[2]
    qg = q.astype(jnp.float32).reshape(n, hkv, h // hkv, s, -1)
    sc = jnp.einsum("ngrqd,ngkd->ngrqk", qg, k.astype(jnp.float32)) * scale
    cols, rows = jnp.arange(t)[None, None, :], jnp.arange(s)[None, :, None]
    if window:
        ok = ((cols <= rows + pre_t) & (cols > rows + pre_t - window)
              & (cols >= pre_t - prefix_len[:, None, None]))
    else:
        ok = jnp.where(cols < pre_t, cols < prefix_len[:, None, None],
                       cols - pre_t <= rows)                 # [n, s, t]
    sc = jnp.where(ok[:, None, None], sc, -jnp.inf)
    if sink is None:
        p = jax.nn.softmax(sc, axis=-1)
    else:       # one more column a row, which gives no value
        col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, hkv, h // hkv, 1, 1), sc.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([sc, col], -1), axis=-1)[..., :-1]
    out = jnp.einsum("ngrqk,ngkd->ngrqd", p, v.astype(jnp.float32))
    return out.reshape(n, h, s, -1).astype(q.dtype)
