"""Kernel tests: pallas flash attention (interpret mode = same code path as
TPU), layer ops vs hand math."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import apply_rope, flash_attention, rmsnorm, rope, swiglu
from ray_tpu.ops.attention import _reference


def _qkv(key, b=1, s=128, h=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return [jax.random.normal(k, (b, s, h, d), dtype) for k in ks]


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_interpret_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = flash_attention(q, k, v, causal=causal, impl="reference")
    got = flash_attention(q, k, v, causal=causal, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_multiblock():
    # sequence longer than one block in interpret mode with small blocks
    from ray_tpu.ops import attention as A
    q, k, v = _qkv(jax.random.PRNGKey(1), s=64, d=32)
    ref = flash_attention(q, k, v, causal=True, impl="reference")
    got, lse = A._flash_fwd(
        q.transpose(0, 2, 1, 3).reshape(2, 64, 32),
        k.transpose(0, 2, 1, 3).reshape(2, 64, 32),
        v.transpose(0, 2, 1, 3).reshape(2, 64, 32),
        scale=32 ** -0.5, causal=True, bq=16, bk=16, interpret=True)
    got = got.reshape(1, 2, 64, 32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert lse.shape == (2, 64, 128)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backward_kernels(causal):
    """The pallas dq/dkv kernels (interpret mode) vs the jnp recompute VJP."""
    q, k, v = _qkv(jax.random.PRNGKey(7), s=256, h=2, d=64)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def loss(impl, q, k, v):
        out = flash_attention(q, k, v, causal=causal, impl=impl)
        return jnp.sum(out.astype(jnp.float32) * g)

    gi = jax.grad(lambda *a: loss("interpret", *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss("reference", *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gi, gr):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2 * max(scale, 1.0), rtol=2e-2)


def test_flash_attention_gqa():
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 32, 8, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    out = flash_attention(q, k, v, impl="reference")
    assert out.shape == q.shape


@pytest.mark.parametrize("axes", [dict(fsdp=4), dict(fsdp=2, tp=2),
                                  dict(fsdp=1, tp=4)])
def test_flash_attention_per_shard_on_a_mesh(axes):
    """Inside a jit over several devices the kernel runs under shard_map
    (GSPMD cannot partition a Mosaic call): batch over the data axes,
    heads over tp — also when tp exceeds the KV heads (GQA broadcast
    first). Forward and gradients match the unsharded reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (4, 128, 8, 16))
    k = jax.random.normal(ks[1], (4, 128, 2, 16))
    v = jax.random.normal(ks[2], (4, 128, 2, 16))

    def loss(impl, mesh, q, k, v):
        out = flash_attention(q, k, v, impl=impl, mesh=mesh)
        return jnp.sum(out ** 2), out

    sh = NamedSharding(mesh, P(("dp", "fsdp")))
    args = [jax.device_put(x, sh) for x in (q, k, v)]
    (_, got), g_got = jax.jit(jax.value_and_grad(
        lambda *a: loss("interpret", mesh, *a), argnums=(0, 1, 2),
        has_aux=True))(*args)
    (_, ref), g_ref = jax.value_and_grad(
        lambda *a: loss("reference", None, *a), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


# (heads, kv heads, q.k width, p.v width, n, S, pre_t, prefix_len[,
# lengths]). The test's blocks are 32 rows, so S = 80 is two and a half of
# them, a prefix of 64 is two pages of 32, and one of 48 ends inside a
# block. `lengths`: the real rows of each right-padded request; a query
# block past them is not run and comes out as zeros.
_PREFILL_CASES = {
    "group1_no_prefix": (3, 3, 16, 16, 2, 64, 0, [0, 0]),
    "group4_no_prefix": (8, 2, 16, 16, 2, 64, 0, [0, 0]),
    "group7_no_prefix": (7, 1, 16, 16, 1, 64, 0, [0]),
    "group4_ragged_prefix": (8, 2, 16, 16, 3, 64, 64, [0, 32, 64]),
    "group7_ragged_prefix": (7, 1, 16, 16, 3, 32, 64, [64, 0, 32]),
    "group1_chunk_off_block": (2, 2, 16, 16, 2, 80, 0, [0, 0]),
    "group4_chunk_off_block_prefix": (4, 1, 16, 16, 2, 80, 64, [32, 64]),
    "group4_prefix_off_block": (4, 1, 16, 16, 2, 64, 48, [48, 20]),
    "latent_widths": (3, 3, 24, 16, 2, 48, 32, [32, 16]),
    "latent_widths_grouped": (4, 2, 24, 16, 2, 64, 64, [64, 0]),
    "one_row_blocks_shrink": (4, 2, 16, 16, 1, 8, 0, [0]),
    "ragged_lengths": (4, 2, 16, 16, 4, 128, 0, [0] * 4, [128, 40, 70, 1]),
    "ragged_lengths_ragged_prefix": (8, 2, 16, 16, 3, 96, 64, [0, 32, 64],
                                     [20, 96, 50]),
    "lengths_at_a_blocks_edge": (3, 3, 16, 16, 3, 96, 0, [0] * 3,
                                 [31, 32, 33]),
    "lengths_at_a_blocks_edge_prefix": (4, 1, 16, 16, 3, 96, 96,
                                        [96, 10, 40], [33, 32, 31]),
    "a_request_of_no_rows": (4, 2, 16, 16, 4, 64, 0, [0] * 4,
                             [0, 64, 0, 5]),
    "a_request_of_no_rows_prefix": (7, 1, 16, 16, 3, 64, 64, [64, 0, 32],
                                    [33, 0, 0]),
    "lengths_chunk_and_prefix_off_block": (4, 1, 24, 16, 2, 80, 48, [48, 20],
                                           [10, 65]),
    "poison_past_lengths": (8, 2, 16, 16, 4, 128, 64, [64, 0, 32, 7],
                            [100, 31, 0, 64]),
}


def poison_past_lengths(q, k, v, pre_t, lengths, ends):
    """q, k, v [n, heads, rows, width] of a prefill call with what may lie
    in a row nothing wrote: NaN in q and K from each request's last real
    row (`lengths`) on, in V from the end of its last block that holds a
    token (`ends`): a masked key's p is 0 and 0 x NaN is NaN, and the rows
    of a block that holds a token are the layer's own, finite."""
    row = jnp.arange(q.shape[2])[None, None, :, None]
    past, skipped = (row >= jnp.asarray(at)[:, None, None, None]
                     for at in (lengths, ends))
    chunk = (slice(None), slice(None), slice(pre_t, None))
    return (jnp.where(past, jnp.nan, q),
            k.at[chunk].set(jnp.where(past, jnp.nan, k[chunk])),
            v.at[chunk].set(jnp.where(skipped, jnp.nan, v[chunk])))


@pytest.mark.parametrize("case", list(_PREFILL_CASES))
def test_prefill_attention_kernel_matches_reference(case):
    """The serving prefill kernel (interpret mode) against the jnp
    reference of the same signature: K and V read by head // group, a
    ragged cached prefix in front of the causal chunk, blocks that do not
    divide S or the prefix, q.k wider than p.v; with `lengths`, real rows
    as the reference's and the rows of a block that holds none exactly 0."""
    from ray_tpu.ops import attention as att
    h, hkv, dq, dv, n, s, pre_t, plen, *lengths = _PREFILL_CASES[case]
    blk = 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (n, h, s, dq))
    k = jax.random.normal(ks[1], (n, hkv, pre_t + s, dq))
    v = jax.random.normal(ks[2], (n, hkv, pre_t + s, dv))
    plen = jnp.array(plen, jnp.int32)
    kw = dict(pre_t=pre_t, scale=0.2)
    flash = functools.partial(att._prefill_flash, name="gqa_prefill_attention",
                              bq=blk, bk=blk, interpret=True, **kw)
    got = flash(q, k, v, plen)
    want = att.prefill_attention_reference(q, k, v, plen, **kw)
    assert got.shape == (n, h, s, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a query head reads ITS group's K and V: repeating them by hand and
    # calling with one head a group is the same function
    if h != hkv:
        rep = att.prefill_attention_reference(
            q, jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1), plen,
            **kw)
        np.testing.assert_allclose(want, rep, atol=2e-5)
    if not lengths:
        return
    lengths, = lengths
    # `lengths` left out says every row is real
    np.testing.assert_array_equal(
        flash(q, k, v, plen, jnp.full((n,), s, jnp.int32)), got)
    # the end of each request's last block that holds a token
    ends = [-(-m // blk) * blk for m in lengths]
    if case.startswith("poison"):
        q, k, v = poison_past_lengths(q, k, v, pre_t, lengths, ends)
    ragged = flash(q, k, v, plen, jnp.asarray(lengths, jnp.int32))
    for i, (m, end) in enumerate(zip(lengths, ends)):
        # the cells a real block runs are the ones it ran without `lengths`
        np.testing.assert_array_equal(ragged[i, :, :m], got[i, :, :m])
        assert np.isfinite(ragged[i, :, :m]).all()
        assert not np.asarray(ragged[i, :, end:]).any()
    assert any(end < s for end in ends)


@pytest.mark.parametrize("h,group,s,pre_t,window,plens,lengths", [
    (2, 1, 128, 0, 0, [0, 0, 0], [128, 40, 0]),         # causal alone
    (4, 2, 128, 96, 0, [96, 20, 0], [128, 33, 64]),     # ragged prefixes
    (2, 2, 96, 80, 0, [80, 0], [96, 10]),       # a prefix off a block
    (3, 3, 256, 0, 40, [0, 0], [256, 70]),      # a window under a block
    (6, 3, 192, 64, 64, [64, 64, 64], [192, 64, 0]),    # held window pages
])
def test_prefill_index_maps_copy_no_block_that_counts_nothing(
        h, group, s, pre_t, window, plens, lengths):
    """What the interpreter cannot see: walking `_prefill_flash`'s grid in
    its order, K's index map names the cell's own block wherever the kernel
    counts something, and changes (a change is a copy) no oftener than
    there are such cells, but once for a request of no token; q's changes
    once a query block that holds a token. Blocks of 32 rows."""
    from ray_tpu.ops import attention as att
    bq = bk = 32
    n, rows = len(plens), s // bq
    nk = -(-(pre_t + s) // bk)
    if window:
        nk = max((pre_t + i * bq + bq - 1) // bk
                 - max(pre_t + i * bq - window + 1, 0) // bk + 1
                 for i in range(rows))
    q_map, kv_map = att._prefill_index_maps(
        h=h, group=group, bq=bq, bk=bk, nk=nk, pre_t=pre_t, window=window)
    plen = np.asarray(plens, np.int32)
    real = np.repeat(-(-np.asarray(lengths, np.int32) // bq), h)
    q_seen, kv_seen, counted = [], [], 0
    for b in range(n * h):
        for i in range(rows):
            for j in range(nk):
                p, last_row = plen[b // h], pre_t + i * bq + bq - 1
                if window:
                    own = max(pre_t + i * bq - window + 1, 0) // bk + j
                    counts = (own * bk <= last_row
                              and own * bk + bk > pre_t - p)
                else:
                    own = j
                    counts = j * bk < min(p, pre_t) or (
                        j * bk + bk > pre_t and j * bk <= last_row)
                counts = counts and i < real[b]
                q_at = tuple(int(x) for x in q_map(b, i, j, plen, real))
                kv_at = tuple(int(x) for x in kv_map(b, i, j, plen, real))
                if counts:
                    counted += 1
                    assert q_at == (b, i, 0)
                    assert kv_at == (b // group, own, 0)
                q_seen.append(q_at)
                kv_seen.append(kv_at)

    def copies(seen):
        return 1 + sum(a != b for a, b in zip(seen, seen[1:]))

    empty = int((real == 0).sum())
    assert copies(kv_seen) <= counted + empty
    assert copies(q_seen) == int(np.maximum(real, 1).sum())
    assert counted < n * h * rows * nk


def test_prefill_attention_auto_is_the_reference_off_the_chip():
    from ray_tpu.ops import attention as att
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 4, 16, 8))
    k = jax.random.normal(ks[1], (1, 2, 16, 8))
    v = jax.random.normal(ks[2], (1, 2, 16, 8))
    plen = jnp.zeros((1,), jnp.int32)
    kw = dict(pre_t=0, scale=0.3)
    np.testing.assert_array_equal(
        att.prefill_attention(q, k, v, plen, name="x", **kw),
        att.prefill_attention_reference(q, k, v, plen, **kw))
    np.testing.assert_allclose(
        att.prefill_attention(q, k, v, plen, name="x", impl="interpret",
                              **kw),
        att.prefill_attention_reference(q, k, v, plen, **kw), atol=2e-5)


def test_flash_attention_grads():
    q, k, v = _qkv(jax.random.PRNGKey(3), s=32, d=16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, impl="reference") ** 2)

    g = jax.grad(loss)(q, k, v)
    assert all(jnp.all(jnp.isfinite(x)) for x in g)


def test_rmsnorm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    w = jnp.ones(16)
    out = rmsnorm(x, w)
    norms = np.sqrt((np.asarray(out) ** 2).mean(-1))
    np.testing.assert_allclose(norms, 1.0, rtol=1e-3)


def test_rope_rotation_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    sin, cos = rope(jnp.arange(8), 16)
    out = apply_rope(x, sin, cos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_rope_relative_property():
    # dot(rope(q,m), rope(k,n)) depends only on m-n: shift both by 3.
    d = 8
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, d))
    def dot_at(m, n):
        sin_m, cos_m = rope(jnp.array([m]), d)
        sin_n, cos_n = rope(jnp.array([n]), d)
        qm = apply_rope(q, sin_m, cos_m)
        kn = apply_rope(k, sin_n, cos_n)
        return float(jnp.sum(qm * kn))
    np.testing.assert_allclose(dot_at(5, 2), dot_at(8, 5), rtol=1e-5)


def test_swiglu_shapes():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 8))
    wg = jax.random.normal(jax.random.PRNGKey(5), (8, 16))
    wu = jax.random.normal(jax.random.PRNGKey(6), (8, 16))
    wd = jax.random.normal(jax.random.PRNGKey(7), (16, 8))
    out = swiglu(x, wg, wu, wd)
    assert out.shape == x.shape and out.dtype == x.dtype
