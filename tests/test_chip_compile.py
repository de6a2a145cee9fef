"""Described-chip compiles: the Pallas kernels of the train and serve main
paths, compiled by the TPU's own compiler for a v5e that is described, not
attached (`on-chip-measurement` guide, section 2, rehearsal 3).

Interpret mode (every other kernel test here) cannot see a slice that is
not aligned to Mosaic's tiling, a kernel that asks for more VMEM than it
may use, or a kernel that GSPMD cannot partition; this compile refuses all
three at no chip time. Shapes are the `qwen2_7b` head geometry the chip
smoke runs (28 query heads, 4 KV heads, head_dim 128). Nothing executes:
a pass here says nothing about results or times.
"""

import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

H, HKV, HD = 28, 4, 128
SLOTS, PAGE, N_PAGES, P_SEQ = 32, 128, 1025, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # A described-chip executable is written to the persistent cache but
    # cannot be read back without a chip (the next compile warns and
    # recompiles): switch the suite's cache off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(seq_shape, grad):
    from ray_tpu.ops.attention import flash_attention

    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])
        b, s = seq_shape
        q = jax.ShapeDtypeStruct((b, s, H, HD), jnp.bfloat16, sharding=one)
        kv = jax.ShapeDtypeStruct((b, s, HKV, HD), jnp.bfloat16,
                                  sharding=one)

        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=True, impl="pallas")

        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32))

        fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
        return fn, (q, kv, kv)
    return build


def _flash_sharded(topo):
    """The kernel inside a jit over four devices: GSPMD cannot partition
    a Mosaic call, so flash_attention must put it under shard_map."""
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=topo.devices)
    sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    q = jax.ShapeDtypeStruct((4, 2048, H, HD), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 2048, HKV, HD), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, impl="pallas", mesh=mesh)
        return jnp.sum(out.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv)


def _paged(kind):
    from ray_tpu.ops import paged_attention as pa

    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        lengths = sds((SLOTS,), jnp.int32)
        tables = sds((SLOTS, P_SEQ), jnp.int32)
        pool = sds((2, HKV, N_PAGES, HD, PAGE), jnp.bfloat16)
        if kind == "decode":
            q = sds((SLOTS, H, HD), jnp.bfloat16)
            return (lambda *a: pa.paged_decode_attention(
                *a, layer=1, interpret=False)), (
                q, pool, pool, lengths, tables)
        # decode_insert: decode_paged's call of every layer
        q, new = sds((SLOTS, H, HD), jnp.bfloat16), sds(
            (SLOTS, HKV, HD), jnp.bfloat16)
        return (lambda q, pk, pv, kn, vn, ln, tb:
                pa.paged_decode_insert_attention(
                    q, pk, pv, kn, vn, ln, tb, layer=1,
                    name="_paged_decode_insert", interpret=False)), (
            q, pool, pool, new, new, lengths, tables)
    return build


def _latent(kind, pool_dtype=jnp.bfloat16, pages_per_seq=68):
    """DeepSeek-V2's widths: 128 heads over a 512 + 64 latent, a 192-wide
    q.k and a 128-wide p.v; the longdoc cell's pool and chunk. The decode
    kernel's block of pages follows its shapes (`_block_pages`): 68 pages
    a slot are eight blocks of 8 and a ragged ninth; a float32 pool halves
    the block under the same bytes; a table of 4 pages holds a block of 4."""
    from ray_tpu.ops import latent_attention as la

    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        if kind == "decode":
            return (lambda q, pool, ln, tb: la.paged_latent_decode_attention(
                q, pool, ln, tb, layer=3, rank=512, scale=0.1,
                interpret=False)), (
                sds((8, 128, 576), pool_dtype),
                sds((8, 704, 576, 128), pool_dtype),
                sds((8,), jnp.int32), sds((8, pages_per_seq), jnp.int32))
        return (lambda q, k, v, pl: la.mla_prefill_attention(
            q, k, v, pl, pre_t=4096, scale=0.1, interpret=False)), (
            sds((1, 128, 4096, 192)), sds((1, 128, 8192, 192)),
            sds((1, 128, 8192, 128)), sds((1,), jnp.int32))
    return build


def _gqa_prefill(h, hkv, n, s, pre_t):
    """The per-head prefill programs' attention at a configuration's head
    geometry: `prefill_batch`'s call (pre_t 0) and
    `prefill_with_prefix_batch`'s over a cached prefix."""
    from ray_tpu.ops.attention import prefill_attention

    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        return (lambda q, k, v, pl: prefill_attention(
            q, k, v, pl, pre_t=pre_t, scale=HD ** -0.5,
            name="gqa_prefill_attention", impl="pallas")), (
            sds((n, h, s, HD)), sds((n, hkv, pre_t + s, HD)),
            sds((n, hkv, pre_t + s, HD)), sds((n,), jnp.int32))
    return build


def _ssm_update(topo):
    """The decode state update at nemotron3_nano_30b's widths and the
    reasoning cell's pool: 64 heads of [64, 128] float32 a row, 64 slots
    of 81 rows, 8 groups."""
    from ray_tpu.ops import ssm
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    return (lambda pool, x, dt, a, b, c, act: ssm.ssm_state_update(
        pool, x, dt, a, b, c, act, layer=3, impl="pallas")), (
        sds((12, 81, 64, 64, 128)), sds((64, 64, 64), jnp.bfloat16),
        sds((64, 64)), sds((64,)), sds((64, 8, 128), jnp.bfloat16),
        sds((64, 8, 128), jnp.bfloat16), sds((64,), jnp.bool_))


def _selective(kind):
    """Mamba-1's two kernels at jamba2_3b's widths (5120 channels over a
    state of 16) and the long-context cell's shapes: a 2 x 8192-row scan
    with bf16 x, B, C; the update over 16 slots of the 33-row pool."""
    def build(topo):
        from ray_tpu.ops import ssm
        one = SingleDeviceSharding(topo.devices[0])
        bf = jnp.bfloat16

        def sds(shape, dt=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        if kind == "scan":
            n, s = 2, 8192
            return (lambda x, dt, a, b, c, st: ssm.selective_scan(
                x, dt, a, b, c, st, impl="pallas")), (
                sds((n, s, 5120), bf), sds((n, s, 5120)), sds((16, 5120)),
                sds((n, s, 16), bf), sds((n, s, 16), bf),
                sds((n, 16, 5120)))
        return (lambda pool, x, dt, a, b, c, act: ssm.selective_state_update(
            pool, x, dt, a, b, c, act, layer=3, impl="pallas")), (
            sds((26, 33, 16, 5120)), sds((16, 5120), bf), sds((16, 5120)),
            sds((16, 5120)), sds((16, 16), bf), sds((16, 16), bf),
            sds((16,), jnp.bool_))
    return build


def _window(kind, h):
    """laguna_s_2_1's window layers (72 query heads on 8 K/V heads; 48 as
    its full layers have them) at the mixed-length cell's shapes: an
    8192-row chunk over the 4 held pages before it, a 2048-row bucket, and
    24 slots over the 5 pages a 512-token window spans."""
    def build(topo):
        from ray_tpu.ops import paged_attention as pa
        from ray_tpu.ops.attention import prefill_attention
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        if kind == "decode":
            ints = sds((24,), jnp.int32)
            return (lambda q, pk, pv, ln, tb, lo: pa.paged_decode_attention(
                q, pk, pv, ln, tb, layer=3, lows=lo, name="swa_paged_decode",
                interpret=False)), (
                sds((24, h, HD)), sds((9, 8, 145, HD, PAGE)),
                sds((9, 8, 145, HD, PAGE)), ints, sds((24, 5), jnp.int32),
                ints)
        n, s, pre_t = kind
        return (lambda q, k, v, pl: prefill_attention(
            q, k, v, pl, pre_t=pre_t, scale=HD ** -0.5, window=512,
            name=f"swa_prefill_n{n}_s{s}_t{pre_t}", impl="pallas")), (
            sds((n, h, s, HD)), sds((n, 8, pre_t + s, HD)),
            sds((n, 8, pre_t + s, HD)), sds((n,), jnp.int32))
    return build


def _mimo(kind):
    """mimo_v2_5's attention at the long-turn cell's shapes: 64 query heads
    in both kinds, keys 192 wide and values 128 (a K page [hkv, 192, 128],
    a V page [hkv, 128, 128], the output 128 wide); window layers on 8 K/V
    heads (8 queries a head) over the 2 pages a 128-token window spans,
    with the heads' sinks; full layers on 4 (16 queries a head) over a
    16512-token table. Prefill: an 8192-row chunk over the one held page
    before it, and two 4096-row prompts."""
    def build(topo):
        from ray_tpu.ops import paged_attention as pa
        from ray_tpu.ops.attention import prefill_attention
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        ints, sink = sds((32,), jnp.int32), sds((64,), jnp.float32)
        if kind == "sinkwin_decode":
            return (lambda q, pk, pv, ln, tb, lo, sk:
                    pa.paged_decode_attention(
                        q, pk, pv, ln, tb, layer=3, lows=lo, sink=sk,
                        name="sinkwin_paged_decode", interpret=False)), (
                sds((32, 64, 192)), sds((9, 8, 104, 192, PAGE)),
                sds((9, 8, 104, 128, PAGE)), ints, sds((32, 2), jnp.int32),
                ints, sink)
        if kind == "splitkv_decode":
            return (lambda q, pk, pv, ln, tb: pa.paged_decode_attention(
                q, pk, pv, ln, tb, layer=1, name="splitkv_paged_decode",
                interpret=False)), (
                sds((32, 64, 192)), sds((2, 4, 4400, 192, PAGE)),
                sds((2, 4, 4400, 128, PAGE)), ints,
                sds((32, 129), jnp.int32))
        n, s, pre_t = kind
        return (lambda q, k, v, pl, ln, sk: prefill_attention(
            q, k, v, pl, pre_t=pre_t, scale=192 ** -0.5, window=128,
            lengths=ln, sink=sk, name=f"sinkwin_prefill_n{n}_s{s}_t{pre_t}",
            impl="pallas")), (
            sds((n, 64, s, 192)), sds((n, 8, pre_t + s, 192)),
            sds((n, 8, pre_t + s, 128)), sds((n,), jnp.int32),
            sds((n,), jnp.int32), sink)
    return build


def _looped_decode(topo):
    """ouro_2_6b's paged decode call: 16 query heads on 16 K/V heads (a
    GROUP OF ONE: a q block of [16, 1, 128], one row a head in both
    products, a page DMA of [16, 128, 128]), 7 slots over the cell's 43
    pages of 192 cache layers, the token's K and V written by the kernel,
    the cache layer a traced scalar as inside the loop over passes."""
    from ray_tpu.ops import paged_attention as pa
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool, new = sds((192, 16, 43, HD, PAGE)), sds((7, 16, HD))
    return (lambda q, pk, pv, kn, vn, ln, tb, layer:
            pa.paged_decode_insert_attention(
                q, pk, pv, kn, vn, ln, tb, layer=layer,
                name="looped_paged_decode", interpret=False)), (
        new, pool, pool, new, new, sds((7,), jnp.int32),
        sds((7, 6), jnp.int32), sds((), jnp.int32))


CASES = {
    "flash_fwd_2x2048": _flash((2, 2048), grad=False),
    "flash_bwd_2x2048": _flash((2, 2048), grad=True),
    "flash_bwd_1x8192": _flash((1, 8192), grad=True),
    "flash_bwd_fsdp2_tp2": _flash_sharded,
    "paged_decode": _paged("decode"),
    "paged_decode_insert": _paged("decode_insert"),
    "latent_decode": _latent("decode"),
    "latent_decode_float32_pool": _latent("decode", pool_dtype=jnp.float32),
    "latent_decode_table_under_a_block": _latent("decode", pages_per_seq=4),
    "mla_prefill_over_prefix": _latent("prefill"),
    "gqa_prefill_qwen2_7b_1x1024": _gqa_prefill(28, 4, 1, 1024, 0),
    "gqa_prefill_qwen2_7b_8x1024_over_prefix": _gqa_prefill(28, 4, 8, 1024,
                                                            1024),
    "gqa_prefill_qwen2_7b_4x64": _gqa_prefill(28, 4, 4, 64, 0),
    "gqa_prefill_mixtral_8x7b_1x1024": _gqa_prefill(32, 8, 1, 1024, 0),
    "gqa_prefill_mixtral_8x7b_4x256_over_prefix": _gqa_prefill(32, 8, 4, 256,
                                                               1024),
    "ssm_state_update_nemotron3_nano_30b": _ssm_update,
    "selective_scan_jamba2_3b_2x8192": _selective("scan"),
    "selective_state_update_jamba2_3b": _selective("update"),
    "gqa_prefill_jamba2_3b_1x8192_over_prefix": _gqa_prefill(20, 1, 1, 8192,
                                                             8192),
    "swa_prefill_laguna_1x8192_over_held_window": _window((1, 8192, 512), 72),
    "swa_prefill_laguna_2x2048": _window((2, 2048, 0), 72),
    "swa_paged_decode_laguna_72_heads": _window("decode", 72),
    "swa_paged_decode_6_to_1": _window("decode", 48),
    "gqa_prefill_laguna_full_1x8192_over_prefix": _gqa_prefill(48, 8, 1, 8192,
                                                               8192),
    "sinkwin_prefill_mimo_1x8192_over_held_page": _mimo((1, 8192, 128)),
    "sinkwin_prefill_mimo_2x4096": _mimo((2, 4096, 0)),
    "sinkwin_paged_decode_mimo_8_to_1": _mimo("sinkwin_decode"),
    "splitkv_paged_decode_mimo_16_to_1": _mimo("splitkv_decode"),
    "looped_paged_decode_ouro_group_of_one": _looped_decode,
    "gqa_prefill_ouro_2_6b_1x512": _gqa_prefill(16, 16, 1, 512, 0),
    "gqa_prefill_ouro_2_6b_1x256_over_prefix": _gqa_prefill(16, 16, 1, 256,
                                                            512),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, args = CASES[case](topo)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, (
        f"{case}: compiled for the described chip without the Pallas "
        "kernel (an XLA fallback took its place)")


# The read-only paged decode kernel at the three hybrid cells' shapes
# (`models/nemotron_h._attention_decode`'s call): slots, query heads, K/V
# heads, the widest decode table, the cell's pages, and G, the pages a turn
# attends by `latent_attention._block_pages` over K and V of a page
# together (128, 64 and 512 KB).
HYBRID_DECODE = {
    "nemotron3_nano_30b": (64, 32, 2, 29, 2048, 8),
    "solar_open2_250b": (128, 64, 8, 73, 5120, 2),
    "jamba2_3b": (16, 20, 1, 131, 2400, 16),
}


@pytest.mark.parametrize("cell", list(HYBRID_DECODE))
def test_hybrid_paged_decode_compiles_at_the_rules_block(topo, monkeypatch,
                                                         cell):
    """Mosaic takes `_dma_kernel` at the G the rule gives these shapes: the
    two blocks of G pages, their float32 upcasts and the scores of a turn
    fit its scoped VMEM (a kernel that asks too much is refused here, which
    the interpreter cannot see)."""
    from ray_tpu.ops import paged_attention as pa
    slots, h, hkv, p_seq, n_pages, want = HYBRID_DECODE[cell]
    one = SingleDeviceSharding(topo.devices[0])
    dma, blocks = pa._paged_decode_dma, []

    def spy(*a, block, **kw):
        blocks.append(block)
        return dma(*a, block=block, **kw)

    monkeypatch.setattr(pa, "_paged_decode_dma", spy)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = sds((2, hkv, n_pages, HD, PAGE))
    text = jax.jit(lambda *a: pa.paged_decode_attention(
        *a, layer=1, interpret=False)).lower(
        sds((slots, h, HD)), pool, pool, sds((slots,), jnp.int32),
        sds((slots, p_seq), jnp.int32)).compile().as_text()
    assert blocks == [want]
    assert "tpu_custom_call" in text


def _qwen2_7b(layers, one):
    """(config, parameter shapes on device `one`) of `layers` layers at
    qwen2_7b's published widths."""
    from ray_tpu.models import ModelConfig, init_params
    c = ModelConfig(vocab=152064, d_model=3584, n_layers=layers,
                    n_heads=H, n_kv_heads=HKV, d_ff=18944, rope_theta=1e6,
                    tie_embeddings=False, dtype="bfloat16")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    return c, params


def _ouro_2_6b(layers, one):
    """(config, parameter shapes on device `one`) of `layers` layers at
    ouro_2_6b's published widths, run its four times."""
    from ray_tpu.models import configs, init_params
    c = configs.ouro_2_6b(n_layers=layers)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    return c, params


@pytest.mark.parametrize("stack", ["once", "looped"])
def test_decode_paged_leaves_its_pools_where_they_lie(topo, monkeypatch,
                                                      stack):
    """The whole `decode_paged` program at the qwen2_7b chat cell's
    geometry (2 of its layers, 16 slots, 257 pages), pools donated: the
    optimized HLO holds no `copy`, `scatter`, `slice` or
    `dynamic-update-slice` whose result is a pool or one layer of it, a
    layer's kernel call writes the token's K and V itself (both pools
    among its results, aliased to its operands), and the program's
    temporaries do not hold a pool. With a scatter over the page axis and
    the kernel called on `pool[li]` this compile held 2 + 2 whole-pool
    copies, 4 scatters and 4 per-layer slices, and 98.5 MiB of temporaries
    against a 64.25 MiB pool; with a column a slot written by
    `dynamic_update_slice` (PR 29 to PR 45) 64 updates of a pool.
    "looped": the same at ouro_2_6b's geometry (2 of its layers run four
    times: 8 cache layers of 16 K/V heads, 7 slots, the cell's 43 pages),
    where the passes are ONE loop that carries the pools and the cache
    layer reaches the kernel as a traced scalar: a dynamic slice of a
    cache layer would be a copy too."""
    from ray_tpu.llm.engine import decode_paged
    # the dispatcher asks the backend whether to interpret the kernel;
    # the process is on the CPU, the compile is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    layers = 2
    if stack == "once":
        slots, n_pages, hkv, p_seq = 16, 257, HKV, P_SEQ
        c, params = _qwen2_7b(layers, one)
    else:
        slots, n_pages, hkv, p_seq = 7, 43, 16, 6
        c, params = _ouro_2_6b(layers, one)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = sds((c.cache_layers, hkv, n_pages, HD, PAGE), jnp.bfloat16)
    compiled = jax.jit(partial(decode_paged, config=c),
                       donate_argnums=(1, 2)).lower(
        params, pool, pool, sds((slots,), jnp.int32),
        sds((slots,), jnp.int32), sds((slots,), jnp.bool_),
        sds((slots, p_seq), jnp.int32)).compile()
    text = compiled.as_text()
    assert (" while(" in text) == (stack == "looped")
    shape = r"bf16\[%d,%d,%d,%d,%d\]\S*" % (c.cache_layers, hkv, n_pages, HD,
                                           PAGE)
    writing = re.findall(
        r"= \(\S+ %s, %s\) custom-call\(.*"
        r'custom_call_target="tpu_custom_call".*'
        r"output_to_operand_aliasing=\{\{1\}: \(\d+, \{\}\), "
        r"\{2\}: \(\d+, \{\}\)\}" % (shape, shape), text)
    assert len(writing) == text.count("tpu_custom_call") == layers
    pool_sized = re.compile(
        r"= bf16\[(?:%d|1),%d,%d,%d,%d\]\S* "
        r"(copy|scatter|slice|dynamic-slice|dynamic-update-slice)[-(]"
        % (c.cache_layers, hkv, n_pages, HD, PAGE))
    assert pool_sized.findall(text) == []
    pool_bytes = c.cache_layers * hkv * n_pages * HD * PAGE * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


@pytest.mark.parametrize("prefix_pages", [1, 4])
def test_looped_prefix_prefill_copies_no_pool(topo, monkeypatch,
                                              prefix_pages):
    """`prefill_with_prefix_batch` of a looped stack (ouro_2_6b's widths, 2
    layers run four times, the cell's 43 pages) reads a pass's cache layers
    out of the whole pools by a traced index: no `copy` of a whole pool in
    its HLO. Over a table of ONE page XLA turned the page gather into a
    slice and re-laid-out both whole pools for it (2 x 4 GiB at 48 layers:
    `Used 21.84G of 15.75G hbm`, on the chip); the program pads such a
    table to two."""
    from ray_tpu.llm.engine import prefill_with_prefix_batch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c, params = _ouro_2_6b(2, one)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = sds((c.cache_layers, 16, 43, HD, PAGE), jnp.bfloat16)
    text = jax.jit(partial(prefill_with_prefix_batch, config=c)).lower(
        params, sds((1, 256)), sds((1,)), pool, pool,
        sds((1, prefix_pages)), sds((1,))).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r"= bf16\[%d,16,43,%d,%d\]\S* copy\("
                      % (c.cache_layers, HD, PAGE), text) == []


def test_hybrid_decode_leaves_its_pools_where_they_lie(topo, monkeypatch):
    """`models/nemotron_h.decode_paged` over both kinds of pool at
    nemotron3_nano_30b's widths (its first four blocks, "MEM*", this
    chip's 16 experts, 64 slots, 81 rows, the cell's 2048 pages), every
    pool donated:
    the state pool is touched by the named kernel alone, and no `copy`,
    `scatter` or `slice` yields it, a K/V pool or a layer of one. (The
    window pool is 6 MB here and the compiler parks so small a buffer in
    faster memory; at the cell's 12 layers it stays put, PERF.md section
    5.)"""
    from ray_tpu.models import configs, init_params, nemotron_h as nh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.nemotron3_nano_30b(
        n_layers=4, layer_pattern="MEM*", vocab=16384, moe_experts=16)
    slots, rows, n_pages = 64, 81, 2048

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = placed(jax.eval_shape(
        lambda: init_params(c, jax.random.PRNGKey(0))))
    pools = placed(nh.page_pools(c, n_pages, PAGE) + nh.row_pools(c, rows))

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    text = jax.jit(partial(nh.decode_paged, config=c),
                   donate_argnums=(1, 2, 3, 4)).lower(
        params, *pools, sds((slots,)), sds((slots,)),
        sds((slots,), jnp.bool_), sds((slots, P_SEQ)),
        sds((nh.N_STATS + 16,))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    state = r"f32\[(?:2,)?%d,64,64,128\]" % rows
    made_by = set(re.findall(r"= \(?%s\S* ([a-z\-]+)\(" % state, text))
    assert made_by <= {"parameter", "custom-call", "get-tuple-element"}
    moved = re.compile(
        r"= (?:%s|bf16\[(?:1,)?2,%d,128,128\])"
        r"\S* (copy|scatter|slice)[-(]" % (state, n_pages))
    assert moved.findall(text) == []


def test_jamba_decode_leaves_its_pools_where_they_lie(topo, monkeypatch):
    """`models/nemotron_h.decode_paged` at jamba2_3b's widths (its layers
    6 and 7, "S-*-": a Mamba-1 mixer, an MLP, the multi-query attention
    mixer, an MLP; 16 slots, 33 rows, the cell's 2400 pages and 256-page
    tables), every pool donated: the state pool is touched by the named
    kernel alone, no `copy`, `scatter` or `slice` yields it or a K/V pool,
    and the tied head reads the embedding where it lies (no transposed
    copy of the [65536, 2560] table)."""
    from ray_tpu.models import configs, init_params, nemotron_h as nh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.jamba2_3b(n_layers=4, layer_pattern="S-*-")
    slots, rows, n_pages = 16, 33, 2400

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = placed(jax.eval_shape(
        lambda: init_params(c, jax.random.PRNGKey(0))))
    pools = placed(nh.page_pools(c, n_pages, PAGE) + nh.row_pools(c, rows))

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    text = jax.jit(partial(nh.decode_paged, config=c),
                   donate_argnums=(1, 2, 3, 4)).lower(
        params, *pools, sds((slots,)), sds((slots,)),
        sds((slots,), jnp.bool_), sds((slots, 256)),
        sds(nh.stats_zero(c).shape)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "selective_state_update" in text
    state = r"f32\[(?:1,)?%d,16,5120\]" % rows
    made_by = set(re.findall(r"= \(?%s\S* ([a-z\-]+)\(" % state, text))
    assert made_by <= {"parameter", "custom-call", "get-tuple-element"}
    moved = re.compile(
        r"= (?:%s|bf16\[(?:1,)?1,%d,128,128\]|bf16\[(?:65536,2560|2560,65536)\])"
        r"\S* (copy|scatter|slice|transpose)[-(]" % (state, n_pages))
    assert moved.findall(text) == []


def test_mixtral_prefill_reads_its_experts_where_they_lie(topo, monkeypatch):
    """The per-head `prefill_batch` over ONE 1024-row bucket at Mixtral-
    8x7B's published widths (2 of its layers, the chat cell's), the expert
    layer in the block (`stats` handed in): it compiles, and its
    temporaries stay under the three [1, 1024, 8, 14336] arrays the form
    that computes every expert for every row carried. With a layer's
    slice of the stacked expert weights taken outside the loop over tiles
    the same compile held 5.3 GiB of temporaries: a copy of both layers'
    experts."""
    from ray_tpu.llm.engine import prefill_batch
    from ray_tpu.models import configs, experts, init_params
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.mixtral_8x7b(n_layers=2, remat=False)
    assert (c.d_model, c.d_ff, c.moe_experts, c.moe_top_k) == (
        4096, 14336, 8, 2)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    compiled = jax.jit(partial(prefill_batch, config=c)).lower(
        params, sds((1, 1024)), sds((1,)),
        sds((experts.N_STATS + c.moe_experts,))).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text   # the flash kernel
    assert "f32[1,1024,8,14336]" not in text
    assert "bf16[1,1024,8,14336]" not in text
    every_expert = 3 * 1024 * 8 * 14336 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < every_expert / 4


def test_mixtral_decode_walks_its_experts_where_they_lie(topo, monkeypatch):
    """The per-head `decode_paged` at the chat cell's geometry (16 slots,
    2 layers of Mixtral-8x7B's published widths): each expert layer has
    its branch (the hit experts walked, or all 8 in one batched product)
    and the walk's loop, and neither is handed a copy of the experts: the
    branches close over the stack of layers and slice inside. One matrix
    of one layer copied is 896 MiB of temporaries; a batched product over
    256 rows in such a branch had both layers' `wg` and `wu` copied for
    another layout (1855 MiB), which is why the walk stops at a small
    tile of rows."""
    from ray_tpu.llm.engine import decode_paged
    from ray_tpu.models import configs, experts, init_params
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.mixtral_8x7b(n_layers=2, remat=False)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    pool = sds((2, c.n_kv_heads, 257, c.head_dim, PAGE), jnp.bfloat16)
    compiled = jax.jit(partial(decode_paged, config=c),
                       donate_argnums=(1, 2)).lower(
        params, pool, pool, sds((16,)), sds((16,)), sds((16,), jnp.bool_),
        sds((16, P_SEQ)), sds((experts.N_STATS + c.moe_experts,))).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 2
    assert len(re.findall(r" while\(", text)) == 2
    assert re.findall(r"bf16\[(?:\d,)*8,(?:4096,14336|14336,4096)\]\S* copy\(",
                      text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", ["prefill_batch",
                                     "prefill_with_prefix_batch"])
def test_prefill_programs_hold_no_scores_and_no_prompt_logits(
        topo, monkeypatch, program):
    """One 1024-token admission at the qwen2_7b chat cell's geometry (2 of
    its layers): the program attends through the kernel (an XLA fallback
    fails here), and no buffer of its optimized HLO is a [.., 1024, keys]
    score array or the logits of every prompt position. With
    `_softmax_attention` and `_head` over [n, S, vocab] this compile held
    f32[1,28,1024,1024] scores and f32[1,1024,152064] logits (623 MB)."""
    from ray_tpu.llm import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, s, pre_pages, n_pages = 2, 1024, 8, 257
    one = SingleDeviceSharding(topo.devices[0])
    c, params = _qwen2_7b(layers, one)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    args = (params, sds((1, s)), sds((1,)))
    if program == "prefill_with_prefix_batch":
        pool = sds((layers, HKV, n_pages, HD, PAGE), jnp.bfloat16)
        args += (pool, pool, sds((1, pre_pages)), sds((1,)))
    text = jax.jit(partial(getattr(engine, program), config=c)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text and "gqa_prefill_attention" in text
    keys = (s, s + pre_pages * PAGE)

    def offends(dims):   # a score array, or logits of every position
        return ((len(dims) >= 3 and dims[-2] == s and dims[-1] in keys)
                or (s in dims and c.vocab in dims))

    arrays = set(re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert sorted(a for a in arrays
                  if offends([int(d) for d in a.split(",")])) == []


@pytest.mark.parametrize("program", ["prefill_batch",
                                     "prefill_with_prefix_batch"])
def test_walked_prefill_reads_its_weights_where_they_lie(topo, monkeypatch,
                                                         program):
    """The same admission, whose 1024 rows are four tiles: the program
    holds its layers twice, the straight pass and the walk over the tiles
    that hold a token (a scan each, and in the walk's two loops a layer),
    and neither is handed a copy of a layer's weights. With the weights
    sliced out of the stack at the layer's index, which does not change
    inside a walk's loop, the compiler moved the slices out of the loop:
    428 MiB of temporaries, a layer's feed-forward copied (one of its three
    matrices is 129.5 MiB)."""
    from ray_tpu.llm import engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, s, pre_pages, n_pages = 2, 1024, 8, 257
    assert engine._walked(s)
    one = SingleDeviceSharding(topo.devices[0])
    c, params = _qwen2_7b(layers, one)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    args = (params, sds((1, s)), sds((1,)))
    if program == "prefill_with_prefix_batch":
        pool = sds((layers, HKV, n_pages, HD, PAGE), jnp.bfloat16)
        args += (pool, pool, sds((1, pre_pages)), sds((1,)))
    compiled = jax.jit(partial(getattr(engine, program), config=c)).lower(
        *args).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 1
    assert len(re.findall(r" while\(", text)) == 4
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", ["prefill_batch",
                                     "prefill_with_prefix_batch"])
def test_laguna_prefill_programs_keep_the_parents_control_flow(
        topo, monkeypatch, program):
    """Two 2048-row prompts at the laguna_s_2_1 mixed-length cell's
    geometry (3 of its layers: full attention over the dense MLP, two
    window layers over its share of 32 experts): both attention kinds go
    through the kernel, which takes the chunk's `lengths` as an operand
    (PR 55) and skips the query blocks past a request's end inside its
    grid. The program around it is the parent's: no `while` and no
    `conditional` beyond those PR 53's text held (three loops an expert
    layer), lowered or compiled; a walk over tiles or a branch on the
    lengths (ROADMAP S13 (1)) would show here as it did in `setup_s`."""
    from ray_tpu.models import configs, init_params, windowed
    from ray_tpu.models.experts import stats_zero
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, s, pre_f, pre_w, n_pages, win_pages = 2, 2048, 16, 4, 2100, 145
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.laguna_s_2_1(n_layers=3, attn_pattern="FWW", vocab=12544,
                             moe_experts=32)

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    args = (on(jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))),
            sds((n, s)), sds((n,)))
    if program == "prefill_with_prefix_batch":
        args += (*on(windowed.page_pools(c, n_pages, PAGE)),
                 *on(windowed.window_pools(c, win_pages, PAGE)),
                 (sds((n, pre_f)), sds((n, pre_w))), sds((n,)))
    lowered = jax.jit(partial(getattr(windowed, program), config=c,
                              page=PAGE)).lower(
        *args, on(jax.eval_shape(lambda: stats_zero(c))))
    text = lowered.compile().as_text()
    pre_t = PAGE * pre_w if program == "prefill_with_prefix_batch" else 0
    assert text.count("tpu_custom_call") == 3
    assert "gqa_prefill_attention" in text
    assert f"swa_prefill_n{n}_s{s}_t{pre_t}" in text
    source = lowered.as_text()
    assert (source.count("stablehlo.while"), source.count("stablehlo.case"),
            source.count("stablehlo.if")) == (6, 0, 0)
    assert len(re.findall(r" while\(", text)) == 6
    assert len(re.findall(r" conditional\(", text)) == 0



# ---- Kimi Delta Attention (ops/kda.py), at solar_open2_250b's widths ----


def test_kda_chunk_scan_compiles_at_the_cells_widths(topo):
    """The prefill kernel at 64 heads of 128 x 128, a 2 x 1024-row call
    (the compile does not depend on the rows), bf16 in and a float32 state:
    its blocks ([64, 256] lanes of two heads, a [2, 128, 128] state, the
    level constants) meet Mosaic's tiling, the transposed-operand products
    and the tile transpose lower, and it fits its VMEM limit."""
    from ray_tpu.ops import kda
    one = SingleDeviceSharding(topo.devices[0])
    n, s, H, D = 2, 1024, 64, 128

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    act = sds((n, s, H, D), jnp.bfloat16)
    compiled = jax.jit(partial(kda.kda_chunk_scan, chunk=64,
                               impl="pallas")).lower(
        act, act, act, sds((n, s, H, D), jnp.float32),
        sds((n, s, H), jnp.float32),
        sds((n, H, D, D), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_chunk_scan_n2_s1024" in text


def test_kda_state_update_compiles_and_moves_no_pool(topo):
    """The decode kernel over the cell's pool (3 layers of 145 rows of
    [64, 128, 128] float32, 128 slots), the pool donated: a row's 4 MiB
    block in and out fits the kernel's VMEM limit, and the program holds
    no copy of the pool (aliased in and out on the call)."""
    from ray_tpu.ops import kda
    one = SingleDeviceSharding(topo.devices[0])
    B, H, D = 128, 64, 128

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    act = sds((B, H, D), jnp.bfloat16)
    compiled = jax.jit(
        partial(kda.kda_state_update, layer=1, impl="pallas"),
        donate_argnums=0).lower(
        sds((3, 145, H, D, D), jnp.float32), act, act, act,
        sds((B, H, D), jnp.float32), sds((B, H), jnp.float32),
        sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_state_update" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_solar_decode_program_compiles_with_its_pools_in_place(
        topo, monkeypatch):
    """`models/nemotron_h.decode_paged` at solar_open2_250b's published
    widths, one period (8 blocks, 40 experts held), 128 slots, pools
    donated: both kernels are in it (three state updates, one paged
    attention) and its temporaries hold neither a row pool nor an expert
    layer's weights."""
    from ray_tpu.models import configs, init_params, nemotron_h as nh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c = configs.solar_open2_250b(
        n_layers=8, layer_pattern=configs.solar_pattern(4, 4), vocab=24576,
        moe_experts=40)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    pk, pv = (sds(p.shape, p.dtype) for p in nh.page_pools(c, 257, PAGE))
    ssm, conv = (sds(p.shape, p.dtype) for p in nh.row_pools(c, 145))
    B = 128
    compiled = jax.jit(partial(nh.decode_paged, config=c),
                       donate_argnums=(1, 2, 3, 4)).lower(
        params, pk, pv, ssm, conv, sds((B,)), sds((B,)),
        sds((B,), jnp.bool_), sds((B, P_SEQ)),
        sds(nh.stats_zero(c).shape)).compile()
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) >= 4
    assert "kda_state_update" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


# ---- MiMo-V2.5 (models/windowed.py), at mimo_v2_5's widths ----


def _mimo_share(layers, one):
    """(config, parameter shapes, `on`, `sds`) of the first `layers` layers
    of mimo_v2_5's cell: published widths, 16 experts held; `on(tree)` and
    `sds(shape, dtype)` put shapes on device `one`."""
    from ray_tpu.models import configs, init_params
    c = configs.mimo_v2_5(
        n_layers=layers, attn_pattern=configs.mimo_v2_pattern(layers),
        vocab=19072, moe_experts=16)

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    return c, on(jax.eval_shape(
        lambda: init_params(c, jax.random.PRNGKey(0)))), on, sds


def test_mimo_decode_paged_leaves_its_four_pools_where_they_lie(
        topo, monkeypatch):
    """The whole `windowed.decode_paged` program at the mimo_v2_5 long-turn
    cell's geometry (3 of its layers: full attention over the dense MLP,
    two sink-window layers over its share of 16 experts; 32 slots, 3700
    full pages under the 129-page table, 80 window pages), the four pools
    donated: K pools 192 wide and V pools 128, 4 K/V heads in the full
    kind's and 8 in the window kind's. Each layer's call is the Pallas
    kernel under its own name; every pool comes back aliased to its
    operand; the optimized HLO holds no `copy`, `scatter` or slice whose
    result is a pool or one layer of it, and the temporaries hold no
    pool."""
    from ray_tpu.models import windowed
    from ray_tpu.models.experts import stats_zero
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c, params, on, sds = _mimo_share(3, one)
    slots, n_pages, win_pages = 32, 3700, 80
    pools = (*on(windowed.page_pools(c, n_pages, PAGE)),
             *on(windowed.window_pools(c, win_pages, PAGE)))
    assert [p.shape for p in pools] == [
        (1, 4, n_pages, 192, PAGE), (1, 4, n_pages, 128, PAGE),
        (2, 8, win_pages, 192, PAGE), (2, 8, win_pages, 128, PAGE)]
    compiled = jax.jit(partial(windowed.decode_paged, config=c),
                       donate_argnums=(1, 2, 3, 4)).lower(
        params, *pools, sds((slots,)), sds((slots,)),
        sds((slots,), jnp.bool_),
        (sds((slots, 129)), sds((slots, 2)), sds((slots,))),
        on(jax.eval_shape(lambda: stats_zero(c)))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "splitkv_paged_decode" in text and "sinkwin_paged_decode" in text
    assert "swa_paged_decode" not in text
    for p in pools:
        L, hkv, n, w, _ = p.shape
        pool_sized = re.compile(
            r"= bf16\[(?:%d|1),%d,%d,%d,%d\]\S* "
            r"(copy|scatter|slice|dynamic-slice)[-(]" % (L, hkv, n, w, PAGE))
        assert pool_sized.findall(text) == []
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(p.shape) * 2 for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("program", ["prefill_batch",
                                     "prefill_with_prefix_batch"])
def test_mimo_chunk_programs_hoist_no_layers_weights(topo, monkeypatch,
                                                     program):
    """One 8192-row chunk at the mimo_v2_5 cell's geometry (5 of its
    layers: F W W W W). `prefill_batch` takes no pool, so the compiler is
    blind to what the pools hold of the chip and its temporaries are what
    the schedule it likes best asks for: with the per-layer fence of
    `windowed._prefill` 1.04 GiB here (1.21 over a prefix), without it the
    re-laid-out copies of every layer's wq, wk and wv live at once from
    the program's start (2.17 GiB at these 5 layers, 4.37 against 1.36 at
    the cell's 11). Both kinds' kernels are in it under their names."""
    from ray_tpu.models import windowed
    from ray_tpu.models.experts import stats_zero
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    c, params, on, sds = _mimo_share(5, one)
    args = (params, sds((1, 8192)), sds((1,)))
    pre_t = 0
    if program == "prefill_with_prefix_batch":
        args += (*on(windowed.page_pools(c, 3700, PAGE)),
                 *on(windowed.window_pools(c, 80, PAGE)),
                 (sds((1, 64)), sds((1, 1))), sds((1,)))
        pre_t = PAGE
    compiled = jax.jit(partial(getattr(windowed, program), config=c,
                               page=PAGE)).lower(
        *args, on(jax.eval_shape(lambda: stats_zero(c)))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5
    assert "gqa_prefill_attention" in text
    assert f"sinkwin_prefill_n1_s8192_t{pre_t}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
