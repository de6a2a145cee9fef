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
        pages = sds((HKV, N_PAGES, HD, PAGE), jnp.bfloat16)
        pool = sds((2, HKV, N_PAGES, HD, PAGE), jnp.bfloat16)
        if kind == "decode":
            q = sds((SLOTS, H, HD), jnp.bfloat16)
            return (lambda *a: pa.paged_decode_attention(
                *a, layer=1, interpret=False)), (
                q, pool, pool, lengths, tables)
        S = 5  # spec_k=4 drafts + the token they follow
        q = sds((SLOTS, S, H, HD), jnp.bfloat16)
        if kind == "verify":
            return (lambda *a: pa.paged_verify_attention(
                *a, interpret=False)), (q, pages, pages, lengths, tables)
        new = sds((SLOTS, S, HKV, HD), jnp.bfloat16)
        return (lambda q, pk, pv, kn, vn, ln, tb:
                pa.paged_verify_insert_attention(
                    q, pk, pv, kn, vn, ln, tb, layer=1, interpret=False)
                ), (q, pool, pool, new, new, lengths, tables)
    return build


def _latent(kind):
    """DeepSeek-V2's widths: 128 heads over a 512 + 64 latent, a 192-wide
    q.k and a 128-wide p.v; the longdoc cell's pool and chunk."""
    from ray_tpu.ops import latent_attention as la

    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        if kind == "decode":
            return (lambda q, pool, ln, tb: la.paged_latent_decode_attention(
                q, pool, ln, tb, layer=3, rank=512, scale=0.1,
                interpret=False)), (
                sds((8, 128, 576)), sds((8, 704, 576, 128)),
                sds((8,), jnp.int32), sds((8, 68), jnp.int32))
        return (lambda q, k, v, pl: la.mla_prefill_attention(
            q, k, v, pl, pre_t=4096, scale=0.1, interpret=False)), (
            sds((1, 128, 4096, 192)), sds((1, 128, 8192, 192)),
            sds((1, 128, 8192, 128)), sds((1,), jnp.int32))
    return build


CASES = {
    "flash_fwd_2x2048": _flash((2, 2048), grad=False),
    "flash_bwd_2x2048": _flash((2, 2048), grad=True),
    "flash_bwd_1x8192": _flash((1, 8192), grad=True),
    "flash_bwd_fsdp2_tp2": _flash_sharded,
    "paged_decode": _paged("decode"),
    "paged_verify": _paged("verify"),
    "paged_verify_insert": _paged("verify_insert"),
    "latent_decode": _latent("decode"),
    "mla_prefill_over_prefix": _latent("prefill"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, args = CASES[case](topo)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, (
        f"{case}: compiled for the described chip without the Pallas "
        "kernel (an XLA fallback took its place)")


def test_decode_paged_leaves_its_pools_where_they_lie(topo, monkeypatch):
    """The whole `decode_paged` program at the qwen2_7b chat cell's
    geometry (2 of its layers, 16 slots, 257 pages), pools donated: the
    optimized HLO holds no `copy`, `scatter` or `slice` whose result is a
    pool or one layer of it, and the program's temporaries do not hold a
    pool. With a scatter over the page axis and the kernel called on
    `pool[li]` this compile held 2 + 2 whole-pool copies, 4 scatters and
    4 per-layer slices, and 98.5 MiB of temporaries against a 64.25 MiB
    pool."""
    from ray_tpu.llm.engine import decode_paged
    from ray_tpu.models import ModelConfig, init_params
    # the dispatcher asks the backend whether to interpret the kernel;
    # the process is on the CPU, the compile is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, slots, n_pages = 2, 16, 257
    c = ModelConfig(vocab=152064, d_model=3584, n_layers=layers, n_heads=H,
                    n_kv_heads=HKV, d_ff=18944, rope_theta=1e6,
                    tie_embeddings=False, dtype="bfloat16")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0))))
    pool = sds((layers, HKV, n_pages, HD, PAGE), jnp.bfloat16)
    compiled = jax.jit(partial(decode_paged, config=c),
                       donate_argnums=(1, 2)).lower(
        params, pool, pool, sds((slots,), jnp.int32),
        sds((slots,), jnp.int32), sds((slots,), jnp.bool_),
        sds((slots, P_SEQ), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= layers
    pool_sized = re.compile(
        r"= bf16\[(?:%d|1),%d,%d,%d,%d\]\S* (copy|scatter|slice)[-(]"
        % (layers, HKV, n_pages, HD, PAGE))
    assert pool_sized.findall(text) == []
    pool_bytes = layers * HKV * n_pages * HD * PAGE * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4
