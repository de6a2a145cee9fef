"""The per-head serving programs with experts on one device: the feed-
forward goes through models/experts.py's layer (llm/engine._expert_block),
told which rows are real, and must equal the same programs with
transformer._moe in the block (the form a mesh keeps), whichever branch
of the layer a shape takes."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.llm.engine import (decode_paged, insert_pages_batch,
                                prefill_batch, prefill_with_prefix_batch)
from ray_tpu.models import ModelConfig, experts, init_params

pytestmark = pytest.mark.heavy

# Mixtral's shape at a tiny size: every expert held, softmax scores, top-2
# renormalised, no shared expert
MOE = ModelConfig(vocab=200, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  d_ff=96, moe_experts=4, moe_top_k=2, dtype="float32")
E, K, F = MOE.moe_experts, MOE.moe_top_k, MOE.d_ff
PAGE = 16
# (rows a prompt, a pass's least rows): which branch of expert_layer a
# batch of two prompts takes
BRANCHES = {
    "dense": (64, None),        # 128 tokens: every expert over every token
    "dispatched": (128, None),  # 256 tokens, 512 pairs in tiles of 256
    "two_passes": (128, 64),    # the same, the pairs in passes of 256 rows
}


@pytest.fixture(scope="module")
def params():
    return init_params(MOE, jax.random.PRNGKey(7))


def _zero():
    return experts.stats_zero(MOE)


def _branch(monkeypatch, name):
    S, pass_rows = BRANCHES[name]
    if pass_rows:
        monkeypatch.setattr(experts, "_MIN_PASS_ROWS", pass_rows)
    return S


def _tokens(S, lengths, pad):
    """[2, S] prompts of `lengths` real tokens, the rest `pad`."""
    toks = jax.random.randint(jax.random.PRNGKey(13), (2, S), 1, MOE.vocab)
    return jnp.where(jnp.arange(S)[None] < lengths[:, None], toks, pad)


def _real(a, lengths):
    """The real rows of a [L, n, S, ...] cache, one array a request."""
    return [np.asarray(a[:, i, :int(n)]) for i, n in enumerate(lengths)]


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_prefill_batch_equals_the_gspmd_form(params, monkeypatch, branch):
    S = _branch(monkeypatch, branch)
    lengths = jnp.asarray([S - 9, S // 2 + 3], jnp.int32)
    want = jax.jit(partial(prefill_batch, config=MOE))(
        params, _tokens(S, lengths, 0), lengths)
    outs = [jax.jit(partial(prefill_batch, config=MOE))(
        params, _tokens(S, lengths, pad), lengths, _zero())
        for pad in (0, 77)]
    for got in outs:
        np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=0)
        for new, old in zip(got[1:3], want[1:]):
            for a, b in zip(_real(new, lengths), _real(old, lengths)):
                np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
        st = np.asarray(got[3])
        real = int(lengths.sum())
        assert st[0] == MOE.n_layers * real           # padding went nowhere
        assert st[1] == K * st[0] == st[experts.N_STATS:].sum()
        assert st[2] == 0 and st[3] == MOE.n_layers
    # a real row does not depend on what the padding holds
    for a, b in zip(outs[0][:3], outs[1][:3]):
        if a.ndim == 2:
            np.testing.assert_array_equal(a, b)
        else:
            for x, y in zip(_real(a, lengths), _real(b, lengths)):
                np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_prefix_prefill_equals_the_gspmd_form(params, monkeypatch, branch):
    """The suffix program over two cached pages a request."""
    S = _branch(monkeypatch, branch)
    n_pre = 2 * PAGE
    cached = jnp.asarray([n_pre, PAGE], jnp.int32)
    lengths = jnp.asarray([S - 5, S // 2 + 1], jnp.int32)
    pool = jnp.zeros((MOE.n_layers, MOE.n_kv_heads, 5, MOE.head_dim, PAGE),
                     jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    prefix = jax.random.randint(jax.random.PRNGKey(3), (2, n_pre), 1,
                                MOE.vocab)
    _, ks, vs = jax.jit(partial(prefill_batch, config=MOE))(
        params, prefix, cached)
    pool_k, pool_v = jax.jit(insert_pages_batch)(pool, pool, ks, vs, tables,
                                                 cached)
    args = (params, _tokens(S, lengths, 0), lengths, pool_k, pool_v, tables,
            cached)
    fn = jax.jit(partial(prefill_with_prefix_batch, config=MOE))
    want, got = fn(*args), fn(*args, _zero())
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=0)
    for new, old in zip(got[1:3], want[1:]):
        for a, b in zip(_real(new, lengths), _real(old, lengths)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    st = np.asarray(got[3])
    assert st[0] == MOE.n_layers * int(lengths.sum()) and st[1] == K * st[0]


@pytest.mark.parametrize("dense_rows", [4096, 0],
                         ids=["dense", "dispatched"])
def test_decode_paged_equals_the_gspmd_form(params, monkeypatch, dense_rows):
    """Three slots, one inactive: its row is the padding, and what it
    writes goes to the scratch page (page 0), garbage by contract."""
    monkeypatch.setattr(experts, "_DENSE_ROWS", dense_rows)
    pool = jax.random.normal(
        jax.random.PRNGKey(1),
        (MOE.n_layers, MOE.n_kv_heads, 7, MOE.head_dim, PAGE), jnp.float32)
    args = (params, pool, pool, jnp.asarray([11, 12, 13], jnp.int32),
            jnp.asarray([7, 20, 5], jnp.int32),
            jnp.asarray([True, False, True]),
            jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32))
    fn = jax.jit(partial(decode_paged, config=MOE))
    want, got = fn(*args), fn(*args, _zero())
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=0)
    for a, b in zip(got[1:3], want[1:]):
        np.testing.assert_allclose(a[:, :, 1:], b[:, :, 1:], atol=1e-5,
                                   rtol=0)
    st = np.asarray(got[3])
    assert st[0] == MOE.n_layers * 2 and st[1] == K * st[0]


def _lowered(program, stats, *args):
    return jax.jit(partial(program, config=MOE)).lower(
        *args, *stats).as_text()


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _program_args(params, name, n, S):
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    pool = jax.ShapeDtypeStruct(
        (MOE.n_layers, MOE.n_kv_heads, 9, MOE.head_dim, PAGE), jnp.float32)
    if name == "decode_paged":
        return (params, pool, pool, _i32(n), _i32(n),
                jax.ShapeDtypeStruct((n,), jnp.bool_), _i32(n, 2))
    prefix = (pool, pool, _i32(n, 2), _i32(n)) if "prefix" in name else ()
    return (params, _i32(n, S), _i32(n), *prefix)


# program, requests, rows a request, whether the expert layer may loop
_SHAPES = {
    "prefill_many_rows": ("prefill_batch", 2, 128, True),
    "prefix_prefill_many_rows": ("prefill_with_prefix_batch", 2, 128, True),
    "prefill_few_rows": ("prefill_batch", 1, 64, False),
    "decode": ("decode_paged", 4, 1, False),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_the_lowered_programs_route(params, shape):
    """A many-row prefill carries no [n, s, E, d_ff] intermediate (nor the
    few-token form's [E, n * s, d_ff]) and walks its tiles in loops; a
    few-row prefill and `decode_paged` gain no `while` over the GSPMD
    form's text (the kernel's own, off the chip, are in both)."""
    name, n, S, loops = _SHAPES[shape]
    program = {"prefill_batch": prefill_batch, "decode_paged": decode_paged,
               "prefill_with_prefix_batch": prefill_with_prefix_batch}[name]
    args = _program_args(params, name, n, S)
    old = _lowered(program, (), *args)
    new = _lowered(program, (jax.ShapeDtypeStruct((experts.N_STATS + E,),
                                                  jnp.int32),), *args)
    every = "tensor<%dx%dx%dx%dxf32>" % (n, S, E, F)
    few = "tensor<%dx%dx%dxf32>" % (E, n * S, F)
    assert every in old and every not in new
    whiles = [len(re.findall(r"stablehlo\.while", t)) for t in (old, new)]
    if loops:
        assert few not in new and whiles[1] > whiles[0]
    else:
        assert few in new and whiles[1] == whiles[0]


def test_moe_stats_count_the_real_tokens(params):
    """Through the engine: `routed_tokens` is the real (unpadded) prompt
    tokens plus the tokens decode fed, an expert layer; `held_pairs` twice
    that; a model without experts, and one under a mesh, count nothing."""
    eng = InferenceEngine(
        MOE, EngineConfig(max_slots=2, max_len=160, page_size=PAGE,
                          prompt_buckets=(16, 64, 128), eos_token=-1),
        params=params)
    prompts, new = [5, 37, 70], 4
    for i, n in enumerate(prompts):
        eng.add_request([1 + (i + j) % 150 for j in range(n)],
                        max_new_tokens=new)
    while eng.has_work():
        eng.step()
    st = eng.moe_stats()
    fed = sum(prompts) + len(prompts) * (new - 1)
    assert st["routed_tokens"] == MOE.n_layers * fed
    assert st["held_pairs"] == K * st["routed_tokens"]
    assert st["held_pairs"] == sum(st["held_expert_load"])
    assert st["tokens_without_held_expert"] == 0
    assert st["expert_layer_calls"] % MOE.n_layers == 0
    assert eng.moe_stats() == st
    # the windows count too (generate() decodes in windows)
    eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=new)
    st2 = eng.moe_stats()
    assert st2["routed_tokens"] >= st["routed_tokens"] + MOE.n_layers * (
        5 + new - 1)
    assert st2["held_pairs"] == K * st2["routed_tokens"]


def test_under_a_mesh_the_experts_keep_the_gspmd_form(params):
    """A mesh of two devices: the engine hands its programs no counters, so
    they keep transformer._moe (the form GSPMD shards), `moe_stats()` has
    nothing to say, and the tokens are the one-device engine's."""
    from ray_tpu.parallel import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(tp=2, fsdp=1, dp=1),
                     devices=jax.devices()[:2],
                     axis_names=("dp", "fsdp", "pp", "sp", "tp", "ep"))
    e = EngineConfig(max_slots=2, max_len=48, page_size=PAGE,
                     prompt_buckets=(16,), eos_token=-1)
    one = InferenceEngine(MOE, e, params=params)
    two = InferenceEngine(MOE, e, params=params, mesh=mesh)
    assert one.serving.stats_zero is not None
    assert two.serving.stats_zero is None and two.moe_stats() == {}
    prompts = [[7, 8, 9], [20, 21]]
    assert (two.generate(prompts, max_new_tokens=5, temperature=0.0)
            == one.generate(prompts, max_new_tokens=5, temperature=0.0))
