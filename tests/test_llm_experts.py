"""The per-head serving programs with experts on one device: the feed-
forward goes through models/experts.py's layer (llm/engine._expert_block),
told which rows are real, and must equal the same programs with
transformer._moe in the block (the form a mesh keeps), whichever branch
of the layer a shape takes."""

import dataclasses
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


@pytest.mark.parametrize("dense_rows, turn_bytes",
                         [(4096, None), (4096, 0), (0, None)],
                         ids=["dense", "walked", "dispatched"])
def test_decode_paged_equals_the_gspmd_form(params, monkeypatch, dense_rows,
                                            turn_bytes):
    """Three slots, one inactive: its row is the padding, and what it
    writes goes to the scratch page (page 0), garbage by contract.
    "walked": a turn costs nothing, so experts this small get the branch
    too and the two active rows' 2 to 4 hit experts are walked."""
    monkeypatch.setattr(experts, "_DENSE_ROWS", dense_rows)
    if turn_bytes is not None:
        monkeypatch.setattr(experts, "_TURN_BYTES", turn_bytes)
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
    few = MOE.n_layers if dense_rows else 0
    assert st[4] == few and K * few <= st[5] <= E * few
    if turn_bytes is None:
        assert st[5] == E * few           # experts this small: all read


# hit pattern -> (top-k, rows that are valid of 16, router column lifted,
# the fewest and the most experts that may be hit)
_HITS = {
    "no_row_valid": (2, 0, None, 0, 0),
    "one_expert_hit": (1, 16, 2, 1, 1),
    "some_hit": (2, 1, None, 1, E - 1),
    "every_expert_hit": (2, 16, None, E, E),
    "stacked_layer": (2, 3, None, 1, E - 1),
}


@pytest.mark.parametrize("pattern", sorted(_HITS))
def test_the_few_token_walk_equals_the_batched_product(monkeypatch, pattern):
    """One layer, 16 rows: where a turn costs nothing the layer walks the
    experts that were hit (all hit: the batched product, by the same
    rule) and must equal the batched product alone; the two counters say
    which experts' weights were read."""
    k, n_valid, lifted, fewest, most = _HITS[pattern]
    c = dataclasses.replace(MOE, moe_top_k=k, moe_grouped="tiles",
                            moe_d_ff=F)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    lp = experts.init_expert_weights(
        lambda shape, fan_in: jax.random.normal(next(keys), shape)
        * fan_in ** -0.5, c, None)
    if lifted is not None:      # every row's first choice
        lp["router"] = lp["router"].at[:, lifted].set(0.0) + 9.0 * (
            jnp.arange(E) == lifted)
    x = jnp.abs(jax.random.normal(next(keys), (16, c.d_model)))
    valid = jnp.arange(16) < n_valid
    layer = None
    if pattern == "stacked_layer":
        other = jax.tree_util.tree_map(lambda a: a[::-1] * 0.5, lp)
        lp = {**lp, **{n: jnp.stack([other[n], lp[n]])
                       for n in ("wg", "wu", "wd")}}
        layer = jnp.int32(1)
    monkeypatch.setattr(experts, "_TURN_BYTES", float("inf"))
    want, st_want = experts.expert_layer(x, lp, c, valid, layer)
    monkeypatch.setattr(experts, "_TURN_BYTES", 0)
    got, st = jax.jit(partial(experts.expert_layer, c=c))(
        x, lp, valid=valid, layer=layer)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    n_hit = int((np.asarray(st[experts.N_STATS:]) > 0).sum())
    assert fewest <= n_hit <= most
    assert st[4] == 1 and st[5] == n_hit      # all E where all were hit
    assert st_want[4] == 1 and st_want[5] == E
    np.testing.assert_array_equal(np.delete(st, 5), np.delete(st_want, 5))
    if not n_valid:
        np.testing.assert_array_equal(got, 0.0)


# held experts, an expert's weights in bf16: the decode shapes of the three
# cells whose programs reach the few-token form (PERF.md section 5)
_CELLS = {
    "mixtral_8x7b": (8, 3 * 4096 * 14336 * 2, True),
    "laguna_s_2_1": (32, 3 * 3072 * 1024 * 2, False),
    "nemotron3_nano_30b": (16, 2 * 2688 * 1856 * 2, False),
}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_which_shapes_get_the_walk(cell):
    """The rule reads an expert's bytes, not a model: a program gets the
    branch and the walk only where they pay with all but one expert hit.
    Mixtral's 352 MB experts do; at 19 and 20 MB a turn's overhead is a
    third of its read and the batched product stays alone."""
    held, nbytes, walks = _CELLS[cell]
    assert bool(experts._walk_pays(held - 1, held, nbytes)) is walks
    assert not experts._walk_pays(held, held, nbytes)    # all hit: batched


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


# program, requests, rows a request, whether the expert layer walks tiles,
# what a turn of the few-token walk costs (None: as shipped)
_SHAPES = {
    "prefill_many_rows": ("prefill_batch", 2, 128, True, None),
    "prefix_prefill_many_rows": ("prefill_with_prefix_batch", 2, 128, True,
                                 None),
    "prefill_few_rows": ("prefill_batch", 1, 64, False, None),
    "prefill_over_a_small_tile": ("prefill_batch", 1, 128, False, 0),
    "decode": ("decode_paged", 4, 1, False, 0),
    "decode_small_experts": ("decode_paged", 4, 1, False, None),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_the_lowered_programs_route(params, monkeypatch, shape):
    """A many-row prefill carries no [n, s, E, d_ff] intermediate (nor the
    few-token form's [E, n * s, d_ff]) and walks its tiles in loops. A
    few-row prefill and `decode_paged` keep the [E, n * s, d_ff] batched
    product in their text (every expert hit) and, where the walk can pay
    ("decode": a turn that costs nothing), gain exactly one branch and
    one loop an expert layer over the GSPMD form's text; experts as small
    as these under the turn's real cost, and more rows than a small tile,
    gain neither (the kernel's own `while`, off the chip, is in both)."""
    name, n, S, loops, turn_bytes = _SHAPES[shape]
    if turn_bytes is not None:
        monkeypatch.setattr(experts, "_TURN_BYTES", turn_bytes)
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
    branches = [len(re.findall(r"stablehlo\.(?:case|if)", t))
                for t in (old, new)]
    if loops:
        assert few not in new and whiles[1] > whiles[0]
        return
    walked = shape == "decode"
    assert few in new
    assert whiles[1] - whiles[0] == branches[1] - branches[0]
    assert whiles[1] - whiles[0] in ((1, MOE.n_layers) if walked else (0,))


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
    # every call here is of few rows, and experts this small are all read
    assert st["few_token_calls"] == st["expert_layer_calls"]
    assert st["experts_read_share"] == 1.0
    assert eng.moe_stats() == st
    # the windows count too (generate() decodes in windows)
    eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=new)
    st2 = eng.moe_stats()
    assert st2["routed_tokens"] >= st["routed_tokens"] + MOE.n_layers * (
        5 + new - 1)
    assert st2["held_pairs"] == K * st2["routed_tokens"]


def test_experts_read_share_by_hand(monkeypatch):
    """Where a turn costs nothing the walk runs: ONE request of a
    one-token prompt feeds every expert layer call one real row or none,
    a real row hits its K distinct experts, so the calls read K experts a
    routed token of the E they hold."""
    monkeypatch.setattr(experts, "_TURN_BYTES", 0)
    # a configuration of its own: the engine's programs are shared by
    # configuration and shape, traced once a process
    own = dataclasses.replace(MOE, vocab=MOE.vocab - 1)
    eng = InferenceEngine(
        own, EngineConfig(max_slots=2, max_len=64, page_size=PAGE,
                          prompt_buckets=(16,), eos_token=-1),
        params=init_params(own, jax.random.PRNGKey(7)))
    new = 5
    eng.add_request([9], max_new_tokens=new)
    while eng.has_work():
        eng.step()
    st = eng.moe_stats()
    assert st["routed_tokens"] == MOE.n_layers * new
    assert st["few_token_calls"] == st["expert_layer_calls"]
    assert st["few_token_calls"] >= st["routed_tokens"]
    assert st["experts_read_share"] == pytest.approx(
        K * st["routed_tokens"] / (st["few_token_calls"] * E), abs=1e-12)
    assert 0 < st["experts_read_share"] <= K / E


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
