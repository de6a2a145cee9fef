"""MiMo-V2.5 on the serving engine (models/windowed.py with K/V heads by
kind, keys and values of different widths in the per-head pools, a learned
sink a head in the window layers' softmax; the sink arms of ops/attention.py
and ops/paged_attention.py), on the CPU in float32 with seeded weights,
against the benchmark's plain reference (perfbench/reference/mimo_v2.py),
which imports nothing of the program and masks an [S, S] score matrix with
the sink as one more column.
"""

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.models import configs, experts, forward, init_params, windowed
from ray_tpu.ops.attention import (prefill_attention,
                                   prefill_attention_reference)
from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_decode_attention_reference)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on both sides; what differs is the order of the sums (pages and
# an online softmax against one masked matrix; [256-row, expert] tiles
# against one expert at a time), ~1e-6 a log-probability over 7 layers
TOL = 2e-5

# The chip's share at test size: 2 of 8 routed experts (share 1 of 4).
SHARE = configs.tiny_mimo_v2(moe_experts=2, moe_held_group=1)
PAGE = 16       # = the window: a window of exactly one page
SPAN = SHARE.window_span(PAGE)


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "mimo_v2.py"))


def _engine(c=SHARE, **kw):
    e = dict(max_slots=3, max_len=192, page_size=PAGE,
             prompt_buckets=(16, 64), eos_token=-1)
    return InferenceEngine(c, EngineConfig(**{**e, **kw}), seed=3)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng, check=None):
    while eng.has_work():
        eng.step()
        if check is not None:
            check(eng)


def _diff(reference, eng, prompt, req, c=None):
    want, _ = reference.logprobs_of(eng.params, c or eng.c, prompt,
                                    req.generated)
    assert len(req.generated) == len(req.token_logprobs) == len(want)
    return float(np.abs(np.array(req.token_logprobs) - np.array(want)).max())


def _pools_empty(eng):
    st = eng.kv_stats()
    assert st["window_pages_in_use"] == st["window_pages_held"] == 0
    assert sorted(eng.free_win) == list(range(1, eng.num_window_pages))
    assert st["pages_in_use"] == 0


# ------------------------------------------------- the model = reference


def test_the_layers_are_the_published_kinds_and_shapes():
    assert SPAN == 2 and configs.mimo_v2_5().window_span(128) == 2
    c = configs.mimo_v2_5()
    published = ([0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 6
                 + [0])
    assert windowed.kinds(c) == "".join("FW"[k] for k in published)
    assert windowed.kinds(c).count("F") == 9 and c.kv_cache == "windowed"
    assert int(c.head_dim * c.rotary_fraction) == 64
    shapes = jax.eval_shape(lambda k: init_params(c, k),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9) == 309            # the family's "309B"
    full, win = shapes["layers"][0], shapes["layers"][1]
    assert [full[w].shape for w in ("wq", "wk", "wv", "wo")] == [
        (4096, 64 * 192), (4096, 4 * 192), (4096, 4 * 128), (64 * 128, 4096)]
    assert [win[w].shape for w in ("wk", "wv")] == [
        (4096, 8 * 192), (4096, 8 * 128)]
    assert "sink" not in full and "router" not in full
    assert (win["sink"].shape, win["sink"].dtype) == ((64,), jnp.float32)
    assert win["router_bias"].shape == (256,) and "shared_wu" not in win
    # every other leaf in the configuration's dtype
    assert {a.dtype for a in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype("bfloat16"), jnp.dtype("float32")}
    assert sum(a.dtype == jnp.float32
               for a in jax.tree_util.tree_leaves(shapes)) == 39
    kf, vf = windowed.page_pools(c, 10, 128)
    kw, vw = windowed.window_pools(c, 6, 128)
    assert (kf.shape, vf.shape) == ((9, 4, 10, 192, 128),
                                    (9, 4, 10, 128, 128))
    assert (kw.shape, vw.shape) == ((39, 8, 6, 192, 128),
                                    (39, 8, 6, 128, 128))


def test_lagunas_tree_is_leaf_for_leaf_what_it_was():
    """The new fields' defaults leave the other family alone: no sink, no
    router bias, one K/V head count, K and V alike."""
    c = configs.tiny_laguna()
    params = init_params(c, jax.random.PRNGKey(5))
    for lp in params["layers"]:
        assert "sink" not in lp and "router_bias" not in lp
        assert lp["wk"].shape == lp["wv"].shape == (64, 2 * 16)
    assert all(a.dtype == jnp.float32
               for a in jax.tree_util.tree_leaves(params))
    kf, vf = windowed.page_pools(c, 5, 8)
    assert kf.shape == vf.shape == (2, 2, 5, 16, 8)
    assert windowed._decode_name(c, "W") == "swa_paged_decode"
    assert windowed._decode_name(c, "F") is None
    assert windowed._prefill_name(c, "W", 2, 64, 8) == "swa_prefill_n2_s64_t8"
    assert (windowed._decode_name(SHARE, "W"), windowed._decode_name(
        SHARE, "F")) == ("sinkwin_paged_decode", "splitkv_paged_decode")
    assert windowed._prefill_name(SHARE, "W", 1, 64, 16) == (
        "sinkwin_prefill_n1_s64_t16")
    assert windowed._prefill_name(SHARE, "F", 1, 64, 16) == (
        "gqa_prefill_attention")


def test_forward_is_the_reference(reference):
    c = configs.tiny_mimo_v2()
    params = init_params(c, jax.random.PRNGKey(5))
    seq = _ids(70, 11)
    got = forward(params, jnp.asarray([seq]), c)[0]
    want = reference.logits_of(params, c, seq)
    # logits of unit spread, float32 on both sides, sums in another order
    np.testing.assert_allclose(got, want, atol=5e-5)
    _, margins = reference.logprobs_of(params, c, seq[:30], seq[30:])
    assert all(math.isfinite(m) and m >= 0 for m in margins)


@pytest.mark.parametrize("n_prompt,new,hits", [
    (16, 5, 0),      # exactly a bucket and exactly the window
    (10, 40, 0),     # decoded far past the window from a short prompt
    (50, 20, 0),     # one bucket: the prompt itself is past the window
    (100, 8, 1),     # a chunk, its held window (one page), a continuation
    (150, 30, 2),    # three chunks, then decode across page edges
])
def test_engine_logprobs_match_the_reference(reference, n_prompt, new, hits):
    eng = _engine()
    ids = _ids(n_prompt, n_prompt)
    req = eng.request(eng.add_request(ids, new, 0.0, logprobs=True))
    _run(eng)
    assert len(req.generated) == new
    assert _diff(reference, eng, ids, req) < TOL
    st = eng.kv_stats()
    assert st["prefix_hits"] == hits
    assert st["window_seq_pages_peak"] <= SPAN
    _pools_empty(eng)


def test_several_admitted_together_at_different_lengths(reference):
    eng = _engine()
    prompts = [_ids(n, 100 + n) for n in (5, 17, 40, 90)]
    reqs = [eng.request(eng.add_request(p, 25, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    for p, r in zip(prompts, reqs):
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)


def test_a_preempted_request_resumes_on_the_reference(reference):
    # five usable full pages for two requests that grow to four each
    eng = _engine(num_pages=6)
    prompts = [_ids(10, 1), _ids(10, 2)]
    reqs = [eng.request(eng.add_request(p, 50, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.generated) == 50
        # a resumed request prefills what it decoded: another order of sums
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)
    # the window pool dry instead: a victim is preempted there too
    eng = _engine(num_window_pages=2 * SPAN, max_slots=2)
    reqs = [eng.request(eng.add_request(p, 50, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)


@pytest.mark.parametrize("fault", [
    dict(attn_sink=""),                 # the sink dropped
    dict(attn_sink="FW"),               # a sink on the full layers too
    dict(window=10**6),                 # the window dropped
    dict(window=12),                    # narrowed
    dict(value_scale=1.0),              # v unscaled
    dict(rope_theta=10000.0, window_rope_theta=1e7),    # bases swapped
    dict(rotary_fraction=1.0, window_rotary_fraction=1.0),  # every dim
    dict(moe_router_bias=False),        # the choice without its bias
])
def test_the_reference_refuses_a_wrong_layer(reference, fault):
    """What the comparison is worth: a reference handed no sink, one sink
    too many, another window, an unscaled v or the other rotary embedding
    lies far from the program, which met the honest one above."""
    eng = _engine()
    ids = _ids(60, 60)
    req = eng.request(eng.add_request(ids, 30, 0.0, logprobs=True))
    _run(eng)
    assert _diff(reference, eng, ids, req) < TOL
    params = eng.params
    if fault == dict(moe_router_bias=False):
        # the choice without its bias: the leaf taken out of the tree
        params = {**params, "layers": [
            {k: v for k, v in lp.items() if k != "router_bias"}
            for lp in params["layers"]]}
    reference.SINK_WHERE_ABSENT = 2.0
    try:
        want, _ = reference.logprobs_of(
            params, dataclasses.replace(eng.c, **fault), ids, req.generated)
    finally:
        reference.SINK_WHERE_ABSENT = None
    assert np.abs(np.array(req.token_logprobs) - np.array(want)).max() > (
        1000 * TOL)


def test_a_sink_on_the_full_layers_runs_too(reference):
    """`attn_sink` names kinds: with "FW" the full layers' calls take the
    sink arms as well (decode with a `lows` of zeros)."""
    c = dataclasses.replace(SHARE, attn_sink="FW")
    eng = _engine(c)
    assert all("sink" in lp for lp in eng.params["layers"])
    ids = _ids(90, 9)
    req = eng.request(eng.add_request(ids, 12, 0.0, logprobs=True))
    _run(eng)
    assert _diff(reference, eng, ids, req) < TOL
    with pytest.raises(ValueError, match="attn_sink='X'"):
        windowed.kinds(dataclasses.replace(SHARE, attn_sink="X"))


def test_refuse_names_what_the_kinds_do_not_share():
    from ray_tpu.llm.engine import PrefillEngine
    with pytest.raises(ValueError, match=(
            "attn_pattern='FWWWWFW'.*window_kv_heads=4 beside n_kv_heads=2.*"
            "v_head_dim=16 beside a head of 24.*attn_sink='W'.*windowed")):
        PrefillEngine(SHARE)


# ------------------------------------------------------------ the kernels


def _by_hand(q, keys, vals, sink, scale):
    """One head's row: softmax over the keys and one more column."""
    sc = jnp.concatenate([(q @ keys.T) * scale, jnp.asarray([sink])])
    return jax.nn.softmax(sc)[:-1] @ vals


@pytest.mark.parametrize("h,hkv", [(8, 1), (16, 2), (8, 4)])
@pytest.mark.parametrize("s,pre_t,window,plens,lengths", [
    (256, 0, 128, (0, 0), None),            # window = a query block
    (384, 128, 128, (128, 40), None),       # one held page, one shorter
    (200, 128, 100, (128, 0), None),        # rows padded
    (512, 128, 128, (128, 100), (129, 300)),    # ragged over a prefix
])
def test_sink_prefill_kernel_is_the_reference(h, hkv, s, pre_t, window,
                                              plens, lengths):
    """K 24 wide, V 16: neither a multiple of the other's tile."""
    rng = np.random.RandomState(s + h)
    q = jnp.asarray(rng.randn(2, h, s, 24), jnp.float32)
    k = jnp.asarray(rng.randn(2, hkv, pre_t + s, 24), jnp.float32)
    v = jnp.asarray(rng.randn(2, hkv, pre_t + s, 16), jnp.float32)
    sink = jnp.asarray(rng.randn(h) + 2.0, jnp.float32)
    plen = jnp.asarray(plens, jnp.int32)
    kw = dict(pre_t=pre_t, scale=24 ** -0.5, window=window)
    want = prefill_attention_reference(q, k, v, plen, sink=sink, **kw)
    run = functools.partial(prefill_attention, q, k, v, plen,
                            name="sinkwin_prefill_test", impl="interpret",
                            **kw)
    got = run(sink=sink)
    assert got.shape == (2, h, s, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the oracle itself, by hand: row 150 of request 1, head 3
    first = max(pre_t + 150 - window + 1, pre_t - plens[1])
    keys = slice(first, pre_t + 151)
    g = 3 // (h // hkv)
    np.testing.assert_allclose(
        want[1, 3, 150], _by_hand(q[1, 3, 150], k[1, g, keys], v[1, g, keys],
                                  sink[3], 24 ** -0.5), atol=2e-5)
    # a sink of -1e30 is the plain softmax; one of +30 takes all the mass
    plain = run()
    np.testing.assert_allclose(run(sink=jnp.full((h,), -1e30)), plain,
                               atol=2e-6)
    assert float(jnp.abs(got - plain).max()) > 0.01
    assert float(jnp.abs(run(sink=jnp.full((h,), 30.0))).max()) < 1e-6
    if lengths is not None:
        ragged = run(sink=sink, lengths=jnp.asarray(lengths, jnp.int32))
        for i, m in enumerate(lengths):
            end = -(-m // 128) * 128
            np.testing.assert_array_equal(ragged[i, :, :m], got[i, :, :m])
            assert not np.asarray(ragged[i, :, end:]).any()


def test_full_prefill_kernel_takes_a_sink_too():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 4, 128, 24), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 256, 24), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 256, 16), jnp.float32)
    sink = jnp.asarray([0.0, 1.0, 2.0, 3.0], jnp.float32)
    kw = dict(pre_t=128, scale=0.2)
    plen = jnp.asarray([70], jnp.int32)
    np.testing.assert_allclose(
        prefill_attention(q, k, v, plen, name="t", impl="interpret",
                          sink=sink, **kw),
        prefill_attention_reference(q, k, v, plen, sink=sink, **kw),
        atol=2e-5)


@pytest.mark.parametrize("group,hkv", [(8, 4), (16, 2), (2, 4)])
def test_sink_decode_kernel_is_the_reference(group, hkv):
    """Pools of K 24 wide and V 16; a table of the window's two pages with
    the position its first page begins at taken off; an empty slot."""
    B, page = 4, 128
    rng = np.random.RandomState(group)
    pool_k = jnp.asarray(rng.randn(2, hkv, 9, 24, page), jnp.float32)
    pool_v = jnp.asarray(rng.randn(2, hkv, 9, 16, page), jnp.float32)
    h = hkv * group
    q = jnp.asarray(rng.randn(B, h, 24), jnp.float32)
    sink = jnp.asarray(rng.randn(h) + 2.0, jnp.float32)
    tables = jnp.asarray([[3, 4], [1, 0], [7, 8], [0, 0]], jnp.int32)
    lengths = jnp.asarray([200, 128, 256, 0], jnp.int32)
    lows = jnp.asarray([72, 0, 128, 0], jnp.int32)
    want = paged_decode_attention_reference(q, pool_k[1], pool_v[1], lengths,
                                            tables, lows, sink)
    run = functools.partial(
        paged_decode_attention, q, pool_k, pool_v, lengths, tables, layer=1,
        lows=lows, name="sinkwin_paged_decode", interpret=True)
    got = run(sink=sink)
    assert got.shape == (B, h, 16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # slot 0 by hand: positions 72..199 of pages 3 and 4, head 5
    keys = jnp.concatenate([pool_k[1, 5 // group, p].T for p in (3, 4)])
    vals = jnp.concatenate([pool_v[1, 5 // group, p].T for p in (3, 4)])
    np.testing.assert_allclose(
        got[0, 5], _by_hand(q[0, 5], keys[72:200], vals[72:200], sink[5],
                            24 ** -0.5), atol=2e-5)
    assert not np.asarray(got[3]).any()     # the empty slot: zeros
    plain = run()
    np.testing.assert_allclose(run(sink=jnp.full((h,), -1e30)), plain,
                               atol=2e-6)
    assert float(jnp.abs(got - plain).max()) > 0.01
    assert float(jnp.abs(run(sink=jnp.full((h,), 30.0))).max()) < 1e-6


def test_split_width_decode_kernel_without_a_window():
    """The full layers' call: no `lows`, no sink, 16 queries a K/V head."""
    rng = np.random.RandomState(1)
    pool_k = jnp.asarray(rng.randn(2, 2, 9, 24, 128), jnp.float32)
    pool_v = jnp.asarray(rng.randn(2, 2, 9, 16, 128), jnp.float32)
    q = jnp.asarray(rng.randn(2, 32, 24), jnp.float32)
    tables = jnp.asarray([[3, 4, 5, 0], [1, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([300, 77], jnp.int32)
    got = paged_decode_attention(q, pool_k, pool_v, lengths, tables, layer=0,
                                 name="splitkv_paged_decode", interpret=True)
    np.testing.assert_allclose(got, paged_decode_attention_reference(
        q, pool_k[0], pool_v[0], lengths, tables), atol=2e-5)


def _eqns(jaxpr, primitive: str):
    """Every equation of `primitive` in a jaxpr, those of its sub-jaxprs (a
    jit, a scan, a Pallas kernel) among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                yield from _eqns(getattr(sub, "jaxpr", sub), primitive)


def test_router_scores_and_sink_are_float32_over_bf16_weights():
    """What a comparison of log-probabilities cannot refuse: with bfloat16
    parameters and pages the router's logits, both sink kernels' scores and
    sums, and the sink itself (the one float32 leaf: a bfloat16 one fails
    here) are float32."""
    c = dataclasses.replace(SHARE, dtype="bfloat16")
    params = jax.eval_shape(lambda k: init_params(c, k),
                            jax.random.PRNGKey(0))
    assert params["embed"].dtype == jnp.bfloat16
    sinks = [lp["sink"] for lp in params["layers"] if "sink" in lp]
    assert len(sinks) == 5 and all(s.dtype == jnp.float32 for s in sinks)
    tokens = jax.ShapeDtypeStruct((1, 24), jnp.int32)
    routed = [e for e in _eqns(jax.make_jaxpr(
        lambda p, t: forward(p, t, c))(params, tokens).jaxpr, "dot_general")
        if e.invars[1].aval.shape == (c.d_model, c.moe_router_experts)]
    assert len(routed) == c.n_layers - c.first_k_dense
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in routed)

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for sink_dtype in (jnp.float32, jnp.bfloat16):
        sink = jax.ShapeDtypeStruct((8,), sink_dtype)
        prefill = jax.make_jaxpr(functools.partial(
            prefill_attention, pre_t=128, scale=24 ** -0.5, window=128,
            name="sinkwin_prefill_test", impl="interpret"))(
            bf16(2, 8, 256, 24), bf16(2, 4, 384, 24), bf16(2, 4, 384, 16),
            i32(2), sink=sink)
        decode = jax.make_jaxpr(functools.partial(
            paged_decode_attention, layer=1, name="sinkwin_paged_decode",
            interpret=True))(
            bf16(3, 8, 24), bf16(2, 4, 12, 24, 128), bf16(2, 4, 12, 16, 128),
            i32(3), i32(3, 2), lows=i32(3), sink=sink)
        for program in (prefill, decode):
            dots = list(_eqns(program.jaxpr, "dot_general"))
            assert len(dots) == 2       # q.K and p.V
            assert all(e.outvars[0].aval.dtype == jnp.float32 for e in dots)
            # the kernel reads the sink as float32 whatever it was handed
            (call,) = _eqns(program.jaxpr, "pallas_call")
            assert call.invars[-1].aval.dtype == jnp.float32


# ------------------------------------------------------- the chip's share


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: 16 shares of 2 of 32 routed
    experts, each the layer as one chip of the deployment computes it (its
    router whole, its experts' part of the sum, no shared expert), add up
    to the layer with every expert held."""
    whole = configs.tiny_mimo_v2(moe_experts=32, moe_router_experts=32,
                                 moe_top_k=8)
    lp = windowed._init_layer(jax.random.PRNGKey(2), whole, 1)
    assert "router_bias" in lp and "shared_wu" not in lp
    x = jax.random.normal(jax.random.PRNGKey(3), (24, whole.d_model))
    valid = jnp.ones((24,), bool)
    want, _ = experts.expert_layer(x, lp, whole, valid)
    total = jnp.zeros_like(want)
    for g in range(16):
        c = dataclasses.replace(whole, moe_experts=2, moe_held_group=g)
        part = {**lp, **{w: lp[w][2 * g:2 * g + 2]
                         for w in ("wg", "wu", "wd")}}
        total = total + experts.expert_layer(x, part, c, valid)[0]
    np.testing.assert_allclose(total, want, atol=1e-5)


# ------------------------------------------- a window of exactly one page


WIN = 128   # the published window and page


@pytest.fixture(scope="module")
def page_engine():
    c = dataclasses.replace(SHARE, window=WIN)
    return InferenceEngine(c, EngineConfig(
        max_slots=2, max_len=512, page_size=WIN, prompt_buckets=(128, 384),
        eos_token=-1), seed=3)


@pytest.mark.parametrize("at,first,pages", [
    (127, 0, 1), (128, 0, 2), (129, 0, 2), (255, 1, 1), (256, 1, 2),
    (257, 1, 2)])
def test_a_window_of_one_page_by_hand(page_engine, at, first, pages):
    """The query at position `at` sees positions at - 127 .. at: one page
    where `at` is a page's last position, else two; never three."""
    eng = page_engine
    assert eng.win_span == 2
    assert eng._window_first(at) == first == max(at - WIN + 1, 0) // WIN
    assert at // WIN - first + 1 == pages


@pytest.mark.parametrize("n", [127, 128, 129, 255, 256, 257])
def test_a_window_of_one_page_through_the_engine(reference, page_engine, n):
    """A prompt of n tokens, then decode across the next page edge: what
    the prefill keeps (`_plan_window` on the host, `_kept_pages` in the
    program), what each decode step holds, and the pages released."""
    eng = page_engine
    released = eng.kv_stats()["window_pages_released"]
    # the prefill's plan: the pages the next query (position n) can see
    plan = eng._plan_window(None, 0, n)
    assert plan is not None
    first, taken, new = plan
    eng.free_win.extend(new)
    assert (first, taken) == (max(n - WIN + 1, 0) // WIN, [])
    assert len(new) == (n - 1) // WIN - first + 1
    kept = windowed._kept_pages(
        eng.c, WIN, -(-(128 if n <= 128 else 384) // WIN),
        jnp.asarray([n], jnp.int32), jnp.zeros((1,), jnp.int32))
    assert list(np.asarray(kept[0])[:len(new)]) == list(
        range(first, first + len(new)))
    ids = _ids(n, n)
    new_tokens = 2 * WIN - n % WIN + 3 if n % WIN else WIN + 3
    new_tokens = min(new_tokens, 140)
    req = eng.request(eng.add_request(ids, new_tokens, 0.0, logprobs=True))
    held = []

    def check(eng):
        if req.slot is not None and eng.active[req.slot]:
            at = int(eng.lengths[req.slot])     # the next position written
            pages = eng.slot_win[req.slot]
            held.append(len(pages))
            assert len(pages) <= 2
            assert eng.slot_win_first[req.slot] >= max(
                at - 1 - WIN + 1, 0) // WIN
    _run(eng, check)
    assert _diff(reference, eng, ids, req) < TOL
    st = eng.kv_stats()
    assert st["window_seq_pages_peak"] <= 2 and max(held) == 2
    # a page is released when the next position written is WIN past its
    # last: once for every page edge the sequence's window crossed, but
    # those before the prefill's first kept page, which were never held
    end = n + new_tokens - 1            # the last position written
    assert st["window_pages_released"] - released == (
        max(end - WIN + 1, 0) // WIN - first)
    _pools_empty(eng)


# the benchmark's own cases for this configuration (tier-1 collects
# `tests/` alone): its cost functions by hand, its file against the
# catalog, its cell and tools/checkwindow_mimo_v2.py at rehearsal sizes
from perfbench.tests.test_mimo_v2 import *  # noqa: E402,F401,F403
