"""Laguna on the serving engine (models/windowed.py, the window pools beside
the page pools in llm/engine.py, the window forms of ops/attention.py and
ops/paged_attention.py, models/experts.py), on the CPU in float32 with
seeded weights, against the benchmark's plain reference
(perfbench/reference/laguna.py), which imports nothing of the program and
masks an [S, S] score matrix.
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
from ray_tpu.llm.engine import PrefillEngine
from ray_tpu.models import configs, experts, forward, init_params, windowed
from ray_tpu.ops import layers
from ray_tpu.ops.attention import (prefill_attention,
                                   prefill_attention_reference, window_block)
from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_decode_attention_reference)

from tests.test_ops import poison_past_lengths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 on both sides; sums in another order

# The chip's share at test size: 2 of 8 routed experts (share 1 of 4).
SHARE = configs.tiny_laguna(moe_experts=2, moe_held_group=1)
PAGE = 8
SPAN = SHARE.window_span(PAGE)    # window 20, pages of 8: 4


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "laguna.py"))


def _engine(**kw):
    e = dict(max_slots=3, max_len=160, page_size=PAGE,
             prompt_buckets=(16, 32), eos_token=-1)
    return InferenceEngine(SHARE, EngineConfig(**{**e, **kw}), seed=3)


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


# ------------------------------------------------------ engine = reference


@pytest.mark.parametrize("n_prompt,new,hits", [
    (16, 5, 0),      # exactly a bucket, shorter than the window
    (10, 30, 0),     # decoded far past the window from a short prompt
    (27, 20, 0),     # padded to 32: the prompt itself is past the window
    (50, 8, 1),      # a chunk, its held window, one continuation
    (70, 45, 2),     # three chunks, then decode across five pages
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
    assert (st["window_pages_released"] > 0) == (n_prompt + new > 20 + PAGE)
    _pools_empty(eng)


def test_several_admitted_together_at_different_lengths(reference):
    eng = _engine()
    prompts = [_ids(n, 100 + n) for n in (5, 17, 30, 45)]
    reqs = [eng.request(eng.add_request(p, 25, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    for p, r in zip(prompts, reqs):
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)


@pytest.mark.parametrize("fault", [
    dict(window=10**6),                 # the window dropped
    dict(window=24),                    # widened
    dict(window=16),                    # narrowed
    dict(rotary_fraction=1.0),          # full layers rotating every dim
    dict(rope_scaling=None),            # full layers without YaRN
    dict(window_rope_theta=500000.0),   # window layers on the other base
    dict(window_rotary_fraction=0.5),   # window layers rotating half
    dict(attn_gate=""),                 # no gate
    dict(moe_score="softmax"),          # the other reading of the router
])
def test_the_reference_refuses_a_wrong_layer(reference, fault):
    """What the comparison is worth: a reference handed another window,
    another rotary embedding in either kind of layer, or no gate lies
    whole units from the program, which met the honest one above."""
    eng = _engine()
    ids = _ids(50, 50)
    req = eng.request(eng.add_request(ids, 30, 0.0, logprobs=True))
    _run(eng)
    assert _diff(reference, eng, ids, req) < TOL
    wrong = dataclasses.replace(eng.c, **fault)
    assert _diff(reference, eng, ids, req, wrong) > 1000 * TOL


def test_the_reference_can_rotate_window_layers_as_full_ones(reference):
    """tools/checkwindow_laguna.py's third fault."""
    eng = _engine()
    ids = _ids(40, 4)
    req = eng.request(eng.add_request(ids, 10, 0.0, logprobs=True))
    _run(eng)
    reference.WINDOW_ROTARY_AS_FULL = True
    try:
        assert _diff(reference, eng, ids, req) > 1000 * TOL
    finally:
        reference.WINDOW_ROTARY_AS_FULL = False


def test_forward_is_the_reference(reference):
    c = configs.tiny_laguna()
    params = init_params(c, jax.random.PRNGKey(5))
    seq = _ids(60, 11)
    logp = jax.nn.log_softmax(forward(params, jnp.asarray([seq]), c)[0], -1)
    want, margins = reference.logprobs_of(params, c, seq[:30], seq[30:])
    got = [float(logp[29 + i, t]) for i, t in enumerate(seq[30:])]
    np.testing.assert_allclose(got, want, atol=TOL)
    assert all(math.isfinite(m) and m >= 0 for m in margins)


def test_yarn_is_the_published_attention_factor(reference):
    c = configs.laguna_s_2_1()
    freqs, amp = layers.yarn_frequencies(64, c.rope_theta,
                                         dict(c.rope_scaling))
    assert amp == pytest.approx(1.4852030263919618, rel=1e-12)
    want, ref_amp = reference._yarn_inv_freq(64, c.rope_theta,
                                             dict(c.rope_scaling))
    assert ref_amp == pytest.approx(amp, rel=1e-12)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    # the slowest dims are interpolated by the factor, the fastest kept
    plain = c.rope_theta ** (-np.arange(32) / 32)
    assert freqs[0] == pytest.approx(plain[0])
    assert freqs[-1] == pytest.approx(plain[-1] / 128, rel=1e-5)


def test_published_sizes():
    c = configs.laguna_s_2_1()
    shapes = jax.eval_shape(lambda k: init_params(c, k),
                            jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9, 2) == 117.56      # the catalog's "118B"
    assert windowed.kinds(c).count("F") == 12 and c.kv_cache == "windowed"
    wq = [lp["wq"].shape for lp in shapes["layers"][:2]]
    assert wq == [(3072, 48 * 128), (3072, 72 * 128)]
    assert shapes["layers"][1]["w_head_gate"].shape == (3072, 72)
    assert "router_bias" not in shapes["layers"][1]


# ------------------------------------------------------------ the kernels


@pytest.mark.parametrize("h,hkv", [(9, 1), (6, 1), (18, 2)])
@pytest.mark.parametrize("s,pre_t,window,plens,lengths,poison", [
    (256, 0, 100, (0, 0), None, False),     # no prefix; window under a block
    (384, 128, 130, (128, 40), None, False),    # a right-aligned prefix,
    #                                             one shorter
    (200, 256, 512, (256, 0), None, False),     # rows padded; window over
    #                                             the chunk
    # `lengths`: a query block (`window_block` rows: 128, 256, 128, 128)
    # past a request's last real row is not run
    (512, 0, 100, (0, 0), (128, 0), False),     # a block's edge; no rows
    (768, 128, 130, (128, 40), (257, 255), False),  # past an edge, before
    (512, 256, 100, (256, 100), (129, 300), False),  # ragged over a prefix
    (384, 128, 100, (128, 40), (200, 60), True),    # NaN past them
])
def test_window_prefill_kernel_is_the_masked_reference(h, hkv, s, pre_t,
                                                       window, plens,
                                                       lengths, poison):
    rng = np.random.RandomState(s + h)
    q = jnp.asarray(rng.randn(2, h, s, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, hkv, pre_t + s, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, hkv, pre_t + s, 32), jnp.float32)
    plen = jnp.asarray(plens, jnp.int32)
    kw = dict(pre_t=pre_t, scale=32 ** -0.5, window=window)
    want = prefill_attention_reference(q, k, v, plen, **kw)
    got = prefill_attention(q, k, v, plen, name="swa_prefill_test",
                            impl="interpret", **kw)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if lengths is not None:
        blk = window_block(window)
        ends = [-(-m // blk) * blk for m in lengths]
        ragged = prefill_attention(
            *(poison_past_lengths(q, k, v, pre_t, lengths, ends) if poison
              else (q, k, v)), plen, name="swa_prefill_test",
            impl="interpret", lengths=jnp.asarray(lengths, jnp.int32), **kw)
        for i, (m, end) in enumerate(zip(lengths, ends)):
            # real rows: the very cells that ran without `lengths`; the
            # rows of a block that holds none: zeros
            np.testing.assert_array_equal(ragged[i, :, :m], got[i, :, :m])
            assert not np.asarray(ragged[i, :, end:]).any()
        assert any(end < s for end in ends)
    # the oracle itself, by hand: row 150 of request 1 sees `window` keys
    # ending at itself and none of the prefix padding
    first = max(pre_t + 150 - window + 1, pre_t - plens[1])
    keys = slice(first, pre_t + 151)
    sc = (q[1, 0, 150] @ k[1, 0, keys].T) * 32 ** -0.5
    np.testing.assert_allclose(want[1, 0, 150],
                               jax.nn.softmax(sc) @ v[1, 0, keys], atol=2e-5)


def test_kv_stats_counts_the_attention_blocks_and_those_that_hold_a_token():
    """A scripted run, every dispatch known. SHARE's layers are "FWWWF": a
    window layer's query blocks are `window_block(20)` = 128 rows, a full
    layer's the whole 256-row bucket (`_PREFILL_BQ` is 1024)."""
    eng = _engine(max_slots=4, max_len=320, prompt_buckets=(32, 256))
    blocks = run = 0

    def prompts(*ns):
        for n in ns:
            eng.add_request(_ids(n, n), 2, 0.0)
        _run(eng)

    # (prompts of one step, the requests of its dispatch's batch, a full
    # layer's blocks that hold a token, a window layer's)
    for ns, batch, full, window in [
            ((20,), 1, 1, 1),           # the 32-row bucket: one block each
            ((100,), 1, 1, 1),          # 256 rows: the second 128 hold none
            ((128,), 1, 1, 1), ((129,), 1, 1, 2), ((256,), 1, 1, 2),
            # three in one step: a batch of four, the fourth of no token
            ((100, 200, 50), 4, 3, 4)]:
        prompts(*ns)
        wide = 2 if max(ns) > 32 else 1     # window blocks a request
        blocks += batch * (2 * 1 + 3 * wide)
        run += 2 * full + 3 * window
        st = eng.kv_stats()
        assert (st["prefill_attn_blocks"],
                st["prefill_attn_blocks_run"]) == (blocks, run)
    assert run < blocks
    # `lengths` is an operand: a program a batch's shape, as at the parent
    assert sorted(eng._prefill_batches) == [(1, 32), (1, 256), (4, 256)]
    assert not eng._prefill_pre


@pytest.mark.parametrize("group", [9, 6])
def test_window_decode_kernel_starts_at_the_windows_first_page(group):
    B, hkv, hd, page = 3, 2, 32, 128
    rng = np.random.RandomState(group)
    pool_k, pool_v = (jnp.asarray(rng.randn(2, hkv, 12, hd, page),
                                  jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.randn(B, hkv * group, hd), jnp.float32)
    tables = jnp.asarray([[3, 4, 5, 6, 7], [1, 2, 0, 0, 0],
                          [8, 9, 10, 11, 0]], jnp.int32)
    lengths = jnp.asarray([600, 130, 385], jnp.int32)
    lows = jnp.asarray([89, 0, 0], jnp.int32)
    want = paged_decode_attention_reference(q, pool_k[1], pool_v[1], lengths,
                                            tables, lows)
    got = paged_decode_attention(q, pool_k, pool_v, lengths, tables, layer=1,
                                 lows=lows, name="swa_paged_decode",
                                 interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # slot 0 by hand: positions 89..599 of pages 3..7, one K/V head a group
    keys = jnp.concatenate([pool_k[1, 0, p].T for p in (3, 4, 5, 6, 7)])
    vals = jnp.concatenate([pool_v[1, 0, p].T for p in (3, 4, 5, 6, 7)])
    sc = (q[0, 0] @ keys[89:600].T) * hd ** -0.5
    np.testing.assert_allclose(got[0, 0], jax.nn.softmax(sc) @ vals[89:600],
                               atol=2e-5)


def _dots(jaxpr):
    """Every dot_general of a jaxpr, those of its sub-jaxprs (a jit, a
    scan, a Pallas kernel) among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.tree_util.tree_leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                yield from _dots(getattr(sub, "jaxpr", sub))


def test_router_logits_and_scores_are_float32_over_bf16_weights():
    """What the benchmark's comparison of log-probabilities cannot refuse
    (a bfloat16 router reads as the honest program does: the
    configuration's `check.why`): with bfloat16 parameters and pages the
    router's logits and both window kernels' scores and sums come out of
    their products in float32."""
    c = dataclasses.replace(SHARE, dtype="bfloat16")
    params = jax.eval_shape(lambda k: init_params(c, k),
                            jax.random.PRNGKey(0))
    assert params["embed"].dtype == jnp.bfloat16
    tokens = jax.ShapeDtypeStruct((1, 24), jnp.int32)
    routed = [e for e in _dots(jax.make_jaxpr(
        lambda p, t: forward(p, t, c))(params, tokens).jaxpr)
        if e.invars[1].aval.shape == (c.d_model, c.moe_router_experts)]
    assert len(routed) == c.n_layers - c.first_k_dense
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in routed)

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    prefill = jax.make_jaxpr(functools.partial(
        prefill_attention, pre_t=128, scale=32 ** -0.5, window=130,
        name="swa_prefill_test", impl="interpret"))(
        bf16(2, 9, 256, 32), bf16(2, 1, 384, 32), bf16(2, 1, 384, 32), i32(2))
    decode = jax.make_jaxpr(functools.partial(
        paged_decode_attention, layer=1, name="swa_paged_decode",
        interpret=True))(
        bf16(3, 18, 32), bf16(2, 2, 12, 32, 128), bf16(2, 2, 12, 32, 128),
        i32(3), i32(3, 5), lows=i32(3))
    for program in (prefill, decode):
        dots = list(_dots(program.jaxpr))
        assert len(dots) == 2       # q.K and p.V
        assert all(e.outvars[0].aval.dtype == jnp.float32 for e in dots)


# ------------------------------------------------------- the chip's share


def test_the_shares_add_up_to_the_uncut_layer():
    """The 4 shares' routed parts plus the shared expert ONCE are the layer
    with every expert held."""
    whole = configs.tiny_laguna()
    lp = windowed._init_layer(jax.random.PRNGKey(2), whole, 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, whole.d_model))
    valid = jnp.ones((24,), bool)
    want, _ = experts.expert_layer(x, lp, whole, valid)
    no_shared = {k: v for k, v in lp.items() if not k.startswith("shared")}
    total = experts.shared_expert(x, lp, whole)
    for g in range(4):
        c = dataclasses.replace(whole, moe_experts=2, moe_held_group=g,
                                moe_shared_experts=0)
        part = {**no_shared, **{w: lp[w][2 * g:2 * g + 2]
                                for w in ("wg", "wu", "wd")}}
        total = total + experts.expert_layer(x, part, c, valid)[0]
    np.testing.assert_allclose(total, want, atol=1e-5)


# -------------------------------------------------------- the page budget


def test_a_sequence_never_holds_more_than_its_window_spans():
    eng = _engine()

    def check(eng):
        for i in np.flatnonzero(eng.active):
            held, first = eng.slot_win[i], eng.slot_win_first[i]
            at = int(eng.lengths[i])
            assert len(held) <= SPAN
            # the table begins at the window's first page or later, and
            # holds no released page (free pages and tables are disjoint)
            assert first >= max(at - 1 - 20 + 1, 0) // PAGE
            assert first + len(held) >= (at - 1) // PAGE + 1
        in_tables = [p for held in eng.slot_win for p in held]
        holds = [p for r in eng.queue if r.win_hold for p in r.win_hold[1]]
        assert len(set(in_tables + holds)) == len(in_tables + holds)
        assert not set(in_tables + holds) & set(eng.free_win)
        assert 0 not in in_tables

    for n in (12, 40, 70):
        eng.add_request(_ids(n, n), 60, 0.0)
    _run(eng, check)
    st = eng.kv_stats()
    assert st["window_seq_pages_peak"] == SPAN
    assert st["window_pages_peak"] <= 3 * SPAN + (SPAN - 1)
    assert st["window_pages_released"] > 20
    _pools_empty(eng)


def test_window_tables_hand_the_kernel_held_pages_only():
    eng = _engine()
    eng.add_request(_ids(30, 1), 40, 0.0)
    seen = []
    build = eng._window_tables

    def spy(active):
        tables, starts = build(active)
        seen.append((tables.copy(), starts.copy(), list(eng.free_win)))
        return tables, starts

    eng._window_tables = spy
    _run(eng)
    assert len(seen) >= 38
    for tables, starts, free in seen:
        assert not set(tables[tables > 0].tolist()) & set(free)
        assert (starts % PAGE == 0).all()
    # the window slid: the table's first page begins later and later
    assert seen[-1][1][0] > seen[0][1][0] >= 0


def test_preemption_cancel_and_finish_return_every_page(reference):
    # five usable full pages for two requests that grow to five each
    eng = _engine(num_pages=8)
    prompts = [_ids(10, 1), _ids(10, 2)]
    reqs = [eng.request(eng.add_request(p, 30, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.generated) == 30
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)
    # the window pool dry instead: a victim is preempted there too
    eng = _engine(num_window_pages=2 * SPAN, max_slots=2)
    reqs = [eng.request(eng.add_request(p, 30, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.generated) == 30
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)
    # a cancel mid-decode and one between two chunks
    eng = _engine()
    a = eng.add_request(_ids(12, 3), 50, 0.0)
    for _ in range(10):
        eng.step()
    b = eng.add_request(_ids(70, 4), 5, 0.0)
    eng.step()                          # b's first chunk: it holds pages
    assert eng.kv_stats()["window_pages_held"] > 0
    eng.cancel(a)
    eng.cancel(b)
    _run(eng)
    assert len(eng.finished[a].generated) < 50
    _pools_empty(eng)


def test_a_prefix_hit_inside_a_released_window_recomputes(reference):
    eng = _engine()
    ids = _ids(50, 7)
    first = eng.request(eng.add_request(ids, 6, 0.0, logprobs=True))
    _run(eng)
    # the prompt's full pages are cached in the growing pool; the window
    # layers' pages before those boundaries were released with the slot
    assert len([h for h in eng.page_hash if len(h) <= 50 * 4]) >= 6
    assert eng._find_prefix(ids) == []
    hits = eng.kv_stats()["prefix_hits"]
    again = eng.request(eng.add_request(ids, 6, 0.0, logprobs=True))
    _run(eng)
    # only the second run's own chunk continuation hit, over its own hold
    assert eng.kv_stats()["prefix_hits"] == hits + 1
    assert again.generated == first.generated
    np.testing.assert_allclose(again.token_logprobs, first.token_logprobs,
                               atol=TOL)
    assert _diff(reference, eng, ids, again) < TOL


def test_a_stolen_hold_is_prefilled_again(reference):
    """The least window pool there may be, and two chunked prompts at once:
    one's continuation takes the pages the other holds between its chunks,
    and that one starts over."""
    with pytest.raises(ValueError, match="num_window_pages=7"):
        _engine(num_window_pages=2 * SPAN - 1)
    eng = _engine(max_slots=2, num_window_pages=2 * SPAN)
    prompts = [_ids(70, 2), _ids(70, 3)]
    reqs = [eng.request(eng.add_request(p, 6, 0.0, logprobs=True))
            for p in prompts]
    dropped, drop = [], eng._drop_hold
    eng._drop_hold = lambda req: (dropped.append(req), drop(req))
    eng.step()
    assert eng.kv_stats()["window_pages_held"] == 2 * (SPAN - 1)
    eng.step()      # one continues over its hold; the other lost its own
    assert eng.kv_stats()["window_pages_held"] == SPAN - 1
    assert dropped == [reqs[0]]
    _run(eng)
    for p, r in zip(prompts, reqs):
        assert len(r.generated) == 6
        assert _diff(reference, eng, p, r) < TOL
    _pools_empty(eng)


# ------------------------------------------------------------ refusals


@pytest.mark.parametrize("what,make", [
    ("speculation", lambda: InferenceEngine(
        SHARE, EngineConfig(speculation="ngram", page_size=PAGE))),
    ("prefill pool", lambda: PrefillEngine(SHARE)),
    ("handoff", lambda: _engine().add_request(
        [1, 2, 3], 2, kv_handoff=(None, None))),
    ("import_kv", lambda: _engine().import_kv([1] * 20, None, None)),
])
def test_refuse_names_the_window_kind(what, make):
    # speculation meets the engine's one check, whatever the model
    with pytest.raises(ValueError, match="one decode loop" if what
                       == "speculation" else "attn_pattern='FWWWF'.*windowed"):
        make()


def test_refuses_a_mesh():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="attn_pattern.*mesh"):
        InferenceEngine(SHARE, EngineConfig(page_size=PAGE), mesh=mesh)
    with pytest.raises(ValueError, match="attn_pattern='FWF'"):
        windowed.kinds(configs.tiny_laguna(attn_pattern="FWF"))


# the benchmark's own cases for this configuration (tier-1 collects
# `tests/` alone): its cost functions by hand, its file against the
# catalog, its cell and tools/checkwindow_laguna.py at rehearsal sizes
from perfbench.tests.test_laguna import *  # noqa: E402,F401,F403
