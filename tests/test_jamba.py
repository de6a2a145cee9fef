"""Jamba (AI21-Jamba2-3B's structure) on the serving engine: the Mamba-1
blocks, the dense MLP blocks and the tied head of models/nemotron_h.py, the
selective scan and state update of ops/ssm.py, on the CPU in float32 with
seeded weights, against the benchmark's plain reference
(perfbench/reference/jamba.py), which imports nothing of the program and
runs the recurrence a position at a time."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.tests.test_jamba import *  # noqa: F401,F403 — its cases
from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import PrefillEngine
from ray_tpu.models import configs, forward, init_params, nemotron_h as nh
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import (prefill_attention,
                                   prefill_attention_reference)
from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_decode_attention_reference)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 on both sides; sums in another order
TINY = configs.tiny_jamba()


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "jamba.py"))


def _engine(c=TINY, **kw):
    e = dict(max_slots=3, max_len=160, page_size=16, prompt_buckets=(16, 32),
             eos_token=-1)
    return InferenceEngine(c, EngineConfig(**{**e, **kw}), seed=3)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng):
    while eng.has_work():
        eng.step()


def _diff(reference, eng, prompt, req):
    want, _ = reference.logprobs_of(eng.params, eng.c, prompt, req.generated)
    assert len(req.generated) == len(req.token_logprobs) == len(want)
    return float(np.abs(np.array(req.token_logprobs) - np.array(want)).max())


# ------------------------------------------------------------ the kernels


def _scan_inputs(n, s, d, N, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (n, s, d)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (n, s, d)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (N, d))),
            jax.random.normal(k[3], (n, s, N)).astype(dtype),
            jax.random.normal(k[4], (n, s, N)).astype(dtype),
            jax.random.normal(k[5], (n, N, d)))


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("n,s,d,N,lengths", [
    (2, 37, 128, 16, (37, 20)),     # ragged, padded to whole groups
    (1, 16, 256, 8, (16,)),         # exactly one group, another tile width
    (3, 150, 128, 16, (150, 1, 129)),   # two kernel blocks of 128 rows
])
def test_selective_scan_is_the_sequential_recurrence(impl, n, s, d, N,
                                                     lengths):
    """Both forms, from a carried state, over ragged lengths: the outputs
    of the real rows and the final state are the oracle's, and padding (dt
    = 0) moves no state."""
    x, dt, a, b, c, st = _scan_inputs(n, s, d, N)
    valid = jnp.arange(s)[None] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, state = ssm.selective_scan(x, dt, a, b, c, st, impl=impl)
    want_y, want_state = ssm.selective_scan_sequential(x, dt, a, b, c, st)
    assert y.shape == x.shape and state.dtype == jnp.float32
    np.testing.assert_allclose(np.where(valid[..., None], y, 0.0),
                               np.where(valid[..., None], want_y, 0.0),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5, rtol=1e-5)
    # the state after a sequence's real rows alone: padding added nothing
    for i, ln in enumerate(lengths):
        _, alone = ssm.selective_scan_sequential(
            x[i:i + 1, :ln], dt[i:i + 1, :ln], a, b[i:i + 1, :ln],
            c[i:i + 1, :ln], st[i:i + 1])
        np.testing.assert_allclose(state[i], alone[0], atol=1e-5, rtol=1e-5)


def test_selective_scan_in_two_calls_is_one_call():
    """A chunk's final state carried into the next call: the snapshot path
    of a chunked prompt."""
    x, dt, a, b, c, st = _scan_inputs(2, 64, 128, 16, seed=3)
    whole_y, whole = ssm.selective_scan(x, dt, a, b, c, st, impl="interpret")
    y1, mid = ssm.selective_scan(x[:, :48], dt[:, :48], a, b[:, :48],
                                 c[:, :48], st, impl="interpret")
    y2, end = ssm.selective_scan(x[:, 48:], dt[:, 48:], a, b[:, 48:],
                                 c[:, 48:], mid, impl="interpret")
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), whole_y,
                               atol=1e-5)
    np.testing.assert_allclose(end, whole, atol=1e-6)


def test_selective_scan_takes_bf16_and_keeps_the_state_float32():
    x, dt, a, b, c, st = _scan_inputs(1, 32, 128, 16, dtype=jnp.bfloat16)
    y, state = ssm.selective_scan(x, dt, a, b, c, st, impl="interpret")
    y_ref, state_ref = ssm.selective_scan(x, dt, a, b, c, st,
                                          impl="reference")
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    np.testing.assert_allclose(y.astype(np.float32),
                               y_ref.astype(np.float32), atol=5e-2, rtol=2e-2)
    np.testing.assert_allclose(state, state_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("active", [
    (True, False, True, True, False), (False,) * 5, (True,) * 5])
def test_selective_state_update_kernel_matches_jnp(active):
    """The interpreted kernel against its jnp form; rows of inactive slots,
    rows beyond the batch and the other layers bit for bit untouched."""
    L, rows, B, N, d = 3, 7, 5, 16, 256
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    pool = jax.random.normal(k[0], (L, rows, N, d))
    x = jax.random.normal(k[1], (B, d))
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, d)))
    a = -jnp.exp(jax.random.normal(k[3], (N, d)))
    b, c = (jax.random.normal(k[4], (B, N)), jax.random.normal(k[5], (B, N)))
    act = jnp.asarray(active)
    y0, p0 = ssm.selective_state_update(pool, x, dt, a, b, c, act, layer=1,
                                        impl="reference")
    y1, p1 = ssm.selective_state_update(pool, x, dt, a, b, c, act, layer=1,
                                        impl="interpret")
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1, p0, atol=1e-6)
    still = np.ones((L, rows), bool)
    still[1, :B] = ~np.asarray(active)
    assert bool((np.asarray(p1)[still] == np.asarray(pool)[still]).all())
    assert bool((np.asarray(y1)[~np.asarray(active)] == 0).all())
    # one row of the update is one position of the scan
    y_scan, s_scan = ssm.selective_scan_sequential(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], pool[1, :B])
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(y1)[on], np.asarray(y_scan)[on, 0],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(p1)[1, :B][on],
                               np.asarray(s_scan)[on], atol=1e-6)


@pytest.mark.parametrize("kernel", ["prefill", "paged_decode"])
def test_a_20_to_1_query_group_through_the_attention_kernels(kernel):
    """jamba2_3b's geometry: 20 query heads read ONE K/V head of 128 (a
    group that is no multiple of 8), through the interpreted kernels."""
    h, hkv, hd, page = 20, 1, 128, 128
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    if kernel == "prefill":
        n, s, pre_t = 2, 128, 128
        q = jax.random.normal(k[0], (n, h, s, hd))
        keys = jax.random.normal(k[1], (n, hkv, pre_t + s, hd))
        vals = jax.random.normal(k[2], (n, hkv, pre_t + s, hd))
        plen = jnp.asarray([128, 40], jnp.int32)
        kw = dict(pre_t=pre_t, scale=hd ** -0.5)
        got = prefill_attention(q, keys, vals, plen, name="gqa_prefill_attention",
                                impl="interpret", **kw)
        want = prefill_attention_reference(q, keys, vals, plen, **kw)
    else:
        B, pages = 3, 5
        q = jax.random.normal(k[0], (B, h, hd))
        pool_k = jax.random.normal(k[1], (2, hkv, pages * B + 1, hd, page))
        pool_v = jax.random.normal(k[2], (2, hkv, pages * B + 1, hd, page))
        tables = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
        lengths = jnp.asarray([5, 300, 640], jnp.int32)
        got = paged_decode_attention(q, pool_k, pool_v, lengths, tables,
                                     layer=1, interpret=True)
        want = paged_decode_attention_reference(q, pool_k[1], pool_v[1],
                                                lengths, tables)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


# ------------------------------------------------------ engine = reference


@pytest.mark.parametrize("n_prompt,new,snapshot_hits", [
    (16, 5, 0),      # exactly a bucket: no padding
    (10, 6, 0),      # shorter than its bucket: padding must not move state
    (27, 20, 0),     # padded to 32, then decode across page edges
    (50, 8, 1),      # two chunks: the second resumes from a snapshot
    (70, 20, 2),     # three chunks, two snapshots
])
def test_engine_logprobs_match_the_reference(reference, n_prompt, new,
                                             snapshot_hits):
    eng = _engine()
    ids = _ids(n_prompt, n_prompt)
    req = eng.request(eng.add_request(ids, new, 0.0, logprobs=True))
    _run(eng)
    assert len(req.generated) == new
    assert _diff(reference, eng, ids, req) < TOL
    st = eng.kv_stats()
    assert st["snapshot_hits"] == snapshot_hits == st["prefix_hits"]


def test_a_prompt_chunked_across_a_snapshot_equals_the_same_unchunked():
    """Buckets (16, 32) cut a 60-token prompt into a 32-token chunk, whose
    state goes to a snapshot row, and a continuation; bucket 64 takes it
    whole. The same tokens and log-probabilities either way."""
    ids = _ids(60, 9)
    runs = []
    for buckets in ((16, 32), (64,)):
        eng = _engine(prompt_buckets=buckets)
        req = eng.request(eng.add_request(ids, 10, 0.0, logprobs=True))
        _run(eng)
        runs.append((req, eng.kv_stats()["snapshot_hits"]))
    (chunked, hits), (whole, none) = runs
    assert (hits, none) == (1, 0)
    assert chunked.generated == whole.generated
    np.testing.assert_allclose(chunked.token_logprobs, whole.token_logprobs,
                               atol=TOL)


def test_several_admitted_together_at_different_lengths(reference):
    eng = _engine()
    prompts = [_ids(n, 100 + n) for n in (5, 17, 30, 45)]
    reqs = [eng.request(eng.add_request(p, 7, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    for p, r in zip(prompts, reqs):
        assert _diff(reference, eng, p, r) < TOL


def test_a_preempted_request_resumes_on_the_reference(reference):
    """Three usable pages for two requests that need two each: one is
    preempted when the pool runs dry and re-prefills what it had seen,
    state and all."""
    eng = _engine(num_pages=4)
    prompts = [_ids(10, 1), _ids(10, 2)]
    reqs = [eng.request(eng.add_request(p, 20, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.generated) == 20
        assert _diff(reference, eng, p, r) < TOL
    assert eng.kv_stats()["state_rows_in_use"] == 0


@pytest.mark.parametrize("n_prompt,n_req,slots", [
    (8, 10, 10), (20, 6, 6), (70, 6, 3)])
def test_a_step_admits_a_budget_of_rows(n_prompt, n_req, slots):
    """A queue that built up, on free slots: one step plans prompt buckets
    of at most engine.ADMIT_BUCKETS times the largest bucket (4 x 32 rows:
    eight of bucket 16, four of bucket 32, four 32-token chunks of longer
    prompts, which take no slot), the rest the step after; every request
    still ends."""
    eng = _engine(max_slots=slots)
    for i in range(n_req):
        eng.add_request(_ids(n_prompt, i), 2, 0.0)
    reqs = list(eng.queue)
    admit, group, steps = eng._admit, eng._prefill_group, []
    eng._admit = lambda: steps.append(0) or admit()

    def counted(members, logits_of, toks, *rest):
        steps[-1] += len(members) * toks.shape[1]
        return group(members, logits_of, toks, *rest)

    eng._prefill_group = counted
    _run(eng)
    assert steps[0] == max(steps) == 128 == engine_mod.ADMIT_BUCKETS * 32
    assert all(len(r.generated) == 2 for r in reqs)


@pytest.mark.parametrize("fault,refused", [
    ("none", False), ("state_bf16", True), ("wrong_snapshot", True)])
def test_the_state_itself_is_the_recurrence(reference, fault, refused,
                                            monkeypatch):
    """perfbench/tools/checkstate_jamba.py's comparison: after a prompt of
    three chunks and 8 decoded tokens, the slot's row and both snapshots'
    rows hold the plain recurrence's [N, D] state, layer by layer, to
    float32 rounding; a state kept in bfloat16 and a snapshot of the
    boundary one page back are refused, with room."""
    from perfbench.tools import checkstate, checkstate_jamba
    monkeypatch.setattr(checkstate, "head_errors",
                        checkstate_jamba.channel_errors)
    monkeypatch.setattr(checkstate, "slow_heads",
                        checkstate_jamba.slow_channels)
    eng = _engine()
    prompt = _ids(70, 11)
    eng.add_request(prompt, 8, 0.0)
    req = eng.queue[0]
    _run(eng)
    d = checkstate.state_diffs(eng, reference, TINY, prompt, req, fault)
    assert sorted(d["rows"]) == ["slot", "snapshot_32", "snapshot_64"]
    assert d["tokens_fed"] == 77 and len(d["rows"]["slot"]) == 3
    rule = {"layer": 0, "limit": {"float32": 1e-4}}
    verdict = checkstate.judge(d, rule, "float32")
    assert verdict["ok"] is not refused
    if refused:
        assert verdict["worst"] > 10 * verdict["limit"]
    else:
        assert max(max(errs) for errs in d["rows"].values()) < 1e-5


@pytest.mark.parametrize("program", ["prefill_batch",
                                     "prefill_with_prefix_batch",
                                     "decode_paged"])
def test_no_gather_or_scatter_touches_a_row_pool(program):
    """As tests/test_nemotron_h.py's, for the [LS, rows, N, D] pools."""
    c, n, s, rows = TINY, 4, 32, 9
    params = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    pk, pv = nh.page_pools(c, 12, 16)
    ssm_pool, conv_pool = nh.row_pools(c, rows)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    stats = i32(*nh.stats_zero(c).shape)
    if program == "decode_paged":
        args = (params, pk, pv, ssm_pool, conv_pool, i32(n), i32(n),
                jax.ShapeDtypeStruct((n,), jnp.bool_), i32(n, 4), stats)
    elif program == "prefill_batch":
        args = (params, i32(n, s), i32(n), ssm_pool, conv_pool, i32(n),
                i32(n), stats)
    else:
        args = (params, i32(n, s), i32(n), pk, pv, i32(n, 2), i32(n),
                ssm_pool, conv_pool, i32(n), i32(n), stats)
    jaxpr = jax.make_jaxpr(functools.partial(getattr(nh, program),
                                             config=c))(*args)
    ls, _, N, d = ssm_pool.shape
    _, taps, _, cd = conv_pool.shape

    def pooled(shape):   # a pool, or any number of its rows
        return len(shape) == 4 and (
            (shape[0], *shape[2:]) == (ls, N, d)
            or (*shape[:2], shape[3]) == (ls, taps, cd))

    moved = []

    def walk(jp):
        for eqn in jp.eqns:
            if ("gather" in eqn.primitive.name
                    or "scatter" in eqn.primitive.name):
                moved.extend(
                    (eqn.primitive.name, v.aval.shape)
                    for v in eqn.invars + eqn.outvars
                    if hasattr(v.aval, "shape") and pooled(v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert moved == []


# ---------------------------------------------- params, head, refusals


def test_forward_is_the_reference_through_the_tied_head(reference):
    params = init_params(TINY, jax.random.PRNGKey(2))
    assert "lm_head" not in params          # logits through embed's transpose
    toks = jnp.asarray([_ids(21, 4)], jnp.int32)
    logits = forward(params, toks, TINY)
    x, _ = reference.hidden_of(params, TINY, np.asarray(toks[0]))
    want = np.asarray(x) @ np.asarray(params["embed"], np.float32).T
    np.testing.assert_allclose(logits[0], want, atol=2e-4)
    # an untied head of the same family keeps its own matrix
    untied = init_params(dataclasses.replace(TINY, tie_embeddings=False),
                         jax.random.PRNGKey(2))
    assert untied["lm_head"].shape == (TINY.d_model, TINY.vocab)


def test_init_params_makes_no_float32_leaf_and_seeds_mamba_1():
    c = dataclasses.replace(TINY, dtype="bfloat16")
    params = init_params(c, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params)
    assert leaves and all(a.dtype == jnp.bfloat16 for a in leaves)
    lp = params["layers"][0]
    np.testing.assert_allclose(
        np.asarray(lp["a_log"], np.float32)[:, 7],
        np.log(np.arange(1, 17)), rtol=1e-2)         # log(1..16) a channel
    step = np.log1p(np.exp(np.asarray(lp["dt_bias"], np.float32)))
    assert 0.0008 < step.min() and step.max() < 0.12
    assert bool((np.asarray(lp["d"], np.float32) == 1).all())
    with pytest.raises(ValueError, match="layer_pattern"):
        init_params(dataclasses.replace(c, layer_pattern="S-M-S-S-*-"),
                    jax.random.PRNGKey(0))          # one recurrent kind


def test_published_sizes():
    c = configs.jamba2_3b()
    p = nh.pattern(c)
    assert len(p) == 56 and (p.count("S"), p.count("*"), p.count("-")) == (
        26, 2, 28)
    assert [i // 2 for i, k in enumerate(p) if k == "*"] == [7, 21]
    assert c.head_dim == 128 and c.kv_cache == "recurrent"
    assert nh.d_inner(c) == 5120 == nh.conv_dim(c)
    state, window = nh.row_pools(c, 33)
    assert state.shape == (26, 33, 16, 5120) and state.dtype == jnp.float32
    assert window.shape == (26, 3, 33, 5120)
    assert nh.page_pools(c, 10, 128)[0].shape == (2, 1, 10, 128, 128)
    shapes = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_029_337_472                       # 6.06 GB of bf16
    mamba = sum(int(np.prod(a.shape)) for a in
                jax.tree.leaves(shapes["layers"][0]))
    # norm, W_in, convolution, W_x, three inner norms, W_dt and its bias,
    # A_log, D, W_out
    assert mamba == (2560 + 2560 * 10240 + 5120 * 5 + 5120 * 192 + 192
                     + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560)


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("what,build", [
    ("speculation", lambda: _engine(speculation="ngram")),
    ("prefill pool", lambda: PrefillEngine(TINY, EngineConfig(
        max_slots=1, max_len=64, page_size=16, prompt_buckets=(16,)))),
])
def test_what_recurrent_state_does_not_run_with_names_the_field(what, build):
    # speculation meets the engine's one check, whatever the model
    with pytest.raises(ValueError, match="one decode loop" if what
                       == "speculation" else "layer_pattern"):
        build()
