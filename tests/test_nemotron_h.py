"""Nemotron-H on the serving engine (models/nemotron_h.py, the row pools of
recurrent state beside the page pools in llm/engine.py, ops/ssm.py,
models/experts.py), on the CPU in float32 with seeded weights, against the
benchmark's plain reference (perfbench/reference/nemotron_h.py), which
imports nothing of the program and runs the recurrence a position at a time.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import PrefillEngine
from ray_tpu.models import (configs, deepseek_v2 as ds, experts, forward,
                            init_params, nemotron_h as nh)
from ray_tpu.ops import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 on both sides; sums in another order

# The chip's share at test size: 2 of 8 routed experts (share 1 of 4).
SHARE = configs.tiny_hybrid(moe_experts=2, moe_held_group=1)


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "nemotron_h.py"))


def _engine(**kw):
    e = dict(max_slots=3, max_len=160, page_size=16, prompt_buckets=(16, 32),
             eos_token=-1)
    return InferenceEngine(SHARE, EngineConfig(**{**e, **kw}), seed=3)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng):
    while eng.has_work():
        eng.step()


def _diff(reference, eng, prompt, req):
    want, _ = reference.logprobs_of(eng.params, eng.c, prompt, req.generated)
    assert len(req.generated) == len(req.token_logprobs) == len(want)
    return float(np.abs(np.array(req.token_logprobs) - np.array(want)).max())


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


def test_several_admitted_together_at_different_lengths(reference):
    """One prefill batch of three prompts, two padded in bucket 32 and one
    in bucket 16 beside a chunked one: each ends in ITS last real token's
    state and window."""
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


def test_the_same_prompt_twice_gives_the_first_runs_logprobs(reference):
    eng = _engine()
    ids = _ids(50, 7)
    first = eng.request(eng.add_request(ids, 6, 0.0, logprobs=True))
    _run(eng)
    again = eng.request(eng.add_request(ids, 6, 0.0, logprobs=True))
    _run(eng)
    assert again.generated == first.generated
    np.testing.assert_allclose(again.token_logprobs, first.token_logprobs,
                               atol=TOL)
    assert _diff(reference, eng, ids, again) < TOL
    # the second run resumed at the 32-token chunk boundary both times
    assert eng.kv_stats()["snapshot_hits"] == 2


def test_a_prefix_hit_never_returns_pages_without_their_state():
    eng = _engine()
    ids = _ids(50, 9)                 # chunk of 32 (2 pages), then 18
    eng.add_request(ids, 3, 0.0)
    _run(eng)
    page = eng.e.page_size
    # all three full pages of the prompt are cached, the state only at 32
    assert all(eng._prefix_hash(ids[:k * page]) in eng.page_hash
               for k in (1, 2, 3))
    assert [len(eng._find_prefix(ids[:n])) for n in (17, 33, 50, 49)] == [
        0, 2, 2, 2]
    # the snapshot goes: the same pages are no hit at all
    eng._drop_snap(next(iter(eng.snap_of_hash.values())))
    assert eng._find_prefix(ids) == []
    assert eng.kv_stats()["snapshot_rows"] == 0


def test_a_snapshot_is_evicted_with_the_pages_it_stands_on():
    eng = _engine(num_pages=8)        # 7 usable pages
    a, b = _ids(50, 11), _ids(60, 12)
    eng.add_request(a, 2, 0.0)
    _run(eng)
    assert eng.kv_stats()["snapshot_rows"] == 1
    eng.add_request(b, 30, 0.0)       # needs 6 pages: a's are evicted
    _run(eng)
    assert eng._prefix_hash(a[:32]) not in eng.snap_of_hash
    assert eng._prefix_hash(a[:16]) not in eng.page_hash
    assert eng.kv_stats()["snapshot_evictions"] >= 1


@pytest.mark.parametrize("how", ["finish", "cancel", "preemption"])
def test_rows_return_to_zero_in_use(how):
    eng = _engine(num_pages=5 if how == "preemption" else None)
    rids = [eng.add_request(_ids(n, n), 24, 0.0) for n in (40, 12)]
    for _ in range(4):
        eng.step()
    st = eng.kv_stats()
    assert st["state_rows_in_use"] >= 1
    if how == "cancel":
        for rid in rids:
            eng.cancel(rid)
        eng.step()
    else:
        _run(eng)
    st = eng.kv_stats()
    if how == "preemption":
        assert st["preemptions"] >= 1
    assert st["state_rows_in_use"] == 0 == st["snapshot_rows_in_use"]
    assert st["pages_in_use"] == 0
    assert sorted(eng.free_snaps + list(eng.snap_lru)) == list(
        range(3, 3 + engine_mod.SNAPSHOT_ROWS))


@pytest.mark.parametrize("fault,refused", [
    ("none", False), ("state_bf16", True), ("wrong_snapshot", True)])
def test_the_state_itself_is_the_recurrence(reference, fault, refused):
    """perfbench/tools/checkstate.py's comparison: after a prompt of three
    chunks and 8 decoded tokens, the slot's row and both snapshots' rows
    hold the plain recurrence's state, layer by layer, to float32
    rounding; a state kept in bfloat16 and a snapshot of the boundary one
    page back are refused, with room."""
    from perfbench.tools import checkstate
    eng = _engine()
    prompt = _ids(70, 11)
    eng.add_request(prompt, 8, 0.0)
    req = eng.queue[0]
    _run(eng)
    d = checkstate.state_diffs(eng, reference, SHARE, prompt, req, fault)
    assert sorted(d["rows"]) == ["slot", "snapshot_32", "snapshot_64"]
    assert d["tokens_fed"] == 77 and req.slot == 0
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
    """The row pools move a row at a time, by dynamic slices. As ONE gather
    of four requests' rows and ONE scatter back, the prefill program over
    a cached prefix hung the v5e (chip probes of PR 33: [12, 81, 64, 64,
    128] float32, rows of 25 MB, four requests of 512 or 1024 tokens;
    cause not established, PERF.md section 7). So no program of the
    hybrid model may lower to a gather or a scatter whose operand or
    result is a row pool or rows of one."""
    c, n, s, rows = SHARE, 4, 32, 9
    params = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    pk, pv = nh.page_pools(c, 12, 16)
    ssm_pool, conv_pool = nh.row_pools(c, rows)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    stats = i32(nh.N_STATS + c.moe_experts)
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
    lm, _, h, p, st = ssm_pool.shape
    _, taps, _, cd = conv_pool.shape

    def pooled(shape):   # a pool, or any number of its rows
        return (len(shape) == 5 and (shape[0], *shape[2:]) == (lm, h, p, st)
                or len(shape) == 4 and (*shape[:2], shape[3]) == (lm, taps,
                                                                   cd))

    seen, moved = set(), []

    def walk(jp):
        for eqn in jp.eqns:
            seen.add(eqn.primitive.name)
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
    assert "dynamic_update_slice" in seen
    assert "dynamic_slice" in seen or program == "prefill_batch"


def test_forward_is_the_reference(reference):
    params = init_params(SHARE, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 29), 0, 256)
    x, _ = reference.hidden_of(params, SHARE, np.asarray(toks[1]))
    np.testing.assert_allclose(forward(params, toks, SHARE)[1],
                               x @ params["lm_head"], atol=TOL)


def test_moe_stats_and_state_counters_add_up():
    eng = _engine()
    eng.add_request(_ids(40, 5), 6, 0.0)    # chunks of 32 and 8, 5 decodes
    _run(eng)
    st = eng.moe_stats()
    assert st["routed_tokens"] == 3 * (40 + 5)     # three expert layers
    assert st["expert_layer_calls"] == 3 * (2 + 5)
    assert st["held_pairs"] == sum(st["held_expert_load"])
    kv = eng.kv_stats()
    assert kv["snapshot_rows"] == 1 == kv["snapshot_hits"]


# ------------------------------------------------------------ the kernels


def _ssm_inputs(n, s, seed=0, H=4, P=8, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (n, s, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (n, s, H))),
            -jnp.exp(jax.random.normal(k[2], (H,)) * 0.5),
            jax.random.normal(k[3], (n, s, G, N)),
            jax.random.normal(k[4], (n, s, G, N)),
            jax.random.normal(k[5], (n, H, P, N)))


@pytest.mark.parametrize("s,chunk", [(37, 8), (37, 16), (16, 16), (5, 64)])
def test_chunked_scan_is_the_sequential_recurrence(s, chunk):
    """Lengths off the chunk, a given initial state, and a padded row
    (dt = 0 past its length) whose state must stop at its last real
    position."""
    x, dt, a, b, c, st = _ssm_inputs(2, s)
    short = max(s - 7, 1)
    dt = jnp.where((jnp.arange(s)[None] < jnp.array([s, short])[:, None])
                   [..., None], dt, 0.0)
    y1, s1 = ssm.ssd_sequential(x, dt, a, b, c, st)
    y2, s2 = ssm.ssd_chunk_scan(x, dt, a, b, c, st, chunk=chunk)
    np.testing.assert_allclose(y2, y1, atol=1e-4)
    np.testing.assert_allclose(s2, s1, atol=1e-4)
    _, s3 = ssm.ssd_sequential(x[1:, :short], dt[1:, :short], a,
                               b[1:, :short], c[1:, :short], st[1:])
    np.testing.assert_allclose(s2[1:], s3, atol=1e-4)


@pytest.mark.parametrize("active", [
    [True, False, True, True], [False, False, True, False], [True] * 4])
def test_state_update_kernel_matches_jnp(active):
    """The Pallas call in interpret mode against the jnp form; rows of
    inactive slots, of other layers and past the slots do not move."""
    L, R, B = 3, 6, 4
    x, dt, a, b, c, _ = _ssm_inputs(B, 1, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(9), (L, R, 4, 8, 16))
    active = jnp.array(active)
    args = (pool, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], active)
    y_r, p_r = ssm.ssm_state_update(*args, layer=1, impl="reference")
    y_k, p_k = ssm.ssm_state_update(*args, layer=jnp.int32(1),
                                    impl="interpret")
    np.testing.assert_allclose(y_k, y_r, atol=TOL)
    np.testing.assert_allclose(p_k, p_r, atol=TOL)
    still = np.ones((L, R), bool)
    still[1, :B] = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(p_k)[still],
                                  np.asarray(pool)[still])
    assert not np.asarray(y_k)[~np.asarray(active)].any()


def test_convolution_over_a_batch_is_the_one_step_form():
    """causal_conv over padded rows = conv_step a position; the window it
    returns is each row's last three REAL inputs."""
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    u = jax.random.normal(k[0], (2, 9, 6))
    win = jax.random.normal(k[1], (2, 3, 6))
    w, bias = jax.random.normal(k[2], (6, 4)), jax.random.normal(k[3], (6,))
    lengths = jnp.array([9, 2])
    out, new = ssm.causal_conv(u, win, w, bias, lengths)
    for row, n in enumerate((9, 2)):
        cur = win[row:row + 1].transpose(1, 0, 2)     # taps outermost
        for t in range(n):
            o, cur = ssm.conv_step(u[row:row + 1, t], cur, w, bias)
            np.testing.assert_allclose(out[row, t], o[0], atol=TOL)
        np.testing.assert_allclose(new[row], cur[:, 0], atol=TOL)


# -------------------------------------------------------- the expert layer


def test_the_eight_shares_and_the_shared_expert_once_make_the_layer(
        reference):
    whole = configs.tiny_hybrid()
    lp = init_params(whole, jax.random.PRNGKey(0))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (40, whole.d_model))
    valid = jnp.ones((40,), bool)
    want, _ = reference._experts(x, lp, whole)
    got, _ = experts.expert_layer(x, lp, whole, valid)
    np.testing.assert_allclose(got, want, atol=TOL)
    shared = experts.shared_expert(x, lp, whole)
    parts = jnp.zeros_like(x)
    for g in range(8):
        c = dataclasses.replace(whole, moe_experts=1, moe_held_group=g)
        cut = {**lp, **{k: lp[k][g:g + 1] for k in ("wu", "wd")}}
        y, _ = experts.expert_layer(x, cut, c, valid)
        np.testing.assert_allclose(y, reference._experts(x, cut, c)[0],
                                   atol=TOL)
        parts = parts + (y - shared)
    np.testing.assert_allclose(parts + shared, want, atol=TOL)


@pytest.mark.parametrize("dense_rows,pass_rows,tile_rows,turn_bytes", [
    (4096, 4096, 64, None),  # few tokens: every held expert, every token
    (4096, 4096, 64, 0),     # few tokens, a turn costs nothing: the walk
    (0, 4096, 64, None),     # one pass, one block of rows: a tile an expert
    (0, 4096, 8, None),      # blocks that hold rows of several experts
    (0, 48, 8, None),        # the pairs in three passes of 48 rows
])
def test_the_forms_of_the_held_experts_product_agree(
        reference, monkeypatch, dense_rows, pass_rows, tile_rows,
        turn_bytes):
    """`moe_grouped="tiles"`: the dense form (decode), the walk over the
    hit experts (which experts as small as the cell's never get: a relu2
    model's big ones would), the tile walk, and the tile walk in passes
    all equal the reference's loop over experts; padding routes nowhere;
    the stats are the same counts."""
    monkeypatch.setattr(experts, "_DENSE_ROWS", dense_rows)
    if turn_bytes is not None:
        monkeypatch.setattr(experts, "_TURN_BYTES", turn_bytes)
    monkeypatch.setattr(experts, "_MIN_PASS_ROWS", pass_rows)
    monkeypatch.setattr(experts, "_SMALL_TILE_ROWS", tile_rows)
    c = configs.tiny_hybrid()
    lp = init_params(c, jax.random.PRNGKey(0))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (48, c.d_model))
    real = 40 if turn_bytes is None else 2    # 2 rows hit 6 of 8 at most
    valid = jnp.arange(48) < real
    got, st = experts.expert_layer(x, lp, c, valid)
    want, _ = reference._experts(x, lp, c)
    shared = experts.shared_expert(x, lp, c)
    np.testing.assert_allclose(got[:real], want[:real], atol=TOL)
    np.testing.assert_allclose(got[real:], shared[real:], atol=TOL)
    assert int(st[0]) == real and int(st[1]) == real * c.moe_top_k
    assert int(st[1]) == int(st[experts.N_STATS:].sum())
    # and XLA's ragged-dot over the sorted pairs (deepseek_v2's way)
    old, st_old = experts.expert_layer(
        x, lp, dataclasses.replace(c, moe_grouped="ragged_dot"), valid)
    np.testing.assert_allclose(got, old, atol=TOL)
    few = slice(4, 6)       # the few-token form's two counters
    np.testing.assert_array_equal(np.delete(st, few), np.delete(st_old, few))
    assert list(st_old[few]) == [0, 0]
    hit = int((np.asarray(st[experts.N_STATS:]) > 0).sum())
    assert list(st[few]) == ([1, c.moe_experts if turn_bytes is None
                              else hit] if dense_rows else [0, 0])


def test_sigmoid_router_by_hand():
    """8 experts, top 3. The bias lifts expert 5 into the choice over
    expert 2, whose score is higher; the weights are the scores WITHOUT the
    bias, divided by their sum, then times 2.5."""
    c = configs.tiny_hybrid(d_model=8)
    logits = jnp.array([[2.0, -1.0, 0.5, 1.5, -2.0, 0.2, -0.5, -3.0]])
    bias = jnp.zeros((8,)).at[5].set(0.2)
    lp = {"router": jnp.eye(8), "router_bias": bias}
    w, idx = experts.route(logits, lp, c)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert s[2] > s[5] and s[5] + 0.2 > s[2]
    assert sorted(idx[0].tolist()) == [0, 3, 5]
    by_expert = dict(zip(idx[0].tolist(), w[0].tolist()))
    for e in (0, 3, 5):
        np.testing.assert_allclose(
            by_expert[e], 2.5 * s[e] / s[[0, 3, 5]].sum(), rtol=1e-6)
    # without the bias the choice is by score
    _, idx = experts.route(logits, {**lp, "router_bias": 0 * bias}, c)
    assert sorted(idx[0].tolist()) == [0, 2, 3]
    # DeepSeek-V2's way (models/deepseek_v2.py): scaled only where the
    # weights are not renormalised
    v2 = dataclasses.replace(c, moe_score="softmax", moe_scale_normed=False)
    w, _ = experts.route(logits, lp, v2)
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-6)


def test_relu2_expert_by_hand():
    x = jnp.array([[1.0, -2.0]])
    w_up = jnp.array([[1.0, 0.5, -1.0], [0.5, 1.0, 1.0]])   # -> [0, -1.5, -3]
    w_up = w_up.at[0, 0].set(3.0)                           # -> [2, -1.5, -3]
    w_down = jnp.array([[1.0, 0.0], [5.0, 5.0], [7.0, 7.0]])
    np.testing.assert_allclose(experts.relu2_mlp(x, w_up, w_down),
                               [[4.0, 0.0]], atol=1e-6)
    lp = {"shared_wu": w_up, "shared_wd": w_down}
    np.testing.assert_allclose(
        experts.shared_expert(x, lp, configs.tiny_hybrid()), [[4.0, 0.0]],
        atol=1e-6)


def test_the_lifted_expert_layer_is_deepseek_v2s():
    """models/deepseek_v2.py's names are the shared layer's, and its
    softmax / SwiGLU path takes none of what was widened."""
    assert ds.expert_layer is experts.expert_layer
    assert ds.route is experts.route and ds.N_STATS == experts.N_STATS
    c = configs.tiny_mla()
    lp = init_params(c, jax.random.PRNGKey(0))["layers"][1]
    assert "router_bias" not in lp and "shared_wg" in lp
    x = jax.random.normal(jax.random.PRNGKey(2), (12, c.d_model))
    from perfbench.harness import cells
    ds_reference = cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "deepseek_v2.py"))
    got, _ = experts.expert_layer(x, lp, c, jnp.ones((12,), bool))
    np.testing.assert_allclose(got, ds_reference._experts(x, lp, c)[0],
                               atol=TOL)


# ------------------------------------------------------ params, refusals


def test_init_params_makes_no_float32_leaf():
    c = dataclasses.replace(SHARE, dtype="bfloat16")
    leaves = jax.tree.leaves(init_params(c, jax.random.PRNGKey(0)))
    assert leaves and all(a.dtype == jnp.bfloat16 for a in leaves)
    # a tied head (Jamba's) keeps no matrix of its own
    tied = init_params(dataclasses.replace(c, tie_embeddings=True),
                       jax.random.PRNGKey(0))
    assert "lm_head" not in tied and "lm_head" in init_params(
        c, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="layer_pattern"):
        init_params(dataclasses.replace(c, layer_pattern="MEX"),
                    jax.random.PRNGKey(0))


def test_published_sizes():
    c = configs.nemotron3_nano_30b()
    assert c.head_dim == 128 and c.kv_cache == "recurrent"
    assert nh.d_inner(c) == 4096 and nh.conv_dim(c) == 6144
    p = nh.pattern(c)
    assert (p.count("M"), p.count("E"), p.count("*")) == (23, 23, 6)
    state, window = nh.row_pools(c, 1)
    assert state.shape == (23, 1, 64, 64, 128) and state.dtype == jnp.float32
    assert window.shape == (23, 3, 1, 6144)
    assert configs.tiny().head_dim == 16       # d_model / heads as before


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("what,build", [
    ("speculation", lambda: _engine(speculation="ngram")),
    ("tensor parallelism", lambda: InferenceEngine(
        SHARE, EngineConfig(max_slots=2, max_len=64), mesh=_tp_mesh())),
    ("prefill pool", lambda: PrefillEngine(SHARE)),
    ("KV handoff", lambda: _engine().add_request(
        [1, 2, 3], kv_handoff=(None, None))),
])
def test_what_recurrent_state_does_not_run_with_names_the_field(what, build):
    with pytest.raises(ValueError, match=what) as e:
        build()
    # speculation meets the engine's one check, whatever the model
    assert ("one decode loop" if what == "speculation"
            else "layer_pattern='MEM*EME'") in str(e.value)
