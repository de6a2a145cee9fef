"""DeepSeek-V2 on the serving engine (models/deepseek_v2.py, the latent page
pool of llm/engine.py, ops/latent_attention.py), on the CPU in float32 with
seeded weights, against the benchmark's plain reference
(perfbench/reference/deepseek_v2.py), which imports nothing of the program.
"""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.llm.engine import PrefillEngine
from ray_tpu.models import (configs, deepseek_v2 as ds, experts, forward,
                            init_params)
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.attention import prefill_attention_reference
from ray_tpu.ops.layers import rope, yarn_frequencies

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 on both sides; sums in another order


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "deepseek_v2.py"))


# The chip's share at test size: 2 of 8 routed experts (group 1 of 4).
SHARE = configs.tiny_mla(moe_experts=2, moe_held_group=1)


def _engine(**kw):
    e = dict(max_slots=2, max_len=128, page_size=16,
             prompt_buckets=(16, 32), eos_token=-1)
    return InferenceEngine(SHARE, EngineConfig(**{**e, **kw}), seed=3)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng):
    while eng.has_work():
        eng.step()


@pytest.mark.parametrize("n_prompt,new,hits", [
    (20, 6, 0),      # one chunk, then decode across a page edge
    (70, 20, 2),     # three chunks: two continue over cached latent pages
])
def test_engine_logprobs_match_the_reference(reference, n_prompt, new, hits):
    eng = _engine()
    ids = _ids(n_prompt, n_prompt)
    req = eng.request(eng.add_request(ids, new, 0.0, logprobs=True))
    _run(eng)
    want, _ = reference.logprobs_of(eng.params, SHARE, ids, req.generated)
    assert len(req.generated) == new == len(req.token_logprobs)
    assert eng.kv_stats()["prefix_hits"] == hits
    np.testing.assert_allclose(req.token_logprobs, want, atol=TOL)


def test_a_preempted_request_resumes_on_the_reference(reference):
    """Three usable pages for two requests that need two each: one is
    preempted when the pool runs dry and re-prefills what it had seen."""
    eng = _engine(num_pages=4)
    prompts = [_ids(10, 1), _ids(10, 2)]
    reqs = [eng.request(eng.add_request(p, 20, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    assert eng.kv_stats()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        # preemption moved generated tokens into r.prompt; p is the original
        want, _ = reference.logprobs_of(eng.params, SHARE, p, r.generated)
        assert len(r.generated) == 20 == len(r.token_logprobs)
        np.testing.assert_allclose(r.token_logprobs, want, atol=TOL)


def test_absorbed_form_equals_up_projected_form():
    c = SHARE
    lp = init_params(c, jax.random.PRNGKey(0))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, c.d_model))
    sin, cos = rope(jnp.arange(24), c.qk_rope_head_dim, c.rope_theta,
                    c.rope_scaling)
    q_nope, q_pe, lat = ds._mla_project(x, lp, c, sin[None], cos[None])
    up = ds._attend_up_projected(q_nope, q_pe, lat,
                                 jnp.zeros((2,), jnp.int32),
                                 jnp.full((2,), 24, jnp.int32), lp, c, 0)
    np.testing.assert_allclose(
        up, ds.attend_absorbed_dense(q_nope, q_pe, lat, lp, c), atol=TOL)


def test_the_groups_shares_and_the_shared_experts_once_make_the_layer(
        reference):
    """Every chip's routed part (4 groups of 2 experts) plus what all
    compute alike, the shared experts, counted once = the uncut layer."""
    whole = configs.tiny_mla()
    lp = init_params(whole, jax.random.PRNGKey(0))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (40, whole.d_model))
    valid = jnp.ones((40,), bool)
    want, _ = reference._experts(x, lp, whole)
    got, _ = ds.expert_layer(x, lp, whole, valid)
    np.testing.assert_allclose(got, want, atol=TOL)
    shared = ds.swiglu(x[None], lp["shared_wg"], lp["shared_wu"],
                       lp["shared_wd"])[0]
    parts = jnp.zeros_like(x)
    for g in range(4):
        c = dataclasses.replace(whole, moe_experts=2, moe_held_group=g)
        cut = {**lp, **{k: lp[k][2 * g:2 * g + 2]
                        for k in ("wg", "wu", "wd")}}
        y, _ = ds.expert_layer(x, cut, c, valid)
        np.testing.assert_allclose(y, reference._experts(x, cut, c)[0],
                                   atol=TOL)
        parts = parts + (y - shared)
    np.testing.assert_allclose(parts + shared, want, atol=TOL)


def test_dispatch_in_passes_equals_one_pass(monkeypatch):
    """A batch longer than a pass's rows takes its pairs in several."""
    c = configs.tiny_mla()
    lp = init_params(c, jax.random.PRNGKey(0))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (48, c.d_model))
    valid = jnp.arange(48) < 40
    one, s1 = ds.expert_layer(x, lp, c, valid)
    monkeypatch.setattr(experts, "_MIN_PASS_ROWS", 8)    # 48 rows a pass of 144
    many, s2 = ds.expert_layer(x, lp, c, valid)
    np.testing.assert_allclose(many, one, atol=TOL)
    np.testing.assert_array_equal(s1, s2)
    assert int(s1[0]) == 40 and int(s1[1]) == 40 * c.moe_top_k


def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, 2 groups a token, top 3. Expert 6 has
    the third-highest score, but its group (3) is only third among the
    groups: it is not chosen; the weights are the softmax values times the
    scaling factor, not renormalised."""
    c = configs.tiny_mla(d_model=8)
    logits = jnp.array([[3.0, 0.1, 2.5, 2.0, 0.0, 0.2, 2.2, 0.3]])
    lp = {"router": jnp.eye(8)}
    w, idx = ds.route(logits, lp, c)
    g = np.exp(logits[0]) / np.exp(logits[0]).sum()
    assert sorted(idx[0].tolist()) == [0, 2, 3]
    np.testing.assert_allclose(sorted(w[0].tolist()),
                               sorted(g[[0, 2, 3]] * 4.0), rtol=1e-6)
    norm = dataclasses.replace(c, moe_norm_topk=True)
    w, _ = ds.route(logits, lp, norm)
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=1e-6)


def test_yarn_frequencies_and_scale_by_hand():
    """From DeepSeek-V2's config: dim 64, base 10000, factor 40 over 4096,
    beta_fast 32, beta_slow 1. Correction dims: 64 ln(4096 / (32 * 2 pi)) /
    (2 ln 10000) = 10.47 -> low 10; with 1 rotation 22.5 -> high 23."""
    c = configs.deepseek_v2()
    freqs, amp = yarn_frequencies(64, 10000.0, dict(c.rope_scaling))
    base = 10000.0 ** (np.arange(32) / 32)
    assert amp == 1.0                         # mscale / mscale_all_dim
    np.testing.assert_allclose(freqs[:11], 1 / base[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], 1 / (40 * base[23:]), rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)              # frequency 16, in between
    np.testing.assert_allclose(
        freqs[16], ramp / (40 * base[16]) + (1 - ramp) / base[16], rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    np.testing.assert_allclose(ds.softmax_scale(c), 192 ** -0.5 * m * m,
                               rtol=1e-6)
    sin, cos = rope(jnp.array([0, 7]), 64, 10000.0, c.rope_scaling)
    np.testing.assert_allclose(sin[1], np.sin(7 * freqs), atol=1e-6)
    plain = dataclasses.replace(c, rope_scaling=None)
    np.testing.assert_allclose(ds.softmax_scale(plain), 192 ** -0.5)


# One turn of the decode kernel attends a block of G pages; G = 4 here
# (`_BLOCK_BYTES` set to two blocks of four toy pages), page = 16.
_BLOCK_CASES = {
    # pages a slot: 1, 3, 4 = G, whose end is a page's and the block's
    "below_and_equal_to_a_block": ([5, 33, 64], 4),
    # 5 = G + 1 (a last block of one page), 5 ending on a page, 4 + a row
    "a_block_and_a_page": ([65, 80, 70], 5),
    # 10, 10, 8 pages: three turns with a ragged last one, an end on a
    # page inside a block, an end on a block's edge
    "several_blocks_a_ragged_last": ([147, 160, 128], 11),
    "an_empty_slot_between_two_live": ([70, 0, 100], 7),
    "empty_slots_first_and_last": ([0, 90, 0], 7),
    "every_slot_empty": ([0, 0, 0], 4),
    # the table names fewer pages than a block holds: G = 2
    "a_table_narrower_than_a_block": ([20, 32, 1], 2),
    # a length past the table's end attends what the table names
    "a_length_past_the_table": ([200, 17, 96], 6),
}


@pytest.mark.parametrize("case", list(_BLOCK_CASES))
def test_latent_decode_kernel_matches_jnp(case, monkeypatch):
    """Every page no slot holds (the scratch page 0 and three more) and
    every page of the other layer is NaN in the kernel's pool: a page
    fetched and not masked, or not fetched and attended, makes the output
    NaN (0 * NaN in p . c_kv), whatever the mask did to its score."""
    lens, P = _BLOCK_CASES[case]
    L, W, page, h, rank = 2, 24, 16, 4, 16
    monkeypatch.setattr(la, "_BLOCK_BYTES", 2 * 4 * W * page * 4)
    assert la._block_pages(W, page, 4, P) == min(4, P)
    held = [min(-(-n // page), P) for n in lens]
    N = 1 + sum(held) + 3
    ids = np.random.RandomState(3).permutation(np.arange(1, N))
    tables = np.zeros((len(lens), P), np.int32)
    for b, n in enumerate(held):
        tables[b, :n], ids = ids[:n], ids[n:]
    unheld = jnp.asarray(np.concatenate([[0], ids]))
    clean = jax.random.normal(jax.random.PRNGKey(0), (L, N, W, page))
    clean = clean.at[:, unheld].set(0.0)
    pool = clean.at[:, unheld].set(jnp.nan).at[0].set(jnp.nan)  # layer 1 runs
    q = jax.random.normal(jax.random.PRNGKey(1), (len(lens), h, W))
    lengths, tables = jnp.array(lens, jnp.int32), jnp.asarray(tables)
    kw = dict(layer=1, rank=rank, scale=0.3)
    got = np.asarray(
        la.paged_latent_decode_attention(q, pool, lengths, tables, **kw))
    want = np.asarray(
        la.paged_latent_decode_reference(q, clean, lengths, tables, **kw))
    live = np.array(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=TOL)
    assert not got[~live].any()             # an empty slot reads zeros


@pytest.mark.parametrize("width,page,itemsize,pages_per_seq,want", [
    (576, 128, 2, 68, 8),     # the longdoc cell: DeepSeek-V2 in bfloat16
    (576, 128, 4, 68, 4),     # a float32 pool: half the pages, same bytes
    (576, 128, 2, 4, 4),      # a page bucket narrower than a block
    (576, 128, 2, 1, 1),
    (24, 16, 4, 11, 11),      # toy pages: the table is the bound
    (4096, 512, 2, 68, 1),    # a page over the budget still goes alone
])
def test_the_decode_block_follows_the_shapes(width, page, itemsize,
                                             pages_per_seq, want):
    """G is a function of what the kernel is handed, under one budget."""
    assert la._block_pages(width, page, itemsize, pages_per_seq) == want
    assert 2 * want * width * page * itemsize <= la._BLOCK_BYTES or want == 1


@pytest.mark.parametrize("n,s,pre_t,plen", [
    (2, 64, 0, [0, 0]),           # no prefix
    (2, 48, 32, [32, 16]),        # ragged prefixes, unaligned chunk
    (2, 2048, 512, [512, 0]),     # several blocks, one straddling the
                                  # prefix; a request without prefix
])
def test_mla_prefill_kernel_matches_jnp(n, s, pre_t, plen):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (n, 3, s, 24))
    k = jax.random.normal(ks[1], (n, 3, pre_t + s, 24))
    v = jax.random.normal(ks[2], (n, 3, pre_t + s, 16))
    kw = dict(pre_t=pre_t, scale=0.2)
    plen = jnp.array(plen, jnp.int32)
    np.testing.assert_allclose(
        la.mla_prefill_attention(q, k, v, plen, **kw),
        prefill_attention_reference(q, k, v, plen, **kw), atol=TOL)


def test_init_params_makes_no_float32_leaf():
    c = dataclasses.replace(SHARE, dtype="bfloat16")
    leaves = jax.tree.leaves(init_params(c, jax.random.PRNGKey(0)))
    assert leaves and all(a.dtype == jnp.bfloat16 for a in leaves)
    with pytest.raises(ValueError, match="untied"):
        init_params(dataclasses.replace(c, tie_embeddings=True),
                    jax.random.PRNGKey(0))


def test_forward_is_the_reference(reference):
    params = init_params(SHARE, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    x, _ = reference.hidden_of(params, SHARE, np.asarray(toks[1]))
    np.testing.assert_allclose(forward(params, toks, SHARE)[1],
                               x @ params["lm_head"], atol=TOL)


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
def test_what_a_latent_cache_does_not_run_with_names_the_field(what, build):
    with pytest.raises(ValueError, match=what) as e:
        build()
    # speculation meets the engine's one check, whatever the model
    assert ("one decode loop" if what == "speculation"
            else "attention='mla'") in str(e.value)


@pytest.mark.parametrize("model", [configs.tiny(), SHARE],
                         ids=["gqa", "mla"])
def test_engine_has_one_kv_layout(model):
    """The field still constructs (the benchmark's harness passes it); an
    engine takes "paged" alone, whatever its model's attention."""
    e = EngineConfig(max_slots=2, max_len=64, kv_layout="dense")
    assert e.kv_layout == "dense"
    with pytest.raises(ValueError, match="EngineConfig.kv_layout") as err:
        InferenceEngine(model, e)
    assert f"ModelConfig.attention={model.attention!r}" in str(err.value)


def test_moe_stats_add_up():
    eng = _engine()
    assert InferenceEngine(configs.tiny(), EngineConfig(
        max_slots=2, max_len=64)).moe_stats() == {}
    ids = _ids(40, 5)                  # chunks of 32 and 8, then 5 decodes
    eng.add_request(ids, 6, 0.0)
    _run(eng)
    st = eng.moe_stats()
    assert st["routed_tokens"] == 40 + 5      # one expert layer
    assert st["expert_layer_calls"] == 2 + 5
    assert st["held_pairs"] == sum(st["held_expert_load"])
    assert 0 < st["held_pairs"] <= st["routed_tokens"] * SHARE.moe_top_k
    assert 0 <= st["tokens_without_held_expert"] <= st["routed_tokens"]
    assert st["load_max_over_mean"] >= 1.0
    assert eng.moe_stats() == st              # reading resets nothing
