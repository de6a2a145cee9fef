"""A looped stack on the serving engine (ModelConfig.loops: the passes of
models/transformer.py and of the per-head programs of llm/engine.py over a
pool of loops x n_layers cache layers), on the CPU in float32 with seeded
weights, against the benchmark's plain reference
(perfbench/reference/ouro.py), which imports nothing of the program, keeps
no cache and runs every pass over all positions at once.
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
from ray_tpu.models import (configs, forward, init_params, loss_fn,
                            model_module)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5      # float32 on both sides; sums in another order, 12 blocks
PAGE = 8
TINY = configs.tiny_ouro()      # 3 layers x 4 passes: 12 cache layers


@pytest.fixture(scope="module")
def reference():
    from perfbench.harness import cells
    return cells.load_module(
        os.path.join(ROOT, "perfbench", "reference", "ouro.py"))


@functools.lru_cache(maxsize=None)
def _params(c=TINY, seed=7):
    """Seeded weights with every norm's weight and the gate's bias moved
    off their initial 1 and 0, so that a norm left out or misplaced
    shows."""
    params = init_params(c, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def moved(a):
        return jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)

    layers = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm"):
        if name in layers:
            layers[name] = moved(layers[name])
    out = {**params, "layers": layers,
           "final_norm": moved(params["final_norm"])}
    if "exit_gate" in params:
        out["exit_gate"] = {"w": params["exit_gate"]["w"],
                            "b": jnp.asarray(-1.0, params["embed"].dtype)}
    return out


def _engine(c=TINY, **kw):
    e = dict(max_slots=3, max_len=160, page_size=PAGE,
             prompt_buckets=(16, 32), eos_token=-1)
    return InferenceEngine(c, EngineConfig(**{**e, **kw}),
                           params=_params(c))


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _run(eng):
    while eng.has_work():
        eng.step()


def _diff(reference, eng, prompt, req, c=None, faults=()):
    want, _ = reference.logprobs_of(eng.params, c or eng.c, prompt,
                                    req.generated, faults)
    assert len(req.generated) == len(req.token_logprobs) == len(want)
    return float(np.abs(np.array(req.token_logprobs) - np.array(want)).max())


# ------------------------------------------------------ program = reference


def test_forward_is_the_reference(reference):
    tokens = jnp.asarray([_ids(40, 1), _ids(40, 2)], jnp.int32)
    got = forward(_params(), tokens, TINY)
    want = reference.logits_of(_params(), TINY, tokens)
    assert got.shape == (2, 40, TINY.vocab)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("n_prompt,new,hits", [
    (5, 30, 0),      # decoded across three page boundaries
    (16, 5, 0),      # exactly a bucket and two whole pages
    (27, 12, 0),     # padded to 32
    (50, 8, 1),      # a chunk, then a continuation over its cached pages
    (70, 20, 2),     # three chunks, then decode
])
def test_engine_logprobs_match_the_reference(reference, n_prompt, new, hits):
    """Prefill (whole, or in chunks over cached pages), then decode through
    the paged cache: every generated token's log-probability is the
    reference's full forward pass's."""
    eng = _engine()
    ids = _ids(n_prompt, n_prompt)
    req = eng.request(eng.add_request(ids, new, 0.0, logprobs=True))
    _run(eng)
    assert len(req.generated) == new
    assert _diff(reference, eng, ids, req) < TOL
    st = eng.kv_stats()
    assert st["prefix_hits"] == hits
    assert st["pages_in_use"] == 0


def test_requests_of_unequal_length_admitted_together(reference):
    eng = _engine()
    prompts = [_ids(n, 100 + n) for n in (3, 14, 9)]
    reqs = [eng.request(eng.add_request(p, 22, 0.0, logprobs=True))
            for p in prompts]
    eng.step()
    assert eng.active.all()     # one admission, one prefill of three rows
    _run(eng)
    for p, r in zip(prompts, reqs):
        assert _diff(reference, eng, p, r) < TOL


def test_a_prefix_hit_reads_every_pass_own_pages(reference):
    """A second request over the first one's cached prompt pages: the
    suffix's pass t attends cache layers t * L .. of the prefix."""
    eng = _engine()
    ids = _ids(30, 9)
    first = eng.request(eng.add_request(ids, 4, 0.0, logprobs=True))
    _run(eng)
    again = eng.request(eng.add_request(ids[:24] + _ids(5, 10), 6, 0.0,
                                        logprobs=True))
    _run(eng)
    assert eng.kv_stats()["prefix_hits"] == 1
    assert _diff(reference, eng, ids, first) < TOL
    assert _diff(reference, eng, ids[:24] + _ids(5, 10), again) < TOL


def test_a_looped_stack_with_experts_counts_every_pass():
    """The expert layers' counters ride the loop over passes: a model with
    experts, run three times, through prefill (one chunk and a
    continuation) and paged decode against its own forward()."""
    c = configs.tiny_moe(loops=3, post_norms=True, tie_embeddings=False)
    eng = _engine(c, max_slots=2, prompt_buckets=(16,))
    prompts = [_ids(7, 1), _ids(30, 2)]
    reqs = [eng.request(eng.add_request(p, 12, 0.0, logprobs=True))
            for p in prompts]
    _run(eng)
    for p, r in zip(prompts, reqs):
        logp = jax.nn.log_softmax(forward(
            eng.params, jnp.asarray([p + r.generated]), c)[0], -1)
        want = [float(logp[len(p) - 1 + i, t])
                for i, t in enumerate(r.generated)]
        assert np.abs(np.array(want) - np.array(r.token_logprobs)
                      ).max() < TOL
    st = eng.moe_stats()
    # every real row of every pass and layer is routed: 37 prompt tokens
    # and 2 x 11 decoded ones (the twelfth token is sampled, never fed)
    assert st["routed_tokens"] == (37 + 22) * c.loops * c.n_layers


FAULTS = {
    "three_passes_of_four": (dict(loops=3), ()),
    "every_pass_on_pass_0s_cache_layers": ({}, ("shared_cache",)),
    "closing_norm_left_out_of_the_stream": ({}, ("open_stream",)),
    "two_norms_a_layer": (dict(post_norms=False), ()),
    "k_and_v_through_float8": ({}, ("kv_f8",)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_reference_refuses_a_wrong_stack(reference, fault):
    """What the comparison is worth, and what pins the readings the
    config's keys do not settle: a reference handed fewer passes, one
    cache for every pass, a stream the closing norm is left out of, two
    norms a layer, or 8-bit keys and values lies far from the program,
    which meets the honest one."""
    fields, switches = FAULTS[fault]
    eng = _engine()
    ids = _ids(27, 27)
    req = eng.request(eng.add_request(ids, 12, 0.0, logprobs=True))
    _run(eng)
    assert _diff(reference, eng, ids, req) < TOL
    wrong = dataclasses.replace(eng.c, **fields)
    assert _diff(reference, eng, ids, req, wrong, switches) > 200 * TOL


def test_the_reference_refuses_a_wrong_token(reference):
    eng = _engine()
    ids = _ids(27, 27)
    req = eng.request(eng.add_request(ids, 12, 0.0, logprobs=True))
    _run(eng)
    wrong = ids[:13] + [(ids[13] + 1) % 256] + ids[14:]
    assert _diff(reference, eng, wrong, req) > 200 * TOL


# ------------------------------------------------------------ the exit rule


@pytest.mark.parametrize("threshold", [0.5, 1.0])
def test_the_exit_rule_is_the_references(reference, threshold):
    """At 1.0 every position reads the last pass; at 0.5 the gate sends
    positions to different passes, the same ones in both programs, through
    forward() and through prefill and paged decode."""
    c = dataclasses.replace(TINY, exit_threshold=threshold)
    tokens = jnp.asarray([_ids(48, 3)], jnp.int32)
    _, gates = reference.states_of(_params(), c, tokens)
    at = np.asarray(reference.exit_pass(gates, threshold))
    if threshold == 1.0:
        assert (at == c.loops - 1).all()
    else:
        assert len(set(at.ravel().tolist())) >= 3
    got = forward(_params(), tokens, c)
    assert float(jnp.abs(got - reference.logits_of(_params(), c, tokens)
                         ).max()) < TOL
    eng = _engine(c)
    ids = _ids(21, 5)
    req = eng.request(eng.add_request(ids, 20, 0.0, logprobs=True))
    _run(eng)
    assert _diff(reference, eng, ids, req) < TOL
    # and the other threshold's reading is another function
    other = dataclasses.replace(c, exit_threshold=1.5 - threshold)
    assert _diff(reference, eng, ids, req, other) > 200 * TOL


def test_exit_pass_by_hand(reference):
    """lambda = (0.2, 0.5, 0.9, .): p = 0.2, 0.4, 0.36, 0.04."""
    lam = np.array([0.2, 0.5, 0.9, 0.3])
    gates = jnp.asarray(np.log(lam / (1 - lam)), jnp.float32)[:, None]
    for threshold, want in ((0.1, 0), (0.2, 0), (0.5, 1), (0.61, 2),
                            (0.97, 3), (1.0, 3)):
        assert int(reference.exit_pass(gates, threshold)[0]) == want


# ------------------------------------------- what a sequence keeps, by name


def test_the_pool_has_a_cache_layer_a_pass_and_layer():
    eng = _engine()
    c = eng.c
    assert c.cache_layers == c.loops * c.n_layers == 12
    assert eng.cache_k.shape == eng.cache_v.shape == (
        12, c.n_kv_heads, eng.num_pages, c.head_dim, PAGE)
    st = eng.kv_stats()
    assert st["cache_layers"] == 12
    # K and V, 12 cache layers, 4 heads of 16, 8 tokens, float32
    assert st["page_bytes"] == 2 * 12 * 4 * 16 * PAGE * 4
    once = InferenceEngine(
        configs.tiny(), EngineConfig(max_slots=2, max_len=32, page_size=PAGE,
                                     prompt_buckets=(16,))).kv_stats()
    assert once["cache_layers"] == 2
    assert once["page_bytes"] == 2 * 2 * 2 * 16 * PAGE * 4


def test_every_pass_writes_its_own_cache_layers():
    """After one prompt the pages it holds are written in all 12 cache
    layers, and no two passes' layers hold the same keys."""
    eng = _engine()
    eng.add_request(_ids(12, 1), 2, 0.0)
    eng.step()
    pid = eng.slot_pages[0][0]
    k = np.asarray(eng.cache_k[:, :, pid])          # [12, hkv, hd, page]
    assert (np.abs(k).reshape(12, -1).max(axis=1) > 0).all()
    L = eng.c.n_layers
    for t in range(1, eng.c.loops):
        assert np.abs(k[t * L:(t + 1) * L] - k[:L]).max() > 1e-3
    _run(eng)


# ------------------------------------------------- the programs, as lowered


def _decode_program(c):
    """(the decode program's jaxpr, its lowered text) at test size."""
    eng = _engine(c)
    B = eng.e.max_slots

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params, pools = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (eng.params, eng._pools()))
    fn = functools.partial(engine_mod.decode_paged, config=c)
    args = (params, *pools, i32(B), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B, 4))
    return (jax.make_jaxpr(fn)(*args).jaxpr, jax.jit(
        fn, donate_argnums=(1, 2)).lower(*args).as_text(debug_info=True))


def _loops(jaxpr):
    return [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]


def test_the_passes_are_one_loop_that_carries_the_pools():
    """The looped decode program: ONE loop at the top of the program, of
    `loops` turns, whose carry holds both pools (a carry is updated where
    it lies; as a scan's xs / ys the pool would be copied), the layers
    unrolled inside it: a kernel call a layer, not one a pass and layer,
    under the names a trace reads."""
    jaxpr, text = _decode_program(TINY)
    (loop,) = _loops(jaxpr)
    assert loop.params["length"] == TINY.loops
    pool = _engine().cache_k.shape
    n_carry = loop.params["num_carry"]
    carried = [v.aval.shape for v in loop.outvars[:n_carry]]
    assert carried.count(pool) == 2
    assert all(v.aval.shape != pool for v in loop.outvars[n_carry:])
    body = loop.params["jaxpr"].jaxpr
    kernels = [e for e in body.eqns if "looped_paged_decode" in str(e)]
    assert len(kernels) == TINY.n_layers and not _loops(body)
    assert "looped_paged_decode" in text
    assert '"pass/' in text and '"exit_gate/' in text


def test_a_stack_that_runs_once_lowers_without_a_loop():
    """loops == 1: no loop over passes, no exit gate, the kernel under its
    old name (tests/test_engine_ahead.py holds the whole text to the
    parent's, byte for byte)."""
    jaxpr, text = _decode_program(configs.tiny(n_kv_heads=4))
    assert not _loops(jaxpr)
    assert "looped_paged_decode" not in text and "exit_gate" not in text


# ---------------------------------------------------------------- refusals


def _handoff():
    ks = np.zeros((TINY.cache_layers, 16, 4, 16), np.float32)
    return _engine().add_request(_ids(20, 1), 2, 0.0, kv_handoff=(ks, ks))


@pytest.mark.parametrize("what,make", [
    ("speculation", lambda: _engine(speculation="ngram")),
    ("PrefillEngine", lambda: PrefillEngine(
        TINY, EngineConfig(page_size=PAGE, prompt_buckets=(16,)))),
    ("kv_handoff", _handoff),
    ("import_kv", lambda: _engine().import_kv(
        _ids(20, 1), np.zeros((12, 16, 4, 16)), np.zeros((12, 16, 4, 16)))),
    ("loss_fn", lambda: loss_fn(
        _params(), {"tokens": jnp.zeros((1, 9), jnp.int32)}, TINY)),
])
def test_what_a_looped_stack_does_not_run_names_the_field(what, make):
    # speculation meets the engine's one check, whatever the model
    with pytest.raises(ValueError, match="one decode loop" if what
                       == "speculation" else r"ModelConfig\.loops=4"):
        make()


def test_refuses_a_mesh():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match=r"ModelConfig\.loops=4.*mesh"):
        InferenceEngine(TINY, EngineConfig(page_size=PAGE), mesh=mesh)


@pytest.mark.parametrize("kind", ["tiny_mla", "tiny_hybrid", "tiny_laguna"])
def test_the_other_modules_refuse_a_loop(kind):
    c = getattr(configs, kind)(loops=2)
    with pytest.raises(ValueError, match=r"ModelConfig\.loops=2"):
        model_module(c)


# the benchmark's own cases for this configuration (tier-1 collects
# `tests/` alone): its cost function by hand, its file against the catalog,
# its cell and tools/checkdist_ouro.py at rehearsal sizes
from perfbench.tests.test_ouro import *  # noqa: E402,F401,F403
