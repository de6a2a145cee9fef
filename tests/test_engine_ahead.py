"""InferenceEngine.step() runs its decode loop one step ahead of the host
(llm/engine.py `_Flight`, `_may_lead`, `_land`): every request's tokens and
log-probabilities against an engine that fetches a step before it
dispatches the next, for a per-head, a latent and a hybrid model, on the
CPU at tiny sizes; and the lowered text of the serving programs, which the
change of schedule must not touch.
"""

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.llm.guided import TokenGuide
from ray_tpu.models import configs

pytestmark = pytest.mark.heavy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = {
    "per_head": configs.tiny(),
    "latent": configs.tiny_mla(moe_experts=2, moe_held_group=1),
    "hybrid": configs.tiny_hybrid(moe_experts=2, moe_held_group=1),
}
KINDS = sorted(MODELS)
# the per-head kind with experts on one device: its prefill programs alone,
# at a shape they walk (`WALKED` below)
EXPERTS = {"experts": configs.tiny_moe()}
# float32 on both sides; a request that was preempted at another token
# re-prefills another length, and sums in another order
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params(kind):
    return InferenceEngine({**MODELS, **EXPERTS}[kind], EngineConfig(
        max_slots=1, max_len=32, page_size=16, prompt_buckets=(16,)),
        seed=3).params


def _engine(kind, ahead=True, **kw):
    e = dict(max_slots=3, max_len=160, page_size=16, prompt_buckets=(16, 32),
             eos_token=-1)
    eng = InferenceEngine({**MODELS, **EXPERTS}[kind],
                          EngineConfig(**{**e, **kw}), params=_params(kind))
    if not ahead:
        # the reference loop: every step is fetched before the next is
        # dispatched, from the tokens the host holds
        eng._may_lead = lambda: False
    return eng


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _drive(eng, script, watch=None):
    """Call step() until nothing is left; `script` {call: [fn(eng)]} runs
    before that call (an `add` keeps its Request in `reqs`); `watch(eng)`
    after every call. -> the requests, in the order they came."""
    reqs, k = [], 0
    while eng.has_work() or any(c >= k for c in script):
        for fn in script.get(k, ()):
            r = fn(eng)
            if r is not None:
                reqs.append(eng.request(r))
        eng.step()
        if watch is not None:
            watch(eng)
        k += 1
        assert k < 2000
    assert eng._flight is None
    return reqs


def add(n_prompt, new, seed, **kw):
    return lambda eng: eng.add_request(_ids(n_prompt, seed), new, 0.0,
                                       logprobs=True, **kw)


def _same(got, want, exact=True):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.done and w.done
        assert g.generated == w.generated
        assert len(g.token_logprobs) == len(g.generated)
        if exact:
            assert g.token_logprobs == w.token_logprobs
        else:
            np.testing.assert_allclose(g.token_logprobs, w.token_logprobs,
                                       atol=TOL)


# ---------------------------------------------- ahead = fetched-then-dispatched


@pytest.mark.parametrize("kind", KINDS)
def test_admissions_mid_stream_and_ends_by_count(kind):
    """Requests join a running batch (one into a free slot, one that waits
    for a slot to end by max_new_tokens), and one runs into max_len."""
    script = {0: [add(10, 12, 1), add(20, 5, 2)], 4: [add(27, 9, 3)],
              6: [add(12, 20, 4), add(30, 200, 5)]}
    got, want = (_drive(_engine(kind, ahead), script)
                 for ahead in (True, False))
    _same(got, want)
    assert [len(r.generated) for r in got] == [12, 5, 9, 20, 160 - 30]


@pytest.mark.parametrize("kind", KINDS)
def test_plain_greedy_traffic_runs_ahead(kind):
    """One burst, long outputs: all but the step after the admission are
    dispatched before the step before is fetched."""
    eng = _engine(kind)
    reqs = _drive(eng, {0: [add(10, 40, 1), add(14, 40, 2), add(9, 40, 3)]})
    assert [len(r.generated) for r in reqs] == [40, 40, 40]
    st = eng.kv_stats()
    assert st["decode_steps"] == 39
    assert st["decode_steps_ahead"] / st["decode_steps"] >= 0.9


@pytest.mark.parametrize("kind", KINDS)
def test_has_work_while_a_token_is_in_flight(kind):
    eng = _engine(kind)
    req = eng.request(eng.add_request(_ids(10, 1), 3, 0.0))
    assert eng.step() == {}        # the first token is not streamed (D11)
    # ... nor fetched: it rides the step in flight, which read it on the
    # device
    assert eng._flight.firsts is not None and req.generated == []
    calls = 1
    while eng.has_work():
        assert eng._flight is not None
        assert len(eng.step()) == 1  # a call later than it was dispatched
        calls += 1
    assert eng._flight is None and req.done
    assert calls == 3 == len(req.generated)


@pytest.mark.parametrize("kind", KINDS)
def test_an_end_on_eos_with_a_step_in_flight(kind):
    """A request ends on eos_token while the step after is in the air: that
    step's token for it is thrown away, the request that keeps running and
    the one that takes over the slot and its pages read nothing of it."""
    script = {0: [add(10, 30, 1), add(22, 25, 2)], 2: [add(12, 10, 3)]}
    probe = _drive(_engine(kind, ahead=False, max_slots=2), script)
    others = set(probe[1].generated + probe[2].generated)
    cut, eos = next((k, t) for k, t in enumerate(probe[0].generated)
                    if k >= 2 and t not in others
                    and t not in probe[0].generated[:k])
    overshot, pages = {}, {}

    def watch(eng):
        a, b = (eng.request(rid) for rid in (0, 1))
        if a.done and id(eng) not in overshot:
            f = eng._flight
            overshot[id(eng)] = (f is not None and bool(f.active[a.slot])
                                 and f.reqs[a.slot] is a)
        if not b.done:
            pages[id(eng)] = list(eng.slot_pages[b.slot])

    engines = [_engine(kind, ahead, max_slots=2, eos_token=eos)
               for ahead in (True, False)]
    got, want = (_drive(eng, script, watch) for eng in engines)
    assert [overshot[id(eng)] for eng in engines] == [True, False]
    _same(got, want)
    assert got[0].generated == probe[0].generated[:cut + 1]
    assert [r.generated for r in got[1:]] == [r.generated for r in probe[1:]]
    # the other request's pages, and its row of state, hold the same
    a, r = engines
    axes = [s.shape.index(977) for s in a.serving.page_pools(a.c, 977, 16)]
    for pa, pr, ax in zip(a._pools(), r._pools(), axes):
        np.testing.assert_array_equal(
            np.take(np.asarray(pa), pages[id(a)], axis=ax),
            np.take(np.asarray(pr), pages[id(r)], axis=ax))
    if kind == "hybrid":
        assert got[1].slot == want[1].slot
        for sa, sr in zip(a.state_rows([got[1].slot]),
                          r.state_rows([want[1].slot])):
            np.testing.assert_array_equal(sa, sr)
    assert a.kv_stats()["pages_in_use"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_last_request_ends_on_eos_under_a_step_that_led_it(kind):
    """Nothing is active any more and a step is still in the air, every row
    of it one too many: has_work() holds until it is fetched."""
    script = {0: [add(10, 30, 1)]}
    gen = _drive(_engine(kind, ahead=False), script)[0].generated
    cut, eos = next((k, t) for k, t in enumerate(gen)
                    if k >= 2 and t not in gen[:k])
    eng = _engine(kind, eos_token=eos)
    req = eng.request(script[0][0](eng))
    while not req.done:
        eng.step()
    assert eng._flight is not None and not eng.active.any()
    assert eng.has_work()
    assert eng.step() == {} and not eng.has_work()
    assert req.generated == gen[:cut + 1]
    assert eng.kv_stats()["decode_steps"] == cut + 1
    assert eng.kv_stats()["pages_in_use"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_a_preemption_on_a_tiny_pool(kind):
    """Three usable pages for two requests that need two each: the step
    that finds the pool dry is fetched first, and the victim is requeued
    with every token it drew."""
    script = {0: [add(10, 20, 1), add(10, 20, 2)]}
    engines = [_engine(kind, ahead, max_slots=2, num_pages=4)
               for ahead in (True, False)]
    got, want = (_drive(eng, script) for eng in engines)
    for eng in engines:
        assert eng.kv_stats()["preemptions"] >= 1
    _same(got, want, exact=False)
    assert [len(r.generated) for r in got] == [20, 20]


def _guide(vocab):
    """A token may not repeat the last one's residue mod 3: the mask for
    token N + 1 follows from token N."""
    tok = np.arange(vocab)
    table = np.stack([np.where(tok % 3 == s, -1, tok % 3)
                      for s in range(3)]).astype(np.int32)
    return TokenGuide(table=table, pattern="residues")


@pytest.mark.parametrize("kind", KINDS)
def test_a_guided_request_runs_in_step(kind):
    guide = _guide(MODELS[kind].vocab)
    script = {0: [add(10, 8, 1, guide=guide), add(16, 20, 2)]}
    seen = []

    def watch(eng):
        if any(r is not None and r.guide for r in eng.slot_req):
            seen.append(eng.kv_stats()["decode_steps_ahead"])

    engines = [_engine(kind, ahead) for ahead in (True, False)]
    got, want = _drive(engines[0], script, watch), _drive(engines[1], script)
    _same(got, want)
    res = [t % 3 for t in got[0].generated]
    assert res[0] != 0 and all(a != b for a, b in zip(res, res[1:]))
    assert seen and set(seen) == {0}
    # ... and the plain request goes ahead again once the guide has ended
    assert engines[0].kv_stats()["decode_steps_ahead"] >= 10
    assert engines[1].kv_stats()["decode_steps_ahead"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_a_cancel_with_a_step_in_flight(kind):
    """The step in the air is fetched before the slot is taken: the
    cancelled request keeps that token, the other one runs on."""
    def cancel(eng):
        assert eng._flight is not None and eng._flight.active[0]
        eng.cancel(0)

    script = {0: [add(10, 30, 1), add(20, 15, 2)], 5: [cancel]}
    engines = [_engine(kind, ahead) for ahead in (True, False)]
    got, want = (_drive(eng, script) for eng in engines)
    _same(got, want)
    assert len(got[0].generated) == 6 and len(got[1].generated) == 15
    for eng in engines:
        assert eng.kv_stats()["pages_in_use"] == 0


def test_a_cancel_between_the_look_at_the_queue_and_the_admission():
    """A cancel that arrives after step() has looked (`_may_lead`) and
    before `_admit` applies it: the slot goes to the queued request under
    the step still in the air, which ran it for the cancelled one. That
    step moves the new owner nothing, and the step after it reads the new
    owner's first token."""
    eng, ref = _engine("per_head", max_slots=1), _engine("per_head",
                                                         ahead=False)
    for e in (eng, ref):
        e.add_request(_ids(10, 1), 8, 0.0)
        e.step()
        e.step()
    gone = eng.request(0)
    queued = [e.request(e.add_request(_ids(12, 2), 4, 0.0, logprobs=True))
              for e in (eng, ref)]
    flight = eng._flight
    assert flight is not None and flight.reqs[0] is gone and eng._may_lead()
    eng.cancel(0)
    assert list(eng._admit()) == [queued[0].request_id]
    assert gone.done and eng._flight is flight
    assert eng.slot_req[0] is queued[0] and eng.lengths[0] == 12
    st = eng.kv_stats()
    assert (st["admissions"], st["admissions_under_flight"]) == (2, 1)
    ref.cancel(0)
    for e in (eng, ref):
        while e.has_work():
            e.step()
    assert len(gone.generated) == 2     # the token of the step in the air
    #                                     was dropped with the slot
    _same(queued[:1], queued[1:])
    assert len(queued[0].generated) == 4
    assert eng.kv_stats()["pages_in_use"] == 0


def test_a_first_token_that_ends_its_request_is_returned():
    """ROADMAP D11 as it stands: the stream misses a first token only where
    a decode step follows it in the admitting call."""
    eng = _engine("per_head")
    rid = eng.add_request(_ids(10, 1), 1, 0.0)
    out = eng.step()
    assert out == {rid: eng.finished[rid].generated[0]}
    assert not eng.has_work() and eng.kv_stats()["decode_steps"] == 0


# ------------------------------------------- the programs are the parent's

# sha256 of the lowered text of each serving program, as the engine calls it
# at _engine()'s sizes, taken on the commit before the decode loop ran ahead
# (ea57df6): a change of schedule on the host moves none of them. The two
# kinds with experts were taken again at PR 43, whose counters grew two
# slots (`experts.N_STATS` 4 -> 6: the stats argument and result, their
# sum, and two constants more in each expert layer's concatenate: 14 lines
# of `deepseek_v2`'s programs, 34 of the hybrid's); every other line is
# the parent's, the values' numbering apart. The per-head `decode_paged`
# was taken again at PR 46: the token's K and V go to the attention call
# (off the chip its XLA insert) where 2 * slots * layers column updates
# stood; its two prefill programs and the six programs of the other kinds
# passed as they were. The latent kind's two prefill programs were taken
# again at PR 55: off the chip they run the prefill kernel's interpreter
# (`mla_prefill_attention`), not the jnp reference, so their text holds the
# kernel, which gained a second prefetched scalar (the query blocks that
# hold a token, from `lengths`) and its clamped index maps; the per-head
# and hybrid kinds take the reference, which reads no `lengths`, and no
# decode program moved. The latent kind's `decode_paged` was taken again at
# PR 57: off the chip it holds the latent decode kernel's interpreter
# (`paged_latent_decode`), whose loop now attends a block of pages a turn;
# its two prefill programs and every program of the other kinds passed as
# they were. The hybrid and the per-head kinds' `decode_paged` were taken
# again at PR 61: off the chip both hold the interpreter of the read-only
# paged decode kernel (`ops/paged_attention._dma_kernel`; the per-head kind
# through `paged_decode_insert_attention`'s XLA insert, since the fused
# kernel's write-back does not interpret), whose loop now attends a block
# of pages a turn; on the chip the per-head kind runs `_fused_kernel`, whose
# jaxpr inside `decode_paged` is the parent's to the character (PERF.md
# section 6, PR 61). The latent kind's three programs and the other kinds'
# four prefill programs passed as they were.
PROGRAMS = {
    "per_head": {
        "decode_paged":
            "b6982c390ecfd61a4254f132dc13d3eb9f96ada07a3bd242dcc68b4a1a75448b",
        "prefill_batch":
            "7fa5792158f9e2cbedd1d91dcd51ffbd100fb5da31623fc8a8c611748f9a3d42",
        "prefill_with_prefix_batch":
            "c2f720bd8ba145b06a2610f9699a76c653222b51d8cda7b550f82a2e52ce2ca9",
    },
    "latent": {
        "decode_paged":
            "326baf3d544d1d6c2fd46d9b61d6af78249b7198cafc39a6f68a04a16b6b1aad",
        "prefill_batch":
            "fcc1e0c06700a4bd81fc23078a6d6628e58f51a1c14e8f2bd02306f8e145170f",
        "prefill_with_prefix_batch":
            "26ca89a5ce1a3357c98fa695b74b140af8f5d1d4ce3ec7f6f2f3c979957b2433",
    },
    "hybrid": {
        "decode_paged":
            "d28eaf0194fa4cf7a425405c34ea8ef8018003033eab43c2e46899131c70ddc7",
        "prefill_batch":
            "b7a9b155bff79759c0263a7097fcc6270b9f73287177e60f59bf0678cff00264",
        "prefill_with_prefix_batch":
            "d16ee88eadd7266f743b74e883f950d3831334829f378177d971348823b1f0d2",
    },
}
# sha256 of the engine's entries (`llm.*`) of tools/graphcheck/
# fingerprints.json, as sorted JSON. Taken again at PR 48 for the two
# entries that left with their programs (the window loop's and the
# speculative verify's); the five that stay are the parent's to the byte.
# Taken again at PR 61 for `llm.decode_paged@1dev` alone (flops 196300 ->
# 210500, bytes 315500 -> 347500): the graph is the per-head program as the
# CPU lowers it, with the read-only paged kernel's interpreter inside,
# whose turn is a block of pages now; the four others are the parent's.
FINGERPRINTS = (
    "3593251875236f5daa6801e4d7a14d6604207c84bd8d4f77385eae8dd506e182")


# The two per-head prefill programs at a shape of more than one tile, [2,
# 1024] rows, where their row-wise products walk the tiles that hold a token
# (llm/engine.py `_walk`, PR 47): the dense kind and the one with experts.
# PROGRAMS' shapes are one tile, which the walk must not touch; these say
# when the walk's own text moves.
WALKED = {
    "per_head": {
        "prefill_batch":
            "cb854b0811e71c27c328160fdc43f0f830085c47aae83583e1724846a35146e7",
        "prefill_with_prefix_batch":
            "22df2ad916fe37bb097687f74aee1cf854a4ca5c5bc66c561c08af0d1d8f102a",
    },
    "experts": {
        "prefill_batch":
            "f43416130f1aa691a0af84eb52e9e04c6f20a2f3604fb1571b771ec37008e5e6",
        "prefill_with_prefix_batch":
            "7bd977cee3b766081b60d002bac57228c25a83b2d3cfa32c73bc7403bb1dc04a",
    },
}


def _lowered_digests(kind, n=2, S=32, names=None) -> dict:
    eng = _engine(kind)
    c, B, page = eng.c, eng.e.max_slots, eng.e.page_size

    def sds(*trees):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), trees)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    params, pools, rows = sds(eng.params, eng._pools(), eng.rows)
    stats = () if eng._moe_acc is None else sds(eng._moe_acc)
    Pp = 2
    row_args = (*rows, i32(n), i32(n)) if rows else ()
    prefix = (*pools, i32(n, Pp), i32(n))
    # name -> (arguments, the first donated one, how many)
    calls = {
        "decode_paged": ((
            params, *pools, *rows, i32(B), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B, 4), *stats),
            1, len(pools) + len(rows)),
        "prefill_batch": (
            (params, i32(n, S), i32(n), *row_args, *stats), 3, len(rows)),
        "prefill_with_prefix_batch": (
            (params, i32(n, S), i32(n), *prefix, *row_args, *stats),
            3 + len(prefix), len(rows)),
    }
    out = {}
    for name, (args, first, donated) in calls.items():
        if names is not None and name not in names:
            continue
        text = jax.jit(
            functools.partial(getattr(eng.serving, name), config=c),
            donate_argnums=tuple(range(first, first + donated))).lower(
            *args).as_text()
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_the_lowered_programs_are_the_parents(kind):
    assert _lowered_digests(kind) == PROGRAMS[kind]


@pytest.mark.parametrize("kind", sorted(WALKED))
def test_the_walked_prefill_programs_are_as_recorded(kind):
    assert _lowered_digests(kind, 2, 1024, WALKED[kind]) == WALKED[kind]


def test_the_graph_fingerprints_are_the_parents():
    with open(os.path.join(ROOT, "tools", "graphcheck",
                           "fingerprints.json")) as f:
        llm = {k: v for k, v in json.load(f).items() if k.startswith("llm.")}
    assert len(llm) == 5
    assert hashlib.sha256(json.dumps(llm, sort_keys=True).encode()
                          ).hexdigest() == FINGERPRINTS
