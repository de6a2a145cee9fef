"""An admission joins the device's stream as a decode step does
(llm/engine.py `_admit_queued`, `_FirstTokens`, `merge_tokens`, `_past`):
its prefill is dispatched behind the decode step in flight, its first
tokens stay on the device for the decode step after it, and `_land` fetches
them with that step's. Every request's tokens and log-probabilities against
an engine that lands before it admits and fetches every first token, for a
per-head, a latent and a hybrid model, on the CPU at tiny sizes; the cases
that keep their fence; and the counters that say how often it engages.
"""

import numpy as np
import pytest

from ray_tpu import diagnostics
from tests.test_engine_ahead import (KINDS, MODELS, _drive, _engine, _guide,
                                     _ids, _same, add)

pytestmark = pytest.mark.heavy


def _fenced(kind, **kw):
    """The reference: every step is fetched before anything else is
    dispatched, and every burst's first tokens before its decode step."""
    eng = _engine(kind, ahead=False, **kw)
    eng._first_token_now = lambda slot, req: True
    return eng


def _pair(kind, **kw):
    return _engine(kind, **kw), _fenced(kind, **kw)


def _stats(eng, *names):
    st = eng.kv_stats()
    return tuple(st[n] for n in names)


ADMISSIONS = ("admissions", "admissions_unfenced", "admissions_under_flight")


# ----------------------------------------------- unfenced = fenced, token for token


@pytest.mark.parametrize("kind", KINDS)
def test_a_mixed_run_returns_what_a_fenced_engine_returns(kind):
    """Admissions mid-stream, bursts of one to four, a prompt of two
    chunks, a prefix hit on its pages and a request that ends on its first
    token."""
    script = {
        0: [add(10, 12, 1)],
        2: [add(20, 7, 2), add(50, 9, 3)],
        4: [add(9, 5, 4), add(14, 6, 5), add(30, 4, 6), add(11, 8, 7)],
        7: [add(50, 6, 3)],
        9: [add(12, 6, 8), add(10, 1, 9)],
        11: [add(13, 3, 10), add(26, 2, 11), add(8, 10, 12)],
    }
    eng, ref = _pair(kind, max_slots=4)
    got, want = _drive(eng, script), _drive(ref, script)
    _same(got, want)
    assert [len(r.generated) for r in got] == [
        12, 7, 9, 5, 6, 4, 8, 6, 6, 1, 3, 2, 10]
    n, unfenced, under = _stats(eng, *ADMISSIONS)
    assert under > 0 and unfenced >= n - 1       # max_new_tokens == 1 alone
    assert eng.kv_stats()["prefix_hits"] >= 2    # the chunk's, the twin's
    assert _stats(ref, *ADMISSIONS)[2] == 0
    assert eng.kv_stats()["pages_in_use"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_a_seeded_run_draws_what_a_fenced_engine_draws(kind):
    """The burst's sampler is the same call at the same place in the key's
    split order, so where every request finds the same slot in the same
    call on both sides (a slot each, none ends before the last came) a
    sampled run draws the same tokens."""
    def hot(n_prompt, new, seed):
        return lambda eng: eng.add_request(_ids(n_prompt, seed), new, 0.8,
                                           logprobs=True, top_k=40)

    script = {0: [hot(10, 19, 1)], 2: [hot(20, 17, 2), hot(12, 15, 3)],
              5: [hot(50, 16, 4)], 6: [hot(9, 14, 5)]}
    eng, ref = _pair(kind, max_slots=5)
    _same(_drive(eng, script), _drive(ref, script))
    assert _stats(eng, *ADMISSIONS)[1:] > (0, 0)


# ------------------------------------------------------- eos_token at a slot's edges


def _eos_probe(kind, script, **kw):
    """(k, token): the first token past the second of the script's first
    request that no request of the run draws before, to be its eos_token."""
    first, *rest = _drive(_fenced(kind, **kw), script)
    gen, others = first.generated, {t for r in rest for t in r.generated}
    return next((k, t) for k, t in enumerate(gen)
                if k >= 2 and t not in others and t not in gen[:k])


@pytest.mark.parametrize("kind", KINDS)
def test_a_first_token_that_is_eos_under_the_step_that_read_it(kind):
    """The decode step was dispatched before anyone saw the token: its row
    ran once too often, `_land` ends the request on its first token and
    throws the step's away, and the call that lands returns the token."""
    eng = _fenced(kind)
    probe = eng.request(eng.add_request(_ids(10, 1), 5, 0.0))
    eng.step()
    eos = probe.generated[0]
    eng, ref = _pair(kind, eos_token=eos)
    other = {0: [add(14, 6, 2)]}
    reqs = _drive(eng, other)           # something else, before and beside
    rid = eng.add_request(_ids(10, 1), 5, 0.0, logprobs=True)
    req = eng.request(rid)
    before = eng.kv_stats()["decode_steps"]
    assert eng.step() == {} and req.generated == [] and not req.done
    assert eng._flight.firsts is not None and eng._flight.reqs[req.slot] is req
    assert eng.step() == {rid: eos}
    assert req.done and req.generated == [eos] and len(
        req.token_logprobs) == 1
    # ... and the step after it, dispatched before that fetch, is still
    # in the air, every row of it one too many
    assert eng.has_work() and eng.step() == {} and not eng.has_work()
    assert eng.kv_stats()["decode_steps"] == before + 2
    assert eng.kv_stats()["pages_in_use"] == 0
    want = _drive(ref, other) + [ref.request(ref.add_request(
        _ids(10, 1), 5, 0.0, logprobs=True))]
    while ref.has_work():
        ref.step()
    _same(reqs + [req], want)


@pytest.mark.parametrize("kind", KINDS)
def test_a_slot_that_ended_on_eos_is_given_out_under_the_step_that_led_it(
        kind):
    """One slot: its request ends on eos_token under a step that led it,
    and the queued request takes the slot, its pages and its row of state
    while that step is in the air. The step moves the new owner nothing
    (its length advances once a step of its own), and what it wrote the
    new owner's prefill, dispatched behind it, overwrites."""
    script = {0: [add(10, 30, 1), add(12, 9, 2)]}
    cut, eos = _eos_probe(kind, script, max_slots=1)
    eng, ref = _pair(kind, max_slots=1, eos_token=eos)
    seen = []

    def watch(e):
        a, b = (e.request(rid) for rid in (0, 1))
        f = e._flight
        if b.slot is not None and not seen:
            # the call that admitted it: the step it was admitted under has
            # landed, the step after it (its first) is in the air
            seen.append((a.done, int(e.lengths[0]), list(b.generated),
                         f is not None and f.reqs[0] is b))
        elif len(seen) == 1:
            seen.append((int(e.lengths[0]), len(b.generated)))

    got, want = _drive(eng, script, watch), _drive(ref, script)
    _same(got, want)
    assert got[0].generated[-1] == eos and len(got[0].generated) == cut + 1
    assert len(got[1].generated) == 9
    assert seen == [(True, 12, [], True), (13, 2)]
    assert _stats(eng, *ADMISSIONS) == (2, 2, 1)
    assert eng.kv_stats()["pages_in_use"] == 0


# ------------------------------------------------------------ what keeps its fence


@pytest.mark.parametrize("kind", KINDS)
def test_a_request_of_one_token_or_at_max_len_or_guided_stays_fenced(kind):
    """No decode step follows the first two, so the admitting call returns
    their token (ROADMAP D11); the guide's next mask follows from it."""
    eng = _engine(kind, max_len=48)
    rid = eng.add_request(_ids(10, 1), 1, 0.0)
    assert list(eng.step()) == [rid] and not eng.has_work()
    rid = eng.add_request(_ids(47, 2), 9, 0.0)      # two chunks, then full
    out = {}
    while eng.has_work():
        out.update(eng.step())
    assert list(out) == [rid] and len(eng.finished[rid].generated) == 1
    assert _stats(eng, "decode_steps", "admissions_unfenced") == (0, 1)
    diagnostics.spans_on()
    try:
        req = eng.request(eng.add_request(
            _ids(10, 3), 6, 0.0, guide=_guide(MODELS[kind].vocab)))
        eng.step()
        assert len(req.generated) == 1 and eng._flight.firsts is None
        while eng.has_work():
            eng.step()
        (admit,) = [r for r in diagnostics.spans()[0]
                    if r.name == "ray_tpu.engine.admit"]
    finally:
        diagnostics.spans_off()
    assert admit.attrs["fenced"] == 1      # nothing in the air: the
    #                                        counter below says so
    assert len(req.generated) == 6
    assert _stats(eng, *ADMISSIONS) == (4, 1, 0)    # the first chunk's alone


@pytest.mark.parametrize("kind", KINDS)
def test_a_cancel_with_a_first_token_pending(kind):
    """The step that carries the first token is fetched before the slot is
    taken: the cancelled request keeps both tokens."""
    eng, ref = _pair(kind)
    for e in (eng, ref):
        e.add_request(_ids(16, 5), 12, 0.0, logprobs=True)
        e.add_request(_ids(10, 1), 30, 0.0, logprobs=True)
        e.step()
        e.cancel(1)
    assert eng.request(1).generated == [] and ref.request(1).generated != []
    reqs = [[e.request(rid) for rid in (0, 1)] for e in (eng, ref)]
    for e in (eng, ref):
        while e.has_work():
            e.step()
        assert e.kv_stats()["pages_in_use"] == 0
    _same(*reqs)
    assert [len(r.generated) for r in reqs[0]] == [12, 2]


@pytest.mark.parametrize("kind", KINDS)
def test_a_preemption_with_a_first_token_pending(kind):
    """Two usable pages: the second request takes the last one in the call
    in which the first needs it, the pool is dry under a first token that
    nobody has fetched, and a victim is requeued with ALL its tokens: the
    token is fetched first."""
    fetched = []
    for arrives in (4, 5, 6):
        script = {0: [add(10, 20, 1)], arrives: [add(10, 12, 2)]}
        eng, ref = _pair(kind, max_slots=2, num_pages=3)
        fetch, room = eng._fetch_firsts, eng._make_room
        state = {"in_room": False}

        def make_room(i, room=room, state=state):
            state["in_room"] = True
            try:
                return room(i)
            finally:
                state["in_room"] = False

        def fetch_firsts(fetch=fetch, state=state, eng=eng):
            if state["in_room"] and eng._firsts is not None:
                fetched.append(arrives)
            fetch()

        eng._make_room, eng._fetch_firsts = make_room, fetch_firsts
        got, want = _drive(eng, script), _drive(ref, script)
        _same(got, want, exact=False)
        assert [len(r.generated) for r in got] == [20, 12]
        assert eng.kv_stats()["preemptions"] >= 1
        assert eng.kv_stats()["pages_in_use"] == 0
    assert fetched == [6]


# ------------------------------------------------------------- has_work, counters


@pytest.mark.parametrize("kind", KINDS)
def test_has_work_with_only_first_tokens_pending(kind):
    """After `_admit` alone the engine holds a request whose only token is
    on the device, in no step yet: has_work() holds, and the next step()
    reads it there."""
    eng, ref = _pair(kind)
    reqs = [e.request(e.add_request(_ids(10, 1), 4, 0.0, logprobs=True))
            for e in (eng, ref)]
    assert list(eng._admit()) == [0]
    assert eng._firsts is not None and eng._flight is None
    assert reqs[0].generated == [] and not eng.queue and eng.has_work()
    for e in (eng, ref):
        while e.has_work():
            e.step()
    assert eng._firsts is None
    _same(reqs[:1], reqs[1:])


def test_the_counters_and_the_spans_say_how_an_admission_went():
    eng = _engine("per_head", max_slots=4)
    _drive(eng, {0: [add(10, 3, 1)]})              # compiled before recording
    base = _stats(eng, *ADMISSIONS)
    diagnostics.spans_on()
    try:
        _drive(eng, {0: [add(10, 14, 1)], 3: [add(20, 6, 2), add(9, 1, 3)],
                     5: [add(12, 5, 4)], 8: [add(50, 4, 5)]})
        records, dropped = diagnostics.spans()
    finally:
        diagnostics.spans_off()
    admits = [r for r in records if r.name == "ray_tpu.engine.admit"]
    n, unfenced, under = np.subtract(_stats(eng, *ADMISSIONS), base)
    assert dropped == 0 and len(admits) == n == 5   # the last in two chunks
    assert sum(1 - r.attrs["fenced"] for r in admits) == unfenced == 4
    # all but the first, which found nothing in the air (`kv_stats()`
    # counts it; the span dropped `under_flight` with PR 58)
    assert under == 4
    # the decode step after an admission under a step in flight led it
    st = eng.kv_stats()
    assert st["decode_steps_ahead"] >= st["decode_steps"] - 3


@pytest.mark.parametrize("kind", KINDS)
def test_a_resumed_request_admitted_under_a_step_in_flight(kind):
    """Its token is the host's and draws no first one: it rides the merge
    as an upload, beside the tokens the step in flight left on the
    device."""
    script = {0: [add(10, 12, 1)],
              3: [lambda eng: eng.add_request(_ids(12, 8), 6, 0.0,
                                              resume_token=17)]}
    eng, ref = _pair(kind)
    got, want = _drive(eng, script), _drive(ref, script)
    assert [r.generated for r in got] == [r.generated for r in want]
    assert got[1].generated[0] == 17 and len(got[1].generated) == 6
    assert _stats(eng, *ADMISSIONS) == (2, 2, 1)
