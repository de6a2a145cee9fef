"""The serving engine has ONE decode loop, `InferenceEngine.step()`:
`generate()` is that loop for every model kind, greedy and sampled; no
`EngineConfig.speculation` but None constructs an engine; and nothing of
the window loop that stood beside it until PR 48 (a second way to drive
the engine, its device-resident twin of the slot state, the n-gram drafter
and its counters) is left to half-work. On the CPU at tiny sizes.
"""

import functools

import numpy as np
import pytest

import ray_tpu.llm.engine as engine_module
from ray_tpu.llm import EngineConfig, InferenceEngine
from ray_tpu.models import configs

pytestmark = pytest.mark.heavy

# the seven kinds of model the engine serves, at their CPU-test presets
KINDS = {
    "tiny": configs.tiny,              # per-head K and V (qwen2_7b)
    "tiny_moe": configs.tiny_moe,      # ... with experts (mixtral_8x7b)
    "tiny_mla": configs.tiny_mla,      # a latent cache (deepseek_v2)
    "tiny_hybrid": configs.tiny_hybrid,  # Mamba-2 state (nemotron3_nano_30b)
    "tiny_jamba": configs.tiny_jamba,  # Mamba-1 state (jamba2_3b)
    "tiny_laguna": configs.tiny_laguna,  # window pools (laguna_s_2_1)
    "tiny_ouro": configs.tiny_ouro,    # a looped stack (ouro_2_6b)
}
PAGE = 8


@functools.lru_cache(maxsize=None)
def _params(kind):
    return InferenceEngine(KINDS[kind](), EngineConfig(
        max_slots=1, max_len=32, page_size=PAGE, prompt_buckets=(16,)),
        seed=3).params


def _engine(kind, **kw):
    e = dict(max_slots=3, max_len=96, page_size=PAGE,
             prompt_buckets=(16, 32), eos_token=-1)
    return InferenceEngine(KINDS[kind](), EngineConfig(**{**e, **kw}),
                           params=_params(kind), seed=5)


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, n)]


def _idle(eng):
    """Nothing in the air, no slot running, and every page, window page
    and row of state back where it came from."""
    assert eng._flight is None and not eng.has_work()
    assert not eng.active.any() and not eng.queue
    assert all(r is None for r in eng.slot_req)
    st = eng.kv_stats()
    assert st["pages_in_use"] == 0
    assert st["window_pages_in_use"] == 0 and st["window_pages_held"] == 0
    assert len(eng.free_win) == max(eng.num_window_pages - 1, 0)
    assert st["state_rows_in_use"] == 0 and st["snapshot_rows_in_use"] == 0


# ----------------------------------------------------- generate() is step()


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_generate_is_the_step_loop(kind, temperature):
    """`generate()` returns what add_request() and a loop over step() give
    an engine of the same seed, token for token: greedy, and sampled (one
    key stream, `_key`, whichever way the engine is driven). Four prompts
    through three slots: one waits for a slot and one is chunked."""
    prompts = [_ids(10, 1), _ids(21, 2), _ids(40, 3), _ids(7, 4)]
    got = _engine(kind)
    outs = got.generate(prompts, 9, temperature)
    want = _engine(kind)
    rids = [want.add_request(p, 9, temperature) for p in prompts]
    calls = 0
    while want.has_work():
        want.step()
        calls += 1
    assert outs == [want.finished[r].generated for r in rids]
    assert [len(o) for o in outs] == [9] * 4
    assert got.kv_stats()["decode_steps"] == want.kv_stats()[
        "decode_steps"] <= calls
    if temperature:
        # a seed's draw, and another seed's another
        other = InferenceEngine(got.c, got.e, params=got.params, seed=6)
        assert other.generate(prompts, 9, temperature) != outs
    _idle(got)
    _idle(want)


@pytest.mark.parametrize("kind", ["tiny", "tiny_mla"])
def test_a_long_queue_streams_through_generate_in_order(kind):
    """Seven requests through two slots, the long prompts ending early on
    max_len so that the slots end apart: the requests take their slots in
    the order they came; every call of step() that hands a slot over finds
    a decode step in flight (the loop led it) and fetches it before it
    admits; and the answers are those of the loop that never leads."""
    prompts = [_ids(n, 10 + i) for i, n in enumerate((6, 30, 8, 25, 5, 31, 7))]
    eng = _engine(kind, max_slots=2, max_len=48)
    step, admit = eng.step, eng._admit
    order, handovers, at_admit = [], [], []

    def watched_admit():
        at_admit.append(eng._flight)
        return admit()

    def watched_step():
        led = eng._flight is not None
        out = step()
        new = sorted(r.request_id for r in eng.slot_req
                     if r is not None and r.request_id not in order)
        if new and order:       # not the first burst: a slot changed hands
            handovers.append((led, at_admit[-1]))
        order.extend(new)
        return out

    eng.step, eng._admit = watched_step, watched_admit
    outs = eng.generate(prompts, 20, 0.0)
    assert order == list(range(len(prompts)))
    assert len(handovers) >= 4
    assert all(led and flight is None for led, flight in handovers)
    st = eng.kv_stats()
    assert 0 < st["decode_steps_ahead"] < st["decode_steps"]
    in_step = _engine(kind, max_slots=2, max_len=48)
    in_step._may_lead = lambda: False
    assert outs == in_step.generate(prompts, 20, 0.0)
    assert [len(o) for o in outs] == [min(20, 48 - len(p)) for p in prompts]
    assert in_step.kv_stats()["decode_steps_ahead"] == 0
    _idle(eng)


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "in_step"])
@pytest.mark.parametrize("kind", ["tiny", "tiny_mla", "tiny_laguna"])
def test_a_slot_holds_the_page_of_the_token_in_flight_and_no_more(kind, ahead):
    """Pages grow one token at a time, the only horizon there is: after
    every call the slot holds the pages up to the position the step in
    flight writes, whether that step was dispatched from the host's view
    (`_grow_pages`) or ahead of the fetch (`_decode_paged_step`), and a
    window layer's pages never more than the window spans."""
    n_prompt, new = 5, 30
    eng = _engine(kind)
    if not ahead:
        eng._may_lead = lambda: False
    req = eng.request(eng.add_request(_ids(n_prompt, 1), new, 0.0))
    while eng.has_work():
        eng.step()
        if eng._flight is not None and eng.slot_req[req.slot] is req:
            at = int(eng.lengths[req.slot])   # where the step in flight writes
            assert len(eng.slot_pages[req.slot]) == at // PAGE + 1
            assert len(eng.slot_win[req.slot]) <= eng.win_span
    assert len(req.generated) == new
    st = eng.kv_stats()
    assert st["pages_peak"] == (n_prompt + new - 2) // PAGE + 1
    assert bool(st["decode_steps_ahead"]) == ahead
    assert st["preemptions"] == 0
    _idle(eng)


# ------------------------------------------------------------- one check


@pytest.mark.parametrize("kind", ["tiny", "tiny_moe"])
def test_speculation_is_refused_for_a_per_head_model_too(kind):
    """The field keeps its name (perfbench/tests/test_lookups.py passes
    it) and one legal value; `spec_k` is read by nothing."""
    e = EngineConfig(max_slots=2, max_len=64, speculation="ngram", spec_k=2)
    assert (e.speculation, e.spec_k) == ("ngram", 2)
    with pytest.raises(ValueError, match=r"EngineConfig\.speculation='ngram'"
                       r".*one decode loop"):
        InferenceEngine(KINDS[kind](), e)
    eng = _engine(kind, speculation=None, spec_k=64)
    assert eng.generate([_ids(5, 1)], 3, 0.0) == _engine(kind).generate(
        [_ids(5, 1)], 3, 0.0)


# what kv_stats() had at the parent (PR 47), written out
PARENT_KV_STATS = {
    "layout", "num_pages", "cache_layers", "page_bytes", "free_pages",
    "cached_pages", "pages_in_use", "pages_peak", "num_window_pages",
    "window_pages_in_use", "window_pages_held", "window_pages_peak",
    "window_seq_pages_peak", "window_pages_released", "prefix_hits",
    "preemptions", "prefill_rows_bucketed", "prefill_rows_run",
    "spec_drafted", "spec_accepted", "decode_steps", "decode_steps_ahead",
    "state_rows_in_use", "snapshot_rows", "snapshot_rows_in_use",
    "snapshot_hits", "snapshot_evictions",
}


def test_kv_stats_loses_the_two_speculation_counters_alone():
    st = _engine("tiny").kv_stats()
    assert PARENT_KV_STATS - set(st) == {"spec_drafted", "spec_accepted"}
    # ... and since PR 51 counts its admissions three ways
    # ... and since PR 55 its prefill kernels' query blocks two ways
    # ... and since PR 56 the bytes of a state row
    # ... and since PR 60 the bytes of a page by kind of pool
    assert set(st) - PARENT_KV_STATS == {
        "admissions", "admissions_unfenced", "admissions_under_flight",
        "prefill_attn_blocks", "prefill_attn_blocks_run", "row_bytes",
        "page_bytes_full", "page_bytes_window"}
    # what perfbench/harness/serve_cell._engine_counters reads
    assert st["preemptions"] == 0 and st["prefix_hits"] == 0


# spelled in pieces: the tree is searched for the whole names
GONE_PROGRAMS = [("verify", "paged"), ("ngram", "draft"),
                 ("spec", "accept", "sample"), ("decode", "window", "spec"),
                 ("decode", "window")]
GONE_ATTRIBUTES = [("hist",), ("step", "window"), ("", "guide", "fp"),
                   ("", "window", "fns"), ("", "win", "buckets")]


def test_nothing_of_the_window_loop_is_left():
    """A half-deletion cannot pass: no device-resident twin of the slot
    state, no token history, no drafter's counters, none of the five
    programs, and no second way to drive the engine."""
    eng = _engine("tiny")
    eng.generate([_ids(5, 1)], 3, 0.0)
    names = set(vars(eng)) | set(dir(InferenceEngine))
    gone = {"_".join(parts) for parts in GONE_ATTRIBUTES}
    assert sorted(n for n in names if n in gone or n.startswith(
        ("_dev", "spec_", "_spec"))) == []
    for parts in GONE_PROGRAMS:
        assert not hasattr(engine_module, "_".join(parts)), parts
    from ray_tpu.ops import paged_attention
    assert sorted(n for n in dir(paged_attention) if "verify" in n) == []
