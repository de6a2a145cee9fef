"""The generator offers every seed the same multiset and the same count."""

import collections

import pytest

from perfbench.harness import traffic as T

CHAT = T.load_traffic("serve-chat")
LONG = T.load_traffic("serve-longprompt")
SEEDS = [0, 1, 7, 2**31 + 11, 3000000019]


@pytest.mark.parametrize("rate,seconds", [(2.0, 45), (3.3, 45), (7.5, 10)])
def test_open_loop_same_multiset_and_exact_count(rate, seconds):
    want = want_gaps = None
    for seed in SEEDS:
        sched = T.open_loop_schedule(CHAT, rate, seconds, 5.0, 30.0, seed)
        counted = [r for r in sched if r.counted]
        assert len(counted) == round(rate * seconds)
        assert all(0.0 < r.due_s < seconds for r in counted)
        assert all(r.due_s < 0 or r.due_s >= seconds
                   for r in sched if not r.counted)
        assert [r.due_s for r in sched] == sorted(r.due_s for r in sched)
        got = collections.Counter(
            (r.prompt_tokens, r.output_tokens) for r in counted)
        want = want or got
        assert got == want
        # the same multiset of gaps between arrivals, in another order
        due = [0.0] + [r.due_s for r in counted] + [float(seconds)]
        gaps = sorted(round(b - a, 9) for a, b in zip(due, due[1:]))
        want_gaps = want_gaps or gaps
        assert gaps == pytest.approx(want_gaps, abs=1e-6)
    assert len({tuple((r.prompt_tokens, r.due_s) for r in
                      T.open_loop_schedule(CHAT, rate, seconds, 5, 30, s))
                for s in SEEDS}) == len(SEEDS)   # the seed does permute


def test_grid_follows_the_file():
    g = T.length_grid(CHAT["prompt_tokens"], 1001)
    assert g == sorted(g) and g[0] >= 32 and g[-1] <= 1500
    assert g[500] == 400                       # the median the file names
    pairs = T.request_pairs(CHAT, 200)
    assert max(p + o for p, o in pairs) <= CHAT["max_total_tokens"]


def test_closed_loop_cycles_the_same_grid():
    g = LONG["grid_size"]
    want = None
    for seed in SEEDS:
        seq = T.closed_loop_sequence(LONG, seed, 3 * g)
        for k in range(3):
            got = collections.Counter(
                (r.prompt_tokens, r.output_tokens)
                for r in seq[k * g:(k + 1) * g])
            want = want or got
            assert got == want
    assert all(1100 <= p <= 1900 and 16 <= o <= 32 for p, o in want)


def test_prompt_ids_repeat_for_a_seed_and_fit_the_vocabulary():
    a = T.prompt_ids(2**31 + 5, 3, 300, 152064)
    assert a == T.prompt_ids(2**31 + 5, 3, 300, 152064)
    assert a != T.prompt_ids(2**31 + 5, 4, 300, 152064)
    assert len(a) == 300 and 0 <= min(a) and max(a) < 152064
