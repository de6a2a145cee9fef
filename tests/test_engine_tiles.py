"""The per-head prefill programs run their row-wise products over the tiles
that hold a token (llm/engine.py `_walk`, `_tile_order`, `_TILE_ROWS`):
`prefill_batch` and `prefill_with_prefix_batch` at shapes of more than one
tile against the same programs made to run the straight pass, on the CPU at
tiny widths; a one-tile program's lowered text, which the walk must not
touch; and the two counters that say how often it engages.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine, engine
from ray_tpu.models import configs, init_params
from ray_tpu.models.experts import stats_zero

pytestmark = pytest.mark.heavy

T = engine._TILE_ROWS
S = 2 * T                   # a bucket of two tiles
PAGE, PRE = 16, 2           # the cached prefix: two pages of 16 a request
MODELS = {
    "dense": configs.tiny(),
    "experts": configs.tiny_moe(),      # `stats` handed in: one device
    "post_norms": configs.tiny(post_norms=True),
    "loops": configs.tiny_ouro(loops=2),
}
# lengths of a batch: 1 / some / all of its tiles hold a token; a batch of
# three is padded to four with a request of no token, as the engine pads
LENGTHS = {
    "one_of_1x2": [100],
    "all_of_1x2": [S],
    "some_of_3x2": [300, 40, S, 0],
    "some_of_4x2": [1, T + 1, T, S - 1],
    "all_of_4x2": [S] * 4,
}
# float32 on both sides; a tile's product may sum in another order than
# the whole batch's
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params(kind):
    return init_params(MODELS[kind], jax.random.PRNGKey(3))


@functools.lru_cache(maxsize=None)
def _program(kind, name, walks: bool):
    """The jitted program; `walks` False: made to run the straight pass
    whatever its rows (traced under a `_walked` that says no)."""
    fn = jax.jit(functools.partial(getattr(engine, name),
                                   config=MODELS[kind]))
    if walks:
        return fn

    def straight(*args):
        saved, engine._walked = engine._walked, lambda s: False
        try:
            return fn(*args)
        finally:
            engine._walked = saved

    return straight


def _args(kind, name, lengths):
    c, n = MODELS[kind], len(lengths)
    rng = np.random.RandomState(len(lengths) + sum(lengths))
    args = [_params(kind), jnp.asarray(rng.randint(0, c.vocab, (n, S))),
            jnp.asarray(lengths, jnp.int32)]
    if name == "prefill_with_prefix_batch":
        pool = (c.cache_layers, c.n_kv_heads, 1 + n * PRE, c.head_dim, PAGE)
        args += [jnp.asarray(rng.randn(*pool), jnp.float32) for _ in "kv"]
        args += [jnp.arange(1, 1 + n * PRE, dtype=jnp.int32).reshape(n, PRE),
                 jnp.asarray(rng.randint(1, PRE * PAGE + 1, n), jnp.int32)]
    if c.moe_experts:
        args.append(stats_zero(c))
    return args


@pytest.mark.parametrize("lengths", sorted(LENGTHS))
@pytest.mark.parametrize("name", ["prefill_batch",
                                  "prefill_with_prefix_batch"])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_walked_prefill_is_the_straight_pass_on_real_rows(kind, name,
                                                          lengths):
    lens = np.asarray(LENGTHS[lengths])
    assert engine._walked(S)
    args = _args(kind, name, LENGTHS[lengths])
    got = _program(kind, name, True)(*args)
    want = _program(kind, name, False)(*args)
    real = np.arange(S)[None] < lens[:, None]                    # [n, S]
    np.testing.assert_allclose(np.asarray(got[0])[lens > 0],
                               np.asarray(want[0])[lens > 0], atol=TOL)
    in_real_tile = np.repeat(real.reshape(-1, T).any(1), T).reshape(real.shape)
    for g, w in zip(got[1:3], want[1:3]):       # K, V [cache layers, n, S, ..]
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape[0] == MODELS[kind].cache_layers
        np.testing.assert_allclose(g[:, real], w[:, real], atol=TOL)
        assert not g[:, ~in_real_tile].any()
        # the straight pass leaves what it computed of the padding there
        assert in_real_tile.all() or w[:, ~in_real_tile].any()
    if MODELS[kind].moe_experts:                # the experts' counters
        np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


@pytest.mark.parametrize("name", ["prefill_batch",
                                  "prefill_with_prefix_batch"])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_program_of_one_tile_keeps_its_text(kind, name):
    """A bucket of one tile, [2, T] rows: the program lowers to what it
    lowers to where nothing walks (the parent's text:
    tests/test_engine_ahead.py holds its digest for the dense kind)."""
    args = _args(kind, name, [T // 2, 3])
    args[1] = args[1][:, :T]
    assert not engine._walked(T)

    def text():
        return jax.jit(functools.partial(
            getattr(engine, name), config=MODELS[kind])).lower(
            *args).as_text()

    here = text()
    saved, engine._walked = engine._walked, lambda s: False
    try:
        assert here == text()
    finally:
        engine._walked = saved


def test_kv_stats_counts_the_rows_bucketed_and_the_rows_run():
    """A scripted run, every dispatch known: one prompt a step in each
    bucket, a prompt of two chunks, then three prompts admitted together
    (padded to four)."""
    eng = InferenceEngine(MODELS["dense"], EngineConfig(
        max_slots=4, max_len=3 * S, page_size=PAGE, eos_token=-1,
        prompt_buckets=(T // 4, S, 2 * S)), params=_params("dense"))
    rng = np.random.RandomState(0)
    bucketed = run = 0

    def prompts(*ns):
        for n in ns:
            eng.add_request([int(t) for t in rng.randint(0, 256, n)], 2, 0.0)
        while eng.has_work():
            eng.step()

    def tiles(*ns):
        return T * sum(-(-n // T) for n in ns)

    for n, bucket, rows in [(40, T // 4, T // 4),       # one tile: the bucket
                            (200, S, tiles(200)), (300, S, tiles(300)),
                            (S + 3, 2 * S, tiles(S + 3)),
                            (2 * S, 2 * S, tiles(2 * S))]:
        prompts(n)
        bucketed, run = bucketed + bucket, run + rows
        stats = eng.kv_stats()
        assert (stats["prefill_rows_bucketed"],
                stats["prefill_rows_run"]) == (bucketed, run)
    # 2 * S + 70 tokens: a chunk of the largest bucket, full, and its
    # continuation of 70 + (what the chunk left of its last page) tokens
    prompts(2 * S + 70)
    bucketed, run = bucketed + 2 * S + S, run + 2 * S + T
    # three prompts of one bucket in one step: a batch of four
    prompts(300, 100, S)
    bucketed, run = bucketed + 4 * S, run + tiles(300, 100, S)
    stats = eng.kv_stats()
    assert (stats["prefill_rows_bucketed"],
            stats["prefill_rows_run"]) == (bucketed, run)
    assert run < bucketed
    assert engine._rows_run(np.asarray([300, 100, S, 0]), S) == tiles(
        300, 100, S)


def test_kv_stats_counts_the_attention_blocks_and_those_run(monkeypatch):
    """The same script for the attention kernel's query blocks, which are
    `ops/attention._PREFILL_BQ` rows (here a tile's 256, so that a bucket
    holds several): every layer's grid has bucket / 256 a request, and runs
    those that hold a token."""
    from ray_tpu.ops import attention
    monkeypatch.setattr(attention, "_PREFILL_BQ", T)
    c = MODELS["dense"]
    eng = InferenceEngine(c, EngineConfig(
        max_slots=4, max_len=3 * S, page_size=PAGE, eos_token=-1,
        prompt_buckets=(T // 4, S, 2 * S)), params=_params("dense"))
    rng = np.random.RandomState(1)
    blocks = run = 0
    # (prompts admitted in one step, their batch's [n, bucket], the blocks
    # of it that hold a token)
    for ns, n, bucket, real in [
            ((40,), 1, T // 4, 1),          # one short block
            ((200,), 1, S, 1), ((T,), 1, S, 1), ((T + 1,), 1, S, 2),
            ((S + 3,), 1, 2 * S, 3), ((2 * S,), 1, 2 * S, 4),
            # three of one bucket: a batch of four, the fourth of no token
            ((300, 100, S), 4, S, 2 + 1 + 2)]:
        for m in ns:
            eng.add_request([int(t) for t in rng.randint(0, 256, m)], 2, 0.0)
        while eng.has_work():
            eng.step()
        blocks += c.n_layers * n * -(-bucket // T)
        run += c.n_layers * real
        stats = eng.kv_stats()
        assert (stats["prefill_attn_blocks"],
                stats["prefill_attn_blocks_run"]) == (blocks, run)
    assert run < blocks
    assert attention.prefill_blocks(np.asarray([300, 100, S, 0]), S) == (8, 5)
    # a window layer's blocks are its window's, within [128, 1024]
    assert attention.prefill_blocks(np.asarray([300, 100, S, 0]), S,
                                    window=100) == (16, 3 + 1 + 4)
