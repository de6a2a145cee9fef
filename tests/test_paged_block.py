"""The read-only paged decode kernel (ops/paged_attention._dma_kernel)
attends a BLOCK of G pages a turn and hands the next slot's first block
over in a slot's last turn: on the CPU in the kernel's interpreter, in
float32, against `paged_decode_attention_reference`, at toy pages of 16
tokens with the block's budget set so that G = 4 (a block spans 64
positions). Every case runs over a pool in which every page no slot holds,
and the whole other layer, is NaN: a page fetched and not masked, or
attended and not fetched, makes the output NaN (0 * NaN in p . v)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops import paged_attention as pa

PAGE = 16
TOL = 2e-5


@dataclasses.dataclass(frozen=True)
class Case:
    lengths: tuple
    pages_per_seq: int
    lows: tuple | None = None
    sink: bool = False
    hkv: int = 2
    g: int = 3
    hd: int = 8
    dv: int = 8
    block: int = 4


CASES = {
    # G = 4, a block is positions [64 i, 64 i + 64): the second block's
    # first page, a middle one, its last
    "ends_in_a_blocks_first_page": Case((70, 5, 33), 8),
    "ends_in_a_blocks_middle_page": Case((100, 97, 64), 8),
    "ends_in_a_blocks_last_page": Case((120, 127, 113), 8),
    "ends_on_a_blocks_edge": Case((128, 64, 192), 12),
    # 9 pages: two blocks and a third of one page
    "a_last_block_of_one_page": Case((129, 144, 130), 9),
    # the table names fewer pages than the budget holds: G = 2
    "a_table_narrower_than_a_block": Case((20, 32, 1), 2, block=2),
    "a_table_of_one_page": Case((16, 3, 9), 1, block=1),
    # the hand-over: a slot's last turn starts the next slot's first block
    "one_token_between_two_long": Case((150, 1, 140), 10),
    "idle_slots_first_and_last": Case((0, 90, 0, 75, 0), 6),
    "two_idle_slots_in_a_row": Case((40, 0, 0, 100), 7),
    "every_slot_idle": Case((0, 0, 0), 4),
    "a_length_past_the_table": Case((200, 17, 96), 6),
    # a window layer: nothing before `lows`
    "lows_mid_block": Case((100, 90, 70), 7, lows=(20, 37, 5)),
    "lows_in_a_blocks_last_page": Case((100, 130, 64), 9,
                                       lows=(50, 63, 48)),
    # the first block holds no real position (its maximum stays `_NEG`
    # and what it accumulated is rescaled by exactly 0 in the next)
    "lows_past_the_first_block": Case((100, 140, 80), 9, lows=(70, 129, 64)),
    "a_sink_over_a_window": Case((100, 90, 30), 7, lows=(20, 37, 0),
                                 sink=True),
    # with a sink the maximum is finite in a block of no real position
    "a_sink_and_a_block_of_no_position": Case(
        (100, 140, 0, 80), 9, lows=(70, 129, 0, 64), sink=True),
    "a_sink_over_whole_sequences": Case((70, 128, 5), 8, lows=(0, 0, 0),
                                        sink=True),
    # mimo_v2_5's pools: keys wider than values
    "keys_192_values_128": Case((100, 64, 7), 7, hd=24, dv=16),
    "keys_192_values_128_sink_window": Case(
        (20, 32, 17), 2, lows=(3, 16, 0), sink=True, hkv=4, g=2, hd=24,
        dv=16, block=2),
    "one_kv_head": Case((100, 130, 1), 9, hkv=1, g=5),
    "eight_kv_heads": Case((100, 130, 1), 9, hkv=8, g=2),
    "a_group_of_one": Case((70, 128), 8, hkv=4, g=1),
}


def _pools(case: Case, seed: int):
    """(clean K, clean V, poisoned K, poisoned V, tables): two layers,
    layer 1 the one that runs; the pages no slot holds (the scratch page 0
    and three more) zero in the clean pools and NaN in the poisoned ones,
    whose whole layer 0 is NaN too."""
    P = case.pages_per_seq
    held = [min(-(-n // PAGE), P) for n in case.lengths]
    N = 1 + sum(held) + 3
    ids = np.random.RandomState(seed).permutation(np.arange(1, N))
    tables = np.zeros((len(held), P), np.int32)
    for b, n in enumerate(held):
        tables[b, :n], ids = ids[:n], ids[n:]
    unheld = jnp.asarray(np.concatenate([[0], ids]))
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    out = []
    for key, width in ((kk, case.hd), (kv, case.dv)):
        clean = jax.random.normal(key, (2, case.hkv, N, width, PAGE))
        clean = clean.at[:, :, unheld].set(0.0)
        out.append(clean)
    out += [c.at[:, :, unheld].set(jnp.nan).at[0].set(jnp.nan) for c in out]
    return (*out, jnp.asarray(tables))


@pytest.mark.parametrize("name", list(CASES))
def test_block_kernel_matches_the_reference(name, monkeypatch):
    case = CASES[name]
    B, h = len(case.lengths), case.hkv * case.g
    # two blocks of four toy pages, K and V together
    monkeypatch.setattr(
        la, "_BLOCK_BYTES", 2 * 4 * case.hkv * (case.hd + case.dv) * PAGE * 4)
    assert la._block_pages(case.hkv * (case.hd + case.dv), PAGE, 4,
                           case.pages_per_seq) == case.block
    k, v, k_nan, v_nan, tables = _pools(case, seed=len(name))
    q = jax.random.normal(jax.random.PRNGKey(7), (B, h, case.hd))
    lengths = jnp.array(case.lengths, jnp.int32)
    lows = None if case.lows is None else jnp.array(case.lows, jnp.int32)
    sink = (jax.random.normal(jax.random.PRNGKey(8), (h,)) if case.sink
            else None)
    got = np.asarray(pa.paged_decode_attention(
        q, k_nan, v_nan, lengths, tables, layer=1, lows=lows, sink=sink))
    want = np.asarray(pa.paged_decode_attention_reference(
        q, k[1], v[1], lengths, tables, lows, sink))
    assert got.shape == (B, h, case.dv)
    assert np.isfinite(got).all()
    live = np.array(case.lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=TOL)
    assert not got[~live].any()             # an idle slot reads zeros


def test_the_block_is_what_a_turn_attends():
    """Whatever G the budget gives, the same numbers: G = 1 (a page a turn,
    the parent's loop), 2, 4 and a block wider than any slot's pages."""
    case = Case((150, 1, 0, 97, 64), 10)
    k, v, k_nan, v_nan, tables = _pools(case, seed=5)
    q = jax.random.normal(jax.random.PRNGKey(7), (5, 6, case.hd))
    lengths = jnp.array(case.lengths, jnp.int32)
    want = np.asarray(pa.paged_decode_attention_reference(
        q, k[1], v[1], lengths, tables))
    live = np.array(case.lengths) > 0
    for block in (1, 2, 4, 8, 16):
        got = np.asarray(pa._paged_decode_dma(
            q, k_nan, v_nan, lengths, tables, 1, block=min(block, 10),
            interpret=True))
        np.testing.assert_allclose(got[live], want[live], atol=TOL)
        assert not got[~live].any()


@pytest.mark.parametrize("cell,hkv,hd,dv,pages_per_seq,want", [
    # the five cells that run the kernel: bfloat16 pages of 128 tokens,
    # their widest tables
    ("nemotron3_nano_30b", 2, 128, 128, 29, 8),
    ("jamba2_3b", 1, 128, 128, 131, 16),
    ("solar_open2_250b", 8, 128, 128, 73, 2),
    ("laguna_s_2_1 full", 8, 128, 128, 130, 2),
    ("laguna_s_2_1 window", 8, 128, 128, 5, 2),
    ("mimo_v2_5 full", 4, 192, 128, 129, 2),
    ("mimo_v2_5 window", 8, 192, 128, 2, 1),
    # a page bucket narrower than the budget's block
    ("nemotron3_nano_30b, 4 pages", 2, 128, 128, 4, 4),
    ("jamba2_3b, 8 pages", 1, 128, 128, 8, 8),
])
def test_the_block_follows_the_shapes(cell, hkv, hd, dv, pages_per_seq, want,
                                      monkeypatch):
    """G is a pure function of what the call is handed (a page's bytes,
    K and V together, and the table's width), by the latent kernel's rule
    under the latent kernel's budget; and the call hands it to the
    kernel."""
    assert la._block_pages(hkv * (hd + dv), 128, 2, pages_per_seq) == want
    blocks = []
    monkeypatch.setattr(pa, "_paged_decode_dma",
                        lambda *a, block, **kw: blocks.append(block))
    pa.paged_decode_attention(
        jnp.zeros((1, hkv, hd), jnp.bfloat16),
        jnp.zeros((1, hkv, 1, hd, 128), jnp.bfloat16),
        jnp.zeros((1, hkv, 1, dv, 128), jnp.bfloat16),
        jnp.ones((1,), jnp.int32), jnp.zeros((1, pages_per_seq), jnp.int32),
        layer=0, interpret=True)
    assert blocks == [want]
