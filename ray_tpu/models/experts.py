"""The expert layer of ONE chip: the routed experts it holds, all of them
or a share. Who calls it: models/deepseek_v2.py, models/nemotron_h.py and
models/windowed.py from their own serving programs, and llm/engine.py's per-head programs
(`_mlp_block`: Mixtral's kind, every expert held, softmax scores, top-k
renormalised: `route()`'s defaults) where they run on one device. Under a
mesh, and in training, a per-head model's experts are
models/transformer._moe's (every expert over every token, the expert axis
sharded over "ep").

The layer is told which experts it holds: `moe_experts` of the
`moe_router_experts` the router scores, group `moe_held_group`. It routes
over all of them at the published width, dispatches the (token, expert)
pairs that land on held experts (ordered by expert, one grouped matrix
product: no capacity, no token dropped, no [tokens, experts, width]
intermediate), adds the shared experts for every token,
and leaves out what the absent experts would add. Nothing stands in for
the absent chips.

What a configuration chooses (`ModelConfig`):
  moe_score         "softmax": g = softmax_fp32(x W_g).
                    "sigmoid": g = sigmoid_fp32(x W_g), and the learned
                    `router_bias` (where the layer has that leaf) joins g
                    in the CHOICE only; the weights are g without it.
  moe_n_group, moe_topk_group   group-limited choice: group score = max of
                    g in each group, the best groups stay, the rest is
                    zeroed; then the top-k of what is left.
  moe_norm_topk, moe_routed_scale, moe_scale_normed   weights renormalised
                    (sum + 1e-20) or not; times the scale where they were
                    not renormalised, and after renormalising too where
                    moe_scale_normed.
  mlp_act           the form of an expert: "swiglu" W_d (silu(W_g x) *
                    W_u x), or "relu2" W_d relu(W_u x)^2 (no W_g).
  moe_shared_experts, moe_shared_d_ff   the always-on expert of the same
                    form, moe_shared_d_ff wide (0: moe_shared_experts *
                    moe_d_ff).
  moe_grouped       how the order and the grouped product are computed:
                    "ragged_dot" (a stable sort, XLA's ragged-dot kernel:
                    deepseek_v2's, as its cell has always run it) or
                    "tiles" (counted order, plain products over tiles;
                    where tokens are few, no order at all: the experts
                    this call's tokens chose, walked a turn each where an
                    expert is large against a turn's overhead, Mixtral's
                    352 MB, else every held expert in one batched product:
                    the one form nemotron_h's programs run with on the
                    chip, and the one llm/engine.py's per-head programs
                    name where they call the layer; the comments above
                    `_TILE_ROWS` and `_TURN_BYTES` have the runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import ModelConfig
from ray_tpu.ops.layers import swiglu

# Rows of the grouped product a dispatch pass may fill. A token sends at
# most top-k pairs to the held experts and 1/n_group of that on average:
# sizing the gathers for the worst case would cost top-k times the memory
# and the row gathers of a typical step, so long batches take the pairs in
# passes of this many rows (one pass unless routing is badly skewed).
_MIN_PASS_ROWS = 4096

N_STATS = 6   # routed tokens, pairs on held experts, tokens with no held
#               expert, expert-layer calls, those of them in the few-token
#               form, experts whose weights these read; then one load
#               count an expert


def stats_zero(c: ModelConfig):
    return jnp.zeros((N_STATS + c.moe_experts,), jnp.int32)


def router_width(c: ModelConfig) -> int:
    return c.moe_router_experts or c.moe_experts


def shared_width(c: ModelConfig) -> int:
    return c.moe_shared_d_ff or c.moe_shared_experts * c.moe_d_ff


def relu2_mlp(x, w_up, w_down):
    """W_down relu(x W_up)^2, outputs in x.dtype (as ops/layers.swiglu)."""
    u = jax.nn.relu(jnp.einsum("...e,ef->...f", x, w_up))
    return jnp.einsum("...f,fe->...e", u * u, w_down)


def init_expert_weights(w, c: ModelConfig, seeded) -> dict:
    """The expert layer's leaves. `w(shape, fan_in)` draws a weight;
    `seeded(shape, scale)` one of a given spread (the router's bias; None:
    a sigmoid router that chooses by its scores alone, no bias leaf)."""
    d, E, f = c.d_model, c.moe_experts, c.moe_d_ff
    gated = c.mlp_act == "swiglu"
    lp = {"router": w((d, router_width(c)), d)}
    if c.moe_score == "sigmoid" and seeded is not None:
        # small and non-zero, so that the choice differs from the weights'
        lp["router_bias"] = seeded((router_width(c),), 0.05)
    if gated:
        lp["wg"] = w((E, d, f), d)
    lp.update(wu=w((E, d, f), d), wd=w((E, f, d), f))
    if c.moe_shared_experts:
        fs = shared_width(c)
        if gated:
            lp["shared_wg"] = w((d, fs), d)
        lp.update(shared_wu=w((d, fs), d), shared_wd=w((fs, d), fs))
    return lp


def route(x, lp, c: ModelConfig):
    """x [T, d] -> (weights [T, k] float32, expert ids [T, k] over the
    router's published width)."""
    k = c.moe_top_k
    logits = jnp.einsum("td,dx->tx", x, lp["router"],
                        preferred_element_type=jnp.float32)
    biased = c.moe_score == "sigmoid"
    if biased:
        g = pick = jax.nn.sigmoid(logits)
        if "router_bias" in lp:
            pick = g + lp["router_bias"].astype(jnp.float32)
    else:
        g = pick = jax.nn.softmax(logits, -1)
    if c.moe_n_group > 1:
        T, X = pick.shape
        per = X // c.moe_n_group
        _, best = jax.lax.top_k(pick.reshape(T, c.moe_n_group, per).max(-1),
                                c.moe_topk_group)
        keep = jax.nn.one_hot(best, c.moe_n_group, dtype=jnp.bool_).any(1)
        pick = jnp.where(jnp.repeat(keep, per, axis=1), pick, 0.0)
    w, idx = jax.lax.top_k(pick, k)
    if biased:
        w = jnp.take_along_axis(g, idx, axis=1)
    if k > 1 and c.moe_norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        if c.moe_scale_normed:
            w = w * c.moe_routed_scale
    else:
        w = w * c.moe_routed_scale
    return w, idx


def _held(lp, name: str, layer):
    """The held experts' `name` weights [E, ...]: `lp[name]`, or layer
    `layer` of it where `lp[name]` is a stack of layers [L, E, ...]."""
    return lp[name] if layer is None else lp[name][layer]


def _grouped_mlp(rows, lp, sizes, c: ModelConfig, layer):
    if c.moe_grouped == "tiles":
        return _grouped_mlp_tiles(rows, lp, sizes, c, layer)
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes)
    if c.mlp_act == "relu2":
        u = jax.nn.relu(dot(rows, _held(lp, "wu", layer)))
        return dot(u * u, _held(lp, "wd", layer))
    act = (jax.nn.silu(dot(rows, _held(lp, "wg", layer)))
           * dot(rows, _held(lp, "wu", layer)))
    return dot(act, _held(lp, "wd", layer))


# `moe_grouped == "tiles"` (nemotron3_nano_30b's): no sort and no
# ragged-dot. It is the only form that RUNS that model on the chip. With the
# ragged-dot kernel in its 27-block programs the cell never got through
# warm-up, twice (chip calls 23 and 24 of PR 33, exit 124): with the sort,
# nothing in 1000 s; with the counted order below, ten programs compiled and
# ran, then no sign of life for 8 minutes after the tenth had compiled -
# where four chunked prompts continue together, the program that had hung
# before over a gather of state rows (models/nemotron_h._load_rows). Cause
# not established; each part runs alone. Two costs it also avoids, both
# measured for PR 33. (1) Compile time: XLA's sort network over a
# 4096-token program's 24,576 pairs took 18 s of its 25 s of described-chip
# compile, in each of a cell's 12 prefill programs; the counted order
# (`_order_by_count`) takes under 2 s. On `deepseek_v2`'s shapes the layer
# alone runs 7.39 ms sorted and 7.76 ms counted at 4096 tokens, 0.53 both
# at 8 (chip microbench, PR 33), so that model keeps the sort its cell was
# measured with. (2) A decode step has few tokens: every held expert over
# every token in one batched product reads each expert's weights once,
# which is the step's whole cost, with no dispatch around it.
# - many rows: the sorted rows in blocks of `_TILE_ROWS`; a block holds rows
#   of a few experts, and the (block, expert) pairs that exist are walked in
#   order (at most blocks + experts - 1 of them), each one product of the
#   block with that expert's weights, kept where the row is the expert's;
# - few tokens (decode): no dispatch at all, `held_few`: every held expert
#   over every token in one batched product (`held_dense`), or, where
#   `_TURN_BYTES` below lets a shape have it, the experts that were HIT
#   alone, a turn each (`held_walk`).
# Which of the two, and the rows a tile, follow from the rows and the
# experts, not from the widths: a tile's product reads its expert's weights
# whole, 2 * rows * d * f operations over 2 * d * f bytes, so under ~240
# rows (the v5e's operations a byte) the read is what the tile costs
# whatever d and f are, and past it the rows are: a tile of `_TILE_ROWS` is
# where the two meet. The walk makes at most rows / tile + experts - 1
# tiles, so full tiles pay once a pass holds half a tile an expert
# (`_full_tiles`). Below that most tiles straddle experts, where a small
# tile wastes fewer masked rows than it re-reads weights; and below that,
# too, every expert over every token costs the walk's reads (every expert
# is hit) with no order, gather or loop round them. The constants were set
# on nemotron3_nano_30b's 16 experts of [2688, 1856], 6 a token (dense at
# 64 x 16 and up to 256 x 16, full tiles from 2048 rows a pass: its
# programs get what they got); for Mixtral's 8 of [4096, 14336], 2 a
# token, the rule gives the dense product up to 256 tokens and full tiles
# from 512, and one layer alone on the chip agrees (PR 40, ms, every row
# real; transformer._moe / dense / walk at 64 / 256 / 512 rows a tile /
# ragged_dot): 256 tokens 5.06 / 5.39 / 8.31 / 5.90 / - / 10.05, 512
# tokens 9.04 / 9.36 / 12.27 / 7.05 / - / 11.23, 2048 tokens 33.06 / - /
# - / 13.78 / 16.37 / 19.23 (PERF.md section 5 has the table).
_TILE_ROWS = 256      # rows a tile where `_full_tiles`, else
_SMALL_TILE_ROWS = 64
_DENSE_ROWS = 4096    # tokens x held experts up to which every held expert
#                       may run over every token (decode: 64 x 16)



# The few-token form reads what its tokens chose. A decode step of 6
# active rows of 16 hits 6.6 of Mixtral's 8 experts and one row hits 2, yet
# the batched product streams all 8 (2.8 GB a layer: 7.5 of the chat cell's
# 8.4 ms step). `held_walk` takes the hit experts a turn each; a turn is
# three cold-started products and a trip of XLA's loop, so it costs its
# expert's read plus an overhead that does not shrink with the expert. One
# layer alone on the chip (PR 43; ms; walk at 1 / half / all experts hit
# against the batched product; PERF.md section 5 has every hit count):
#   Mixtral   8 x [4096, 14336] swiglu, 16 rows: 0.517 / 1.942 / 3.842, 3.797
#   Laguna   32 x [3072, 1024]  swiglu, 24 rows: 0.068 / 0.602 / 1.174, 0.867
#   nemotron 16 x [2688, 1856]  relu2,  64 rows: 0.093 / 0.332 / 0.599, 0.459
# A turn costs 475 us at Mixtral's 352 MB an expert, which IS an eighth of
# the batched product (overhead under 1 us, lost in the read), 35.7 us at
# Laguna's 18.9 MB against 27.1 (8.6 us more: 6.0 MB at the batched
# product's own 697 GB/s) and 33.8 us at nemotron's 20 MB against 28.7 (5.1
# us: 3.5 MB); a walk also pays 30 to 60 us once. `_TURN_BYTES` is the
# largest of the three with a third of room. The call's own hit count
# decides on the device (`lax.cond`): walk where n_hit turns read less than
# the batched product's E experts, so every expert hit is the batched
# product exactly as before (3.804 ms through the branch, 3.797 without).
# The branch is not free where experts are small: the batched product ran
# 0.03 ms slower inside it at Laguna's shape (3 %) and 0.06 to 0.08 ms at
# nemotron's (13 to 17 %; the same products, started cold behind the
# predicate), and nemotron's 64 rows hit 15.3 of its 16 experts on nearly
# every step. So a shape gets the branch and the walk only where the walk
# pays even with E - 1 experts hit, i.e. a turn's overhead is under 1 /
# (E - 1) of its read: Mixtral (0.024 against 0.143) does; Laguna (0.44
# against 0.032) and nemotron (0.42 against 0.067) keep the batched
# product alone and their programs' text. What that leaves on the table at
# Laguna (20 of 32 hit: 0.742 against 0.872 ms) wants ONE kernel whose
# grid runs over the hit list, not this loop (PERF.md section 7). Rows: a
# turn multiplies ALL T rows, free only while T is far under the ridge; and
# at 256 rows the compiler copied both layers' `wg` and `wu` into another
# layout for the branch's batched product (1855 MiB of temporaries,
# described-chip compile, PR 43), at 64 and 16 it does not: the walk stops
# at `_SMALL_TILE_ROWS`.
_TURN_BYTES = 8 << 20


def _walk_pays(n_hit, E: int, expert_bytes: int):
    """Whether `n_hit` turns of the few-token walk (an int, or traced: the
    call's own count) cost less than ONE batched product over all E."""
    return n_hit * (1.0 + _TURN_BYTES / expert_bytes) < E


def _full_tiles(rows: int, E: int) -> bool:
    """Whether a pass of `rows` sorted rows over E held experts holds half
    a `_TILE_ROWS` tile an expert or more."""
    return 2 * rows >= E * _TILE_ROWS


def _one_expert(x, lp, e, c: ModelConfig, layer):
    """Expert e's feed-forward of x [rows, d], its weights read where they
    lie (a dynamic slice that XLA fuses into the product): ONE slice of
    what `lp` holds, a stack of layers too (`expert_layer`)."""
    def w(name):
        if layer is None:
            return jax.lax.dynamic_index_in_dim(lp[name], e, 0,
                                                keepdims=False)
        return jax.lax.dynamic_slice(
            lp[name], (layer, e, 0, 0), (1, 1) + lp[name].shape[2:])[0, 0]

    if c.mlp_act == "relu2":
        u = jax.nn.relu(jnp.dot(x, w("wu")))
        return jnp.dot(u * u, w("wd"))
    return jnp.dot(jax.nn.silu(jnp.dot(x, w("wg"))) * jnp.dot(x, w("wu")),
                   w("wd"))


def _grouped_mlp_tiles(rows, lp, sizes, c: ModelConfig, layer):
    """rows [R, d] sorted by expert, sizes [E] rows an expert (their sum at
    most R) -> [R, d]; rows past the sum are left zero."""
    R, d = rows.shape
    tm = (_TILE_ROWS if _full_tiles(R, sizes.shape[0])
          else min(_SMALL_TILE_ROWS, R))
    pad = -R % tm
    if pad:
        rows = jnp.pad(rows, [(0, pad), (0, 0)])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm                                 # an expert's blocks
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    tile_ends = jnp.cumsum(tiles)
    E = sizes.shape[0]

    def one_tile(t_out):
        t, out = t_out
        e = jnp.minimum(jnp.sum(t >= tile_ends), E - 1)
        at = (first[e] + t - (tile_ends[e] - tiles[e])) * tm
        y = _one_expert(jax.lax.dynamic_slice(rows, (at, 0), (tm, d)), lp, e,
                        c, layer)
        r = at + jnp.arange(tm)
        mine = (r >= starts[e]) & (r < ends[e])
        old = jax.lax.dynamic_slice(out, (at, 0), (tm, d))
        return t + 1, jax.lax.dynamic_update_slice(
            out, jnp.where(mine[:, None], y.astype(out.dtype), old), (at, 0))

    _, out = jax.lax.while_loop(
        lambda t_out: t_out[0] < tile_ends[-1], one_tile,
        (jnp.int32(0), jnp.zeros_like(rows)))
    return out[:R]


def _expert_bytes(lp, c: ModelConfig) -> int:
    """The bytes of ONE expert's weights as `lp` holds them."""
    names = ("wu", "wd") if c.mlp_act == "relu2" else ("wg", "wu", "wd")
    return sum(lp[n].shape[-2] * lp[n].shape[-1] * lp[n].dtype.itemsize
               for n in names)


def _gate(w, local, held, E: int):
    """[T, E] float32: the router's weight where the token chose the held
    expert (w, local, held [T, k]), else 0."""
    return jnp.sum(
        jnp.where(held[..., None] & (local[..., None] == jnp.arange(E)),
                  w[..., None], 0.0), axis=1)


def held_dense(x, lp, c: ModelConfig, w, local, held, layer=None):
    """x [T, d], few tokens: every held expert over every token, weighted
    by the router's weight where the token chose it (w, local, held
    [T, k]) -> [T, d] float32. One batched product over the experts, the
    stacked weights read once where they lie (transformer._moe's form):
    no sort, no gather."""
    if c.mlp_act == "relu2":
        u = jax.nn.relu(jnp.einsum("td,edf->etf", x, _held(lp, "wu", layer)))
        act = u * u
    else:
        act = (jax.nn.silu(jnp.einsum("td,edf->etf", x,
                                      _held(lp, "wg", layer)))
               * jnp.einsum("td,edf->etf", x, _held(lp, "wu", layer)))
    y = jnp.einsum("etf,efd->etd", act, _held(lp, "wd", layer))
    return jnp.einsum("etd,te->td", y.astype(jnp.float32),
                      _gate(w, local, held, c.moe_experts))


def held_walk(x, lp, c: ModelConfig, w, local, held, hit_ends, layer=None):
    """The same sum over the experts that were HIT alone, in the order of
    their ids (`hit_ends` [E]: hit experts up to and with each one): a
    turn an expert, its weights sliced where they lie (`_one_expert`) and
    its product of ALL T rows added under its column of the gate. An
    expert nobody chose adds an exact zero to `held_dense`'s sum and is
    not read here. Rows are few, the read is the cost: no sort, no
    gather."""
    gate = _gate(w, local, held, c.moe_experts)

    def turn(i_y):
        i, y = i_y
        e = jnp.sum(i >= hit_ends)
        g = jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1)       # [T, 1]
        return i + 1, y + g * _one_expert(x, lp, e, c, layer).astype(
            jnp.float32)

    return jax.lax.while_loop(
        lambda i_y: i_y[0] < hit_ends[-1], turn,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))[1]


def held_few(x, lp, c: ModelConfig, w, local, held, counts, layer=None):
    """x [T, d], few tokens (w, local, held [T, k]; counts [E] pairs an
    expert received) -> (the held experts' part [T, d] float32, experts
    whose weights were read). ONE sum, over the experts this call's tokens
    chose: by `held_walk` where reading the hit experts a turn each costs
    less than reading all E in one batched product (`_walk_pays`, decided
    on the device from this call's own hit count), else by `held_dense`,
    which is the walk's every-expert-hit case without its turns. A shape
    whose walk would not pay with E - 1 experts hit has no branch in its
    program at all (the comment above `_TURN_BYTES`)."""
    E = c.moe_experts
    nbytes = _expert_bytes(lp, c)
    if x.shape[0] > _SMALL_TILE_ROWS or not _walk_pays(E - 1, E, nbytes):
        return held_dense(x, lp, c, w, local, held, layer), jnp.int32(E)
    hit_ends = jnp.cumsum(counts > 0, dtype=jnp.int32)
    walks = _walk_pays(hit_ends[-1], E, nbytes)
    # the branches close over `lp` WHOLE and slice inside: a layer's slice
    # handed in as an operand would be a copy of the layer's experts
    y = jax.lax.cond(
        walks,
        lambda: held_walk(x, lp, c, w, local, held, hit_ends, layer),
        lambda: held_dense(x, lp, c, w, local, held, layer))
    return y, jnp.where(walks, hit_ends[-1], E)


def shared_expert(x, lp, c: ModelConfig):
    """x [T, d] -> the always-on expert's output [T, d]."""
    if c.mlp_act == "relu2":
        return relu2_mlp(x, lp["shared_wu"], lp["shared_wd"])
    return swiglu(x[None], lp["shared_wg"], lp["shared_wu"],
                  lp["shared_wd"])[0]


def _order_by_sort(held, local, E: int):
    """The held pairs sorted by expert (absent experts' pairs last), by a
    stable sort of the T * k pairs -> (token_of(rows) for sorted rows,
    where_sorted [T, k]: pair -> sorted row, counts [E], ends [E])."""
    T, k = held.shape
    key = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(key, stable=True)       # sorted row -> pair
    where_sorted = jnp.argsort(order).reshape(T, k)  # pair -> sorted row
    counts = jnp.sum(key[:, None] == jnp.arange(E)[None], axis=0,
                     dtype=jnp.int32)
    return (lambda rows: order[jnp.minimum(rows, T * k - 1)] // k,
            where_sorted, counts, jnp.cumsum(counts))


def _order_by_count(held, local, E: int):
    """The same order without a sort: an expert's pairs are counted down
    the tokens, so
    a pair's row is its expert's start plus the count above it, and a
    row's token is found back by a binary search down its expert's column
    of counts."""
    T = held.shape[0]
    onehot = held[..., None] & (local[..., None] == jnp.arange(E))
    above = jnp.cumsum(onehot.any(1), axis=0, dtype=jnp.int32)  # [T, E]
    counts = above[-1]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    where_sorted = jnp.sum(
        jnp.where(onehot, (starts + above - 1)[:, None, :], 0), axis=2)
    flat_above = above.reshape(-1)

    def token_of(rows):
        col = jnp.minimum(
            jnp.sum(rows[:, None] >= ends[None], axis=1), E - 1)
        nth = rows - jnp.take(starts, col) + 1   # its expert's nth pair

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = (lo + hi) // 2
            right = jnp.take(flat_above,
                             jnp.minimum(mid, T - 1) * E + col) < nth
            return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

        lo, _ = jax.lax.fori_loop(
            0, T.bit_length(), halve,
            (jnp.zeros_like(rows), jnp.full_like(rows, T)))
        return jnp.minimum(lo, T - 1)   # past the held pairs: any row

    return token_of, where_sorted, counts, ends


def expert_layer(x, lp, c: ModelConfig, valid, layer=None):
    """x [T, d] (normed), valid [T] bool (padding routes nowhere) ->
    (held experts' part + shared experts [T, d], stats [N_STATS + E]).

    `layer`: the experts' `wg`, `wu`, `wd` in `lp` are stacks over a
    model's layers [L, E, ...] and this (an int, or traced) is the layer
    to read. A model that keeps its layers stacked (models/transformer.py)
    hands the stack down: a layer's slice taken outside is an operand of
    the loop over tiles, which XLA makes a COPY of the layer's experts
    (5.3 GiB of temporaries at Mixtral's two layers, described-chip
    compile, PR 40); one slice [layer, expert] inside the loop reads the
    weights where they lie."""
    T, d = x.shape
    E, k = c.moe_experts, c.moe_top_k
    with jax.named_scope("expert_layer"):
        w, idx = route(x, lp, c)
        local = idx - c.moe_held_group * E
        held = (local >= 0) & (local < E) & valid[:, None]      # [T, k]
        rows_a_pass = min(T * k, max(T, _MIN_PASS_ROWS))
        dense = (c.moe_grouped == "tiles" and T * E <= _DENSE_ROWS
                 and not _full_tiles(rows_a_pass, E))
        if dense:
            counts = jnp.sum(
                held[..., None] & (local[..., None] == jnp.arange(E)),
                axis=(0, 1), dtype=jnp.int32)
            ends = jnp.cumsum(counts)
        elif c.moe_grouped == "tiles":
            token_of, where_sorted, counts, ends = _order_by_count(
                held, local, E)
        else:
            # pairs sorted by held expert; pairs of absent experts sort last
            token_of, where_sorted, counts, ends = _order_by_sort(
                held, local, E)
        n_held = ends[-1]

        def one_pass(start, y):
            rows = jnp.take(x, token_of(start + jnp.arange(rows_a_pass)),
                            axis=0)
            sizes = (jnp.clip(ends, start, start + rows_a_pass)
                     - jnp.clip(ends - counts, start, start + rows_a_pass))
            out = _grouped_mlp(rows, lp, sizes, c, layer)
            rel = where_sorted - start
            here = held & (rel >= 0) & (rel < rows_a_pass)
            for j in range(k):      # a row gather a choice; no scatter
                got = jnp.take(out, jnp.clip(rel[:, j], 0, rows_a_pass - 1),
                               axis=0)
                y = y + jnp.where(here[:, j, None],
                                  got.astype(jnp.float32) * w[:, j, None], 0)
            return y

        y = jnp.zeros((T, d), jnp.float32)
        n_read = jnp.int32(0)
        if dense:
            y, n_read = held_few(x, lp, c, w, local, held, counts, layer)
        elif rows_a_pass == T * k:
            y = one_pass(0, y)
        else:
            _, y = jax.lax.while_loop(
                lambda sy: sy[0] < n_held,
                lambda sy: (sy[0] + rows_a_pass, one_pass(sy[0], sy[1])),
                (jnp.int32(0), y))
        y = y.astype(x.dtype)
        if c.moe_shared_experts:
            y = y + shared_expert(x, lp, c)
        stats = jnp.concatenate([jnp.stack([
            jnp.sum(valid, dtype=jnp.int32), n_held,
            jnp.sum(valid & ~held.any(1), dtype=jnp.int32),
            jnp.int32(1), jnp.int32(dense), n_read]), counts])
    return y, stats
