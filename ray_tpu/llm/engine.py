"""Continuous-batching LLM inference engine, jit-first.

Parity: the role vLLM plays under the reference's llm stack
(`python/ray/llm/_internal/serve/deployments/llm/vllm/` — continuous
batching, paged KV, TP sizing consumed for placement). TPU-native redesign
(JetStream-shaped rather than a vLLM port):

- **Static shapes everywhere.** The decode batch is a fixed array of
  `max_slots` sequence slots over ONE preallocated pool of KV pages
  [layers, kv_heads, pages, head_dim, page]; admission, growth and
  eviction mutate slot state and page tables, never array shapes, so XLA
  compiles prefill once a prompt-length bucket and decode once a
  page-table bucket. A model whose window layers see only the last few
  hundred positions keeps those layers' pages in a second pool, a bounded
  number a sequence, under a table of its own (`_slide_window`).
- **Decode is one jit for ALL slots** — a [slots, 1] batched step keeps the
  MXU busy and lets GSPMD shard heads over the "tp" mesh axis; per-slot
  positions, masks and page ids are data, not shapes.
- **Prefill/decode disaggregation is a host-side policy**: prefill runs as
  its own jit per bucket and its KV is written into the slot's pages.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import diagnostics
from ray_tpu.models import ModelConfig, init_params, model_module
from ray_tpu.models.experts import N_STATS, expert_layer, stats_zero
from ray_tpu.models.transformer import exit_step, exit_zero
from ray_tpu.ops.attention import prefill_attention, prefill_blocks
from ray_tpu.ops.layers import apply_rope, last_rows, rmsnorm, rope

# ---- shared compiled-step cache -------------------------------------
# Engines used to create their own jax.jit wrappers, so two engines with
# the SAME model config re-traced and re-compiled every step variant from
# scratch (each wrapper owns a private executable cache). Keying the
# wrappers process-globally on (step, model config, static lowering args)
# lets every engine with equal statics share one wrapper — and therefore
# one compile per input-shape bucket. This is what keeps a test suite (or
# a serve process hosting several replicas of one model) from paying the
# prefill/decode compile tax per engine instance. Shapes/shardings stay
# OUT of the key: the wrapper's own aval-keyed cache handles those.
_JIT_CACHE: dict[tuple, object] = {}
_JIT_CACHE_LOCK = threading.Lock()
# Rows of recurrent state the prefix cache keeps beside the slots' own (a
# model with ModelConfig.kv_cache == "recurrent"): snapshots of the state
# at a prefill chunk's end, where a continuation, or a later request with
# the same prefix, resumes.
SNAPSHOT_ROWS = 16
# What one step may admit, in rows: the prompt buckets of the requests it
# plans (a chunk of a long prompt counts its bucket) add up to at most this
# many times the largest bucket; the first request always passes. A
# prefill's activations grow with its rows, and a chunked admission takes
# no slot, so without it a queue that built up is bounded by pages alone
# (sixteen 8192-row chunks in one program). The rest of the queue waits a
# step, with a decode step in between.
ADMIT_BUCKETS = 4


def _shared_jit(key: tuple, program, **jit_kwargs):
    """The process's one `jax.jit` of `program()` under `key`. The callable
    takes the key's first element as its name, so a trace's module line
    and a compile's log say `jit_decode_paged`, where a partial or a
    lambda would leave `jit__unknown` or `jit__lambda`."""
    with _JIT_CACHE_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is None:
            f = program()
            f.__name__ = key[0]
            fn = _JIT_CACHE[key] = jax.jit(f, **jit_kwargs)
        return fn


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8             # concurrent decoding sequences
    max_len: int = 2048            # per-sequence context bound (prompt + gen)
    prompt_buckets: tuple = (64, 256, 1024)  # prefill compile buckets
    eos_token: int = 2
    default_max_new_tokens: int = 128
    default_temperature: float = 0.0  # 0 = greedy
    # --- KV layout (parity: vLLM's paged KV under the reference's llm
    # stack, vllm_models.py:123-137; TPU-shaped: static page pool +
    # bucketed gathers instead of CUDA page kernels) ---
    # One layout, "paged": InferenceEngine refuses any other value. The
    # field is here only because the benchmark's harness passes it
    # (perfbench/harness/modelcfg.py) and refuses keys EngineConfig lacks.
    kv_layout: str = "paged"
    page_size: int = 128           # tokens per KV page (TPU lane-friendly)
    num_pages: int | None = None   # pool size; None = slots*ceil(max_len/
    #                                page)+1 (every slot can reach max_len).
    #                                A page holds `page_size` tokens of every
    #                                cache layer (kv_stats()["page_bytes"])
    prefix_cache: bool = True      # reuse full prompt pages across requests
    # Rows one step may admit (the prompt buckets of the requests it plans,
    # added up); None = ADMIT_BUCKETS times the largest bucket. A model
    # whose prefill activations at that many rows do not fit beside its
    # weights and pools names fewer.
    admit_rows: int | None = None
    # Pages of the window layers' pool (a model with ModelConfig.kv_cache
    # == "windowed"; None = slots * (the pages a window spans + 1) + that
    # span: every slot its window and a page to spare, and room for one
    # chunk's held tail beside its continuation's own pages)
    num_window_pages: int | None = None
    # The engine speculates nothing: InferenceEngine refuses any
    # `speculation` but None, and reads `spec_k` nowhere. The two fields
    # are here only because perfbench/tests/test_lookups.py passes them.
    speculation: str | None = None
    spec_k: int = 4


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: list
    max_new_tokens: int
    temperature: float
    top_p: float = 1.0     # 1.0 = no nucleus truncation
    top_k: int = 0         # 0 = no top-k truncation
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # Set when the request was preempted mid-decode: the token that was
    # sampled but never fed back. Re-admission resumes from it instead of
    # re-sampling the position.
    resume_token: int | None = None
    # Guided decoding: a compiled TokenGuide (guided.py) and the host
    # mirror of the slot's DFA state (advanced as tokens are read back;
    # survives preemption/re-admission).
    guide: object | None = None
    guide_state: int = 0
    # OpenAI logprobs: when True, token_logprobs collects log p(token)
    # for each generated token.
    logprobs: bool = False
    token_logprobs: list = dataclasses.field(default_factory=list)
    # Disaggregated serving: a (ks, vs) prompt-KV handoff exported by a
    # prefill worker (PrefillEngine.prefill_export). The pump imports it
    # into the prefix cache right before this request's admission, so the
    # suffix prefill only covers what the handoff does not.
    kv_handoff: tuple | None = None
    # Tokens of `prompt` that came with the request; preemption appends
    # the generated tokens the model has seen after them.
    n_prompt: int = 0
    # The slot it decodes in, or decoded in last (its row of the row
    # pools: InferenceEngine.state_rows).
    slot: int | None = None
    # Stamped always (time.perf_counter_ns; 0 = not yet): when add_request
    # took it, and the start of the admission that gave it a slot (the
    # first, if it was preempted). The `ray_tpu.request` span's start and
    # its `queue_ms` come from them.
    t_arrive_ns: int = dataclasses.field(default_factory=time.perf_counter_ns)
    t_slot_ns: int = 0
    # Window pools: (pages b, window pages) a chunked prefill holds for
    # its continuation: the window layers' last pages before position
    # b * page, where the next chunk begins.
    win_hold: tuple | None = None

    def __post_init__(self):
        self.n_prompt = len(self.prompt)


@dataclasses.dataclass
class _FirstTokens:
    """The first tokens of an admission burst, drawn and not fetched: they
    stay on the device, the decode step after the admission reads them
    there (`merge_tokens`), and `_land` fetches them with that step's."""
    tokens: object      # [k] int32 on the device, a row a request
    logps: object       # [k] log p(token) on the device; None if none asked
    owners: list        # k (slot, request)
    admitted: dict      # `_admit`'s result, whose values they will be


@dataclasses.dataclass
class _Flight:
    """A decode step that was dispatched and not fetched yet
    (InferenceEngine.step). The host's view (`lengths`, `active`,
    `last_tokens`, each request's `generated`) is the one BEFORE it until
    `_land` fetches the tokens; of a slot that was admitted since (under
    this step, into a slot it led once too often: `reqs[i]` is then no
    longer the slot's request) the host's view is the new owner's."""
    tokens: object      # [B] int32 on the device: the token every slot drew
    logps: object       # [B] log p(token) on the device; None if none asked
    active: np.ndarray  # [B] bool: the slots that decoded in it
    reqs: list          # [B] whose slots they were when it was dispatched
    ends: np.ndarray    # [B] bool: its token is the slot's last, by
    #                     max_new_tokens or max_len (eos_token is known only
    #                     once the token is fetched)
    firsts: _FirstTokens | None = None  # first tokens it read on the device
    #                     and the host has not seen: `_land` applies them
    #                     before the step's own


def merge_tokens(base, host, keep, firsts, src):
    """A decode step's `tokens` operand [B] where not every slot's token
    lies in one place: `base` (the step in flight's, on the device) where
    `keep`, else `host` (the host's upload), and row `src[i]` of `firsts`
    (an admission's first tokens, on the device) where `src[i]` >= 0."""
    out = jnp.where(keep, base, host)
    return jnp.where(src >= 0, firsts[jnp.maximum(src, 0)], out)


# ---------------- pure model steps ----------------


def _qkv(x, lp, c: ModelConfig, fence: bool = False):
    """x [b, s, d] -> q [b, s, h, hd], k, v [b, s, hkv, hd]; each weight
    is read by its matmul where it lies in `params["layers"]`.

    `fence` (the paged decode / verify programs) keeps the head split
    out of the projections. At a handful of tokens XLA otherwise folds
    the reshape and the transposes after it into each projection as a
    convolution over a heads axis, which wants the weight transposed —
    and re-lays-out wq, wk, wv of every layer on every step (1.09 of
    qwen2_7b's 20.2 ms decode step, chip trace, PR 26)."""
    b, s, _ = x.shape
    h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    hold = jax.lax.optimization_barrier if fence else (lambda a: a)
    q = hold(jnp.einsum("bsd,dq->bsq", x, lp["wq"])).reshape(b, s, h, hd)
    k = hold(jnp.einsum("bsd,dk->bsk", x, lp["wk"])).reshape(b, s, hkv, hd)
    v = hold(jnp.einsum("bsd,dk->bsk", x, lp["wv"])).reshape(b, s, hkv, hd)
    return q, k, v


def _mlp_block(x, lp, c: ModelConfig):
    """x [b, s, d] + the layer's feed-forward; a model's experts in the
    GSPMD form, every expert over every row (transformer._moe: what a mesh
    of several devices shards over "ep")."""
    from ray_tpu.models.transformer import _mlp, _moe
    normed = rmsnorm(x, lp["mlp_norm"], c.norm_eps)
    y = _moe(normed, lp, c) if c.moe_experts else _mlp(normed, lp)
    if c.post_norms:
        y = rmsnorm(y, lp["mlp_post_norm"], c.norm_eps)
    return x + y


def _expert_block(x, lp, c: ModelConfig, routed):
    """`_mlp_block` of a model with experts on ONE device -> (x, stats).
    `routed` = (valid [b, s] bool, stats, the model's stacked layers, this
    layer's index): each row of `valid` goes to the experts it chose and
    padding to none (models/experts.py's layer, which counts what it routed
    into `stats` and reads the expert weights out of the stack where they
    lie). The caller names the form, "tiles": where tokens are few (a
    decode step) the experts they chose, walked one by one while fewer
    than all are hit, else every expert over every token
    (transformer._moe's batched products, the expert weights read once);
    the counted order and the tiled grouped product where they are many
    (an admission)."""
    valid, stats, layers, li = routed
    b, s, d = x.shape
    normed = rmsnorm(x, lp["mlp_norm"], c.norm_eps)
    y, st = expert_layer(
        normed.reshape(b * s, d),
        {**lp, **{w: layers[w] for w in ("wg", "wu", "wd")}},
        dataclasses.replace(c, moe_grouped="tiles"),
        jnp.broadcast_to(valid, (b, s)).reshape(b * s), layer=li)
    y = y.reshape(b, s, d)
    if c.post_norms:
        y = rmsnorm(y, lp["mlp_post_norm"], c.norm_eps)
    return x + y, stats + st


def _embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


# Rows a tile of the prefill programs' walk. A prompt is padded to its
# bucket, and every matrix product of a layer but attention's is a row's
# own: where a bucket is several tiles, the two prefill programs run those
# products over the tiles that hold a token, a loop whose trip count the
# device takes from `lengths` (models/experts._grouped_mlp_tiles' loop, for
# rows that were never sorted). A bucket of one tile has nothing to skip
# but whole padded requests, and its programs keep the straight pass and
# their text; so does the program that is handed no lengths to walk
# (decode). 256: a tile's products re-read the layer's weights,
# at qwen2_7b's widths 466 MB (0.57 ms) for 0.60 ms of products, and run at
# 0.86 of the straight pass's rate; 512-row tiles run at 0.96 of it and
# cannot tell a 600-token prompt from a 1024-token one (PERF.md section 5).
_TILE_ROWS = 256


def _walked(s: int) -> bool:
    """Whether a prefill program of [n, s] rows walks its tiles."""
    return s > _TILE_ROWS and s % _TILE_ROWS == 0


def _tiles_of(lengths):
    """lengths [n] -> the tiles of each request's rows that hold a token,
    tile j where j * _TILE_ROWS < its length (a numpy array on the host,
    for `prefill_rows_run`, as on the device)."""
    return -(-lengths // _TILE_ROWS)


def _rows_run(lengths: np.ndarray, s: int) -> int:
    """The rows a per-head prefill program of [len(lengths), s] runs its
    row-wise products over, on the host: its real tiles', or all of them
    where its bucket is one tile."""
    if not _walked(s):
        return len(lengths) * s
    return int(_tiles_of(lengths).sum()) * _TILE_ROWS


def _attn_blocks(c: ModelConfig, lengths: np.ndarray, s: int):
    """(The query blocks in the grids of one prefill dispatch's attention
    kernels, those of them that hold a token), on the host: every attention
    layer's call counted once, whatever its heads (a window layer's blocks
    are `window_block` rows, a full one's `_PREFILL_BQ`)."""
    if c.kv_cache == "windowed":
        kinds = c.attn_pattern
    else:
        kinds = "F" * (c.layer_pattern.count("*") if c.layer_pattern
                       else c.cache_layers)
    blocks = run = 0
    for kind in set(kinds):
        grid, real = prefill_blocks(lengths, s,
                                    c.window if kind == "W" else 0)
        blocks += kinds.count(kind) * grid
        run += kinds.count(kind) * real
    return blocks, run


def _tile_order(lengths, s: int):
    """What `_walk` takes of a right-padded [n, s] batch of `lengths`: (the
    ids of the tiles of its flattened rows, those that hold a token first;
    how many do), or None where the bucket is one tile."""
    if not _walked(s):
        return None
    real = _tiles_of(lengths)
    skipped = jnp.arange(s // _TILE_ROWS)[None] >= real[:, None]
    return (jnp.argsort(skipped.reshape(-1), stable=True).astype(jnp.int32),
            jnp.sum(real, dtype=jnp.int32))


def _straight_or_walked(tiles, run):
    """`run(tiles)`, a whole prefill program. A bucket of one tile:
    `run(None)`, the straight pass. More: a branch on the device, the
    straight pass where every tile holds a token (a prompt that fills its
    bucket, a long prompt's first chunk: a tile's products fall short of
    the whole batch's, `_TILE_ROWS`), else the walk; whole programs, so
    that the straight one is compiled as it was alone."""
    if tiles is None:
        return run(None)
    return jax.lax.cond(tiles[1] == tiles[0].shape[0],
                        lambda: run(None), lambda: run(tiles))


def _walk(tiles, ins, widths, dtype, body):
    """`body(this_turn, *tile of every ins [R, ...]) -> a tile of every
    output`, over the tiles that hold a token alone -> the outputs [R, w]
    for w in `widths`, zero where a tile was skipped. `tiles` = (ids
    [R / _TILE_ROWS], the real tiles' first; how many are real).
    `this_turn(a)` is `a`, bound to the turn: a weight sliced out of a
    stack at an index that does not change in the loop is moved out of it
    by the compiler, a copy of the layer (428 MiB of temporaries at
    qwen2_7b's widths, described-chip compile, PR 47), where a slice in the
    loop is read by its product where it lies."""
    order, count = tiles
    rows = ins[0].shape[0]

    def turn(i_outs):
        i, outs = i_outs
        at = order[i] * _TILE_ROWS
        got = body(lambda a: jax.lax.optimization_barrier((a, i))[0],
                   *(jax.lax.dynamic_slice(
                       a, (at, 0), (_TILE_ROWS, a.shape[1])) for a in ins))
        return i + 1, tuple(
            jax.lax.dynamic_update_slice(o, g.astype(dtype), (at, 0))
            for o, g in zip(outs, got))

    return jax.lax.while_loop(
        lambda i_outs: i_outs[0] < count, turn,
        (jnp.int32(0), tuple(jnp.zeros((rows, w), dtype) for w in widths)))[1]


def _attn_out(x, attn, lp, c: ModelConfig):
    """x [b, s, d] + `wo` of the heads' outputs attn [b, s, h * hd]."""
    out = jnp.einsum("bsq,qd->bsd", attn, lp["wo"])
    if c.post_norms:
        out = rmsnorm(out, lp["attn_post_norm"], c.norm_eps)
    return x + out


def _block(x, lp, c: ModelConfig, turn, attend, fence: bool = False,
           routed=None, walk=None):
    """One layer of a per-head program, x [b, s, d] -> (x, kept, stats).
    What a program brings: `turn(t)` rotates q and k to its positions, and
    `attend(q, k, v)` -> (attention output [b, s, h, hd] in any grouping
    of its axes, what the program keeps of this layer: its K and V, or
    the pools it wrote them to). `fence` as in `_qkv`. With `routed` (a
    program that was handed the expert layers' counters: a model with
    experts on one device, `_serving_of`) the feed-forward is
    `_expert_block`'s; without, `_mlp_block`'s and `stats` is None. With
    `ModelConfig.post_norms` both sublayers' outputs are normed before the
    residual add.

    With `walk` = (`_tile_order`'s two, the model's stacked layers, this
    layer's index: a prefill program whose bucket is several tiles) the
    layer's row-wise stretches, the norm and projections before attention
    and `wo` to the feed-forward's residual after it, run over the tiles
    that hold a token (`_walk`): a skipped tile's x, K and V are zero,
    which no real row attends to, `insert_pages_batch` masks and
    `last_rows` never reads. Attention between them sees every row, as it
    did. Experts stay outside the walk: `expert_layer` runs its real pairs
    already."""
    b, s, d = x.shape
    if walk is None:
        normed = rmsnorm(x, lp["attn_norm"], c.norm_eps)
        q, k, v = _qkv(normed, lp, c, fence)
    else:
        *tiles, layers, li = walk

        def here(this_turn, *names):
            # `lp` with these weights sliced out of the stack INSIDE the
            # loop; `lp`'s own, an operand of the loop, would be a copy of
            # the layer (experts.expert_layer's `layer`)
            at = this_turn(li)
            return {**lp, **{w: jax.lax.dynamic_index_in_dim(
                layers[w], at, keepdims=False) for w in names}}

        def qkv(this_turn, xt):
            normed = rmsnorm(xt[None], lp["attn_norm"], c.norm_eps)
            return [t.reshape(_TILE_ROWS, -1) for t in _qkv(
                normed, here(this_turn, "wq", "wk", "wv"), c)]

        hd = c.head_dim
        q, k, v = (t.reshape(b, s, -1, hd) for t in _walk(
            tiles, (x.reshape(b * s, d),),
            (c.n_heads * hd, c.n_kv_heads * hd, c.n_kv_heads * hd),
            x.dtype, qkv))
    attn, kept = attend(turn(q), turn(k), v)
    attn = attn.reshape(b, s, c.n_heads * c.head_dim).astype(x.dtype)
    if walk is None:
        h = _attn_out(x, attn, lp, c)
    else:
        def out(this_turn, xt, at):
            w = here(this_turn, "wo", *(
                () if c.moe_experts else ("wg", "wu", "wd")))
            ht = _attn_out(xt[None], at[None], w, c)
            if not c.moe_experts:
                ht = _mlp_block(ht, w, c)
            return (ht[0],)

        h = _walk(tiles, (x.reshape(b * s, d), attn.reshape(b * s, -1)),
                  (d,), x.dtype, out)[0].reshape(b, s, d)
        if not c.moe_experts:
            return h, kept, None
    if routed is None:
        return _mlp_block(h, lp, c), kept, None
    x, stats = _expert_block(h, lp, c, routed)
    return x, kept, stats


def _prefill_attention(q, keys, values, prefix_len, lengths, pre_t: int,
                       c: ModelConfig):
    """q [n, s, h, hd] over keys and values [n, hkv, pre_t + s, hd] = a
    cached prefix of which request i has prefix_len[i] positions | the
    chunk itself, of which lengths[i] rows are real, causal; -> [n, s, h,
    hd]. One flash kernel for both prefill programs (the jnp reference
    where no chip is): no score array in HBM, K and V read by head // (h //
    hkv), never repeated, no query block run past a request's last row."""
    out = prefill_attention(
        q.transpose(0, 2, 1, 3), keys, values, prefix_len, pre_t=pre_t,
        scale=c.head_dim ** -0.5, name="gqa_prefill_attention",
        lengths=lengths)
    return out.transpose(0, 2, 1, 3)


def _head(x, params, c: ModelConfig, active=None, at=None,
          closed: bool = False):
    """Final norm and the fp32 head: x [b, s, d] -> logits [b, s, vocab],
    or [b, vocab] at the one position `at` of every sequence (or from
    x [b, d], the rows a prefill program picked itself). An inactive
    slot (`active` [b]) reads -1e30 except token 0: it must not corrupt
    metrics downstream, and argmax / categorical stay defined. `closed`:
    x is a closed pass's state (`_passes`), normed already."""
    if not closed:
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    if at is not None:
        x = x[:, at]
    logits = jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                        head.astype(jnp.float32))
    if active is None:
        return logits
    neg = jnp.full_like(logits, -1e30).at[..., 0].set(0.0)
    return jnp.where(jnp.expand_dims(active, tuple(range(1, logits.ndim))),
                     logits, neg)


def _real_rows(s: int, lengths):
    """[n, s] bool: the rows of a right-padded batch that hold a token."""
    return jnp.arange(s)[None] < lengths[:, None]


def _some(stats) -> tuple:
    return () if stats is None else (stats,)


def _layers(block, x, xs, layers, valid, stats, tiles=None):
    """x through `block(x, xs[i], routed, walk) -> (x, kept, stats)`, layer
    i after layer i - 1, in one scan -> (x, kept [L, ...], stats). `routed`
    is None without `stats`, else `_expert_block`'s, and `walk` None
    without `tiles` (`_tile_order`), else `_block`'s: made here a layer."""
    if stats is None and tiles is None:
        return jax.lax.scan(
            lambda x, xi: block(x, xi, None, None)[:2], x, xs) + (None,)

    def one(x_stats, xi_li):
        (x, *stats), (xi, li) = x_stats, xi_li
        x, kept, stats = block(
            x, xi, (valid, stats[0], layers, li) if stats else None,
            None if tiles is None else (*tiles, layers, li))
        return (x, *_some(stats)), kept

    n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    (x, *stats), kept = jax.lax.scan(one, (x, *_some(stats)),
                                     (xs, jnp.arange(n_layers)))
    return x, kept, stats[0] if stats else None


def _passes(c: ModelConfig, params, x, one_pass, rows, stats):
    """x through the layer stack, `c.loops` times over the ONE set of layer
    weights -> (the rows the head reads [n, d], what every cache layer
    keeps [loops * L, ...], stats). `one_pass(x, t, stats) -> (x, kept
    [L, ...], stats)` runs pass t's layers, which read and keep cache
    layers t * L ..; `rows(x)` picks the head's rows. One pass: that is all
    (the head norms its rows). More: a scan over the passes, `final_norm`
    closing each and feeding the next, the exit rule (transformer.
    exit_step) choosing a row's pass; the rows come back closed."""
    if c.loops == 1:
        x, kept, stats = one_pass(x, 0, stats)
        return rows(x), kept, stats

    def turn(carry, t):
        x, state, *stats = carry
        with jax.named_scope("pass"):
            x, kept, stats = one_pass(x, t, stats[0] if stats else None)
            x = rmsnorm(x, params["final_norm"], c.norm_eps)
        return (x, exit_step(params, c, t, rows(x), state),
                *_some(stats)), kept

    (_, state, *stats), kept = jax.lax.scan(
        turn, (x, exit_zero(rows(x)), *_some(stats)), jnp.arange(c.loops))
    return (state[0], jax.tree_util.tree_map(
        lambda a: a.reshape(-1, *a.shape[2:]), kept),
        stats[0] if stats else None)


def prefill_batch(params, tokens, lengths, stats=None, *,
                  config: ModelConfig):
    """tokens [n, S] (right-padded), lengths [n] -> (logits [n, vocab]
    fp32 at each request's last token, the one row that is sampled from,
    k,v caches [cache layers, n, S, hkv, hd], [stats]). Causal; padding
    contributes garbage KV beyond each true length, which insert never
    reads (length mask), and is sent to no expert. Batched so an admission
    burst pays ONE dispatch, not one per prompt (the vLLM-style batched
    prefill role). `stats` (`_Serving`: the expert layers' counters,
    `_mlp_block`) comes back counted up."""
    c = config
    n, s = tokens.shape

    def run(tiles):
        x = _embed(params, tokens)
        sin, cos = rope(jnp.arange(s), c.head_dim, c.rope_theta)
        no_prefix = jnp.zeros((n,), jnp.int32)

        def turn(t):  # [None] stays in here, staged once a use inside the
            # scan: the lowered text keys this program's compile-cache entry
            return apply_rope(t, sin[None], cos[None])

        def attend(q, k, v):
            return _prefill_attention(q, k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3), no_prefix,
                                      lengths, 0, c), (k, v)

        valid = None if stats is None else _real_rows(s, lengths)
        x, (ks, vs), counted = _passes(
            c, params, x, lambda x, _t, stats: _layers(
                lambda x, lp, routed, walk: _block(
                    x, lp, c, turn, attend, routed=routed, walk=walk),
                x, params["layers"], params["layers"], valid, stats, tiles),
            lambda x: last_rows(x, lengths), stats)
        return (_head(x, params, c, closed=c.loops > 1), ks, vs) + _some(
            counted)

    return _straight_or_walked(_tile_order(lengths, s), run)


def prefill(params, tokens, lengths, config: ModelConfig):
    """tokens [1, S], lengths [1] -> (logits [vocab] at the last token,
    k/v [L, S, hkv, hd]); the single-prompt view of prefill_batch
    (PrefillEngine's program)."""
    last, ks, vs = prefill_batch(params, tokens, lengths, config=config)
    return last[0], ks[:, 0], vs[:, 0]


def prefill_with_prefix_batch(params, tokens, lengths, pool_k, pool_v,
                              prefix_pages, prefix_len, stats=None, *,
                              config: ModelConfig):
    """Prefill only the SUFFIX of prompts whose prefix pages are already
    cached (prefix caching), a whole burst per dispatch. tokens [n, S] =
    suffixes (right-padded) of lengths [n]; prefix_pages [n, Pp] page ids
    into the pool (0-padded); prefix_len [n] true prefix token counts.
    Cached K is stored post-RoPE at absolute positions, so it is reused
    as-is; suffix positions offset by prefix_len. Returns (logits
    [n, vocab] f32 at each suffix's last token, suffix k/v caches
    [cache layers, n, S, hkv, hd], [stats]); `stats` as in prefill_batch.
    A looped stack's pass t reads cache layers t * L .. of the pools (its
    OWN keys of the prefix), one layer's slice at a time."""
    c = config
    if c.loops > 1 and prefix_pages.shape[1] == 1:
        # A table of one page turns the page gather into a slice, and XLA
        # then re-lays-out BOTH WHOLE pools for it (2 x 4 GiB of
        # temporaries at ouro_2_6b's 43 pages: the described-chip compile
        # refuses it, and so did the chip). Two pages, the second scratch
        # and masked by prefix_len, stay a gather.
        prefix_pages = jnp.pad(prefix_pages, ((0, 0), (0, 1)))
    n, s = tokens.shape
    pre_t = prefix_pages.shape[1] * pool_k.shape[4]

    def behind(pages, new):  # pages [hkv, N, hd, page], new [n, S, hkv, hd]
        # [hkv, n, Pp, hd, page] -> [n, hkv, Pp, page, hd]
        #                        -> [n, hkv, preT | S, hd]
        cached = pages[:, prefix_pages].transpose(1, 0, 2, 4, 3).reshape(
            n, pages.shape[0], pre_t, -1)
        return jnp.concatenate(
            [cached.astype(new.dtype), new.transpose(0, 2, 1, 3)], axis=2)

    def attend(pk, pv, q, k, v):
        return _prefill_attention(q, behind(pk, k), behind(pv, v),
                                  prefix_len, lengths, pre_t, c), (k, v)

    def run(tiles):
        x = _embed(params, tokens)
        positions = prefix_len[:, None] + jnp.arange(s)[None]      # [n, S]
        sin, cos = rope(positions, c.head_dim, c.rope_theta)

        def layer(x, scan_in, routed, walk):
            lp, pk, pv = scan_in
            if c.loops > 1:     # pk: the cache layer's index in both pools
                pk, pv = (
                    jax.lax.dynamic_index_in_dim(pool, pk, keepdims=False)
                    for pool in (pool_k, pool_v))
            return _block(x, lp, c, partial(apply_rope, sin=sin, cos=cos),
                          partial(attend, pk, pv), routed=routed, walk=walk)

        def one_pass(x, t, stats):
            if c.loops == 1:
                xs = (params["layers"], pool_k, pool_v)
            else:
                at = t * c.n_layers + jnp.arange(c.n_layers)
                xs = (params["layers"], at, at)
            return _layers(layer, x, xs, params["layers"], valid, stats,
                           tiles)

        valid = None if stats is None else _real_rows(s, lengths)
        x, (ks, vs), counted = _passes(
            c, params, x, one_pass, lambda x: last_rows(x, lengths), stats)
        return (_head(x, params, c, closed=c.loops > 1), ks, vs) + _some(
            counted)

    return _straight_or_walked(_tile_order(lengths, s), run)


def insert_pages_batch(pool_k, pool_v, ks, vs, page_ids, lengths):
    """insert_pages for a whole admission burst in one dispatch.
    ks/vs [L, n, S, hkv, hd] (vs of its own width where the pools'
    differ), L the pools' cache layers; page_ids
    [n, n_tab] (0 = scratch, where duplicate writes may race — scratch
    holds garbage by contract); lengths [n]."""
    L, n, S, hkv, hd = ks.shape
    page = pool_k.shape[4]
    n_tab = page_ids.shape[1]
    s_pad = n_tab * page
    if s_pad != S:
        padding = [(0, 0), (0, 0), (0, s_pad - S), (0, 0), (0, 0)]
        ks = jnp.pad(ks, padding)
        vs = jnp.pad(vs, padding)
    mask = (jnp.arange(s_pad)[None] < lengths[:, None])[None, :, :, None,
                                                        None]
    ks = jnp.where(mask, ks, 0).transpose(0, 3, 1, 2, 4).reshape(
        L, hkv, n * n_tab, page, hd).swapaxes(3, 4)
    vs = jnp.where(mask, vs, 0).transpose(0, 3, 1, 2, 4).reshape(
        L, hkv, n * n_tab, page, vs.shape[4]).swapaxes(3, 4)
    flat = page_ids.reshape(-1)
    pool_k = pool_k.at[:, :, flat].set(ks.astype(pool_k.dtype))
    pool_v = pool_v.at[:, :, flat].set(vs.astype(pool_v.dtype))
    return pool_k, pool_v


def decode_paged(params, pool_k, pool_v, tokens, lengths, active,
                 page_tables, stats=None, *, config: ModelConfig):
    """One token for every slot against the paged pool. page_tables
    [B, P] page ids in position order (0 = unused -> scratch page, whose
    garbage the position mask hides). The new token's KV is written at
    position `lengths` of its slot, by the attention kernel; compute
    scales with the bucketed P, not the model's max context. Pool layout
    [cache layers, hkv, N, hd, page]: a layer of K and V a layer of the
    model, and of a looped stack (`ModelConfig.loops` > 1) one a pass and
    layer, pass t's layer l reading and writing cache layer
    t * n_layers + l.
    -> (logits, pool_k, pool_v, [stats]): `stats` as in prefill_batch, an
    inactive slot's row the padding.

    TPU-shaped (the three costs that matter on this hardware):
    - the layer loop is UNROLLED python, not lax.scan with the pools as
      scan xs/ys — scan materializes a fresh stacked pool output every
      step (a full-pool HBM copy per token: measured ~30ms/step for a
      0.6GB pool). A looped stack's PASSES are a loop whose carry is the
      pools (a carry stays where it lies), the layers unrolled inside it;
      the cache layer reaches the kernel as a scalar it prefetches,
      traced inside that loop;
    - the pools are touched only where they lie, and only by the kernel:
      it reads the stacked pool at the layer it is handed and writes the
      token's K and V into the page that holds them as that page streams
      through VMEM (`paged_decode_insert_attention`: ONE write path for
      every per-head model; an inactive slot moves nothing). A scatter
      `pool.at[li, heads, w_page, :, w_off].set(...)` indexes the pool's
      minor (page) axis, so XLA moved the whole donated pool into an
      hd-minor layout, scattered there, sliced each `pool[li]` out and
      re-tiled it for the kernel, and moved the pool back: 6.8 ms of
      qwen2_7b's 18.9 ms step on the v5e (PR 29); a `dynamic_update_slice`
      column a slot, pool and layer, which followed, was 384 updates and
      0.68 ms of op time plus 0.21 ms of gaps between them in its 10.45
      ms step, 21.8 of ouro_2_6b's 58.9 (PERF.md section 5, PR 45, PR 46);
    - attention runs the Pallas paged-decode kernel
      (ops/paged_attention.py), which DMAs exactly the pages each slot
      owns — XLA lowers the gather-then-attend formulation at ~10% of
      HBM bandwidth and it dominated the whole step (measured 40+ ms vs
      ~1.5ms/step for the same KV working set through the kernel)."""
    from ray_tpu.ops.paged_attention import paged_decode_insert_attention
    c = config
    x = _embed(params, tokens)[:, None, :]  # [B,1,d]
    sin, cos = rope(lengths[:, None], c.head_dim, c.rope_theta)
    # attend INCLUSIVE of the token being written: positions < lengths + 1.
    # An inactive slot's 0 moves nothing (no page read, merged or written
    # back; its output row is 0 and _head ignores it), and its table is
    # all scratch for the fallback off the chip, whose insert has no such
    # length. A slot past its table bucket overlaps no page of it: the
    # kernel writes nothing.
    limits = jnp.where(active, lengths + 1, 0)
    write_tables = jnp.where(active[:, None], page_tables, 0)
    # the call's name in a device trace, which the benchmark's readers
    # match: looped_attn_* a looped stack's, paged_attn_* (`^_paged_decode`)
    # every other per-head model's
    name = "looped_paged_decode" if c.loops > 1 else "_paged_decode_insert"

    def attend(li, pool_k, pool_v, q, k, v):
        attn, pool_k, pool_v = paged_decode_insert_attention(
            q[:, 0], pool_k, pool_v, k[:, 0], v[:, 0], limits, write_tables,
            layer=li, name=name)
        return attn, (pool_k, pool_v)

    def layers(x, pool_k, pool_v, stats, first):
        # one pass: cache layers first .. first + n_layers
        for li in range(c.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
            x, (pool_k, pool_v), stats = _block(
                x, lp, c, partial(apply_rope, sin=sin, cos=cos),
                partial(attend, first + li, pool_k, pool_v), fence=True,
                routed=None if stats is None else (
                    active[:, None], stats, params["layers"], li))
        return x, pool_k, pool_v, stats

    if c.loops == 1:
        x, pool_k, pool_v, stats = layers(x, pool_k, pool_v, stats, 0)
        return (_head(x, params, c, active, at=0), pool_k,
                pool_v) + _some(stats)

    def turn(t, carry):
        x, state, pool_k, pool_v, *stats = carry
        with jax.named_scope("pass"):
            x, pool_k, pool_v, stats = layers(
                x, pool_k, pool_v, stats[0] if stats else None,
                t * c.n_layers)
            x = rmsnorm(x, params["final_norm"], c.norm_eps)
        return (x, exit_step(params, c, t, x[:, 0], state), pool_k, pool_v,
                *_some(stats))

    _, state, pool_k, pool_v, *stats = jax.lax.fori_loop(
        0, c.loops, turn,
        (x, exit_zero(x[:, 0]), pool_k, pool_v, *_some(stats)))
    return (_head(state[0], params, c, active, closed=True), pool_k,
            pool_v) + tuple(stats)


def sample(logits, temperature, key, top_p=None, top_k=None, mask=None):
    """Per-row temperature (0 = greedy) with optional nucleus (top_p) and
    top_k truncation — all branch-free under jit.

    top_p/top_k are per-row arrays; top_p=1.0 / top_k=0 disable the
    respective filter for that row. mask [B, V] bool (True = allowed)
    constrains both greedy and stochastic paths (guided decoding)."""
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    neg = jnp.finfo(scaled.dtype).min
    if top_k is not None:
        V = scaled.shape[-1]
        # rank of each logit within its row (0 = largest)
        order = jnp.argsort(scaled, axis=-1)[:, ::-1]
        ranks = jnp.zeros_like(order).at[
            jnp.arange(order.shape[0])[:, None], order].set(
            jnp.arange(V)[None, :])
        k = jnp.where(top_k > 0, top_k, V)[:, None]
        scaled = jnp.where(ranks < k, scaled, neg)
    if top_p is not None:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p; <= (not
        # <) so the argmax survives even top_p == 0 (cum - probs is exactly
        # 0 for the first sorted element)
        keep_sorted = (cum - probs) <= top_p[:, None]
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        scaled = jnp.where(scaled >= cutoff, scaled, neg)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy)


# ---------------- the engine ----------------


def _samplers():
    """Two compiled samplers: the plain one (no sorts) serves the default
    top_k=0/top_p=1 case on the hot decode loop; the truncating one
    compiles the top-k/top-p masking only when some request asks for it."""
    return (_shared_jit(("sample",), lambda: sample),
            _shared_jit(
                ("sample_trunc",),
                lambda: lambda lg, t, k, p, tk, m=None: sample(
                    lg, t, k, top_p=p, top_k=tk, mask=m)))


def _resolve_params(model_config: ModelConfig, params, mesh, rules,
                    seed: int):
    """Init (or accept) params and shard them over the replica mesh —
    shared by the decode engine and the prefill-pool engine."""
    if params is None:
        params = init_params(model_config, jax.random.PRNGKey(seed))
    if mesh is not None:
        from ray_tpu.models import param_logical_axes
        from ray_tpu.parallel.sharding import ShardingRules, shard_params
        rules = rules or ShardingRules.default()
        params = shard_params(params, param_logical_axes(model_config),
                              rules, mesh)
    return params


def _refuse(c: ModelConfig, what: str):
    """What a model that brings its own serving programs, or a looped
    stack, does not run with: each is a program this file spells for
    per-head K and V of layers that run once (ROADMAP D1)."""
    if c.kv_cache == "per_head":
        raise ValueError(
            f"ModelConfig.loops={c.loops} runs its {c.n_layers} layers "
            f"{c.loops} times, a pass with K and V of its own "
            f"({c.cache_layers} cache layers), which does not run with "
            f"{what}: only prefill_batch, prefill_with_prefix_batch, "
            f"insert and decode_paged loop over the passes")
    keeps = {
        "latent": f"ModelConfig.attention={c.attention!r} keeps a latent KV "
                  f"cache",
        "recurrent": f"ModelConfig.layer_pattern={c.layer_pattern!r} keeps "
                     f"recurrent state beside its KV pages" + (
            f": a delta-rule state [ssm_heads={c.ssm_heads}, ssm_head_dim="
            f"{c.ssm_head_dim}, {c.ssm_head_dim}] a \"K\" layer (kda_rank="
            f"{c.kda_rank}, kda_neg_eigval={c.kda_neg_eigval}, attn_gate="
            f"{c.attn_gate!r})" if "K" in c.layer_pattern else ""),
        "windowed": f"ModelConfig.attn_pattern={c.attn_pattern!r} keeps its "
                    f"window layers' K and V in a second pool of "
                    f"ModelConfig.window={c.window} positions a sequence" + (
            f", the kinds' pools of other shapes (window_kv_heads="
            f"{c.window_kv_heads} beside n_kv_heads={c.n_kv_heads}, "
            f"v_head_dim={c.v_head_dim} beside a head of {c.head_dim}, "
            f"attn_sink={c.attn_sink!r})"
            if c.window_kv_heads or c.v_head_dim or c.attn_sink else ""),
    }[c.kv_cache]
    raise ValueError(
        f"{keeps} (ModelConfig.kv_cache == \"{c.kv_cache}\"), which does "
        f"not run with {what}: only the paged single-device programs "
        f"(prefill_batch, prefill_with_prefix_batch, insert, decode_paged) "
        f"exist for it")


@dataclasses.dataclass(frozen=True)
class _Serving:
    """What the engine asks of a model for serving (ROADMAP D18): what a
    sequence keeps, and the four programs over it. One calling convention
    for every kind, `pools` the page pools, `rows` the row pools of
    recurrent state (none for most), `stats` the expert layers' counters
    (none for most), `[...]` only over a cached prefix:

      prefill(params, tokens, lengths, [*pools, prefix_pages, prefix_len],
              [*rows, src_rows, dst_rows], [stats])
          -> (last-token logits, *what insert takes, [*rows], [stats])
      insert(*pools, *what prefill gave, page_ids, lengths) -> pools
      decode(params, *pools, *rows, tokens, lengths, active, page_tables,
             [stats]) -> (logits, *pools, *rows, [stats])

    With window pools (a second kind of page pool, for layers that see a
    window of positions) `pools` is the page pools then the window pools,
    and each table is a tuple: prefix_pages (the growing kind's pages from
    position 0, the window kind's last pages before the suffix,
    right-aligned), page_ids (the chunk's pages, the window kind's kept
    pages), page_tables (the slots' pages, their held window pages, the
    position the first of those begins at); the prefill programs take the
    page size as a static `page`, to cut a chunk's kept pages out.

    The per-head programs are this file's; a model with another kind of
    cache brings its own in its module (models.model_module)."""
    page_pools: object   # (c, num_pages, page) -> ShapeDtypeStructs
    window_pools: object  # the same for the window pools, or None
    row_pools: object    # (c, rows) -> ShapeDtypeStructs, or None
    stats_zero: object   # (c) -> counters, or None
    prefill_batch: object
    prefill_with_prefix_batch: object
    insert_batch: object
    decode_paged: object


def _per_head_pools(c: ModelConfig, num_pages: int, page: int) -> tuple:
    # [cache layers, hkv, N, hd, page] — a cache layer a layer (of a looped
    # stack: a pass and layer), kv-heads outermost after them and
    # head_dim BEFORE page so the Pallas decode kernel can DMA
    # per-page blocks [hkv, hd, page] whose trailing dims
    # (hd, 128) satisfy Mosaic's (8, 128) tiling.
    shape = (c.cache_layers, c.n_kv_heads, num_pages, c.head_dim, page)
    return (jax.ShapeDtypeStruct(shape, c.jdtype),) * 2


def _serving_of(c: ModelConfig, mesh=None) -> _Serving:
    if c.kv_cache == "per_head":
        # experts on ONE device go through the expert layer, which counts;
        # under a mesh they stay in the GSPMD form (`_mlp_block`)
        counted = c.moe_experts and (mesh is None or mesh.devices.size == 1)
        return _Serving(_per_head_pools, None, None,
                        stats_zero if counted else None, prefill_batch,
                        prefill_with_prefix_batch, insert_pages_batch,
                        decode_paged)
    m = model_module(c)
    return _Serving(
        m.page_pools, getattr(m, "window_pools", None),
        getattr(m, "row_pools", None), m.stats_zero,
        m.prefill_batch, m.prefill_with_prefix_batch,
        getattr(m, "insert_pages_batch", insert_pages_batch), m.decode_paged)


def _sampling_of(reqs) -> tuple:
    """(temperatures, top_ps, top_ks), a row a request, on the host; None
    (an empty slot) reads greedy and untruncated."""
    return (np.array([r.temperature if r else 0.0 for r in reqs], np.float32),
            np.array([r.top_p if r else 1.0 for r in reqs], np.float32),
            np.array([r.top_k if r else 0 for r in reqs], np.int32))


def _prompt_bucket(e: EngineConfig, n: int) -> int:
    """The prefill compile bucket for an n-token prompt. Buckets above
    max_len are unusable: their prefill KV could not be spliced into the
    [.., max_len, ..] cache."""
    usable = [b for b in e.prompt_buckets if b <= e.max_len]
    limit = min(max(usable, default=0), e.max_len - 1)
    if n > limit:
        raise ValueError(
            f"prompt of {n} tokens exceeds the engine limit {limit} "
            f"(buckets={e.prompt_buckets}, max_len={e.max_len})")
    for b in usable:
        if n <= b:
            return b
    raise ValueError(f"no prompt bucket fits {n} tokens")


class InferenceEngine:
    """Slot-based continuous batching over the jitted steps above.

    Thread-compatible: callers serialize through `step()` (the serve layer
    runs one engine loop thread per replica).
    """

    def __init__(self, model_config: ModelConfig,
                 engine_config: EngineConfig | None = None, *,
                 params=None, mesh=None, rules=None, seed: int = 0):
        self.c = model_config
        self.e = engine_config or EngineConfig()
        self.mesh = mesh
        if self.e.kv_layout != "paged":
            raise ValueError(
                f"EngineConfig.kv_layout={self.e.kv_layout!r}: the engine "
                f"keeps one KV layout, \"paged\" (a pool of pages; what a "
                f"page holds follows ModelConfig.attention="
                f"{model_config.attention!r})")
        if self.e.speculation is not None:
            raise ValueError(
                f"EngineConfig.speculation={self.e.speculation!r}: the "
                f"engine keeps one decode loop, step(), which drafts "
                f"nothing (ROADMAP D17: no served or measured path ever "
                f"reached the n-gram window loop)")
        # A model with another kind of cache than per-head K and V
        # (ModelConfig.kv_cache) brings its own four programs in its
        # module, over pools of its own shapes (_Serving); page accounting,
        # prefix hashing, chunked prefill and preemption below are shared.
        self.serving = _serving_of(model_config, mesh)
        # (a looped stack keeps per-head K and V and this file's four
        # programs, and is refused what the others are)
        self._own = (model_config.kv_cache != "per_head"
                     or model_config.loops > 1)
        if self._own and mesh is not None and mesh.devices.size > 1:
            _refuse(model_config, f"a mesh of {dict(mesh.shape)} "
                                  f"(tensor parallelism)")
        self.params = _resolve_params(model_config, params, mesh, rules,
                                      seed)
        c, e = self.c, self.e
        # Paged pool (parity: vLLM paged KV, vllm_models.py:123-137):
        # HBM tracks the pool size — actual token load — not
        # slots x max_len; sequences grow page by page and shared
        # prompt prefixes share pages. Page 0 is reserved scratch
        # (unused page-table entries point at it).
        page = e.page_size
        self.pages_per_slot = -(-e.max_len // page)
        self.num_pages = (e.num_pages
                          or e.max_slots * self.pages_per_slot + 1)

        def zeros(shapes):
            return tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)

        # the page pools: K and V a head, or one latent pool (`cache_v`
        # is None then)
        self.win_pools: tuple = ()
        self._set_pools(zeros(self.serving.page_pools(c, self.num_pages,
                                                      page)))
        # Window pools: K and V of the layers that see the last
        # `ModelConfig.window` positions, under tables of their own. A slot
        # holds the pages its next query can see, `win_span` at most, in
        # position order from logical page `slot_win_first`; a page the
        # window has left goes back to `free_win` (`_slide_window`). Page 0
        # is scratch here too.
        self.win_span = 0       # 0 = the model has no window pools
        self.num_window_pages = 0
        self.free_win: list[int] = []
        self.slot_win: list[list[int]] = [[] for _ in range(e.max_slots)]
        self.slot_win_first = [0] * e.max_slots
        self.window_pages_released = 0
        self.window_pages_peak = 0       # in use at once
        self.window_seq_pages_peak = 0   # in one slot's table
        self.pages_peak = 0
        if self.serving.window_pools is not None:
            self.win_span = c.window_span(page)
            self.num_window_pages = (e.num_window_pages or e.max_slots
                                     * (self.win_span + 1) + self.win_span)
            if self.num_window_pages < 2 * self.win_span:
                # a continuation reads the span - 1 pages held for it while
                # it writes up to a span of its own; page 0 is scratch
                raise ValueError(
                    f"EngineConfig.num_window_pages={self.num_window_pages}"
                    f": a chunk's continuation alone needs "
                    f"{2 * self.win_span} (ModelConfig.window={c.window}, "
                    f"page_size={page})")
            self.win_pools = zeros(self.serving.window_pools(
                c, self.num_window_pages, page))
            self.free_win = list(range(1, self.num_window_pages))
        # Row pools of recurrent state: row b is slot b's, then
        # SNAPSHOT_ROWS rows the prefix cache keeps (the state at a
        # chunk's end, keyed as the page that ends there), then one
        # scratch row for the padding of a prefill batch.
        self.rows: tuple = ()
        self._scratch_row = e.max_slots + SNAPSHOT_ROWS
        self._admit_rows = e.admit_rows or ADMIT_BUCKETS * max(
            (b for b in e.prompt_buckets if b <= e.max_len), default=0)
        self.free_snaps: list[int] = []
        if self.serving.row_pools is not None:
            self.rows = zeros(self.serving.row_pools(
                c, self._scratch_row + 1))
            self.free_snaps = list(range(e.max_slots, self._scratch_row))
        self.snap_of_hash: dict = {}       # prefix-hash -> snapshot row
        self.hash_of_snap: dict[int, object] = {}
        self.snap_lru: "collections.OrderedDict[int, object]" = (
            collections.OrderedDict())     # unpinned snapshot rows (LRU)
        self.snap_pins: dict[int, int] = {}  # rows an admission is using
        self.snapshot_hits = 0
        self.snapshot_evictions = 0
        self._moe_acc = None               # device; moe_stats()
        if self.serving.stats_zero is not None:
            self._moe_acc = self.serving.stats_zero(c)
            self._moe_total = np.zeros(self._moe_acc.shape, np.int64)
        # page bookkeeping (host side)
        self.free_pages: list[int] = list(range(1, self.num_pages))
        self.page_refs: dict[int, int] = {}
        self.page_hash: dict = {}          # prefix-hash -> page id
        self.hash_of_page: dict[int, object] = {}
        self.cached_lru: "collections.OrderedDict[int, object]" = (
            collections.OrderedDict())     # ref-0 cached pages (LRU)
        self.slot_pages: list[list[int]] = [[] for _ in
                                            range(e.max_slots)]
        self.prefix_hits = 0
        self.preemptions = 0
        # rows of every prefill dispatch: its [n, S] batch's, and those of
        # them its row-wise products ran over (`_rows_run`)
        self.prefill_rows_bucketed = 0
        self.prefill_rows_run = 0
        # query blocks in the grids of their attention kernels, and those
        # of them that held a token (`_attn_blocks`): the rest were not run
        self.prefill_attn_blocks = 0
        self.prefill_attn_blocks_run = 0
        # decode compile buckets over pages-in-use: powers of two up
        # to the per-slot page bound, then the bound. A power of two that
        # the bound is within a quarter of is left out: the two would be
        # two programs (a whole compile each, and an entry each in the
        # compile cache) for nearly the same table, and a table a few
        # entries wider costs a step nothing (the decode kernels walk the
        # pages a slot holds, not the table's width).
        pb, b = [], 1
        while b < self.pages_per_slot:
            pb.append(b)
            b *= 2
        if len(pb) > 1 and 4 * self.pages_per_slot <= 5 * pb[-1]:
            pb.pop()
        pb.append(self.pages_per_slot)
        self._page_buckets = pb
        self._decode_paged: dict[int, object] = {}
        self._prefill_pre: dict[tuple, object] = {}
        self._flight: _Flight | None = None  # step()'s step in the air
        # an admission's first tokens, from `_admit` to the decode step
        # that reads them on the device and carries them from there
        self._firsts: _FirstTokens | None = None
        self._merge = _shared_jit(("merge_tokens",), lambda: merge_tokens)
        self.decode_steps = 0        # dispatched by step()
        self.decode_steps_ahead = 0  # ... before the step before was fetched
        self.admissions = 0          # `_admit` calls that dispatched a prefill
        self.admissions_unfenced = 0      # ... and fetched no first token
        self.admissions_under_flight = 0  # ... with a decode step in the air
        # Donate the pool/cache: without donation every step round-trips
        # the full KV through a fresh HBM allocation (~GBs/step).
        insert = self.serving.insert_batch
        self._insert_batch = _shared_jit(
            (insert.__name__,), lambda: insert,
            donate_argnums=tuple(range(len(self._pools()))))
        self._prefill_batches: dict[tuple, object] = {}
        if mesh is not None and "tp" in mesh.axis_names:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # kv-head axis: position 1 of [L, hkv, N, hd, page]
            kv_sharding = NamedSharding(mesh, P(None, "tp"))
            self.cache_k = jax.device_put(self.cache_k, kv_sharding)
            self.cache_v = jax.device_put(self.cache_v, kv_sharding)

        self._sample, self._sample_trunc = _samplers()
        self._key = jax.random.PRNGKey(seed + 1)

        # host-side slot state
        B = e.max_slots
        self.lengths = np.zeros(B, np.int32)
        self.active = np.zeros(B, bool)
        self.last_tokens = np.zeros(B, np.int32)
        self.slot_req: list[Request | None] = [None] * B
        self.queue: collections.deque[Request] = collections.deque()
        self.finished: dict[int, Request] = {}
        # rid -> live Request, weakly: streaming consumers (the decode
        # pool's logprob plane) read incremental per-token state without
        # any pop bookkeeping — entries vanish with their request.
        self._req_by_id = weakref.WeakValueDictionary()
        self._next_id = 0
        self._lock = threading.Lock()
        self._cancel_rids: set[int] = set()

    def _pools(self) -> tuple:
        return tuple(p for p in (self.cache_k, self.cache_v)
                     if p is not None) + self.win_pools

    def _set_pools(self, pools):
        """The page pools, then the window pools (as many as there are)."""
        pools = tuple(pools)
        n = len(pools) - len(self.win_pools)
        self.cache_k, self.cache_v = (pools[:n] + (None,))[:2]
        self.win_pools = pools[n:]

    # ---- request API ----

    def add_request(self, prompt_tokens, max_new_tokens=None,
                    temperature=None, top_p: float = 1.0,
                    top_k: int = 0, guide=None,
                    logprobs: bool = False, resume_token: int | None = None,
                    kv_handoff: tuple | None = None) -> int:
        """`resume_token`/`kv_handoff` serve the disaggregated decode pool:
        resume_token is a token already SAMPLED for this sequence (by a
        prefill worker, or by a decode replica that died mid-stream) —
        decoding resumes from it without re-sampling its position;
        kv_handoff is the exported prompt KV the pump imports into the
        prefix cache at admission (import_kv) so only the un-handed-off
        suffix re-prefills."""
        # Validate at submission, in the CALLER's thread: an invalid prompt
        # must fail its own request, not blow up the shared engine pump.
        # chunked prefill admits any prompt under max_len
        if not (self._chunk_size() and len(prompt_tokens) < self.e.max_len):
            self._bucket(len(prompt_tokens))
        if kv_handoff is not None and self._own:
            _refuse(self.c, "a per-head KV handoff")
        if guide is not None:
            if guide.table.shape[1] != self.c.vocab:
                raise ValueError(
                    f"guide compiled for vocab {guide.table.shape[1]}, "
                    f"model vocab is {self.c.vocab}")
        with self._lock:
            rid = self._next_id
            self._next_id += 1
        req = Request(
            rid, list(map(int, prompt_tokens)),
            max_new_tokens or self.e.default_max_new_tokens,
            self.e.default_temperature if temperature is None
            else temperature, top_p=float(top_p), top_k=int(top_k),
            guide=guide, logprobs=bool(logprobs), kv_handoff=kv_handoff)
        if resume_token is not None:
            # Same contract as preemption resume: the token is already part
            # of the sequence (it counts against max_new_tokens) and seeds
            # decoding without re-sampling its position.
            req.generated.append(int(resume_token))
            req.resume_token = int(resume_token)
        self.queue.append(req)
        self._req_by_id[rid] = req
        return rid

    def request(self, request_id: int) -> "Request | None":
        """The live (or finished-but-referenced) Request for `request_id`.
        Incremental readers (streaming logprobs) may read append-only
        fields like token_logprobs; the entry disappears with the
        request object itself."""
        return self._req_by_id.get(request_id)

    def cancel(self, request_id: int):
        """Abort a request from ANY thread: flagged here, applied by the
        pump thread at its next admission pass (a queued request drops;
        an active slot finishes immediately with generated-so-far, its
        pages released). An early-stopped stream must not keep burning
        decode slots to max_new_tokens."""
        self._cancel_rids.add(request_id)

    def _apply_cancels(self):
        if not self._cancel_rids:
            return
        rids: set[int] = set()
        while True:
            try:
                rids.add(self._cancel_rids.pop())
            except KeyError:
                break
        kept: collections.deque[Request] = collections.deque()
        for req in self.queue:
            if req.request_id in rids:
                self._finish(req)
            else:
                kept.append(req)
        self.queue = kept
        for i in range(self.e.max_slots):
            req = self.slot_req[i]
            if req is None or req.request_id not in rids:
                continue
            self._finish(req)
            self.active[i] = False
            self.slot_req[i] = None
            self._release_slot(i)

    def has_work(self) -> bool:
        """Whether step() has anything left to do or to hand over. True
        while a decode step is in flight (its slots stay `active` until
        step() has fetched their tokens; the last one may have ended on
        eos_token under a step that led it), so a loop over has_work()
        ends with nothing in the air."""
        return (bool(self.queue) or bool(self.active.any())
                or self._flight is not None)

    # ---- scheduling ----

    def _chunk_size(self) -> int:
        """Page-aligned chunk for chunked prefill (0 = unavailable).
        Prompts longer than every bucket prefill one chunk per engine
        step, registering each chunk's pages in the prefix cache so the
        NEXT admission resumes where this one stopped — long-prompt
        admission interleaves with decode instead of stalling it (parity:
        vLLM chunked prefill, `llm/_internal/serve/.../vllm/`)."""
        if not self.e.prefix_cache:
            return 0
        page = self.e.page_size
        usable = [b for b in self.e.prompt_buckets if b <= self.e.max_len]
        if not usable:
            return 0
        return (max(usable) // page) * page

    def _bucket(self, n: int) -> int:
        return _prompt_bucket(self.e, n)

    # ---- page pool ----

    def _alloc_page(self) -> int | None:
        """A free page, else evict the LRU ref-0 cached page, else None."""
        if self.free_pages:
            self.pages_peak = max(self.pages_peak, self.num_pages
                                  - len(self.free_pages)
                                  - len(self.cached_lru))
            return self.free_pages.pop()
        if self.cached_lru:
            pid, h = self.cached_lru.popitem(last=False)
            self.page_hash.pop(h, None)
            self.hash_of_page.pop(pid, None)
            self.page_refs.pop(pid, None)
            # a snapshot goes with, or before, the pages it stands on: a
            # key is the prefix's own bytes, so every snapshot at or past
            # this page's boundary begins with the page's key
            for sh, row in list(self.snap_of_hash.items()):
                if sh[:len(h)] == h:
                    self._drop_snap(row)
            return pid
        return None

    # ---- snapshot rows of recurrent state ----

    def _drop_snap(self, row: int):
        """Forget the snapshot in `row` (never a pinned one: its pages are
        pinned with it, so nothing evicts them) and free the row."""
        self.snap_of_hash.pop(self.hash_of_snap.pop(row), None)
        self.snap_lru.pop(row, None)
        self.free_snaps.append(row)
        self.snapshot_evictions += 1

    def _alloc_snap(self) -> int | None:
        """A free snapshot row, pinned, else the least recently used
        unpinned one, else None."""
        if not self.free_snaps and self.snap_lru:
            self._drop_snap(next(iter(self.snap_lru)))
        if not self.free_snaps:
            return None
        row = self.free_snaps.pop()
        self.snap_pins[row] = 1
        return row

    def _pin_snap(self, row: int):
        self.snap_pins[row] = self.snap_pins.get(row, 0) + 1
        self.snap_lru.pop(row, None)

    def _unpin_snap(self, row: int):
        n = self.snap_pins.pop(row) - 1
        if n > 0:
            self.snap_pins[row] = n
        elif row in self.hash_of_snap:
            self.snap_lru[row] = self.hash_of_snap[row]   # most recent
        else:
            self.free_snaps.append(row)    # never registered: rolled back

    # ---- window pool ----

    def _window_first(self, at: int) -> int:
        """The first logical page a window layer's query at position `at`
        can see."""
        return max(at - self.c.window + 1, 0) // self.e.page_size

    def _alloc_window_page(self) -> int | None:
        """A free window page; else the pages a queued request holds for
        its continuation (the last such request's: it prefills its prompt
        again); else None."""
        if not self.free_win:
            for req in reversed(self.queue):
                if req.win_hold is not None:
                    self._drop_hold(req)
                    break
            else:
                return None
        pid = self.free_win.pop()
        self.window_pages_peak = max(
            self.window_pages_peak,
            self.num_window_pages - 1 - len(self.free_win))
        return pid

    def _drop_hold(self, req: Request):
        self.free_win.extend(req.win_hold[1])
        req.win_hold = None

    def _slide_window(self, slot: int, at: int) -> bool:
        """Slot `slot` writes position `at` next: release the window pages
        its query can no longer see, then hold the page `at` lies in.
        False if the window pool is dry."""
        held, first = self.slot_win[slot], self._window_first(at)
        while held and self.slot_win_first[slot] < first:
            self.free_win.append(held.pop(0))
            self.slot_win_first[slot] += 1
            self.window_pages_released += 1
        while self.slot_win_first[slot] + len(held) <= at // self.e.page_size:
            pid = self._alloc_window_page()
            if pid is None:
                return False
            held.append(pid)
        self.window_seq_pages_peak = max(self.window_seq_pages_peak,
                                         len(held))
        return True

    def _window_short(self, lengths, active) -> int:
        """How many slots of `active` hold no window page yet for the token
        at `lengths` (what `_slide_window` releases is not counted)."""
        page = self.e.page_size
        return sum(int(lengths[i]) // page
                   >= self.slot_win_first[i] + len(self.slot_win[i])
                   for i in np.flatnonzero(active))

    def _plan_window(self, req: Request, hit: int, n: int):
        """The window pages of a prefill that ends with `n` tokens cached,
        the first `hit` pages of them a cached prefix: -> (first logical
        page kept, pages taken over from the request's hold, new pages), or
        None if the window pool is dry (nothing is kept then)."""
        first, last = self._window_first(n), (n - 1) // self.e.page_size
        taken: list[int] = []
        if hit:     # the hold's pages are logical [hit - len, hit)
            held = req.win_hold[1]
            taken = held[max(first - (hit - len(held)), 0):]
        new: list[int] = []
        for _ in range(max(first, hit), last + 1):
            pid = self._alloc_window_page()
            if pid is None:
                self.free_win.extend(new)
                return None
            new.append(pid)
        return first, taken, new

    def _incref_page(self, pid: int):
        self.page_refs[pid] = self.page_refs.get(pid, 0) + 1
        self.cached_lru.pop(pid, None)  # in use: not evictable

    def _decref_page(self, pid: int):
        n = self.page_refs.get(pid, 0) - 1
        if n > 0:
            self.page_refs[pid] = n
            return
        self.page_refs.pop(pid, None)
        h = self.hash_of_page.get(pid)
        if h is not None:
            # Keep the content cached for future prefix hits; evictable.
            self.cached_lru[pid] = h
        else:
            self.free_pages.append(pid)

    def _release_slot(self, slot: int):
        for pid in self.slot_pages[slot]:
            self._decref_page(pid)
        self.slot_pages[slot] = []
        self.free_win.extend(self.slot_win[slot])
        self.slot_win[slot] = []

    @staticmethod
    def _prefix_hash(tokens: list) -> bytes:
        """Exact key (the token bytes themselves): a non-cryptographic
        hash collision would silently serve another prompt's KV."""
        return np.asarray(tokens, np.int32).tobytes()

    def _find_prefix(self, prompt: list, req: Request | None = None
                     ) -> list[int]:
        """Longest run of already-cached full prompt pages (at least one
        token is always left to prefill — its logits seed sampling). With
        recurrent state: the longest such run that ends at a boundary
        whose state is held (`snap_of_hash`, same key) — never pages
        without their state. With window pools: the run that ends where
        request `req` holds the window layers' last pages (`win_hold`: a
        chunk's continuation), or none: a window layer's pages before any
        other boundary were released, so that prefix is prefilled again."""
        if not self.e.prefix_cache:
            return []
        page = self.e.page_size
        full = len(prompt) // page
        if full * page == len(prompt):
            full -= 1
        pages = []
        for i in range(full):
            pid = self.page_hash.get(
                self._prefix_hash(prompt[:(i + 1) * page]))
            if pid is None:
                break
            pages.append(pid)
        if self.rows:
            while pages and self._prefix_hash(
                    prompt[:len(pages) * page]) not in self.snap_of_hash:
                pages.pop()
        if self.win_span:
            hold = req.win_hold if req is not None else None
            if hold is not None and len(pages) >= hold[0]:
                return pages[:hold[0]]
            if hold is not None:    # the pages it stood on were evicted
                self._drop_hold(req)
            return []
        return pages

    def import_kv(self, prompt_tokens, ks, vs) -> int:
        """Splice a handed-off prompt KV (PrefillEngine.prefill_export
        output: [L, S, hkv, hd] host arrays, K post-RoPE at absolute
        positions) into the paged pool as prefix-cache pages. The pages
        land ref-0 in the eviction LRU — exactly like pages released by a
        finished request — so the next admission of this prompt (or any
        prompt sharing the prefix) pins them via the normal prefix-hit
        path and only prefills the tail. Returns pages imported.

        NOT thread-safe against step(): call from the pump thread (the
        engine queue's kv_handoff field routes a handoff there)."""
        if not self.e.prefix_cache:
            return 0
        if self._own:
            _refuse(self.c, "a per-head KV handoff")
        page = self.e.page_size
        prompt = list(map(int, prompt_tokens))
        full = len(prompt) // page
        if full * page == len(prompt):
            full -= 1  # >=1 token always re-prefills (its logits seed)
        full = min(full, int(ks.shape[1]) // page)
        if full <= 0:
            return 0
        hit = len(self._find_prefix(prompt))
        if hit >= full:
            return 0  # everything the handoff covers is already cached
        new_pages: list[int] = []
        for _ in range(full - hit):
            pid = self._alloc_page()
            if pid is None:
                break  # pool full of pinned pages: partial import is fine
            new_pages.append(pid)
        if not new_pages:
            return 0
        n_tab = len(new_pages)
        seg = slice(hit * page, (hit + n_tab) * page)
        self.cache_k, self.cache_v = self._insert_batch(
            self.cache_k, self.cache_v,
            jnp.asarray(ks[:, seg])[:, None], jnp.asarray(vs[:, seg])[:, None],
            jnp.asarray(np.asarray(new_pages, np.int32)[None]),
            jnp.asarray([n_tab * page], jnp.int32))
        for i, pid in enumerate(new_pages):
            self.page_refs[pid] = 1
            h = self._prefix_hash(prompt[:(hit + i + 1) * page])
            if h not in self.page_hash:
                self.page_hash[h] = pid
                self.hash_of_page[pid] = h
            # ref 0 -> cached_lru (evictable) via the standard release path
            self._decref_page(pid)
        return len(new_pages)

    def _preempt_victim(self, needer: int) -> bool:
        """Pool exhausted mid-decode: requeue the youngest re-prefillable
        active slot (vLLM recompute-preemption semantics); its generated
        tokens become prompt tail on re-admission. Returns True if a page
        was freed."""
        candidates = []
        for i in range(self.e.max_slots):
            req = self.slot_req[i]
            if not self.active[i] or req is None:
                continue
            total = len(req.prompt) + len(req.generated)
            usable = [b for b in self.e.prompt_buckets
                      if b <= self.e.max_len]
            if total <= min(max(usable, default=0), self.e.max_len - 1):
                candidates.append((len(req.generated), i))
        if not candidates:
            return False
        _, victim = min(candidates)
        req = self.slot_req[victim]
        self._release_slot(victim)
        self.active[victim] = False
        self.slot_req[victim] = None
        # Re-prefill everything the model has SEEN (prompt + all fed-back
        # tokens); the final sampled-but-never-fed token resumes decoding
        # exactly where it stopped, without re-sampling its position.
        # (from the prompt as it came: a request preempted before holds
        # its earlier generated tokens in `prompt` already)
        req.prompt = req.prompt[:req.n_prompt] + req.generated[:-1]
        req.resume_token = req.generated[-1]
        self.queue.appendleft(req)
        self.preemptions += 1
        return True

    def _admit(self) -> dict[int, int]:
        self._apply_cancels()
        if not self.queue:    # a decode turn's call: nothing to do, no span
            return {}
        with diagnostics.span("ray_tpu.engine.admit") as sp:
            return self._admit_queued(sp)

    def _admit_queued(self, sp) -> dict[int, int]:
        """`_admit` with a request in the queue, under its span `sp`."""
        self._fetch_firsts()    # none, unless no decode step followed the
        #                         last admission (it raised; a test's call)
        t_ns = time.perf_counter_ns()
        admitted: dict[int, int] = {}
        pending: list[tuple] = []  # (slot, req, last-logits row) to sample
        e = self.e
        page = e.page_size
        # The slots free on the host's view, a decode step in flight or
        # not: one that step merely `ends` is its own until it lands; one
        # it led once too often (an end on eos_token) it only wrote, and
        # every program dispatched from here on runs behind it.
        under_flight = self._flight is not None
        free = [i for i in range(e.max_slots) if not self.active[i]]
        # Phase 1 — host-side planning: pop requests, match prefixes,
        # allocate pages. No device work yet, so a whole admission burst
        # can share one batched prefill dispatch below (one dispatch and
        # one host sync instead of one per prompt).
        with diagnostics.span("ray_tpu.engine.admit.plan"):
            planned: list[dict] = []
            rows = 0    # prompt-bucket rows planned so far, against the budget
            while free and self.queue:
                req = self.queue.popleft()
                slot = free[0]
                n = len(req.prompt)
                if req.kv_handoff is not None:
                    # Disaggregated handoff: splice the prefill worker's KV
                    # into the prefix cache NOW (pump thread — page
                    # bookkeeping is single-threaded here), so _find_prefix
                    # below hits it and only the tail re-prefills.
                    ks_h, vs_h = req.kv_handoff
                    req.kv_handoff = None
                    self.import_kv(req.prompt, ks_h, vs_h)
                pre_pages = self._find_prefix(req.prompt, req)
                hit = len(pre_pages)
                suffix = req.prompt[hit * page:]
                ns = len(suffix)
                chunk = self._chunk_size()
                is_partial = bool(chunk) and ns > max(
                    b for b in self.e.prompt_buckets if b <= self.e.max_len)
                if is_partial:
                    # Chunked prefill: admit only the next page-aligned chunk;
                    # phase 3 registers its pages and requeues the request, so
                    # the next step continues from the longer prefix. Decode
                    # steps of the slots already running pass in between.
                    suffix = suffix[:chunk]
                    ns = chunk
                    n = hit * page + chunk
                bucket = self._bucket(ns)
                if planned and rows + bucket > self._admit_rows:
                    # The step's row budget is spent: the rest of the queue
                    # waits for the next step (decode runs in between).
                    self.queue.appendleft(req)
                    break
                rows += bucket
                # Pin the matched prefix pages FIRST: they may sit ref-0 in
                # the eviction LRU, and the suffix allocation below must not
                # be able to evict and reuse them.
                for pid in pre_pages:
                    self._incref_page(pid)
                # Recurrent state: resume from the snapshot at the prefix's
                # end (pinned like its pages), end in the slot's own row, or,
                # for a partial chunk, in a snapshot row keyed as the page
                # that ends there.
                src_row = dst_row = None
                if self.rows:
                    if hit:
                        src_row = self.snap_of_hash[
                            self._prefix_hash(req.prompt[:hit * page])]
                        self._pin_snap(src_row)
                    dst_row = self._alloc_snap() if is_partial else slot
                no_row = bool(self.rows) and dst_row is None
                # Pages covering [hit*page, n): allocated up front; growth
                # pages come later, one decode page at a time.
                need = -(-n // page) - hit
                new_pages = []
                while len(new_pages) < need and not no_row:
                    pid = self._alloc_page()
                    if pid is None:
                        break
                    new_pages.append(pid)
                # Window pools: the pages the sequence's next query can
                # see, those before the suffix taken over from the hold.
                window = None
                if self.win_span and len(new_pages) == need:
                    window = self._plan_window(req, hit, n)
                if len(new_pages) < need or (self.win_span
                                             and window is None):
                    # Pool exhausted (pages, window pages, or every snapshot
                    # row pinned): put everything back and stop admitting.
                    self.free_pages.extend(new_pages)
                    for pid in pre_pages:
                        self._decref_page(pid)
                    for row in (src_row, dst_row if is_partial else None):
                        if row is not None:
                            self._unpin_snap(row)
                    self.queue.appendleft(req)
                    break
                for pid in new_pages:
                    self.page_refs[pid] = 1
                if hit:
                    self.prefix_hits += 1
                    self.snapshot_hits += src_row is not None
                if is_partial:
                    # A partial chunk never occupies the slot — and must not
                    # reuse its id either: a later full admission in this same
                    # burst takes free[0], and a shared id would collide in
                    # logits_of below.
                    slot = None
                else:
                    free.pop(0)
                planned.append(dict(slot=slot, req=req, n=n, ns=ns,
                                    bucket=bucket, hit=hit, partial=is_partial,
                                    suffix=suffix, pre_pages=pre_pages,
                                    new_pages=new_pages, src_row=src_row,
                                    dst_row=dst_row, window=window))

        # Phase 2 — device work, grouped: prefix-hit prompts batch by
        # (suffix bucket, prefix-page bucket), the rest by suffix bucket —
        # each group pays ONE prefill dispatch + ONE page-insert dispatch.
        logits_of: dict[int, object] = {}  # slot -> last-logits row
        groups: dict[tuple, list[dict]] = {}
        for p in planned:
            pre_bucket = 0  # no prefix hit: the plain prefill program
            if p["hit"]:
                pre_bucket = 1
                while pre_bucket < p["hit"]:
                    pre_bucket *= 2
            groups.setdefault((p["bucket"], pre_bucket), []).append(p)
        for (bucket, pre_bucket), group in groups.items():
            # Pad the batch to a power of two: bounded compile variants.
            n_pad = 1
            while n_pad < len(group):
                n_pad *= 2
            toks = np.zeros((n_pad, bucket), np.int32)
            pres = np.zeros((n_pad, pre_bucket), np.int32)
            plens = np.zeros((n_pad,), np.int32)
            lens = np.zeros((n_pad,), np.int32)
            tabs = np.zeros((n_pad, -(-bucket // page)), np.int32)
            # window pools: the kept pages of the chunk, in the order the
            # program cuts them out (models/windowed._kept_pages), and the
            # hold's pages, right-aligned, as the suffix's window prefix
            win_tabs = np.zeros((n_pad, min(tabs.shape[1], self.win_span)),
                                np.int32)
            win_pres = np.zeros((n_pad, max(self.win_span - 1, 0)), np.int32)
            # rows of recurrent state to start from and to end in; the
            # batch's padding reads and writes the scratch row
            srcs = np.full((n_pad,), self._scratch_row, np.int32)
            dsts = np.full((n_pad,), self._scratch_row, np.int32)
            for j, p in enumerate(group):
                toks[j, :p["ns"]] = p["suffix"]
                pres[j, :p["hit"]] = p["pre_pages"]
                plens[j] = p["hit"] * page
                lens[j] = p["ns"]
                tabs[j, :len(p["new_pages"])] = p["new_pages"]
                if self.win_span:
                    new = p["window"][2]
                    win_tabs[j, :len(new)] = new
                    if p["hit"]:
                        held = p["req"].win_hold[1]
                        win_pres[j, win_pres.shape[1] - len(held):] = held
                if self.rows:
                    dsts[j] = p["dst_row"]
                    if p["hit"]:
                        srcs[j] = p["src_row"]
            if self.win_span:
                tabs, pres = (tabs, win_tabs), (pres, win_pres)
            with diagnostics.span("ray_tpu.engine.admit.prefill") as gsp:
                if gsp.on:
                    # what the dispatch works on: the new tokens and the
                    # cached-prefix tokens of each real request (the
                    # batch's padding is none) in a bucket of S rows
                    gsp.set(bucket=bucket,
                            tokens=tuple(p["ns"] for p in group),
                            prefix=tuple(p["hit"] * page for p in group))
                self._prefill_group(group, logits_of, toks, lens, tabs,
                                    pres, plens, srcs, dsts)

        # Phase 3 — host-side registration.
        with diagnostics.span("ray_tpu.engine.admit.register"):
            for p in planned:
                slot, req = p["slot"], p["req"]
                n, hit, new_pages = p["n"], p["hit"], p["new_pages"]
                # Register the full suffix pages for future prefix hits.
                if e.prefix_cache:
                    for i in range(hit, n // page):
                        pid = new_pages[i - hit]
                        h = self._prefix_hash(req.prompt[:(i + 1) * page])
                        if h not in self.page_hash:
                            self.page_hash[h] = pid
                            self.hash_of_page[pid] = h
                if p["src_row"] is not None:
                    self._unpin_snap(p["src_row"])
                if self.win_span:
                    # every program that reads the hold's pages is
                    # dispatched: those the window has left go back
                    first, taken, new = p["window"]
                    if req.win_hold is not None:
                        left = len(req.win_hold[1]) - len(taken)
                        self.free_win.extend(req.win_hold[1][:left])
                        self.window_pages_released += left
                    req.win_hold = (n // page, taken + new) if p[
                        "partial"] else None
                if p["partial"] and self.rows:
                    # the state at this chunk's end, under its last page's key
                    h = self._prefix_hash(req.prompt[:n])
                    if h in self.snap_of_hash:     # an older copy of the same
                        self._drop_snap(self.snap_of_hash[h])
                    self.snap_of_hash[h] = p["dst_row"]
                    self.hash_of_snap[p["dst_row"]] = h
                    self._unpin_snap(p["dst_row"])
                if p["partial"]:
                    # Chunk prefilled and registered; hand the pages to the
                    # prefix cache (ref 0 -> protected in the LRU until the
                    # continuation re-pins them) and put the request back at
                    # the head of the queue for its next chunk.
                    for pid in p["pre_pages"] + new_pages:
                        self._decref_page(pid)
                    self.queue.appendleft(req)
                    continue
                self.slot_pages[slot] = p["pre_pages"] + new_pages
                if self.win_span:
                    self.slot_win[slot] = taken + new
                    self.slot_win_first[slot] = first
                    self.window_seq_pages_peak = max(
                        self.window_seq_pages_peak, len(taken + new))
                self.slot_req[slot] = req
                req.slot = slot
                req.t_slot_ns = req.t_slot_ns or t_ns
                self.lengths[slot] = n
                self.active[slot] = True
                if req.resume_token is not None:
                    first = req.resume_token  # already in req.generated
                    req.resume_token = None
                    self.last_tokens[slot] = first
                    self._maybe_finish(slot, first)
                else:
                    # Defer the first-token sampling: one batched readback for
                    # the whole admission burst instead of a fence per prompt.
                    pending.append((slot, req, logits_of[slot]))
        fenced = False
        if pending:
            # One sampler dispatch for the burst, and no fetch: the tokens
            # stay on the device for the decode step that follows. One
            # fence for the burst where the host must know a token now.
            owners = [(slot, req) for slot, req, _l in pending]
            fenced = any(self._first_token_now(slot, req)
                         for slot, req in owners)
            with diagnostics.span("ray_tpu.engine.admit.sample"):
                self._firsts = _FirstTokens(*self._sample_dispatch(
                    jnp.stack([row for _s, _r, row in pending]),
                    [req for _s, req in owners]), owners, admitted)
                admitted.update((req.request_id, None) for _s, req in owners)
                if fenced:
                    self._fetch_firsts()
        if planned:
            self.admissions += 1
            self.admissions_unfenced += not fenced
            self.admissions_under_flight += under_flight
        if sp.on:
            sp.set(rows=sum(p["bucket"] for p in planned),
                   fenced=int(fenced))
        return admitted

    def _first_token_now(self, slot: int, req: Request) -> bool:
        """Whether the host must see the first token of `req`, just
        admitted to `slot`, before anything else is dispatched: no decode
        step follows it (the request ends there by max_new_tokens or
        max_len, and the call that admits it returns the token, ROADMAP
        D11), or the mask of its next token follows from it (a guide)."""
        return (req.guide is not None or req.max_new_tokens <= 1
                or self.lengths[slot] + 1 >= self.e.max_len)

    def _fetch_firsts(self):
        """Fetch the first tokens `_admit` left on the device (a fence) and
        move the host's view past them, as `_land` would with the decode
        step that read them."""
        ft, self._firsts = self._firsts, None
        if ft is not None:
            ft.admitted.update(self._apply_firsts(ft))

    def _apply_firsts(self, ft: _FirstTokens) -> dict[int, int]:
        """The host's view moves past the first tokens `ft`, fetched here:
        {request_id: token}. A request that left its slot since (a cancel)
        gets none."""
        toks = np.asarray(ft.tokens)
        logps = None if ft.logps is None else np.asarray(ft.logps)
        firsts: dict[int, int] = {}
        for j, (slot, req) in enumerate(ft.owners):
            if self.slot_req[slot] is req:
                firsts[req.request_id] = self._take_token(
                    slot, req, toks[j], None if logps is None else logps[j])
        return firsts

    def _take_token(self, slot: int, req: Request, tok, logp) -> int:
        """Token `tok`, fetched, becomes the newest of `req` in `slot`
        (`lengths[slot]` counts what the cache holds before it)."""
        tok = int(tok)
        if req.logprobs:
            req.token_logprobs.append(float(logp))
        req.generated.append(tok)
        self.last_tokens[slot] = tok
        self._advance_guide(req, tok)
        self._maybe_finish(slot, tok)
        return tok

    def _prefill_group(self, group: list, logits_of: dict, toks, lens, tabs,
                       pres, plens, srcs, dsts):
        """ONE prefill dispatch and ONE page-insert dispatch for a group of
        planned admissions (over cached prefix pages `pres` [n, Pp] of
        `plens` tokens; Pp is 0 where the group hit no cached prefix; from
        rows `srcs` of recurrent state into rows `dsts`, where the model
        has any); the last-token logits row of every request that takes a
        slot goes into `logits_of` (the programs run the head at that
        position only). `_Serving` has the calling convention."""
        pre_bucket = (pres[0] if self.win_span else pres).shape[1]
        hit = pre_bucket > 0
        name = "prefill_with_prefix_batch" if hit else "prefill_batch"
        cache = self._prefill_pre if hit else self._prefill_batches
        key = toks.shape + ((pre_bucket,) if hit else ())
        pools = self._pools()
        n_before = 3 + (len(pools) + 2 if hit else 0)
        fn = cache.get(key)
        if fn is None:
            program = getattr(self.serving, name)
            donate = tuple(range(n_before, n_before + len(self.rows)))
            # with window pools the program cuts whole pages out of a chunk
            statics = {"page": self.e.page_size} if self.win_span else {}
            fn = cache[key] = _shared_jit(
                (name, self.c, *statics.values()),
                lambda: partial(program, config=self.c, **statics),
                donate_argnums=donate)
        # only the per-head programs walk their tiles; every other kind's
        # run their bucket (ROADMAP S13)
        self.prefill_rows_bucketed += toks.size
        self.prefill_rows_run += (
            _rows_run(lens, toks.shape[1]) if self.c.kv_cache == "per_head"
            else toks.size)
        blocks, run = _attn_blocks(self.c, lens, toks.shape[1])
        self.prefill_attn_blocks += blocks
        self.prefill_attn_blocks_run += run
        to_device = partial(jax.tree_util.tree_map, jnp.asarray)
        toks, lens, tabs = to_device((toks, lens, tabs))
        args = (self.params, toks, lens)
        if hit:
            args += (*pools, to_device(pres), jnp.asarray(plens))
        if self.rows:
            args += (*self.rows, jnp.asarray(srcs), jnp.asarray(dsts))
        last, *out = self._counted(fn(*args, *self._stats()))
        if self.rows:
            out, self.rows = out[:-len(self.rows)], tuple(
                out[-len(self.rows):])
        pools = self._insert_batch(*pools, *out, tabs, lens)
        self._set_pools(pools if isinstance(pools, tuple) else (pools,))
        for j, p in enumerate(group):
            if p["slot"] is not None:
                logits_of[p["slot"]] = last[j]

    def kv_stats(self) -> dict:
        """Pool/HBM accounting for tests, the dashboard, and the bench."""
        pools = [p for p in (self.cache_k, self.cache_v) if p is not None]
        page_bytes = sum(p.nbytes // self.num_pages for p in pools)
        return {
            "layout": "paged", "num_pages": self.num_pages,
            # layers of K and V (or of latents) a token keeps in the page
            # pools, and the bytes one page takes of them: pages x
            # page_bytes = bytes, for any model
            "cache_layers": pools[0].shape[0],
            "page_bytes": page_bytes,
            # the same by kind of pool, K + V over the kind's layers (the
            # growing pools'; the window pools', 0 without them): K and V,
            # and the kinds, may differ in heads and width
            "page_bytes_full": page_bytes,
            "page_bytes_window": sum(p.nbytes // self.num_window_pages
                                     for p in self.win_pools),
            "free_pages": len(self.free_pages),
            "cached_pages": len(self.cached_lru),
            "pages_in_use": self.num_pages - 1 - len(self.free_pages)
            - len(self.cached_lru),
            "pages_peak": self.pages_peak,
            # the window pool (zeros for a model without window layers):
            # pages slots hold, pages queued requests hold between two
            # chunks, the most in use at once, the most in one slot's
            # table, and pages given back because the window had left them
            "num_window_pages": self.num_window_pages,
            "window_pages_in_use": sum(map(len, self.slot_win)),
            "window_pages_held": sum(len(r.win_hold[1]) for r in self.queue
                                     if r.win_hold is not None),
            "window_pages_peak": self.window_pages_peak,
            "window_seq_pages_peak": self.window_seq_pages_peak,
            "window_pages_released": self.window_pages_released,
            "prefix_hits": self.prefix_hits,
            "preemptions": self.preemptions,
            # rows the prefill dispatches were padded to (n x bucket), and
            # rows their row-wise products ran over (the tiles that hold a
            # token, where a program walks them)
            "prefill_rows_bucketed": self.prefill_rows_bucketed,
            "prefill_rows_run": self.prefill_rows_run,
            # query blocks in the grids of the prefill dispatches' attention
            # kernels (a layer's call each), and those that held a token:
            # the kernel runs no block past a request's last row
            "prefill_attn_blocks": self.prefill_attn_blocks,
            "prefill_attn_blocks_run": self.prefill_attn_blocks_run,
            # step()'s decode steps, and those of them dispatched before
            # the step before was fetched (the rest waited for the host)
            "decode_steps": self.decode_steps,
            "decode_steps_ahead": self.decode_steps_ahead,
            # `_admit` calls that dispatched a prefill; those of them that
            # waited for no first token (it stayed on the device), and
            # those dispatched behind a decode step still in the air
            "admissions": self.admissions,
            "admissions_unfenced": self.admissions_unfenced,
            "admissions_under_flight": self.admissions_under_flight,
            # recurrent state (zeros for a model that keeps none): a row a
            # running sequence; snapshots held, of them pinned by an
            # admission under way; prefix hits that resumed from one
            "state_rows_in_use": int(self.active.sum()) if self.rows else 0,
            "snapshot_rows": len(self.hash_of_snap),
            "snapshot_rows_in_use": len(self.snap_pins),
            "snapshot_hits": self.snapshot_hits,
            "snapshot_evictions": self.snapshot_evictions,
            # the bytes one row takes of the row pools, over all recurrent
            # layers: rows x row_bytes = bytes, as page_bytes for pages
            "row_bytes": sum(p.nbytes // (self._scratch_row + 1)
                             for p in self.rows),
        }

    def state_rows(self, rows: list) -> tuple:
        """Rows of the two row pools, on the host: (state [n, LM, H, P,
        N], window [n, LM, W - 1, channels]). A slot's row is its number
        (`Request.slot`; it keeps the state after the last token fed until
        the slot is taken again), a snapshot's is `snapshot_row`'s. For
        tests and perfbench/tools/checkstate.py, which compare the state
        itself with the plain recurrence."""
        ssm, conv = self.rows      # a slice a row: never a gather here
        return (np.stack([np.asarray(ssm[:, int(r)]) for r in rows]),
                np.stack([np.asarray(conv[:, :, int(r)].astype(jnp.float32))
                          for r in rows]))

    def snapshot_row(self, prefix: list) -> int | None:
        """The row that holds the state at the end of `prefix` (a whole
        number of pages), if the prefix cache keeps it."""
        return self.snap_of_hash.get(self._prefix_hash(prefix))

    def _stats(self) -> tuple:
        """The last argument of a program that counts (`_Serving`)."""
        return () if self._moe_acc is None else (self._moe_acc,)

    def _counted(self, out: tuple) -> tuple:
        """A program's results without the counters `_stats` handed it,
        which it gives back last and counted up."""
        if self._moe_acc is None:
            return out
        *out, self._moe_acc = out
        return tuple(out)

    def moe_stats(self) -> dict:
        """What the expert layers (models/experts.py: of a model that
        holds a share of its experts, or of a per-head model on one device)
        routed since the engine began: counted on the device inside the
        programs and fetched only here (never a host fence in step())."""
        if self._moe_acc is None:
            return {}
        fresh, self._moe_acc = self._moe_acc, jnp.zeros_like(self._moe_acc)
        self._moe_total += np.asarray(fresh)
        tokens, pairs, none_held, calls, few, n_read = map(
            int, self._moe_total[:N_STATS])
        load = self._moe_total[N_STATS:]
        return {
            "expert_layer_calls": calls, "routed_tokens": tokens,
            "held_pairs": pairs, "tokens_without_held_expert": none_held,
            "held_expert_load": load.tolist(),
            # of the few-token calls (a decode step's): the experts whose
            # weights they read, over all they hold
            "few_token_calls": few,
            "experts_read_share": (n_read / (few * len(load))
                                   if few else 0.0),
            "load_max_over_mean": (float(load.max() / load.mean())
                                   if pairs else 0.0),
        }

    def _finish(self, req: Request):
        """`req` is over: handed to `finished`, and (while spans are
        recorded) its life written as one `ray_tpu.request` span, from its
        arrival to here."""
        req.done = True
        self.finished[req.request_id] = req
        if req.win_hold is not None:    # cancelled between two chunks
            self._drop_hold(req)
        if diagnostics.recording():
            diagnostics.record(
                "ray_tpu.request", req.t_arrive_ns, time.perf_counter_ns(),
                queue_ms=((req.t_slot_ns - req.t_arrive_ns) / 1e6
                          if req.t_slot_ns else None))

    def _maybe_finish(self, slot: int, token: int):
        req = self.slot_req[slot]
        total = self.lengths[slot] + 1  # +1: the just-sampled token
        if (token == self.e.eos_token
                or len(req.generated) >= req.max_new_tokens
                or total >= self.e.max_len):
            self._finish(req)
            self.active[slot] = False
            self.slot_req[slot] = None
            self._release_slot(slot)

    def step(self) -> dict[int, int]:
        """Admit queued prompts, dispatch one decode step, fetch the one
        before; returns {request_id: token}, one token a streaming request.

        The decode loop runs one step ahead of the host: the step this
        call dispatches stays IN FLIGHT when it returns, and a token it
        returns was drawn by the step the call before dispatched (a
        caller that counts tokens a call sees them one call later; one
        that loops over has_work() misses none). Step N + 1 is dispatched
        from the tokens N left on the device before N's are fetched, so
        the fence on N, the loop over the slots, the caller's fan-out and
        the next call's page tables all pass while the device works. What
        N + 1 needs of N without its tokens the host knows: every length
        is one more, and a slot that N ends by max_new_tokens or max_len
        sits out. An end on eos_token shows only at the fetch: N + 1 then
        ran that row once too often, and `_land` throws its token away.

        An admission joins the device's stream the same way: with a slot
        free on the host's view its prefill is planned and dispatched
        BEHIND step N, still in the air; its first tokens stay on the
        device, where step N + 1 reads them (`merge_tokens`), and come
        back with N + 1's own, one fence for both (`_first_token_now` says
        which requests keep a fence of their own). So a call waits for the
        device once, admission or none.

        The step before is fetched FIRST, and everything after it
        dispatched from the host's view (in step, the device waiting as
        long), where `_may_lead` says so before the admission; or between
        the admission and the decode step, where it says so after (the
        admission took pages, or brought a guide). A prompt's first token
        is returned by the call that admits it only if no decode step
        follows it there: the stream has never carried it otherwise
        (ROADMAP D11); one that ends its request on eos_token comes back
        with the step that read it."""
        emitted = {} if self._may_lead() else self._land()
        queued = bool(self.queue)
        admitted = self._admit()
        if queued and not self._may_lead():
            emitted.update(self._land())
        flight = self._decode_paged_step()
        emitted.update(self._land())
        self._flight = flight
        led = set() if flight is None else {
            r.request_id for r in flight.reqs if r is not None}
        emitted.update({rid: tok for rid, tok in admitted.items()
                        if tok is not None and rid not in led})
        return emitted

    def _may_lead(self) -> bool:
        """Whether what this call dispatches next (an admission, then the
        decode step) may go out before the step in flight is fetched (True
        too with nothing in flight). Not when the host needs that step's
        tokens, or its slots, first: a cancel takes a slot; a request is
        queued and the only slots it could take are those the step ends;
        a guide's mask for the next token follows from this one; or a
        pool cannot grow every slot its next page (the page pool, then the
        window pool), and a victim of preemption is requeued with all its
        tokens. step() asks twice, before `_admit` and after one that may
        have taken pages."""
        f = self._flight
        if f is None:
            return True
        if self._cancel_rids or (
                self.queue and self.active.all() and f.ends.any()):
            return False
        if any(r is not None and r.guide is not None for r in self.slot_req):
            return False
        lengths, active, _own = self._past(f)
        short = self._pages_short(lengths, active)
        return (len(short) <= len(self.free_pages) + len(self.cached_lru)
                and (not self.win_span or self._window_short(lengths, active)
                     <= len(self.free_win)))

    def _past(self, f: _Flight) -> tuple:
        """(lengths, active) as the host will have them once `f`, the step
        in flight, has landed, as far as it knows without its tokens, and
        the slots `f` moves: a slot it ran for a request that is gone (it
        led an end on eos_token) may have a new owner by now, whom it
        moves nothing."""
        own = f.active & np.array([r is q for r, q in
                                   zip(f.reqs, self.slot_req)])
        return self.lengths + own, self.active & ~(f.ends & own), own

    def _land(self) -> dict[int, int]:
        """Fetch the tokens of the step in flight (the one host fence a
        token), with the first tokens of the admission before it, and move
        the host's view past both; {} with nothing in flight."""
        f, self._flight = self._flight, None
        if f is None:
            return {}
        with diagnostics.span("ray_tpu.engine.land"):
            with diagnostics.span("ray_tpu.engine.land.fence"):
                tokens = np.asarray(f.tokens)
                logps = None if f.logps is None else np.asarray(f.logps)
            # a first token that is eos_token ends its request here: the
            # step ran that row once too often, as below
            emitted = {} if f.firsts is None else self._apply_firsts(f.firsts)
            for i in np.flatnonzero(f.active):
                req = f.reqs[i]
                if self.slot_req[i] is not req:
                    # The slot ended on eos_token (or was cancelled) at the
                    # step before, which this one led: its row ran once too
                    # often, and the token is no part of the request. What the
                    # row wrote, a K/V column in a page that was the slot's
                    # own (never a shared prompt page: those lie below the
                    # first generated position) and the slot's own row of
                    # state, nobody else sees: pages and rows are handed out
                    # on the host only after this fetch's step was dispatched
                    # (since PR 51 maybe before its fetch: the slot's request
                    # is then its new owner, whom this step moves nothing),
                    # so every program that writes them for their next owner
                    # runs after it on the device, and a reader is masked to
                    # the positions its owner wrote.
                    continue
                self.lengths[i] += 1
                emitted[req.request_id] = self._take_token(
                    i, req, tokens[i], None if logps is None else logps[i])
        return emitted

    def _grow_pages(self) -> bool:
        """Ensure every active slot has the page of its next token,
        preempting when the pool is dry. Returns False if nothing is left
        active."""
        e = self.e
        for i in range(e.max_slots):
            if not self.active[i]:
                continue
            pi = min(int(self.lengths[i]), e.max_len - 1) // e.page_size
            while pi >= len(self.slot_pages[i]):
                pid = self._alloc_page()
                if pid is None:
                    if not self._make_room(i):
                        break
                    continue
                self.page_refs[pid] = 1
                self.slot_pages[i].append(pid)
            while (self.win_span and self.active[i]
                   and not self._slide_window(i, int(self.lengths[i]))):
                if not self._make_room(i):
                    break
        return bool(self.active.any())

    def _make_room(self, i: int) -> bool:
        """A pool is dry under slot i: preempt a victim, and say whether
        slot i is still there to try again."""
        if self._firsts is not None:
            # a victim is requeued with ALL its tokens, and a first token
            # may end its request and free the page: fetch, then try again
            self._fetch_firsts()
            return bool(self.active[i])
        if not self._preempt_victim(i):
            # Nothing preemptable: finish this request early rather than
            # deadlock the pump (pool too small for even one sequence — a
            # config error).
            self._finish(self.slot_req[i])
            self.active[i] = False
            self.slot_req[i] = None
            self._release_slot(i)
            return False
        return bool(self.active[i])     # False: self-preempted

    def _pages_short(self, lengths, active) -> list[int]:
        """The slots of `active` whose token at `lengths` starts a page
        they do not hold yet."""
        page, last = self.e.page_size, self.e.max_len - 1
        return [i for i in np.flatnonzero(active)
                if min(int(lengths[i]), last) // page
                >= len(self.slot_pages[i])]

    def _decode_paged_step(self) -> _Flight | None:
        """Dispatch one decode step and its sampler and fetch nothing: ->
        the step in flight, or None if no slot decodes.

        With nothing in flight the step runs IN STEP with the host: pages
        grow for slots whose next token starts a fresh page (preempting
        if the pool is dry) and the tokens are the host's. With a step in
        flight (`_may_lead` allowed it) this one runs AHEAD of it: its
        tokens are that step's, where the sampler left them on the device
        (the same shape and dtype as the host's upload: one program
        either way), its lengths that step's plus one, and the pool has
        the pages (`_may_lead` counted them). Either way a slot admitted
        since the host's view last moved reads its token where the
        admission left it: its first token on the device (`_firsts`), a
        resumed one in the host's upload; `merge_tokens` puts the vector
        together, and the step carries the first tokens to their fetch.
        Every upload is an array of its own: the program may run after the
        host has moved on, and on the CPU backend jnp.asarray can alias
        the host's buffer."""
        e, prev = self.e, self._flight
        if prev is None:
            if not self._grow_pages():
                return None
            lengths, active = self.lengths.copy(), self.active.copy()
            keep = np.zeros(e.max_slots, bool)
        else:
            # `keep`: the slots whose token that step draws; any other's
            # is the host's, or a first token's
            lengths, active, keep = self._past(prev)
            if not active.any():
                return None
            for i in self._pages_short(lengths, active):
                pid = self._alloc_page()
                self.page_refs[pid] = 1
                self.slot_pages[i].append(pid)
            if self.win_span:
                for i in np.flatnonzero(active):
                    self._slide_window(i, int(lengths[i]))
            self.decode_steps_ahead += 1
        firsts, self._firsts = self._firsts, None
        if prev is not None and firsts is None and keep[active].all():
            tokens = prev.tokens
        elif prev is None and firsts is None:
            tokens = jnp.asarray(self.last_tokens.copy())
        else:
            host = jnp.asarray(self.last_tokens.copy())
            # row of `firsts` a slot (-1: none); without first tokens (a
            # resumed request under a step in flight) the one-row program
            src = np.full(e.max_slots, -1, np.int32)
            if firsts is not None:
                src[[slot for slot, _r in firsts.owners]] = np.arange(
                    len(firsts.owners))
            tokens = self._merge(
                host if prev is None else prev.tokens, host,
                jnp.asarray(keep), jnp.asarray(np.zeros(1, np.int32))
                if firsts is None else firsts.tokens, jnp.asarray(src))
        # requests with a first token the host has not seen: this call's,
        # and those the step in flight carries
        unseen = {id(r) for ft in (
            firsts, None if prev is None else prev.firsts)
            if ft is not None for _s, r in ft.owners}
        self.decode_steps += 1
        with diagnostics.span("ray_tpu.engine.decode", step=self.decode_steps,
                              ahead=int(prev is not None)) as sp:
            if sp.on:
                # the work: the `lengths` operand of the active slots, in
                # slot order (the kernels attend one key more a slot, the
                # token this step writes)
                sp.set(lengths=tuple(lengths[active].tolist()))
            tables = self._build_tables(active)
            p_bucket = tables.shape[1]
            if self.win_span:
                tables = (tables, *self._window_tables(active))
            pools = self._pools()
            n_donated = len(pools) + len(self.rows)
            fn = self._decode_paged.get(p_bucket)
            if fn is None:
                fn = _shared_jit(
                    ("decode_paged", self.c),
                    lambda: partial(self.serving.decode_paged, config=self.c),
                    donate_argnums=tuple(range(1, 1 + n_donated)))
                self._decode_paged[p_bucket] = fn
            logits, *out = self._counted(fn(
                self.params, *pools, *self.rows, tokens, jnp.asarray(lengths),
                jnp.asarray(active), jax.tree_util.tree_map(
                    jnp.asarray, tables), *self._stats()))
            self._set_pools(out[:len(pools)])
            self.rows = tuple(out[len(pools):])
            reqs = [r if active[i] else None
                    for i, r in enumerate(self.slot_req)]
            # what the host knows of this step's end without its tokens: a
            # slot's count of tokens after it (one more is in flight where
            # this step leads the slot, and one where its first token is
            # still on the device), and the length its sequence reaches
            ends = np.array([
                r is not None and (
                    len(r.generated) + (id(r) in unseen) + int(keep[i]) + 1
                    >= r.max_new_tokens
                    or lengths[i] + 2 >= e.max_len)
                for i, r in enumerate(reqs)])
            return _Flight(*self._sample_dispatch(logits, reqs), active,
                           reqs, ends, firsts)

    def _build_tables(self, active) -> np.ndarray:
        """Page tables [B, bucket] of the `active` slots."""
        e = self.e
        p_need = max(
            (len(self.slot_pages[i]) for i in range(e.max_slots)
             if active[i]), default=1)
        p_bucket = next(b for b in self._page_buckets if b >= p_need)
        tables = np.zeros((e.max_slots, p_bucket), np.int32)
        for i in range(e.max_slots):
            if active[i]:
                row = self.slot_pages[i][:p_bucket]
                tables[i, :len(row)] = row
        return tables

    def _window_tables(self, active) -> tuple:
        """(the `active` slots' held window pages [B, win_span] in position
        order, the position [B] the first of them begins at)."""
        tables = np.zeros((self.e.max_slots, self.win_span), np.int32)
        starts = np.zeros((self.e.max_slots,), np.int32)
        for i in np.flatnonzero(active):
            tables[i, :len(self.slot_win[i])] = self.slot_win[i]
            starts[i] = self.slot_win_first[i] * self.e.page_size
        return tables, starts

    def _sample_dispatch(self, logits, reqs) -> tuple:
        """One token a row of `logits` [len(reqs), vocab] by that row's
        request (None = an empty slot: greedy, untruncated, unguided) ->
        (tokens, log p(token) a row, or None where no request asks for
        it), both left on the device: nothing is fetched."""
        temps, top_ps, top_ks = _sampling_of(reqs)
        self._key, sub = jax.random.split(self._key)
        mask = None
        if any(r is not None and r.guide is not None for r in reqs):
            m = np.ones((len(reqs), self.c.vocab), bool)
            for j, r in enumerate(reqs):
                if r is not None and r.guide is not None:
                    m[j] = r.guide.table[r.guide_state] >= 0
            mask = jnp.asarray(m)
        if (top_ks == 0).all() and (top_ps >= 1.0).all():
            toks = self._sample(logits, jnp.asarray(temps), sub, mask=mask)
        else:
            toks = self._sample_trunc(
                logits, jnp.asarray(temps), sub, jnp.asarray(top_ps),
                jnp.asarray(top_ks), mask)
        logps = None
        if any(r is not None and r.logprobs for r in reqs):
            logps = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), toks[:, None], 1)[:, 0]
        return toks, logps

    @staticmethod
    def _advance_guide(req: Request, tok: int):
        if req.guide is not None:
            req.guide_state = max(int(req.guide.table[req.guide_state,
                                                      tok]), 0)

    # ---- conveniences ----

    def generate(self, prompts: list, max_new_tokens=None,
                 temperature=None) -> list[list[int]]:
        """Blocking batch generate; returns generated token ids per prompt
        (continuous batching underneath — prompts longer than max_slots
        stream through)."""
        ids = [self.add_request(p, max_new_tokens, temperature)
               for p in prompts]
        while self.has_work():
            self.step()
        out = []
        for rid in ids:
            req = self.finished.pop(rid)
            gen = req.generated
            if gen and gen[-1] == self.e.eos_token:
                gen = gen[:-1]
            out.append(gen)
        return out


class PrefillEngine:
    """Prefill-only engine for the disaggregated serving plane's prefill
    pool (llm/serve.py): runs the bucketed prefill jit, samples the first
    continuation token, and EXPORTS the prompt KV for the decode-pool
    handoff — a prefill worker owns no decode pool, no slots, no pages.
    The exported K is post-RoPE at absolute positions, so a decode
    replica's `import_kv` splices it verbatim into its prefix cache."""

    def __init__(self, model_config: ModelConfig,
                 engine_config: EngineConfig | None = None, *,
                 params=None, mesh=None, rules=None, seed: int = 0):
        self.c = model_config
        self.e = engine_config or EngineConfig()
        self.mesh = mesh
        if model_config.kv_cache != "per_head" or model_config.loops > 1:
            _refuse(model_config, "the prefill pool, which exports "
                                  "per-head K and V of layers that run once")
        self.params = _resolve_params(model_config, params, mesh, rules,
                                      seed)
        self._prefill = _shared_jit(
            ("prefill", self.c), lambda: partial(prefill, config=self.c))
        self._sample, self._sample_trunc = _samplers()
        self._key = jax.random.PRNGKey(seed + 1)

    def prefill_export(self, prompt_tokens, temperature=None,
                       top_p: float = 1.0, top_k: int = 0,
                       want_logp: bool = False):
        """-> (first_token, ks, vs[, first_logp]): the sampled
        continuation token plus the prompt's full-page KV as host arrays
        [L, S, hkv, hd] with S = page-aligned prefix length (0 when the
        prompt spans less than one full page — nothing worth handing
        off). Greedy (temp 0) picks match the decode engine's
        bit-exactly. `want_logp` additionally returns log p(first_token)
        under the unmasked distribution — the OpenAI-logprobs value for
        the position the prefill pool samples (the decode pool covers
        the rest of the stream)."""
        ids = list(map(int, prompt_tokens))
        n = len(ids)
        bucket = _prompt_bucket(self.e, n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = ids
        last, ks, vs = self._prefill(self.params, jnp.asarray(toks),
                                     jnp.asarray([n], jnp.int32))
        temp = (self.e.default_temperature if temperature is None
                else temperature)
        self._key, sub = jax.random.split(self._key)
        row = last[None]
        if top_k == 0 and top_p >= 1.0:
            first = int(self._sample(
                row, jnp.asarray([temp], jnp.float32), sub)[0])
        else:
            first = int(self._sample_trunc(
                row, jnp.asarray([temp], jnp.float32), sub,
                jnp.asarray([top_p], jnp.float32),
                jnp.asarray([top_k], jnp.int32))[0])
        page = self.e.page_size
        full = n // page
        if full * page == n:
            full -= 1  # the decode side always re-prefills >=1 token
        cut = max(full, 0) * page
        ks_np = np.asarray(ks[:, :cut])
        vs_np = np.asarray(vs[:, :cut])
        if not want_logp:
            return first, ks_np, vs_np
        first_logp = float(jax.nn.log_softmax(row[0])[first])
        return first, ks_np, vs_np, first_logp


def __graphcheck__(gc):
    """graphcheck hook (tools/graphcheck): the steady-state serving
    graphs, lowered at a tiny config. Pins per graph: the KV pool/cache
    donation pattern (dropping one silently doubles the pool's HBM), zero
    host callbacks on the decode hot loop, and the collective/flops
    fingerprint. Shapes mirror the engine's paged layout
    [L, hkv, pages, hd, page]."""
    c = ModelConfig(vocab=128, d_model=32, n_layers=2, n_heads=2,
                    n_kv_heads=1, d_ff=64, dtype="float32")
    page, npages, slots, ptab = 16, 17, 4, 4

    def _params():
        return jax.eval_shape(lambda k: init_params(c, k),
                              jax.random.PRNGKey(0))

    def _sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def _pool():
        return _sds((c.n_layers, c.n_kv_heads, npages, c.head_dim, page),
                    jnp.float32)

    def build_prefill(mesh):
        return gc.GraphSpec(
            name="llm.prefill", fn=partial(prefill_batch, config=c),
            args=(_params(), _sds((2, 32), jnp.int32),
                  _sds((2,), jnp.int32)),
            arg_names=("params", "tokens", "lengths"))

    def build_decode(mesh):
        return gc.GraphSpec(
            name="llm.decode_paged", fn=partial(decode_paged, config=c),
            args=(_params(), _pool(), _pool(), _sds((slots,), jnp.int32),
                  _sds((slots,), jnp.int32), _sds((slots,), jnp.bool_),
                  _sds((slots, ptab), jnp.int32)),
            donate_argnums=(1, 2), min_donate_bytes=16384,
            arg_names=("params", "pool_k", "pool_v", "tokens", "lengths",
                       "active", "page_tables"))

    def build_insert(mesh):
        return gc.GraphSpec(
            name="llm.insert_kv", fn=insert_pages_batch,
            args=(_pool(), _pool(),
                  _sds((c.n_layers, 2, 32, c.n_kv_heads, c.head_dim),
                       jnp.float32),
                  _sds((c.n_layers, 2, 32, c.n_kv_heads, c.head_dim),
                       jnp.float32),
                  _sds((2, 2), jnp.int32), _sds((2,), jnp.int32)),
            donate_argnums=(0, 1), min_donate_bytes=16384,
            arg_names=("pool_k", "pool_v", "ks", "vs", "page_ids",
                       "lengths"))

    # ---- disaggregated serving plane (llm/serve.py) ----
    # The prefill-pool export graph and the decode-side KV-handoff import
    # (the splice fed by the host device_put of the sealed arena object;
    # the decode pool's step is llm.decode_paged above). Pinning these
    # keeps router churn from silently dropping the pool donations (a
    # dropped donation doubles every decode replica's HBM).

    def build_prefill_pool(mesh):
        return gc.GraphSpec(
            name="llm.prefill_pool", fn=partial(prefill, config=c),
            args=(_params(), _sds((1, 32), jnp.int32),
                  _sds((1,), jnp.int32)),
            arg_names=("params", "tokens", "lengths"))

    def build_kv_handoff(mesh):
        # import_kv's splice: ONE request, a multi-page contiguous handoff
        # segment (vs llm.insert_kv's admission-burst shape).
        kv = _sds((c.n_layers, 1, 2 * page, c.n_kv_heads, c.head_dim),
                  jnp.float32)
        return gc.GraphSpec(
            name="llm.kv_handoff", fn=insert_pages_batch,
            args=(_pool(), _pool(), kv, kv, _sds((1, 2), jnp.int32),
                  _sds((1,), jnp.int32)),
            donate_argnums=(0, 1), min_donate_bytes=16384,
            arg_names=("pool_k", "pool_v", "ks", "vs", "page_ids",
                       "lengths"))

    gc.register("llm.prefill", build_prefill)
    gc.register("llm.decode_paged", build_decode)
    gc.register("llm.insert_kv", build_insert)
    gc.register("llm.prefill_pool", build_prefill_pool)
    gc.register("llm.kv_handoff", build_kv_handoff)
