"""Llama-family decoder: RMSNorm + RoPE + GQA attention + SwiGLU (or MoE).

TPU-first choices:
- layers stacked on a leading axis and iterated with lax.scan: one compiled
  layer body regardless of depth (fast compiles, remat-friendly);
- attention pluggable: pallas flash (single shard), ring (sp over ICI ring),
  ulysses (sp all-to-all) — long-context parallelism is a config, not a fork;
- MoE in GSPMD dense form: experts on the "ep" mesh axis, einsum over the
  expert dimension so the partitioner places each expert's FLOPs on its
  owner device;
- bfloat16 params/activations, fp32 logits + softmax accumulation.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.layers import apply_rope, rmsnorm, rope, swiglu


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # MoE: 0 = dense. When > 0 every layer is a top-k MoE layer.
    moe_experts: int = 0
    moe_top_k: int = 2
    dtype: str = "float32"
    remat: bool = False
    attn_impl: str = "auto"  # auto|pallas|reference|interpret|ring|ulysses
    tie_embeddings: bool = True
    # Layer-loop lowering: None = auto (unroll small models — the scan's
    # per-iteration dynamic-update-slice activation stacking costs ~13% of
    # a GPT-small train step; at billion-param scale the copies amortize
    # and scan keeps compiles fast). True/False forces it.
    unroll_layers: bool | None = None
    # --- what models/deepseek_v2.py reads (attention == "mla") ---
    # "gqa": this file. "mla": latent attention, a dense layer before the
    # expert layers, shared experts, a group-limited router; the serving
    # engine then keeps a latent page pool (`kv_cache`).
    attention: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The source's `rope_scaling` object as a tuple of (key, value) pairs
    # (a frozen dataclass stays hashable); None = plain rotary embedding.
    rope_scaling: tuple | None = None
    first_k_dense: int = 0          # leading layers with the dense d_ff MLP
    moe_d_ff: int = 0               # width of one routed expert
    moe_shared_experts: int = 0     # always-on experts, each moe_d_ff wide
    # The router's published width; `moe_experts` of them are held here:
    # experts [moe_held_group * moe_experts, (moe_held_group + 1) *
    # moe_experts). 0 = every expert is held (moe_experts wide).
    moe_router_experts: int = 0
    moe_held_group: int = 0
    moe_n_group: int = 1            # group-limited routing: groups,
    moe_topk_group: int = 1         # and how many of them a token may use
    moe_routed_scale: float = 1.0   # routed_scaling_factor
    moe_norm_topk: bool = True      # renormalise the top-k weights
    # --- what models/nemotron_h.py reads besides (layer_pattern != "") ---
    # One mixer a block, a letter a block: "M" a Mamba-2 mixer, "S" a
    # Mamba-1 (selective scan) mixer, "K" a Kimi-Delta-Attention mixer (one
    # recurrent kind a pattern), "E" the expert layer, "-" the dense
    # gated MLP of `d_ff`, "*" attention (`n_layers` letters; "" = every
    # layer is attention then feed-forward, the two modules above).
    layer_pattern: str = ""
    head_size: int = 0              # stated head size; 0 = d_model / heads
    rotary: bool = True             # False: attention rotates nothing
    ssm_heads: int = 0              # Mamba-2: heads, each ssm_head_dim
    ssm_head_dim: int = 0           # wide, over a state of ssm_state
    ssm_state: int = 0              # numbers a head and channel;
    ssm_groups: int = 1             # B and C are shared by heads in groups
    ssm_conv_width: int = 4         # taps of the causal convolution
    ssm_chunk: int = 128            # rows a step of the prefill scan
    ssm_state_dtype: str = "float32"   # of the recurrent state a sequence
    #                                    keeps between tokens
    ssm_expand: int = 0             # Mamba-1 ("S"): d_inner = ssm_expand *
    ssm_dt_rank: int = 0            # d_model; the step's low-rank width
    # "K", Kimi Delta Attention: `ssm_heads` heads, keys and values both
    # `ssm_head_dim` wide, a state [ssm_head_dim, ssm_head_dim] a head
    # updated by a delta rule (ops/kda.py), chunks of `ssm_chunk`.
    kda_rank: int = 0               # width of the two low-rank pairs (the
    #                                 decay's and the output gate's)
    kda_neg_eigval: bool = False    # beta = 2 sigmoid(.), not sigmoid(.):
    #                                 the transition's eigenvalues in (-1, 1)
    mlp_act: str = "swiglu"         # "relu2": W_down relu(W_up x)^2, no gate
    moe_score: str = "softmax"      # "sigmoid": scores are sigmoids, and a
    #                                 learned bias joins them in the CHOICE
    moe_scale_normed: bool = False  # x moe_routed_scale after renormalising
    #                                 too (False: only where not renormalised)
    moe_shared_d_ff: int = 0        # the shared expert's width; 0 =
    #                                 moe_shared_experts * moe_d_ff
    # The held experts' grouped product: "ragged_dot" (a stable sort, XLA's
    # kernel: deepseek_v2's), or "tiles" (counted order, plain products
    # over [block of rows, expert] tiles, every held expert over every
    # token where tokens are few). Not a choice of taste: with "ragged_dot"
    # the 27-block hybrid programs of models/nemotron_h.py HANG the v5e in
    # warm-up (models/experts.py has the chip runs), so "tiles" is the one
    # form that runs that model, and deepseek_v2's cell was only ever
    # measured on the other.
    moe_grouped: str = "ragged_dot"
    # --- what models/windowed.py reads besides (attn_pattern != "") ---
    # Attention by layer kind, a letter a layer: "F" attends over the whole
    # context, "W" over the last `window` positions (query i sees keys j
    # with i - window < j <= i). The kinds differ in more than the mask:
    # `n_heads`, `rope_theta`, `rope_scaling` and `rotary_fraction` are the
    # "F" layers', the `window_*` fields the "W" layers'; the head size
    # (q's and k's) and the page are shared. "" = every layer alike (the
    # modules above).
    attn_pattern: str = ""
    window: int = 0
    window_heads: int = 0           # query heads of a "W" layer
    window_rope_theta: float = 10000.0   # its rotary base, never scaled
    window_rotary_fraction: float = 1.0  # share of a head's dims it rotates
    rotary_fraction: float = 1.0    # the same of an "F" layer (the FIRST
    #                                 dims rotate, the rest pass through)
    # "per_head": o_h <- sigmoid(u W_gate)_h * o_h, one scalar a head and
    # token from the layer's normed input, before W_o (models/windowed.py);
    # "per_element": the same with one scalar a head AND channel, W_gate
    # [d, heads * head size] (models/nemotron_h.py). "" = no gate.
    attn_gate: str = ""
    # What a pattern's kinds need NOT share (models/windowed.py; each
    # default reads "as the fields above say"): the "W" layers' K/V heads
    # (0 = `n_kv_heads`, the "F" layers'); the kinds, of "F" and "W", whose
    # softmax has a learned sink, one logit a query head that joins every
    # row's denominator and gives no value ("" = none); a factor on v
    # before it is cached; whether a sigmoid router's choice adds a learned
    # bias. The V width is `v_head_dim` above (0 = the head size).
    window_kv_heads: int = 0
    attn_sink: str = ""
    value_scale: float = 1.0
    moe_router_bias: bool = False
    # --- a looped stack (loops > 1): this file and llm/engine.py ---
    # The `n_layers` layers run `loops` times over ONE set of weights, pass
    # t's layer l with K and V of its own (cache layer t * n_layers + l);
    # `final_norm` closes every pass and its output is the next pass's
    # input; an exit gate (one linear unit on the closed pass's state)
    # chooses, a position at a time, the pass whose state the head reads:
    # the first whose exit probabilities add up to `exit_threshold` (the
    # last at 1.0). Every pass is computed whatever the gate says.
    loops: int = 1
    exit_threshold: float = 1.0
    # Two further norms a layer, on the attention's and the feed-forward's
    # OUTPUT before the residual add (`attn_post_norm`, `mlp_post_norm`).
    post_norms: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token keeps: one a pass and layer."""
        return self.loops * self.n_layers

    @property
    def kv_cache(self) -> str:
        """What a sequence keeps in the serving engine: "per_head" K and V
        pages, the "latent" (normed c_kv | rotated k_pe) pages of latent
        attention, "recurrent": per-head pages for the attention layers
        beside one fixed-size row of state for the Mamba layers, or
        "windowed": per-head pages that grow for the "F" layers beside a
        bounded number of pages for the "W" layers, in a pool of their
        own."""
        if self.layer_pattern:
            return "recurrent"
        if "W" in self.attn_pattern:
            return "windowed"
        return "latent" if self.attention == "mla" else "per_head"

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def window_span(self, page: int) -> int:
        """Pages of `page` positions that hold the `window` positions a
        window layer's query sees, at most: the window's first position
        may lie anywhere in its page."""
        return -(-(self.window - 1) // page) + 1


def init_params(config: ModelConfig, key) -> dict:
    c = config
    dt = c.jdtype
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, hd = c.d_model, c.head_dim

    def norm_init(shape):
        return jnp.ones(shape, dt)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    L = c.n_layers
    ks = jax.random.split(k_layers, 8)
    layer = {
        "attn_norm": norm_init((L, d)),
        "wq": dense_init(ks[0], (L, d, c.n_heads * hd), d),
        "wk": dense_init(ks[1], (L, d, c.n_kv_heads * hd), d),
        "wv": dense_init(ks[2], (L, d, c.n_kv_heads * hd), d),
        "wo": dense_init(ks[3], (L, c.n_heads * hd, d), c.n_heads * hd),
        "mlp_norm": norm_init((L, d)),
    }
    if c.post_norms:
        # Seeded gains of (2 L)^-1/2, the depth-scaled residual init at
        # the normed outputs: at gains of 1 every sublayer adds a unit-RMS
        # vector whatever its input, a looped stack's closing norm makes
        # that the whole stream's size at each pass's start, and the
        # seeded function then amplifies rounding ten times and more
        # (Ouro-2.6B's widths on the chip: bfloat16 forward against the
        # float32 reference, median |d log p| 0.20-0.31 at gains of 1,
        # 0.019 at 0.1; PERF.md section 2, PR 45)
        post = jnp.full((L, d), (2 * L) ** -0.5, dt)
        layer.update({"attn_post_norm": post, "mlp_post_norm": post})
    if c.moe_experts:
        X = c.moe_experts
        layer.update({
            "router": dense_init(ks[4], (L, d, X), d),
            "wg": dense_init(ks[5], (L, X, d, c.d_ff), d),
            "wu": dense_init(ks[6], (L, X, d, c.d_ff), d),
            "wd": dense_init(ks[7], (L, X, c.d_ff, d), c.d_ff),
        })
    else:
        layer.update({
            "wg": dense_init(ks[5], (L, d, c.d_ff), d),
            "wu": dense_init(ks[6], (L, d, c.d_ff), d),
            "wd": dense_init(ks[7], (L, c.d_ff, d), c.d_ff),
        })
    params = {
        "embed": (jax.random.normal(k_embed, (c.vocab, d), jnp.float32)
                  * 0.02).astype(dt),
        "layers": layer,
        "final_norm": norm_init((d,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (d, c.vocab), d)
    if c.loops > 1:
        params["exit_gate"] = {
            "w": dense_init(jax.random.fold_in(k_head, 1), (d,), d),
            "b": jnp.zeros((), dt)}
    return params


def param_logical_axes(config: ModelConfig) -> dict:
    """Logical sharding axes per param (leading scan axis = "layer")."""
    c = config
    layer = {
        "attn_norm": ("layer", None),
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "kv_heads"),
        "wv": ("layer", "embed", "kv_heads"),
        "wo": ("layer", "heads", "embed"),
        "mlp_norm": ("layer", None),
    }
    if c.post_norms:
        layer.update({"attn_post_norm": ("layer", None),
                      "mlp_post_norm": ("layer", None)})
    if c.moe_experts:
        layer.update({
            "router": ("layer", "embed", None),
            "wg": ("layer", "expert", "embed", "mlp"),
            "wu": ("layer", "expert", "embed", "mlp"),
            "wd": ("layer", "expert", "mlp", "embed"),
        })
    else:
        layer.update({
            "wg": ("layer", "embed", "mlp"),
            "wu": ("layer", "embed", "mlp"),
            "wd": ("layer", "mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": (None,),
    }
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if c.loops > 1:
        axes["exit_gate"] = {"w": (None,), "b": ()}
    return axes


def _attention(x, lp, c: ModelConfig, sin, cos, mesh):
    b, s, d = x.shape
    h, hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim
    q = jnp.einsum("bsd,dk->bsk", x, lp["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,dk->bsk", x, lp["wk"]).reshape(b, s, hkv, hd)
    v = jnp.einsum("bsd,dk->bsk", x, lp["wv"]).reshape(b, s, hkv, hd)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    if c.attn_impl in ("ring", "ulysses"):
        if hkv != h:  # GQA broadcast before the sp collective
            rep = h // hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if c.attn_impl == "ring":
            from ray_tpu.parallel.ring_attention import ring_attention
            o = ring_attention(q, k, v, mesh, causal=True)
        else:
            from ray_tpu.parallel.ulysses import ulysses_attention
            o = ulysses_attention(q, k, v, mesh, causal=True)
    else:
        o = flash_attention(q, k, v, causal=True, impl=c.attn_impl,
                            mesh=mesh)
    o = o.reshape(b, s, h * hd)
    return jnp.einsum("bsk,kd->bsd", o, lp["wo"])


def _moe(x, lp, c: ModelConfig):
    """Top-k MoE in GSPMD dense form: every expert computes, the router's
    top-k weights zero the rest; the "expert" einsum axis shards over "ep".
    Who calls it: the trainer and `forward` (`hidden_states`), and the
    serving engine's per-head programs under a mesh of several devices
    (llm/engine._mlp_block). On one device those programs send a token to
    the experts it chose instead (llm/engine._expert_block ->
    models/experts.expert_layer: the same router, the same weights)."""
    probs = jax.nn.softmax(
        jnp.einsum("bsd,dx->bsx", x, lp["router"],
                   preferred_element_type=jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, c.moe_top_k)          # [b,s,k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None, None],
        jnp.arange(probs.shape[1])[None, :, None],
        top_i].set(top_w.astype(probs.dtype))                  # [b,s,X]
    h = jnp.einsum("bsd,xdf->bsxf", x, lp["wg"])
    u = jnp.einsum("bsd,xdf->bsxf", x, lp["wu"])
    act = jax.nn.silu(h) * u
    y = jnp.einsum("bsxf,xfd->bsxd", act, lp["wd"])
    return jnp.einsum("bsxd,bsx->bsd", y, gate.astype(x.dtype))


def _mlp(x, lp):
    return swiglu(x, lp["wg"], lp["wu"], lp["wd"])


def exit_zero(rows) -> tuple:
    """The exit rule's state before the first pass, for rows shaped as
    `rows` [..., d]: (the state the head reads, the probability that no
    pass so far was the exit, the exit probabilities added up, whether the
    exit pass is behind)."""
    lead = rows.shape[:-1]
    return (jnp.zeros_like(rows), jnp.ones(lead, jnp.float32),
            jnp.zeros(lead, jnp.float32), jnp.zeros(lead, bool))


def exit_step(params, c: ModelConfig, t, rows, state) -> tuple:
    """The exit rule past pass `t` (traced or not), whose closed state is
    `rows` [..., d]: lambda_t = sigmoid(rows . w + b) in float32, p_t =
    lambda_t * prod_{s<t}(1 - lambda_s) (the last pass takes what is
    left), and the exit pass is the first whose p add up to
    `exit_threshold`, the last if none does. -> `exit_zero`'s tuple, moved
    on. It chooses what the head reads; it skips nothing."""
    h_exit, still, cdf, chosen = state
    gate = params["exit_gate"]
    with jax.named_scope("exit_gate"):
        lam = jax.nn.sigmoid(
            jnp.einsum("...d,d->...", rows.astype(jnp.float32),
                       gate["w"].astype(jnp.float32))
            + gate["b"].astype(jnp.float32))
        last = t == c.loops - 1
        cdf = cdf + jnp.where(last, still, lam * still)
        now = ~chosen & ((cdf >= c.exit_threshold) | last)
        return (jnp.where(now[..., None], rows, h_exit),
                still * (1.0 - lam), cdf, chosen | now)


def _chip_index(mesh, axes):
    """Inside a shard_map: this chip's row-major index over `axes`."""
    index = 0
    for a in axes:
        index = index * mesh.shape[a] + jax.lax.axis_index(a)
    return index


def _embed(table, tokens, mesh):
    """tokens [b, s] -> their rows of `table`, [b, s, d] in the activation
    layout, under a mesh of several devices.

    The declared table is [vocab, embed] -> P("tp", "fsdp") and the batch is
    sharded over fsdp too, so whatever is left to GSPMD moves the matrix.
    `_head_shard_axes` decides on the table's transposed shape (the table
    IS the head's transpose, declared so): where one axis of several chips
    shards both and the sizes divide, `_embed_rows` moves the tokens and
    the activations and leaves the table and its gradient where they lie.
    Otherwise (no such axis: `forward()` under a tp mesh; sizes that do
    not divide; a sequence axis of several chips, which the lookup's
    layout does not know) the FALLBACK is the one-hot product (the
    iota-embed trick): the SPMD partitioner handles a [b,s,v] x [v,d]
    contraction over the tp-sharded vocab axis cleanly (masked matmul +
    psum), where the equivalent gather forced "Involuntary full
    rematerialization" (spmd_partitioner.cc:652) of the embedding
    activation in fwd AND bwd. It gathers the whole table over fsdp and
    all-reduces its whole gradient; the explicit constraint pins the result
    to the activation layout (batch over the data axes, embed replicated)
    so the bwd table grad partitions as a plain matmul too."""
    from ray_tpu.parallel.sharding import (ShardingRules,
                                           activation_batch_sharded)
    seq_axis = ShardingRules.default().rules["seq"]
    axes = None
    if mesh.shape.get(seq_axis, 1) == 1:
        axes = _head_shard_axes(mesh, table.shape[::-1], tokens.shape[0])
    with jax.named_scope("embed_lookup"):
        if axes is not None:
            return _embed_rows(table, tokens, mesh, axes)
        onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
        x = jnp.einsum("bsv,vd->bsd", onehot, table)
        return activation_batch_sharded(x, mesh)


def _embed_rows(table, tokens, mesh, axes):
    """The lookup where the table's slice lies: [b, s, d], bit for bit the
    rows `jnp.take(table, tokens, axis=0)` reads.

    Inside a shard_map a chip holds the DECLARED [V/tp, d/g] slice (`g` the
    axis that shards the model dimension and the batch, fsdp) and its
    [b/(dp*g), s] tokens. Forward: the group's token ids are gathered over
    `g` (bytes), the chip reads ITS d/g columns of every token of the group
    from its own slice (under tp an id outside the chip's vocabulary range
    reads zero and one psum over tp adds the ranges up), and ONE all-to-all
    over `g` (split the batch, concatenate the model dimension) gives the
    batch layout. Backward, the transpose: the cotangent goes back through
    one all-to-all to [b*g, s, d/g] and the chip scatter-adds every token
    of its group into its own slice of the table's gradient: a COMPLETE
    slice, no reduction over `g` at all, nothing over tp (a chip owns its
    vocabulary range), one psum over dp where the table is replicated. The
    sum is accumulated in fp32 and rounded once, as the matrix unit
    accumulated the transposed one-hot product: a batch repeats tokens."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import data_axes, shard_map_compat
    g, v_axes, dp_axes = axes
    tp_axes = tuple(a for a in v_axes if a != g)    # vocabulary only
    batch_axes = data_axes(mesh)
    x_spec, t_spec = P(batch_axes, None, None), P(batch_axes, None)
    table_spec = P(tp_axes or None, g)              # the declared layout
    n_g = mesh.shape[g]
    rows = table.shape[0] // math.prod(mesh.shape[a] for a in tp_axes)
    cols = table.shape[1] // n_g

    def local_ids(tokens):
        """The group's ids as rows of this chip's slice; `rows` (past the
        slice's end) where another chip's vocabulary range holds the id."""
        ids = jax.lax.all_gather(tokens, g, axis=0, tiled=True)
        ids = ids - _chip_index(mesh, tp_axes) * rows
        return jnp.where((ids >= 0) & (ids < rows), ids, rows)

    def fwd_body(table, tokens):
        x = jnp.take(table, local_ids(tokens), axis=0, mode="fill",
                     fill_value=0)
        if tp_axes:
            x = jax.lax.psum(x, tp_axes)
        # [chip of the group, b, s, d/g] -> [b, s, slice of d, d/g]
        x = jax.lax.all_to_all(x.reshape(n_g, -1, *x.shape[1:]), g, 0, 2)
        return x.reshape(*x.shape[:2], -1)

    def bwd_body(tokens, ct):
        # the forward's exchange back; written as ONE tiled all-to-all that
        # splits the minor dimension it costs the chip's compiler 5 s
        ct = jax.lax.all_to_all(ct.reshape(*ct.shape[:2], n_g, cols), g, 2, 0)
        dtable = jnp.zeros((rows, cols), jnp.float32).at[
            local_ids(tokens)].add(
                ct.reshape(-1, *ct.shape[2:]).astype(jnp.float32),
                mode="drop")
        if dp_axes:         # the table is replicated over them
            dtable = jax.lax.psum(dtable, dp_axes)
        return dtable.astype(table.dtype)

    @jax.custom_vjp
    def lookup(table, tokens):
        return shard_map_compat(fwd_body, mesh, (table_spec, t_spec),
                                x_spec)(table, tokens)

    def fwd(table, tokens):
        return lookup(table, tokens), tokens

    def bwd(tokens, ct):
        return shard_map_compat(bwd_body, mesh, (t_spec, x_spec),
                                table_spec)(tokens, ct), None

    lookup.defvjp(fwd, bwd)
    return lookup(table, tokens)


def hidden_states(params, tokens, config: ModelConfig, mesh=None):
    """tokens [batch, seq] -> final-norm hidden states [batch, seq, d]: of
    a looped stack (`loops` > 1) the closed state of each position's exit
    pass."""
    c = config
    if mesh is not None and mesh.devices.size > 1:
        x = _embed(params["embed"], tokens, mesh)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(tokens.shape[1])
    sin, cos = rope(positions, c.head_dim, c.rope_theta)

    def layer_body(x, lp):
        a = _attention(rmsnorm(x, lp["attn_norm"], c.norm_eps),
                       lp, c, sin, cos, mesh)
        if c.post_norms:
            a = rmsnorm(a, lp["attn_post_norm"], c.norm_eps)
        h = x + a
        normed = rmsnorm(h, lp["mlp_norm"], c.norm_eps)
        m = _moe(normed, lp, c) if c.moe_experts else _mlp(normed, lp)
        if c.post_norms:
            m = rmsnorm(m, lp["mlp_post_norm"], c.norm_eps)
        return h + m, None

    body = layer_body
    if c.remat:
        body = jax.checkpoint(layer_body)
    unroll = c.unroll_layers
    if unroll is None:
        unroll = (not c.remat and c.n_layers <= 12 and c.d_model <= 1024)

    def one_pass(x):
        x, _ = jax.lax.scan(body, x, params["layers"],
                            unroll=c.n_layers if unroll else 1)
        return rmsnorm(x, params["final_norm"], c.norm_eps)

    if c.loops == 1:
        return one_pass(x)

    def turn(carry, t):
        with jax.named_scope("pass"):
            x = one_pass(carry[0])
        return (x, exit_step(params, c, t, x, carry[1])), None

    (_, state), _ = jax.lax.scan(turn, (x, exit_zero(x)),
                                 jnp.arange(c.loops))
    return state[0]


def forward(params, tokens, config: ModelConfig, mesh=None):
    """tokens [batch, seq] -> logits [batch, seq, vocab] (fp32).

    The head matmul keeps bf16 inputs with an fp32 accumulator
    (preferred_element_type): full MXU rate, fp32 logits out — upcasting the
    operands first would run the largest matmul in the model at fp32 rate.
    """
    x = hidden_states(params, tokens, config, mesh)
    head = (params["embed"].T if config.tie_embeddings else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x, head,
                      preferred_element_type=jnp.float32)


def _xent(x, head, targets):
    """Cross entropy of one sequence chunk; logits never leave this scope.

    Gathers target logits and subtracts the row logsumexp directly rather
    than materializing the full log-softmax tensor (which would double the
    [b, s, vocab] fp32 footprint)."""
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return tgt - lse


# Chunk the head only when the whole fp32 logits tensor would be large
# enough to matter; below that the extra scan costs more than it saves.
LOSS_CHUNK_MIN_BYTES = 1 << 30


def _head_shard_axes(mesh, head_shape, batch: int):
    """(gather axis, vocabulary axes, batch-only axes) when the chunked
    head can run vocabulary-parallel on this mesh, and the lookup in the
    table's slices (`_embed`, with the table's transposed shape), else
    None.

    Read off the declared table (parallel/sharding.py): the head is
    [embed, vocab] -> P("fsdp", "tp"), the batch P(("dp", "fsdp")). The
    axis that shards BOTH the head's model dimension and the batch is the
    one GSPMD can only serve by moving the matrix; the tokens are gathered
    over it instead. None (the GSPMD product) on one device, where that
    axis has one chip, or where the sizes do not divide."""
    from ray_tpu.parallel.sharding import ShardingRules, data_axes
    if mesh is None or mesh.devices.size == 1:
        return None
    d_axis, v_axis = ShardingRules.default().spec(("embed", "vocab"))
    batch_axes = data_axes(mesh)
    if (not isinstance(d_axis, str) or d_axis not in batch_axes
            or mesh.shape[d_axis] == 1):
        return None
    v_axes = tuple(a for a in (v_axis, d_axis)
                   if a in mesh.axis_names and mesh.shape[a] > 1)

    def size(axes):
        return math.prod(mesh.shape[a] for a in axes)

    d, v = head_shape
    if d % mesh.shape[d_axis] or v % size(v_axes) or batch % size(batch_axes):
        return None
    return d_axis, v_axes, tuple(a for a in batch_axes
                                 if a != d_axis and mesh.shape[a] > 1)


def _xent_vocab_parallel(x, head, targets, mesh, axes, chunk: int):
    """`_xent` over every sequence chunk, the head product vocabulary-
    parallel: log p(target) [b, s] in fp32.

    The declared head is sharded along the model dimension on the axis
    that also shards the batch (`g`, fsdp), so `bsd,dv->bsv` wants every
    token or the whole matrix on a chip, and GSPMD moves the matrix: once
    a chunk, forward and backward, and its fp32 gradient back (13.9 GB
    into each chip a step at Qwen2-7B's widths, PERF.md §6, PR 36). Here
    the tokens move. One all-to-all over `g` turns the chip's [d/g, V]
    slice into a [d, V/g] one (the only time head bytes travel, 3/4 of
    the slice); per chunk the hidden states are gathered over `g`, each
    chip computes fp32 logits for every token over ITS slice of the
    vocabulary, and three [tokens]-sized reductions (max, sum of
    exponentials, the target's logit) make the log-sum-exp exact. The
    backward recomputes the chunk's logits (the rematerialisation the
    GSPMD path has), forms the chip's slice of the softmax gradient, its
    own COMPLETE [d, V/g] slice of the head's gradient (every token of
    the group contracted locally, accumulated in fp32 over the chunks: no
    reduction over `g` at all) and a partial dx that is reduce-scattered
    back to the batch layout. The same sums as `_xent`, in another order.
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import data_axes, shard_map_compat
    g, v_axes, dp_axes = axes
    tp_axes = tuple(a for a in v_axes if a != g)    # vocabulary only
    batch_axes = data_axes(mesh)
    x_spec, t_spec = P(batch_axes, None, None), P(batch_axes, None)
    head_spec = P(g, tp_axes or None)               # the declared layout
    slice_spec = P(None, v_axes)

    def chunks(a):
        # [bl, s, ...] -> [nc, bl, chunk, ...]
        bl, s = a.shape[:2]
        a = a.reshape(bl, s // chunk, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    def gathered(a):
        return jax.lax.all_gather(a, g, axis=0, tiled=True)

    def local_logits(xa, ta, w):
        """fp32 logits of the group's tokens over this chip's slice, the
        exact row log-sum-exp, and where each target falls in the slice."""
        logits = jnp.einsum("bsd,dv->bsv", xa, w,
                            preferred_element_type=jnp.float32)
        col = ta - _chip_index(mesh, v_axes) * w.shape[1]
        m = jax.lax.pmax(jnp.max(logits, axis=-1), v_axes)
        se = jax.lax.psum(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), v_axes)
        return logits, m + jnp.log(se), col

    def mine(a):
        bl = a.shape[0] // mesh.shape[g]
        return jax.lax.dynamic_slice_in_dim(
            a, jax.lax.axis_index(g) * bl, bl, axis=0)

    def fwd_body(x, head, targets):
        # [d/g, V/tp] -> [d, V/(tp*g)]: columns j*Vl.. of the chip's
        # vocabulary range go to chip j of the group.
        w = jax.lax.all_to_all(head, g, 1, 0, tiled=True)

        def one(args):
            xa, ta = gathered(args[0]), gathered(args[1])
            logits, lse, col = local_logits(xa, ta, w)
            hit = (col >= 0) & (col < w.shape[1])
            tgt = jnp.take_along_axis(
                logits, jnp.clip(col, 0, w.shape[1] - 1)[..., None],
                axis=-1)[..., 0]
            tgt = jax.lax.psum(jnp.where(hit, tgt, 0.0), v_axes)
            return mine(tgt - lse)

        ll = jax.lax.map(one, (chunks(x), chunks(targets)))
        bl, s = targets.shape
        return jnp.moveaxis(ll, 0, 1).reshape(bl, s), w

    def bwd_body(x, w, targets, ct):
        def one(dw, args):
            xa, ta, ga = (gathered(a) for a in args)
            logits, lse, col = local_logits(xa, ta, w)
            onehot = col[..., None] == jnp.arange(w.shape[1])
            dlogits = (onehot - jnp.exp(logits - lse[..., None])) \
                * ga[..., None]
            dw = dw + jnp.einsum("bsd,bsv->dv", xa, dlogits,
                                 preferred_element_type=jnp.float32)
            dxa = jnp.einsum("bsv,dv->bsd", dlogits, w,
                             preferred_element_type=jnp.float32)
            dx = jax.lax.psum_scatter(dxa, g, scatter_dimension=0,
                                      tiled=True)
            if tp_axes:
                dx = jax.lax.psum(dx, tp_axes)
            return dw, dx.astype(x.dtype)

        dw, dx = jax.lax.scan(
            one, jnp.zeros(w.shape, jnp.float32),
            (chunks(x), chunks(targets), chunks(ct)))
        if dp_axes:         # the head is replicated over them
            dw = jax.lax.psum(dw, dp_axes)
        bl, s, d = x.shape
        dx = jnp.moveaxis(dx, 0, 1).reshape(bl, s, d)
        # back to the declared layout, [d/g, V/tp]
        return dx, jax.lax.all_to_all(dw.astype(w.dtype), g, 0, 1,
                                      tiled=True)

    @jax.custom_vjp
    def xent(x, head, targets):
        return fwd(x, head, targets)[0]

    def fwd(x, head, targets):
        ll, w = shard_map_compat(
            fwd_body, mesh, (x_spec, head_spec, t_spec),
            (t_spec, slice_spec))(x, head, targets)
        return ll, (x, w, targets)

    def bwd(res, ct):
        x, w, targets = res
        dx, dhead = shard_map_compat(
            bwd_body, mesh, (x_spec, slice_spec, t_spec, t_spec),
            (x_spec, head_spec))(x, w, targets, ct)
        return dx, dhead, None

    xent.defvjp(fwd, bwd)
    return xent(x, head, targets)


def loss_fn(params, batch, config: ModelConfig, mesh=None,
            loss_chunk: int = 512):
    """Next-token cross entropy; batch = {"tokens": [b, s+1]} or
    {"inputs": [b,s], "targets": [b,s]}.

    The [b, s, vocab] fp32 logits tensor dominates training HBM at scale, so
    the head+softmax runs in rematerialized sequence chunks: peak logits
    memory is b*loss_chunk*vocab and the backward recomputes each chunk.

    Where the layout of the two vocabulary-wide matrices is decided: the
    DECLARED layout is parallel/sharding.py's table (`lm_head` [embed,
    vocab] -> P("fsdp", "tp"), `embed` and a tied table its transpose),
    and the optimizer state, the checkpoints and the serving engine all
    read that one table. Under an fsdp axis of more than one chip neither
    product is left to GSPMD, which can only serve a batch and a model
    dimension sharded over the same axis by gathering the whole matrix
    (the head's in every chunk) and reducing its whole gradient: the
    lookup reads the tokens of the group from the chip's own slice and
    exchanges activations (`hidden_states` -> `_embed_rows`), and the
    chunked head turns the chip's slice into a vocabulary slice once a
    step and moves the tokens (`_xent_vocab_parallel`). One function
    decides for both, `_head_shard_axes`. `mesh=None`, one device, an
    fsdp axis of one and sizes that do not divide (then both FALL BACK to
    the GSPMD products; nothing is padded) keep `jnp.take` or the one-hot
    product, and the plain `_xent` program, which a logits tensor under
    LOSS_CHUNK_MIN_BYTES keeps too.
    """
    if config.loops > 1:
        raise ValueError(
            f"ModelConfig.loops={config.loops}: loss_fn does not train a "
            f"looped stack (its loss over the passes' exits and "
            f"rematerialisation over passes are not written); forward() "
            f"and the serving engine run it")
    if config.kv_cache != "per_head":
        field = {"latent": f"attention={config.attention!r}",
                 "recurrent": f"layer_pattern={config.layer_pattern!r}",
                 "windowed": f"attn_pattern={config.attn_pattern!r}"}
        raise ValueError(
            f"ModelConfig.{field[config.kv_cache]}: loss_fn trains this "
            f"file's decoders only; a model of another module "
            f"(models.model_module) runs through forward() and the serving "
            f"engine, and has no training step")
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    x = hidden_states(params, inputs, config, mesh)
    head = (params["embed"].T if config.tie_embeddings else params["lm_head"])
    b, s, d = x.shape
    if (s % loss_chunk == 0 and s > loss_chunk
            and 4 * b * s * config.vocab > LOSS_CHUNK_MIN_BYTES):
        axes = _head_shard_axes(mesh, head.shape, b)
        if axes is not None:
            ll = _xent_vocab_parallel(x, head, targets, mesh, axes,
                                      loss_chunk)
        else:
            nc = s // loss_chunk
            xc = x.reshape(b, nc, loss_chunk, d).transpose(1, 0, 2, 3)
            tc = targets.reshape(b, nc, loss_chunk).transpose(1, 0, 2)
            ll = jax.lax.map(
                jax.checkpoint(lambda args: _xent(args[0], head, args[1])),
                (xc, tc))                                # [nc, b, loss_chunk]
            ll = ll.transpose(1, 0, 2).reshape(b, s)
    else:
        ll = _xent(x, head, targets)
    mask = batch.get("mask")
    if mask is None:
        return -jnp.mean(ll)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
