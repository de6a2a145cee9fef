"""Sharded train step construction (the GSPMD lowering).

The scaling-book recipe in code: put params+optimizer state in sharded
TrainState, jit the step with NamedShardings derived from the logical-axis
rules, and let XLA insert the gradient psums / FSDP all-gathers on ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import ShardingRules, declared_param_specs


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def make_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh: Mesh, param_axes, rules: ShardingRules | None = None,
                    batch_spec: P | None = None, donate: bool = True):
    """Returns (init_fn, step_fn, state_shardings).

    loss_fn(params, batch) -> scalar. param_axes: logical-axis pytree matching
    params. Both fns are jit-compiled with explicit in/out shardings so the
    same code runs 1-chip or N-chip.
    """
    rules = rules or ShardingRules.default()
    # The declared table (parallel/sharding.py): graphcheck cross-checks
    # the lowered step against the same source, so in_shardings here can
    # never silently diverge from the declaration.
    param_specs = declared_param_specs(param_axes, rules)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs)
    batch_spec = batch_spec if batch_spec is not None else P(("dp", "fsdp"))
    batch_sharding = NamedSharding(mesh, batch_spec)
    repl = NamedSharding(mesh, P())

    def init_fn(params):
        state = TrainState(params=params, opt_state=optimizer.init(params),
                           step=jnp.zeros((), jnp.int32))
        # Born in the step's layout. A state that arrives in another one
        # (say, everything committed to device 0) is resharded by the
        # first call AND compiled for, and the state that call returns
        # then compiles the step a second time (chip run, PR 21: 35 s at
        # the 125M bench config, inside the timed loop).
        return jax.device_put(state, state_shardings(state))

    # Optimizer state mirrors param sharding: optax moment trees (adam mu/nu,
    # momentum trace, ...) have the params' tree STRUCTURE, so substitute the
    # param shardings wholesale at any matching subtree. Shape-based matching
    # would mis-assign when differently-sharded params share a shape (e.g.
    # wq P(None,'fsdp','tp') vs wo P(None,'tp','fsdp'), both (L,d,d)).
    def opt_shardings(opt_state, params):
        param_treedef = jax.tree.structure(params)
        if param_treedef.num_leaves <= 1:
            # Degenerate single-leaf params: every leaf "matches" the
            # structure, so fall back to shape matching (no ambiguity with
            # one param) to avoid sharding adam's scalar count.
            p_shape = getattr(jax.tree.leaves(params)[0], "shape", None)
            p_shard = jax.tree.leaves(param_shardings)[0]
            return jax.tree.map(
                lambda leaf: p_shard
                if getattr(leaf, "shape", None) == p_shape else repl,
                opt_state)

        def is_param_tree(node):
            return jax.tree.structure(node) == param_treedef

        return jax.tree.map(
            lambda sub: param_shardings if is_param_tree(sub) else repl,
            opt_state, is_leaf=is_param_tree)

    def step_fn(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, new_opt = optimizer.update(grads, state.opt_state,
                                            state.params)
        new_params = optax.apply_updates(state.params, updates)
        return TrainState(new_params, new_opt, state.step + 1), loss

    def state_shardings(state: TrainState) -> TrainState:
        """The full TrainState sharding tree for THIS mesh — the abstract
        restore target of the elastic re-mesh path: feed it through
        `checkpoint.abstract_state` and orbax assembles an N-way save
        directly into this mesh's layout (no gather, no host blowup)."""
        return TrainState(
            params=param_shardings,
            opt_state=opt_shardings(state.opt_state, state.params),
            step=repl)

    def compile_for(state: TrainState, sample_batch):
        # One path for 1 and N chips. On a one-device mesh every
        # NamedSharding is the trivial one, and on a v5e chip the
        # annotated step gives the same losses at the same step time as
        # a plain jit (chip run, PR 21), so there is no special case.
        shardings = state_shardings(state)
        batch_shardings = jax.tree.map(lambda _: batch_sharding, sample_batch)
        return jax.jit(
            step_fn,
            in_shardings=(shardings, batch_shardings),
            out_shardings=(shardings, repl),
            donate_argnums=(0,) if donate else ())

    # Attached rather than returned: the 4-tuple is a public surface.
    compile_for.state_shardings = state_shardings
    return init_fn, step_fn, compile_for, param_shardings


def __graphcheck__(gc):
    """graphcheck hook (tools/graphcheck): the sharded train step, lowered
    through the REAL compile_for wrapper on a simulated dp2 x fsdp2 mesh.
    Pins: state donated (params + opt moments aliased into the outputs),
    FSDP params never lower replicated, lowered in-shardings match the
    declared parallel/sharding.py table, and the collective counts of the
    FSDP gather/psum pattern."""

    def build(mesh):
        d, f, b = 256, 512, 32
        param_axes = {"w_in": ("embed", "mlp"), "w_out": ("mlp", "embed")}

        def loss_fn(params, batch):
            h = jnp.tanh(batch["x"] @ params["w_in"])
            y = h @ params["w_out"]
            return jnp.mean((y - batch["y"]) ** 2)

        init_fn, step_fn, compile_for, _ = make_train_step(
            loss_fn, optax.adam(1e-3), mesh, param_axes)
        params = {
            "w_in": jax.ShapeDtypeStruct((d, f), jnp.float32),
            "w_out": jax.ShapeDtypeStruct((f, d), jnp.float32)}
        state = jax.eval_shape(init_fn, params)
        batch = {"x": jax.ShapeDtypeStruct((b, d), jnp.float32),
                 "y": jax.ShapeDtypeStruct((b, d), jnp.float32)}
        specs = declared_param_specs(param_axes)
        return gc.GraphSpec(
            name="train.step", fn=step_fn, args=(state, batch),
            jit_fn=compile_for(state, batch), donate_argnums=(0,),
            declared_in_specs=tuple(
                (f"'{k}'", s) for k, s in sorted(specs.items())),
            expect_sharded=("w_in", "w_out"),
            min_donate_bytes=1 << 16, arg_names=("state", "batch"))

    # tp rides along at size 1: the declared rules map "mlp" -> "tp", so
    # the mesh must carry the axis name even when it is not being tested.
    gc.register("train.step", build,
                meshes=({"dp": 2, "fsdp": 2, "tp": 1},))

    def build_lm(mesh):
        # The decoder's own loss at Qwen2's ratios cut small, but with a
        # logits tensor over models/transformer.LOSS_CHUNK_MIN_BYTES, so
        # the chunked head is in the graph. Its fingerprint is that of the
        # vocabulary-parallel head and of the lookup in the table's slices
        # (`_embed_rows`). The compiled step holds 20 all-gathers (a
        # layer's seven weights in the forward and in the backward scan's
        # body 14, the tokens, targets and cotangent of a loss chunk 2 + 3,
        # the lookup's token ids 1: bytes, where the whole table was), 6
        # all-reduces (max, sum and target logit of a chunk 2 + 2, a
        # layer's gradients in the backward scan's body 1 (ROADMAP S11
        # (2)), the final norm's and the loss 1), 1 reduce-scatter (a
        # chunk's dx) and 4 all-to-alls (the head's slice each way, the
        # lookup's rows and their cotangent); no gather or reduction
        # carries the vocabulary (tests/test_train_loss_sharding.py reads
        # the shapes). The fingerprint's counter does not see a collective
        # of a tuple type and records 20 / 3 / 1, the same as with the
        # one-hot product: what a silent fall-back to that product moves
        # is `flops` (46.5 -> 115.7 G) and `bytes` (338 -> 867 M).
        from ray_tpu.models import (ModelConfig, init_params, loss_fn,
                                    param_logical_axes)
        cfg = ModelConfig(vocab=65536, d_model=128, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=256, dtype="bfloat16",
                          remat=True, tie_embeddings=False,
                          attn_impl="reference")
        param_axes = param_logical_axes(cfg)
        init_fn, step_fn, compile_for, _ = make_train_step(
            lambda p, b: loss_fn(p, b, cfg, mesh), optax.adamw(1e-4), mesh,
            param_axes)
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        state = jax.eval_shape(init_fn, params)
        batch = {"tokens": jax.ShapeDtypeStruct((4, 2049), jnp.int32)}
        specs = declared_param_specs(param_axes)
        return gc.GraphSpec(
            name="train.lm_step", fn=step_fn, args=(state, batch),
            jit_fn=compile_for(state, batch), donate_argnums=(0,),
            declared_in_specs=(("'lm_head'", specs["lm_head"]),
                               ("'embed'", specs["embed"])),
            expect_sharded=("lm_head", "embed", "wq", "wd"),
            min_donate_bytes=1 << 16, arg_names=("state", "batch"))

    gc.register("train.lm_step", build_lm,
                meshes=({"dp": 1, "fsdp": 4, "tp": 1},))
