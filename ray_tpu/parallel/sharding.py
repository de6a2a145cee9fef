"""Logical-axis sharding rules → PartitionSpecs (GSPMD lowering).

The scaling-book recipe: annotate arrays with *logical* axis names
("batch", "seq", "embed", "mlp", "heads", "vocab", "expert", ...), map those
to mesh axes with a rules table, and let GSPMD insert collectives. FSDP is
just "embed→fsdp on params + gather before use"; TP is "mlp/heads→tp";
sequence parallelism is "seq→sp".

Two products are NOT left to GSPMD: the loss head and the embedding
lookup. The table below is where their layout is DECLARED (`lm_head`
[embed, vocab] → P("fsdp", "tp"), `embed` and a tied table its transpose),
and the optimizer state, the checkpoints, graphcheck and the serving
engine all read that one declaration. But the batch is sharded over fsdp
too, so `bsd,dv->bsv` wants every token or the whole matrix on a chip, and
"gather before use" moves the matrix — the largest in the model, once a
rematerialised loss chunk, and its fp32 gradient back; the lookup as a
one-hot product gathers the table and all-reduces its gradient the same
way. `models/transformer.loss_fn` reads `ShardingRules.default()` and
`data_axes(mesh)` and, where one axis shards both, turns the chip's
[d/fsdp, V] slice into a [d, V/fsdp] one by one all-to-all a step and
moves the TOKENS between chips instead (`_xent_vocab_parallel`);
`hidden_states` reads the group's tokens from the chip's own [V, d/fsdp]
slice and exchanges the rows (`_embed_rows`). The declared layout stays
as it is so that nothing else follows: a layout declared by vocabulary
would save the head's all-to-all (0.2 GB out of a chip at Qwen2-7B's
widths) and would refuse every vocabulary that fsdp × tp does not divide,
at `device_put`, where the table knows no shapes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class ShardingRules:
    """logical name -> mesh axis (or None = replicated)."""

    rules: dict[str, str | tuple[str, ...] | None]

    @classmethod
    def default(cls) -> "ShardingRules":
        return cls({
            # activations
            "batch": ("dp", "fsdp"),
            "seq": "sp",
            "embed_act": None,
            # params
            "embed": "fsdp",       # ZeRO-3: shard the "long" param axis
            "mlp": "tp",
            "heads": "tp",
            "kv_heads": "tp",
            "head_dim": None,
            "vocab": "tp",
            "expert": "ep",
            "stage": "pp",
        })

    def spec(self, logical_axes: tuple[str | None, ...]) -> P:
        out = []
        used: set[str] = set()
        for name in logical_axes:
            axis = None if name is None else self.rules.get(name)
            if isinstance(axis, tuple):
                axis = tuple(a for a in axis if a not in used)
                used.update(axis)
                out.append(axis if axis else None)
            else:
                if axis in used:
                    axis = None
                if axis is not None:
                    used.add(axis)
                out.append(axis)
        return P(*out)


def logical_to_physical(rules: ShardingRules, logical_tree):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def declared_param_specs(param_axes, rules: ShardingRules | None = None):
    """THE declared param shardings: the single table both the jit sites
    (train/step.py in_shardings) and the graphcheck cross-check read.
    graphcheck compares the shardings a hot graph actually LOWERED with
    against this declaration, so an edit that drops in_shardings from a
    jit site — or a rules edit that silently de-shards a param — fails
    the static gate instead of surfacing as an MFU cliff on hardware."""
    return logical_to_physical(rules or ShardingRules.default(),
                               param_axes)


def shard_params(params, logical_tree, rules: ShardingRules, mesh: Mesh):
    """Device-put a param pytree with its sharding (for init / restore)."""
    specs = logical_to_physical(rules, logical_tree)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def reshard(tree, shardings):
    """Device-put every leaf onto its (new-mesh) sharding — the elastic
    restore step: state saved on an N-device mesh lands on an M-device
    mesh (jax moves shards through host memory where layouts differ).
    `shardings` is a matching pytree of NamedShardings, e.g. the
    state_shardings make_train_step derives for the NEW mesh."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


def with_sharding(x, mesh: Mesh, spec: P):
    """Sharding constraint inside jit (GSPMD hint)."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map_compat(fn, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (the wrappers here
    all psum/permute explicitly). Every sp/pp entry point routes through
    this, so that choice is made in one place."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that shard the batch dimension of activations (the
    activation-layout half of the "batch" rule): the axes present on this
    mesh, in rule order, so constraints built from it agree with
    batch_spec = P(("dp", "fsdp")) on any mesh shape."""
    return tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)


def __graphcheck__(gc):
    """graphcheck hook (tools/graphcheck): the canonical activation
    batch-constraint graph. Pins that `activation_batch_sharded` lowers
    to a pure layout constraint on a dp x fsdp mesh — zero collectives,
    zero callbacks — i.e. the embedding-seam constraint stays a hint,
    never a resharding round trip."""

    def build(mesh):
        from jax.sharding import NamedSharding

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        batch_spec = P(("dp", "fsdp"))

        def fn(a):
            return activation_batch_sharded(a, mesh) * 2.0

        return gc.GraphSpec(
            name="parallel.batch_constraint", fn=fn, args=(x,),
            in_shardings=(NamedSharding(mesh, batch_spec),),
            declared_in_specs=(("acts", batch_spec),),
            expect_sharded=("acts",), arg_names=("acts",))

    gc.register("parallel.batch_constraint", build,
                meshes=({"dp": 2, "fsdp": 2},))


def activation_batch_sharded(x, mesh: Mesh):
    """Constrain a [batch, ...] activation to the canonical layout: batch
    over the data axes, everything else replicated. Used at layout seams
    where the partitioner would otherwise propagate a PARAM sharding into
    the activation: the embedding lookup's FALLBACK, the one-hot product
    (`models/transformer._embed`), whose natural output inherits the
    table's embed sharding on a transposed device order, which XLA can
    only leave via involuntary full rematerialization. The lookup in the
    table's slices (`_embed_rows`) states its output's layout itself."""
    axes = data_axes(mesh)
    spec = P(axes if axes else None, *([None] * (x.ndim - 1)))
    return with_sharding(x, mesh, spec)
