"""The train step's two vocabulary-wide matrices under an fsdp axis: the
loss head vocabulary-parallel, the embedding looked up where the table's
slice lies.

Two readings, both on the virtual CPU mesh (conftest forces 8 devices):

(1) the PROGRAM's collectives — the real `compile_for(...)` step over
`models.loss_fn`, lowered at Qwen2's ratios cut small but with a logits
tensor over the chunking threshold, must hold no gather or reduction whose
operand or result carries the whole vocabulary, in the entry or in any loop
body, the embedding's included. One collective is what it is and is
counted, not excused: where dp > 1 the table is replicated over dp, and the
chip's [vocab, d/fsdp] slice of its gradient is summed over dp once a step.
The GSPMD products the step used to leave the head and the lookup to (still
the fallbacks) fail the same reading: the head gathers the [d, vocab] matrix
twice and reduces it inside the backward loop, the lookup gathers the whole
table and all-reduces its whole gradient over every chip.

(2) parity — loss and every gradient leaf of the sharded paths against the
plain `jnp.take` and `_xent` program on one device, float32 to 1e-5 and
bf16 at the train cell's LOSS_TOL; the lookup alone bit for bit, its
gradient against the float32 sum rounded once.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (ModelConfig, configs, init_params, loss_fn,
                            transformer)
from ray_tpu.parallel import MeshConfig, make_mesh

# perfbench/harness/train_cell.py's LOSS_TOL (bf16 losses near ln(vocab))
LOSS_TOL_BF16 = 1e-2

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?\S+ = (.*?) (all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\((.*)$")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
# replica_groups={{0,2},{1,3}} or the iota form [groups,size]<=[...]
_GROUP = re.compile(r"replica_groups=(?:\{\{([\d,]*)\}|\[\d+,(\d+)\])")


def _vocab_collectives(hlo: str, vocab: int):
    """(computation, kind, shapes, chips a group) of every collective with
    a dimension of the whole vocabulary among its results' or operands'
    shapes."""
    out, comp = [], None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        m = _COLLECTIVE.match(line)
        if not m:
            continue
        typed = m.group(1) + " " + m.group(3).split(", channel_id")[0]
        shapes = [tuple(int(d) for d in s.split(",") if d)
                  for s in _SHAPE.findall(typed)]
        if any(vocab in s for s in shapes):
            listed, size = _GROUP.search(line).groups()
            out.append((comp, m.group(2), shapes,
                        int(size) if size else listed.count(",") + 1))
    return out


def _lowered_step(mesh_axes):
    """The graph graphcheck fingerprints as `train.lm_step` (train/step.py's
    hook: the real `compile_for` step over `models.loss_fn` at Qwen2-7B's
    ratios cut small, 4 x 2048 tokens over a vocabulary of 65536 — a 2 GiB
    logits tensor, over LOSS_CHUNK_MIN_BYTES, so the head chunks), compiled
    for `mesh_axes`: (partitioned HLO text, vocabulary)."""
    from ray_tpu.train import step
    from tools import graphcheck
    from tools.graphcheck import lowering
    step.__graphcheck__(graphcheck)
    spec = graphcheck._REGISTRY["train.lm_step"].build(
        lowering.make_mesh(mesh_axes))
    vocab = spec.args[0].params["embed"].shape[0]
    return spec.jit_fn.lower(*spec.args).compile().as_text(), vocab


@pytest.mark.parametrize("mesh_axes", [{"dp": 1, "fsdp": 4, "tp": 1},
                                       {"dp": 2, "fsdp": 2, "tp": 1}],
                         ids=["fsdp4", "dp2_fsdp2"])
def test_step_moves_no_vocab_wide_matrix(mesh_axes):
    hlo, vocab = _lowered_step(mesh_axes)
    found = _vocab_collectives(hlo, vocab)
    # the data-parallel sum of the table gradient's [vocab, d/fsdp] slice
    # (`_embed_rows`): one all-reduce a step in the entry, over the dp
    # chips that hold the same slice and no others (the head's [d,
    # vocab/fsdp] slice may ride in the same op); none where dp is 1
    dp, d = mesh_axes["dp"], 128
    dp_sum = [f for f in found
              if f[1] == "all-reduce" and "region" not in f[0] and dp > 1
              and f[3] == dp and all(
                  s == (vocab, d // mesh_axes["fsdp"])
                  for s in f[2] if vocab in s)]
    assert len(dp_sum) == (dp > 1), found
    assert found == dp_sum, found
    assert "embed_lookup" in hlo


@pytest.mark.parametrize("part", ["head", "lookup"])
def test_gspmd_fails_the_same_reading(part, monkeypatch):
    """The reading has teeth: with both products left to GSPMD (the
    fallbacks, and the programs before the vocabulary-parallel head and
    `_embed_rows`) the backward loop's body gathers the whole [d, vocab]
    head and reduces its whole gradient, and the entry gathers the whole
    [vocab, d] table and all-reduces its whole gradient over every chip."""
    monkeypatch.setattr(transformer, "_head_shard_axes",
                        lambda *a: None)
    found = _vocab_collectives(
        *_lowered_step({"dp": 1, "fsdp": 4, "tp": 1}))
    if part == "head":
        moved = [f for f in found if "region" in f[0]]
    else:
        moved = [f for f in found if "region" not in f[0]
                 and {(65536, 128), (128, 65536)} & set(f[2]) and f[3] == 4]
    assert {f[1] for f in moved} >= {"all-gather", "all-reduce"}, found


# ---- parity ---------------------------------------------------------------

def _batch(kind, vocab, b=8, s=64, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, vocab)
    if kind == "repeated":      # one id fills half the positions
        toks = jnp.where(jax.random.bernoulli(
            jax.random.PRNGKey(seed + 2), 0.5, toks.shape), 7, toks)
    if kind == "inputs_targets":
        batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    else:
        batch = {"tokens": toks}
    if kind == "mask":
        batch["mask"] = (jax.random.uniform(
            jax.random.PRNGKey(seed + 1), (b, s)) > 0.3).astype(jnp.float32)
    return batch


def _micro(**kw):
    base = dict(vocab=256, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
                d_ff=64, dtype="float32", attn_impl="reference")
    base.update(kw)
    return ModelConfig(**base)


PARITY = {
    # name: (config, mesh, batch kind, loss_chunk, head vocabulary-
    #        parallel?, tokens looked up in the table's slices?)
    "untied_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "tokens",
                     16, True, True),
    "tied_fsdp4": (_micro(tie_embeddings=True), {"fsdp": 4}, "tokens", 16,
                   True, True),
    "mask_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "mask", 16,
                   True, True),
    "inputs_targets_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4},
                             "inputs_targets", 16, True, True),
    # one chunk would hold the sequence: the plain `_xent` program (the
    # lookup does not care how the head chunks)
    "unchunked_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "tokens",
                        64, False, True),
    # 250 = 2 * 5^3 does not divide over 4 chips: the head and the lookup
    # FALL BACK to the GSPMD products (nothing is padded), and the table's
    # vocabulary has no tp to divide over
    "vocab_not_divisible_fsdp4": (_micro(tie_embeddings=False, vocab=250),
                                  {"fsdp": 4}, "tokens", 16, False, False),
    # 44 columns do not divide over 8 chips
    "width_not_divisible_fsdp8": (_micro(tie_embeddings=True, d_model=44,
                                         n_heads=2, n_kv_heads=1),
                                  {"fsdp": 8}, "tokens", 16, False, False),
    # a sequence axis of several chips: the lookup's layout does not know
    # it and falls back, the head is what it was
    "seq_axis_fsdp2_sp2": (_micro(tie_embeddings=False),
                           {"fsdp": 2, "sp": 2}, "tokens", 16, True, False),
    "untied_dp2_fsdp2": (_micro(tie_embeddings=False), {"dp": 2, "fsdp": 2},
                         "mask", 16, True, True),
    "tied_dp2_fsdp2": (_micro(tie_embeddings=True), {"dp": 2, "fsdp": 2},
                       "tokens", 16, True, True),
    "untied_dp2_fsdp2_tp2": (_micro(tie_embeddings=False),
                             {"dp": 2, "fsdp": 2, "tp": 2}, "mask", 16, True,
                             True),
    "tied_dp2_fsdp2_tp2": (_micro(tie_embeddings=True),
                           {"dp": 2, "fsdp": 2, "tp": 2}, "tokens", 16, True,
                           True),
    # no axis shards both the batch and the table's columns
    "tied_tp4": (_micro(tie_embeddings=True), {"fsdp": 1, "tp": 4},
                 "tokens", 16, False, False),
    "moe_fsdp4": (configs.tiny_moe(vocab=256, d_model=32, d_ff=64,
                                   n_layers=1, attn_impl="reference",
                                   tie_embeddings=False),
                  {"fsdp": 4}, "tokens", 16, True, True),
    # half the positions hold one id: 260 summands in one row of the
    # table's gradient
    "repeated_untied_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4},
                              "repeated", 16, True, True),
    "repeated_tied_dp2_fsdp2": (_micro(tie_embeddings=True),
                                {"dp": 2, "fsdp": 2}, "repeated", 16, True,
                                True),
    "repeated_tied_dp2_fsdp2_tp2": (_micro(tie_embeddings=True),
                                    {"dp": 2, "fsdp": 2, "tp": 2},
                                    "repeated", 16, True, True),
    "bf16_fsdp4": (_micro(tie_embeddings=False, dtype="bfloat16"),
                   {"fsdp": 4}, "mask", 16, True, True),
    "bf16_tied_dp2_fsdp2_tp2": (_micro(tie_embeddings=True, dtype="bfloat16"),
                                {"dp": 2, "fsdp": 2, "tp": 2}, "tokens", 16,
                                True, True),
    "bf16_untied_dp2_fsdp2": (_micro(tie_embeddings=False, dtype="bfloat16"),
                              {"dp": 2, "fsdp": 2}, "tokens", 16, True, True),
    "bf16_repeated_tied_fsdp4": (_micro(tie_embeddings=True,
                                        dtype="bfloat16"),
                                 {"fsdp": 4}, "repeated", 16, True, True),
}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_vocab_parallel_head_matches_one_device(case, monkeypatch):
    cfg, mesh_kw, kind, chunk, sharded, looked_up = PARITY[case]
    # micro sizes: let the head chunk below the production threshold
    monkeypatch.setattr(transformer, "LOSS_CHUNK_MIN_BYTES", 0)
    lookups, embed_rows = [], transformer._embed_rows
    monkeypatch.setattr(
        transformer, "_embed_rows",
        lambda *a: lookups.append(a[2]) or embed_rows(*a))
    n = int(np.prod(list(mesh_kw.values())))
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:n])
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(kind, cfg.vocab)
    b, s = (batch.get("inputs", batch.get("tokens"))).shape
    s = s if "inputs" in batch else s - 1
    took = (s > chunk and transformer._head_shard_axes(
        mesh, (cfg.d_model, cfg.vocab), b) is not None)
    assert took == sharded

    def grad_of(m):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, m, chunk)))(params)

    (l1, g1), (ln, gn) = grad_of(None), grad_of(mesh)
    assert lookups == [mesh] * looked_up
    if cfg.dtype == "float32":
        loss_tol, rel = 1e-5, 1e-5
    else:
        loss_tol, rel = LOSS_TOL_BF16, 5e-2
    assert abs(float(l1) - float(ln)) <= loss_tol, (float(l1), float(ln))
    flat1 = jax.tree_util.tree_leaves_with_path(g1)
    flatn = jax.tree.leaves(gn)
    assert len(flat1) == len(flatn)
    for (path, a), b_ in zip(flat1, flatn):
        a = np.asarray(a, np.float32)
        b_ = np.asarray(b_, np.float32)
        assert a.shape == b_.shape
        scale = float(np.max(np.abs(a))) or 1.0
        err = float(np.max(np.abs(a - b_))) / scale
        assert err <= rel, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("mesh_kw", [{"fsdp": 4}, {"dp": 2, "fsdp": 2},
                                     {"dp": 2, "fsdp": 2, "tp": 2}],
                         ids=["fsdp4", "dp2_fsdp2", "dp2_fsdp2_tp2"])
def test_lookup_is_the_rows_and_sums_in_float32(mesh_kw):
    """`_embed` alone in bf16, one id in half of 8 x 256 positions: the
    rows are `jnp.take`'s bit for bit; the table's gradient is the float32
    sum of the cotangent's rows rounded ONCE (within one bf16 step of it:
    the chips add in another order), which a bf16 scatter-add misses by
    far more on the repeated row."""
    n = int(np.prod(list(mesh_kw.values())))
    mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:n])
    vocab, d = 512, 64
    table = jax.random.normal(jax.random.PRNGKey(0), (vocab, d), jnp.bfloat16)
    tokens = _batch("repeated", vocab, s=255)["tokens"]
    ct = jax.random.normal(jax.random.PRNGKey(3), (*tokens.shape, d),
                           jnp.bfloat16)
    assert transformer._head_shard_axes(mesh, (d, vocab), 8) is not None
    rows, vjp = jax.vjp(lambda t: transformer._embed(t, tokens, mesh), table)
    assert rows.dtype == table.dtype
    np.testing.assert_array_equal(
        np.asarray(rows, np.float32),
        np.asarray(jnp.take(table, tokens, axis=0), np.float32))
    (got,) = vjp(ct)
    exact = jnp.zeros((vocab, d), jnp.float32).at[tokens].add(
        ct.astype(jnp.float32))
    in_bf16 = jnp.zeros((vocab, d), jnp.bfloat16).at[tokens].add(ct)
    step = 2.0 ** -8 * np.maximum(np.abs(np.asarray(exact)), 1e-3)

    def steps_off(a):
        return float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(exact)) / step))

    assert got.dtype == table.dtype and got.shape == table.shape
    assert steps_off(got) <= 1.0, steps_off(got)
    assert steps_off(in_bf16) > 4.0, steps_off(in_bf16)


def _parent_loss(params, batch, config, mesh, loss_chunk=512):
    """`loss_fn` as it read before the vocabulary-parallel head (PR 35),
    on the module's own `hidden_states` and `_xent`."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    x = transformer.hidden_states(params, inputs, config, mesh)
    head = (params["embed"].T if config.tie_embeddings
            else params["lm_head"])
    b, s, d = x.shape
    if (s % loss_chunk == 0 and s > loss_chunk
            and 4 * b * s * config.vocab > (1 << 30)):
        nc = s // loss_chunk
        xc = x.reshape(b, nc, loss_chunk, d).transpose(1, 0, 2, 3)
        tc = targets.reshape(b, nc, loss_chunk).transpose(1, 0, 2)
        ll = jax.lax.map(
            jax.checkpoint(
                lambda args: transformer._xent(args[0], head, args[1])),
            (xc, tc))
        ll = ll.transpose(1, 0, 2).reshape(b, s)
    else:
        ll = transformer._xent(x, head, targets)
    return -jnp.mean(ll)


@pytest.mark.parametrize("mesh_kw", [None, {"fsdp": 1}],
                         ids=["no_mesh", "one_device_mesh"])
@pytest.mark.parametrize("seq", [64, 2048], ids=["unchunked", "chunked"])
def test_one_device_program_is_the_parents(mesh_kw, seq):
    """`mesh=None` and a one-device mesh lower the loss and its gradient
    to the program they lowered to before: the same StableHLO text."""
    cfg = _micro(tie_embeddings=False, vocab=65536 if seq == 2048 else 256)
    mesh = mesh_kw and make_mesh(MeshConfig(**mesh_kw),
                                 devices=jax.devices()[:1])
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((4, seq + 1), jnp.int32)}

    def text(fn):
        return jax.jit(jax.value_and_grad(fn)).lower(params, batch).as_text()

    def now(p, b):
        return loss_fn(p, b, cfg, mesh)

    def parent(p, b):
        return _parent_loss(p, b, cfg, mesh)

    strip = re.compile(r"(jit_|@)(now|parent)\b")
    assert strip.sub(r"\1f", text(now)) == strip.sub(r"\1f", text(parent))
