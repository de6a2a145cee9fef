"""The train step's loss head under an fsdp axis: vocabulary-parallel.

Two readings, both on the virtual CPU mesh (conftest forces 8 devices):

(1) the PROGRAM's collectives — the real `compile_for(...)` step over
`models.loss_fn`, lowered at Qwen2's ratios cut small but with a logits
tensor over the chunking threshold, must hold no gather or reduction whose
operand or result carries the whole vocabulary beside the model dimension,
in the entry or in any loop body. The GSPMD product the step used to leave
the head to (still the fallback) fails the same reading: two gathers and a
reduction of the [d, vocab] matrix inside the backward loop.

(2) parity — loss and every gradient leaf of the vocabulary-parallel path
against the plain `_xent` path on one device, float32 to 1e-5 and bf16 at
the train cell's LOSS_TOL.
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
# the one-hot embedding lookup and its transpose: hidden_states gathers the
# table for `bsv,vd->bsd` and all-reduces the table's gradient (ROADMAP
# S11, not the head's traffic)
_EMBED = "bsv,vd->bsd"


def _vocab_collectives(hlo: str, vocab: int):
    """(computation, kind, shapes) of every collective with a dimension of
    the whole vocabulary among its results' or operands' shapes, the
    embedding lookup's own aside."""
    out, comp = [], None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        m = _COLLECTIVE.match(line)
        if not m or _EMBED in line:
            continue
        typed = m.group(1) + " " + m.group(3).split(", channel_id")[0]
        shapes = [tuple(int(d) for d in s.split(",") if d)
                  for s in _SHAPE.findall(typed)]
        if any(vocab in s for s in shapes):
            out.append((comp, m.group(2), shapes))
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
    found = _vocab_collectives(*_lowered_step(mesh_axes))
    assert not found, found


def test_gspmd_head_fails_the_same_reading(monkeypatch):
    """The reading has teeth: with the head left to GSPMD (the fallback,
    and the program before the vocabulary-parallel head) the backward
    loop's body gathers the whole [d, vocab] head and reduces its whole
    gradient."""
    monkeypatch.setattr(transformer, "_head_shard_axes",
                        lambda *a: None)
    found = _vocab_collectives(
        *_lowered_step({"dp": 1, "fsdp": 4, "tp": 1}))
    in_loops = [f for f in found if "region" in f[0]]
    assert {k for _, k, _ in in_loops} >= {"all-gather", "all-reduce"}, found


# ---- parity ---------------------------------------------------------------

def _batch(kind, vocab, b=8, s=64, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, vocab)
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
    # name: (config, mesh, batch kind, loss_chunk, vocabulary-parallel?)
    "untied_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "tokens",
                     16, True),
    "tied_fsdp4": (_micro(tie_embeddings=True), {"fsdp": 4}, "tokens", 16,
                   True),
    "mask_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "mask", 16,
                   True),
    "inputs_targets_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4},
                             "inputs_targets", 16, True),
    # one chunk would hold the sequence: the plain `_xent` program
    "unchunked_fsdp4": (_micro(tie_embeddings=False), {"fsdp": 4}, "tokens",
                        64, False),
    # 250 = 2 * 5^3 does not divide over 4 chips: the head FALLS BACK to
    # the GSPMD product (nothing is padded), and the table's vocabulary
    # has no tp to divide over
    "vocab_not_divisible_fsdp4": (_micro(tie_embeddings=False, vocab=250),
                                  {"fsdp": 4}, "tokens", 16, False),
    "untied_dp2_fsdp2": (_micro(tie_embeddings=False), {"dp": 2, "fsdp": 2},
                         "mask", 16, True),
    "tied_dp2_fsdp2": (_micro(tie_embeddings=True), {"dp": 2, "fsdp": 2},
                       "tokens", 16, True),
    "untied_dp2_fsdp2_tp2": (_micro(tie_embeddings=False),
                             {"dp": 2, "fsdp": 2, "tp": 2}, "mask", 16, True),
    "tied_dp2_fsdp2_tp2": (_micro(tie_embeddings=True),
                           {"dp": 2, "fsdp": 2, "tp": 2}, "tokens", 16, True),
    "moe_fsdp4": (configs.tiny_moe(vocab=256, d_model=32, d_ff=64,
                                   n_layers=1, attn_impl="reference",
                                   tie_embeddings=False),
                  {"fsdp": 4}, "tokens", 16, True),
    "bf16_fsdp4": (_micro(tie_embeddings=False, dtype="bfloat16"),
                   {"fsdp": 4}, "mask", 16, True),
    "bf16_tied_dp2_fsdp2_tp2": (_micro(tie_embeddings=True, dtype="bfloat16"),
                                {"dp": 2, "fsdp": 2, "tp": 2}, "tokens", 16,
                                True),
}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_vocab_parallel_head_matches_one_device(case, monkeypatch):
    cfg, mesh_kw, kind, chunk, sharded = PARITY[case]
    # micro sizes: let the head chunk below the production threshold
    monkeypatch.setattr(transformer, "LOSS_CHUNK_MIN_BYTES", 0)
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
