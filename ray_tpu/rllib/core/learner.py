"""Learner / LearnerGroup: jit-compiled gradient updates on the accelerator.

Parity: reference `rllib/core/learner/learner.py` + torch-DDP
`core/learner/torch/torch_learner.py` and `learner_group.py:72`.
TPU-native redesign: an update is ONE jit-compiled pure function
(loss+grad+optax apply) — data-parallel scaling is a `jax.sharding` batch
sharding over the learner's device mesh (XLA inserts the psum over ICI),
not a DDP wrapper. Multi-host learner groups are learner *actors* whose
gradients ride the host collective layer (`ray_tpu.util.collective`),
mirroring the reference's NCCL group between learner workers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu


class Learner:
    """Owns params + optimizer state; `update(batch)` is jitted once.

    `loss_fn(params, batch, **cfg)` -> (loss, aux_dict) is supplied by the
    algorithm; the learner is algorithm-agnostic (parity: Learner.update
    driving compute_loss_for_module)."""

    def __init__(self, module, loss_fn, *, lr=3e-4, seed=0,
                 grad_clip: float | None = None, optimizer=None,
                 loss_cfg: dict | None = None, mesh=None, fused=True):
        self.module = module
        self.params = module.init(jax.random.PRNGKey(seed))
        tx = [optax.clip_by_global_norm(grad_clip)] if grad_clip else []
        tx.append(optimizer if optimizer is not None else optax.adam(lr))
        self.tx = optax.chain(*tx)
        self.opt_state = self.tx.init(self.params)
        self.mesh = mesh
        loss_cfg = dict(loss_cfg or {})
        self._loss_fn = loss_fn
        self._loss_cfg = loss_cfg
        self._fused_epochs: dict = {}  # shape signature -> compiled sweep
        if not fused:
            # Subclasses that split grad/allreduce/apply skip the fused jit
            # (it would just hold a dead second copy of the pipeline).
            self._update = None
            self._step_fn = None
            return

        def _step(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, **loss_cfg)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        self._step_fn = _step  # shared by the fused multi-epoch sweep
        _update = _step

        # params/opt_state are threaded through the step and immediately
        # replaced by the caller, so donate them: without donation XLA
        # holds BOTH generations of every param + both adam moments live
        # across the update (graphcheck donation-missing finding; 3x the
        # steady-state footprint at scale). tx.init here is EAGER, so the
        # moment buffers are real distinct allocations — the zero-buffer
        # double-donation hazard that keeps ondevice.py's fused iter
        # un-donated does not apply.
        if mesh is not None:
            # Batch rides the "dp" mesh axis; params replicated. XLA lowers
            # the mean-gradient to a psum over ICI (scaling-book recipe).
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            data = NamedSharding(mesh, P("dp"))
            self._update = jax.jit(
                _update,
                in_shardings=(rep, rep, data),
                out_shardings=(rep, rep, rep, rep),
                donate_argnums=(0, 1))
        else:
            self._update = jax.jit(_update, donate_argnums=(0, 1))

    @staticmethod
    def _finalize_metrics(loss, aux) -> dict:
        # ONE device fetch for every metric — per-scalar float() costs a
        # blocking host sync each.
        loss, aux = jax.device_get((loss, aux))
        out = {"total_loss": float(loss)}
        out.update({k: float(v) for k, v in aux.items()})
        return out

    def update(self, batch: dict) -> dict:
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        self.params, self.opt_state, loss, aux = self._update(
            self.params, self.opt_state, batch)
        return self._finalize_metrics(loss, aux)

    def update_epochs(self, batch: dict, *, num_epochs: int,
                      minibatch_size: int, seed: int = 0) -> dict | None:
        """The whole epochs x shuffled-minibatches sweep as ONE jit call
        (lax.scan over epochs, nested scan over minibatches). One
        dispatch + one metrics fetch per training step instead of one per
        minibatch — the difference between an accelerator-bound and a
        dispatch-latency-bound PPO (SURVEY: no data-dependent Python
        control flow inside the hot loop).

        Returns None (caller falls back to the per-minibatch loop) when
        the sweep can't express the config faithfully: a mesh-sharded
        learner (the fused jit carries no shardings) or a batch that
        doesn't tile into minibatches (scan needs uniform sizes; silently
        dropping the remainder would diverge from the fallback)."""
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        n = next(iter(batch.values())).shape[0]
        if self.mesh is not None or n % minibatch_size:
            return None
        nmb = n // minibatch_size
        mb = minibatch_size
        key_shape = (n, nmb, mb, num_epochs)
        fused = self._fused_epochs.get(key_shape)
        if fused is None:
            fused = self._build_fused_epochs(n, nmb, mb, num_epochs)
            self._fused_epochs[key_shape] = fused
        self.params, self.opt_state, loss, aux = fused(
            self.params, self.opt_state, batch,
            jax.random.PRNGKey(seed))
        return self._finalize_metrics(loss, aux)

    def _build_fused_epochs(self, n, nmb, mb, num_epochs):
        step_fn = self._step_fn

        def one_minibatch(carry, idx):
            params, opt_state, batch = carry
            sl = jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0),
                                        batch)
            params, opt_state, loss, aux = step_fn(params, opt_state, sl)
            return (params, opt_state, batch), (loss, aux)

        def one_epoch(carry, key):
            perm = jax.random.permutation(key, n)[:nmb * mb]
            idxs = perm.reshape(nmb, mb)
            carry, (losses, auxs) = jax.lax.scan(one_minibatch, carry,
                                                 idxs)
            return carry, (losses, auxs)

        def fused(params, opt_state, batch, key):
            keys = jax.random.split(key, num_epochs)
            (params, opt_state, _b), (losses, auxs) = jax.lax.scan(
                one_epoch, (params, opt_state, batch), keys)
            last_aux = jax.tree_util.tree_map(lambda a: a[-1, -1], auxs)
            return params, opt_state, losses[-1, -1], last_aux

        # Same donation rationale as _update (eager tx.init, distinct
        # moment buffers): the sweep threads params/opt_state.
        return jax.jit(fused, donate_argnums=(0, 1))

    def get_weights(self):
        return jax.device_get(self.params)

    def set_weights(self, params):
        self.params = jax.device_put(params)


class _CollectiveLearner(Learner):
    """Learner actor for multi-learner groups: averages gradients across the
    group with a host-collective allreduce before applying (parity: the DDP
    allreduce between torch learner workers)."""

    def __init__(self, rank: int, world: int, group: str, module, loss_fn,
                 **kw):
        from ray_tpu.util import collective
        self.rank, self.world, self.group = rank, world, group
        collective.init_collective_group(world, rank, group_name=group)
        super().__init__(module, loss_fn, fused=False, **kw)
        # Split update: grads computed jitted, allreduced host-side, applied.
        loss_cfg = dict(kw.get("loss_cfg") or {})
        self._grad_fn = jax.jit(
            lambda p, b: jax.value_and_grad(loss_fn, has_aux=True)(
                p, b, **loss_cfg))
        # params/opt_state threaded and replaced by the caller: donate
        # (same rationale as Learner._update).
        self._apply_fn = jax.jit(
            lambda p, s, g: self._apply(p, s, g), donate_argnums=(0, 1))

    def _apply(self, params, opt_state, grads):
        updates, opt_state = self.tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def update(self, batch: dict) -> dict:
        from ray_tpu.util import collective
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        (loss, aux), grads = self._grad_fn(self.params, batch)
        flat, tree = jax.tree_util.tree_flatten(grads)
        # Use the RETURN value: np views of jax arrays are read-only, so the
        # in-place writeback inside allreduce is skipped for them.
        host = [collective.allreduce(np.asarray(g), group_name=self.group)
                / self.world
                for g in flat]
        grads = jax.tree_util.tree_unflatten(tree, host)
        self.params, self.opt_state = self._apply_fn(
            self.params, self.opt_state, grads)
        out = {"total_loss": float(loss)}
        out.update({k: float(v) for k, v in aux.items()})
        return out

    def ping(self):
        return "ok"


class LearnerGroup:
    """num_learners == 0: one in-process learner (default; the mesh gives it
    every local device). num_learners > 0: learner actors + collective
    allreduce (multi-host shape, parity: learner_group.py:72)."""

    def __init__(self, module, loss_fn, *, num_learners: int = 0,
                 config: dict | None = None, mesh=None):
        cfg = dict(config or {})
        if num_learners == 0:
            self.local = Learner(module, loss_fn, mesh=mesh, **cfg)
            self.remotes = []
        else:
            self.local = None
            group = f"learners-{id(self)}"
            cls = ray_tpu.remote(num_cpus=1)(_CollectiveLearner)
            self.remotes = [
                cls.remote(i, num_learners, group, module, loss_fn, **cfg)
                for i in range(num_learners)]
            ray_tpu.get([r.ping.remote() for r in self.remotes], timeout=120)

    def update_epochs(self, batch: dict, *, num_epochs: int,
                      minibatch_size: int, seed: int = 0) -> dict | None:
        """Fused multi-epoch sweep on the local learner (one accelerator
        dispatch); None for actor groups — callers fall back to the
        per-minibatch loop there."""
        if self.local is not None:
            return self.local.update_epochs(
                batch, num_epochs=num_epochs,
                minibatch_size=minibatch_size, seed=seed)
        return None

    def update(self, batch: dict) -> dict:
        if self.local is not None:
            return self.local.update(batch)
        n = len(self.remotes)
        B = next(iter(batch.values())).shape[0]
        if B < n:
            # Every learner must participate in the allreduce; an empty
            # shard would feed NaN gradients into the whole group.
            raise ValueError(
                f"batch of {B} rows cannot be sharded across {n} learners")
        bounds = np.linspace(0, B, n + 1, dtype=int)
        refs = []
        for i, r in enumerate(self.remotes):
            sl = {k: v[bounds[i]:bounds[i + 1]] for k, v in batch.items()}
            refs.append(r.update.remote(sl))
        results = ray_tpu.get(refs, timeout=300)
        return {k: float(np.mean([m[k] for m in results]))
                for k in results[0]}

    def get_weights(self):
        if self.local is not None:
            return self.local.get_weights()
        return ray_tpu.get(self.remotes[0].get_weights.remote(), timeout=120)

    def stop(self):
        for r in self.remotes:
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass


def __graphcheck__(gc):
    """graphcheck hook (tools/graphcheck): the PPO learner update through
    the REAL Learner jit (donation included), at a tiny module. Pins:
    params + adam moments donated (the graphcheck finding that motivated
    donate_argnums above), no host callbacks in the update, and the
    flops/bytes fingerprint of loss+grad+apply."""

    def build(mesh):
        import functools  # noqa: F401 — loss_cfg carries the statics
        from ray_tpu.rllib.algorithms.ppo import ppo_loss
        from ray_tpu.rllib.core.rl_module import ActorCriticModule

        module = ActorCriticModule(obs_dim=8, num_actions=4)
        lr = Learner(module, ppo_loss,
                     loss_cfg=dict(module=module, clip=0.2, vf_coef=0.5,
                                   ent_coef=0.01))
        n = 64
        batch = {
            "obs": jax.ShapeDtypeStruct((n, 8), jnp.float32),
            "actions": jax.ShapeDtypeStruct((n,), jnp.int32),
            "logp": jax.ShapeDtypeStruct((n,), jnp.float32),
            "advantages": jax.ShapeDtypeStruct((n,), jnp.float32),
            "returns": jax.ShapeDtypeStruct((n,), jnp.float32),
        }
        params = jax.eval_shape(module.init, jax.random.PRNGKey(0))
        opt_state = jax.eval_shape(lr.tx.init, params)
        return gc.GraphSpec(
            name="rl.ppo_learner", fn=lr._step_fn,
            args=(params, opt_state, batch), jit_fn=lr._update,
            donate_argnums=(0, 1), min_donate_bytes=8192,
            arg_names=("params", "opt_state", "batch"))

    gc.register("rl.ppo_learner", build)
