"""PPO — clipped-surrogate policy optimization.

Parity: reference `rllib/algorithms/ppo/ppo.py:388` (new-stack
training_step: synchronous_parallel_sample -> GAE -> LearnerGroup.update
with minibatch epochs). TPU-native: GAE is a jitted `lax.scan` over the
time axis and the update is one jit-compiled loss+grad+apply; there is no
torch/tf policy twin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.rllib.algorithms.algorithm import Algorithm
from ray_tpu.rllib.algorithms.algorithm_config import AlgorithmConfig


class PPOConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__(algo_class=PPO)
        self.clip_param = 0.2
        self.vf_loss_coeff = 0.5
        self.entropy_coeff = 0.01
        self.lambda_ = 0.95

    def training(self, *, clip_param=None, vf_loss_coeff=None,
                 entropy_coeff=None, lambda_=None, **kw):
        super().training(**kw)
        if clip_param is not None:
            self.clip_param = clip_param
        if vf_loss_coeff is not None:
            self.vf_loss_coeff = vf_loss_coeff
        if entropy_coeff is not None:
            self.entropy_coeff = entropy_coeff
        if lambda_ is not None:
            self.lambda_ = lambda_
        return self


@functools.partial(jax.jit, static_argnames=("gamma", "lam"))
def _gae(rewards, values, dones, last_values, *, gamma, lam,
         bootstrap=None):
    """Generalized advantage estimation over [T, B] via lax.scan
    (time-reversed; no Python loop under jit).

    `dones` marks episode boundaries (terminated OR truncated): the lambda
    chain always cuts there. `bootstrap`, when given, holds the value of the
    post-step state at boundary rows — zero for true terminations, V(s_next)
    for truncations — so truncated episodes are bootstrapped instead of
    treated as if the return were zero."""
    if bootstrap is None:
        bootstrap = jnp.zeros_like(rewards)

    def step(carry, xs):
        r, v, d, v_next, bv = xs
        v_eff = (1.0 - d) * v_next + d * bv
        delta = r + gamma * v_eff - v
        adv = delta + gamma * lam * (1.0 - d) * carry
        return adv, adv

    v_next = jnp.concatenate([values[1:], last_values[None]], axis=0)
    _, advs = jax.lax.scan(
        step, jnp.zeros_like(last_values),
        (rewards, values, dones, v_next, bootstrap), reverse=True)
    return advs, advs + values


def ppo_loss(params, batch, *, module, clip, vf_coef, ent_coef):
    logits, value = module.forward_train(params, batch["obs"])
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(
        logp_all, batch["actions"][..., None].astype(jnp.int32), -1)[..., 0]
    ratio = jnp.exp(logp - batch["logp"])
    adv = batch["advantages"]
    surr = jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv)
    pi_loss = -surr.mean()
    vf_loss = jnp.square(value - batch["returns"]).mean()
    entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    total = pi_loss + vf_coef * vf_loss - ent_coef * entropy
    return total, {"policy_loss": pi_loss, "vf_loss": vf_loss,
                   "entropy": entropy,
                   "kl": (batch["logp"] - logp).mean()}


class PPO(Algorithm):
    supports_ondevice_env = True  # jax-native envs (env/jax_env.py)

    def _loss_fn(self):
        return functools.partial(ppo_loss, module=self.module)

    def _loss_cfg(self):
        c = self.config
        return {"clip": c.clip_param, "vf_coef": c.vf_loss_coeff,
                "ent_coef": c.entropy_coeff}

    def training_step(self) -> dict:
        if self._jax_vec_env is not None:
            return self._training_step_ondevice()
        import time as _time
        c = self.config
        _t0 = _time.perf_counter()
        params = self.learner_group.get_weights()
        batches = []
        steps = 0
        while steps < c.train_batch_size:
            frags = self.env_runner_group.sample(
                params, c.rollout_fragment_length)
            for f in frags:
                adv, ret = _gae(
                    jnp.asarray(f["rewards"]), jnp.asarray(f["values"]),
                    jnp.asarray(f["dones"]), jnp.asarray(f["last_values"]),
                    gamma=c.gamma, lam=c.lambda_)
                # One fetch for both outputs (two np.asarray calls = two
                # blocking device round trips).
                f["advantages"], f["returns"] = jax.device_get((adv, ret))
                steps += f["rewards"].size
            batches.extend(frags)
        self._timesteps += steps
        batch = self._concat_fragments(batches)
        adv = batch["advantages"]
        batch["advantages"] = (adv - adv.mean()) / (adv.std() + 1e-8)
        batch = {k: batch[k] for k in
                 ("obs", "actions", "logp", "advantages", "returns")}
        n = batch["obs"].shape[0]
        _sample_ms = (_time.perf_counter() - _t0) * 1e3
        _t0 = _time.perf_counter()
        # Local learner: the whole epochs x minibatches sweep is one jit
        # call (one dispatch + one metrics fetch per training step).
        metrics = self.learner_group.update_epochs(
            batch, num_epochs=c.num_epochs,
            minibatch_size=c.minibatch_size, seed=self.iteration)
        if metrics is not None:
            # sample vs learner split (the bench reports the learner step
            # time on the accelerator separately from host env stepping)
            metrics["sample_ms"] = round(_sample_ms, 1)
            metrics["learner_update_ms"] = round(
                (_time.perf_counter() - _t0) * 1e3, 1)
            return metrics
        metrics = {}
        rng = np.random.default_rng(self.iteration)
        for _ in range(c.num_epochs):
            perm = rng.permutation(n)
            for s in range(0, n, c.minibatch_size):
                idx = perm[s:s + c.minibatch_size]
                if len(idx) < 2:
                    continue
                metrics = self.learner_group.update(
                    {k: v[idx] for k, v in batch.items()})
        return metrics

    def _training_step_ondevice(self) -> dict:
        """Jax-native env: the ENTIRE iteration (rollout + GAE + epochs)
        is one compiled dispatch (core/ondevice.py) — obs never touch the
        host, so an iteration costs one host sync instead of one per env
        step."""
        import time as _time

        c = self.config
        learner = self.learner_group.local
        if learner is None:
            raise ValueError("on-device PPO uses a local learner "
                             "(num_learners=0)")
        if self._ondev_iter is None:
            from ray_tpu.rllib.core.ondevice import build_ppo_train_iter
            B = self._jax_vec_env.num_envs
            T = max(1, c.train_batch_size // B)
            self._ondev_iter = build_ppo_train_iter(
                self._jax_vec_env, self.module, T=T,
                num_epochs=c.num_epochs,
                minibatch_size=min(c.minibatch_size, T * B),
                gamma=c.gamma, lam=c.lambda_, clip=c.clip_param,
                vf_coef=c.vf_loss_coeff, ent_coef=c.entropy_coeff,
                tx=learner.tx)
            self._ondev_T = T
            import jax as _jax
            self._ondev_vs = self._jax_vec_env.reset(
                _jax.random.PRNGKey(c.seed or 0))
            self._ondev_key = _jax.random.PRNGKey((c.seed or 0) + 1)
        _t0 = _time.perf_counter()
        (learner.params, learner.opt_state, self._ondev_vs,
         self._ondev_key, m) = self._ondev_iter(
            learner.params, learner.opt_state, self._ondev_vs,
            self._ondev_key)
        import jax as _jax
        m = {k: float(v)
             for k, v in _jax.device_get(m).items()}  # ONE device fetch
        dt_ms = (_time.perf_counter() - _t0) * 1e3
        steps = self._ondev_T * self._jax_vec_env.num_envs
        self._timesteps += steps
        self.env_runner_group.record(
            m.pop("ep_ret_sum"), m.pop("ep_len_sum"), m.pop("ep_count"))
        m["learner_update_ms"] = round(dt_ms, 1)
        m["sample_ms"] = 0.0  # sampling IS the update dispatch
        return m
