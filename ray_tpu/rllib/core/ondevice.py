"""Fully on-device PPO training: rollout + GAE + minibatch epochs in ONE
compiled program.

Parity target: the reference's PPO training_step
(`rllib/algorithms/ppo/ppo.py:388` — synchronous_parallel_sample on host
workers, obs tensors shipped to a torch-GPU learner). TPU-native
redesign: with a jax-native env (env/jax_env.py), the entire training
iteration — T env steps x B envs of policy forwards + env dynamics +
frame rendering, GAE over the trajectory, advantage normalization, and
the epochs x shuffled-minibatches PPO update — is a single `jax.jit`
dispatch. Observations never leave the accelerator; the host fetches
five scalars per iteration: one host sync per *iteration* instead of one
per *step*.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from ray_tpu.rllib.env.jax_env import JaxVecEnv, build_rollout


def build_ppo_train_iter(vec_env: JaxVecEnv, module, *, T: int,
                         num_epochs: int, minibatch_size: int,
                         gamma: float, lam: float, clip: float,
                         vf_coef: float, ent_coef: float, tx):
    """Returns jit(train_iter)(params, opt_state, vec_state, key) ->
    (params, opt_state, vec_state, key, metrics). `tx` is the optax
    transform shared with the Learner so checkpoints stay compatible."""
    from ray_tpu.rllib.algorithms.ppo import ppo_loss

    rollout = build_rollout(vec_env, module, T)
    B = vec_env.num_envs
    n = T * B
    if n % minibatch_size:
        raise ValueError(f"T*B={n} must tile into minibatches "
                         f"of {minibatch_size}")
    nmb = n // minibatch_size

    loss_fn = functools.partial(ppo_loss, module=module, clip=clip,
                                vf_coef=vf_coef, ent_coef=ent_coef)

    def sgd_step(params, opt_state, mb):
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, aux

    def gae(rew, val, done, last_val):
        def step(carry, xs):
            r, v, d, v_next = xs
            delta = r + gamma * (1.0 - d) * v_next - v
            adv = delta + gamma * lam * (1.0 - d) * carry
            return adv, adv
        v_next = jnp.concatenate([val[1:], last_val[None]], axis=0)
        _, advs = jax.lax.scan(step, jnp.zeros_like(last_val),
                               (rew, val, done, v_next), reverse=True)
        return advs, advs + val

    def train_iter(params, opt_state, vs, key):
        vs, key, traj = rollout(params, vs, key)
        adv, ret = gae(traj["rewards"], traj["values"], traj["dones"],
                       traj["last_values"])
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        flat = {
            "obs": traj["obs"].reshape((n,) + traj["obs"].shape[2:]),
            "actions": traj["actions"].reshape((n,)
                                               + traj["actions"].shape[2:]),
            "logp": traj["logp"].reshape(n),
            "advantages": adv.reshape(n),
            "returns": ret.reshape(n),
        }

        def one_minibatch(carry, idx):
            params, opt_state = carry
            mb = jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx, axis=0), flat)
            params, opt_state, loss, aux = sgd_step(params, opt_state, mb)
            return (params, opt_state), (loss, aux)

        def one_epoch(carry, ekey):
            perm = jax.random.permutation(ekey, n).reshape(
                nmb, minibatch_size)
            return jax.lax.scan(one_minibatch, carry, perm)

        key, ekey = jax.random.split(key)
        (params, opt_state), (losses, auxs) = jax.lax.scan(
            one_epoch, (params, opt_state),
            jax.random.split(ekey, num_epochs))
        metrics = {k: v[-1, -1] for k, v in auxs.items()}
        metrics["total_loss"] = losses[-1, -1]
        metrics["ep_ret_sum"] = vs.done_ret_sum
        metrics["ep_len_sum"] = vs.done_len_sum
        metrics["ep_count"] = vs.done_count
        return params, opt_state, vs, key, metrics

    # No donation: freshly-initialized optimizer states can alias
    # identical zero buffers, which XLA rejects as double-donation.
    return jax.jit(train_iter)


def build_impala_train_iter(vec_env: JaxVecEnv, module, *, T: int,
                            minibatch_size: int, gamma: float,
                            rho_bar: float, c_bar: float, vf_coef: float,
                            ent_coef: float, tx):
    """On-device IMPALA (the Anakin/Podracer architecture: DeepMind's
    published TPU formulation of IMPALA — sebulba/anakin, Hessel et al.
    2021): envs live on the accelerator, acting uses a STALE behavior
    policy, and V-trace corrects the off-policyness, all in ONE compiled
    dispatch. The host refreshes behavior params every
    broadcast_interval iterations (same knob as the async actor-learner
    path), so the off-policy gap the reference creates with queue lag is
    created here with deliberate staleness.

    Returns jit(train_iter)(params, behavior_params, opt_state, vs, key)
    -> (params, opt_state, vs, key, metrics)."""
    from ray_tpu.rllib.algorithms.impala import _vtrace_core, impala_loss

    rollout = build_rollout(vec_env, module, T)
    B = vec_env.num_envs
    n = T * B
    if n % minibatch_size:
        raise ValueError(f"T*B={n} must tile into minibatches "
                         f"of {minibatch_size}")
    nmb = n // minibatch_size
    loss_fn = functools.partial(impala_loss, module=module,
                                vf_coef=vf_coef, ent_coef=ent_coef)

    def train_iter(params, behavior_params, opt_state, vs, key):
        # Act with the stale behavior policy; traj["logp"]/["values"]
        # are the BEHAVIOR policy's.
        vs, key, traj = rollout(behavior_params, vs, key)
        obs = traj["obs"]                       # [T, B, ...]
        flat_obs = obs.reshape((n,) + obs.shape[2:])
        # Learner-side forward: target logp + current value estimates.
        logits, values_l = module.forward_train(params, flat_obs)
        logp_all = jax.nn.log_softmax(logits)
        acts = traj["actions"].reshape(n)
        target_logp = jnp.take_along_axis(
            logp_all, acts[:, None].astype(jnp.int32), -1)[:, 0]
        last_vals = traj["last_values"]  # behavior bootstrap (host path
        #                                  uses the same approximation)
        vs_t, pg_adv = _vtrace_core(
            traj["logp"], target_logp.reshape(T, B), traj["rewards"],
            values_l.reshape(T, B), traj["dones"], last_vals,
            gamma=gamma, rho_bar=rho_bar, c_bar=c_bar)
        flat = {
            "obs": flat_obs,
            "actions": acts,
            "vs": vs_t.reshape(n),
            "pg_advantages": pg_adv.reshape(n),
        }

        def one_minibatch(carry, idx):
            params, opt_state = carry
            mb = jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx, axis=0), flat)
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), (
                loss, aux)

        key, pkey = jax.random.split(key)
        perm = jax.random.permutation(pkey, n).reshape(nmb,
                                                       minibatch_size)
        (params, opt_state), (losses, auxs) = jax.lax.scan(
            one_minibatch, (params, opt_state), perm)
        metrics = {k: v[-1] for k, v in auxs.items()}
        metrics["total_loss"] = losses[-1]
        metrics["ep_ret_sum"] = vs.done_ret_sum
        metrics["ep_len_sum"] = vs.done_len_sum
        metrics["ep_count"] = vs.done_count
        return params, opt_state, vs, key, metrics

    return jax.jit(train_iter)


class OnDeviceSamplerGroup:
    """Stands in for EnvRunnerGroup when the env is jax-native: episode
    statistics live on-device (banked by JaxVecEnv.step) and surface
    through the same aggregate_metrics() interface."""

    def __init__(self):
        self._ret_sum = 0.0
        self._len_sum = 0.0
        self._count = 0
        self._window = []  # recent completed-episode means per iter

    def record(self, ret_sum: float, len_sum: float, count: float):
        d_ret = ret_sum - self._ret_sum
        d_len = len_sum - self._len_sum
        d_n = count - self._count
        self._ret_sum, self._len_sum, self._count = ret_sum, len_sum, count
        if d_n > 0:
            self._window.append((d_ret / d_n, d_len / d_n, d_n))
            self._window = self._window[-100:]

    def aggregate_metrics(self) -> dict:
        if not self._window:
            return {"episode_return_mean": float("nan"),
                    "episode_len_mean": float("nan"), "num_episodes": 0}
        rets = [r for r, _, _ in self._window]
        lens = [l for _, l, _ in self._window]
        return {"episode_return_mean": float(sum(rets) / len(rets)),
                "episode_len_mean": float(sum(lens) / len(lens)),
                "num_episodes": int(self._count)}

    def sample(self, *a, **kw):  # pragma: no cover - guard rail
        raise RuntimeError("on-device PPO does not sample via runners")

    def stop(self):
        pass
