"""EnvRunner: vectorized-environment sampling actor.

Parity: reference `rllib/env/single_agent_env_runner.py:68` (gymnasium
vector envs + ConnectorV2 pipelines) inside `EnvRunnerGroup`
(`env/env_runner_group.py:71`). TPU split kept from the reference: env
stepping is CPU-bound actor work; only the learner touches the accelerator.
The runner does batched policy inference with jitted module forwards on its
local (CPU) jax backend.
"""

from __future__ import annotations

import numpy as np

import ray_tpu


def _flat(obs):
    return np.asarray(obs, dtype=np.float32).reshape(len(obs), -1)


class SingleAgentEnvRunner:
    """Steps `num_envs` copies of a gymnasium env, collecting fixed-length
    rollout fragments (PPO/IMPALA) or transition batches (DQN)."""

    def __init__(self, env_name: str, module, num_envs: int = 1,
                 seed: int = 0, env_config: dict | None = None):
        import gymnasium as gym
        import jax

        from ray_tpu.rllib.env.minatar import register_builtin_envs
        register_builtin_envs()
        # SAME_STEP autoreset (gym<1.0 behavior): on done, step() returns
        # the reset obs. gymnasium 1.x's NEXT_STEP default would record a
        # phantom transition per episode boundary (terminal obs as the new
        # episode's first obs, ignored action, reward 0) in every fragment.
        try:
            self.env = gym.make_vec(
                env_name, num_envs=num_envs, vectorization_mode="sync",
                vector_kwargs={
                    "autoreset_mode": gym.vector.AutoresetMode.SAME_STEP},
                **(env_config or {}))
        except (AttributeError, TypeError):  # older gymnasium
            self.env = gym.make_vec(env_name, num_envs=num_envs,
                                    vectorization_mode="sync",
                                    **(env_config or {}))
        self.num_envs = num_envs
        self.module = module
        # Acting runs on the CPU backend even in-process: env stepping is
        # a per-step host round-trip, and paying an accelerator dispatch
        # plus a device->host fetch per step caps env-steps/s far below
        # the CPU forward itself. The remote
        # runner actors get this for free (CPU-backend workers); this
        # makes local mode match. The learner keeps the accelerator.
        try:
            act_dev = jax.devices("cpu")[0]
        except RuntimeError:
            act_dev = None
        self._act_device = act_dev
        # Placement rides the committed inputs (params + key device_put to
        # CPU below; obs is numpy): jit compiles for the CPU backend with
        # no deprecated device= hint.
        self._explore = jax.jit(module.forward_exploration)
        self._infer = jax.jit(module.forward_inference)
        # The RNG key must live on the acting device too: a key on the
        # default accelerator makes every per-step split a device dispatch
        # and a host sync.
        self._key = jax.random.PRNGKey(seed)
        if act_dev is not None:
            self._key = jax.device_put(self._key, act_dev)
        obs, _ = self.env.reset(seed=seed)
        self.obs = _flat(obs)
        # Per-env accumulators for completed-episode returns.
        self._ep_ret = np.zeros(num_envs, dtype=np.float64)
        self._ep_len = np.zeros(num_envs, dtype=np.int64)
        self.completed_returns: list[float] = []
        self.completed_lengths: list[int] = []

    def sample(self, params, num_steps: int, explore: bool = True) -> dict:
        """Collect a [T, B, ...] fragment. Returns numpy arrays (they ride
        the object plane zero-copy)."""
        import jax

        if self._act_device is not None:
            # One transfer up front; otherwise every per-step jit call
            # re-copies accelerator-resident params to the CPU backend.
            params = jax.device_put(params, self._act_device)
        T, B = num_steps, self.num_envs
        obs_buf = np.empty((T, B, self.obs.shape[-1]), np.float32)
        if getattr(self.module, "action_kind", "discrete") == "continuous":
            act_buf = np.empty((T, B, self.module.action_dim), np.float32)
        else:
            act_buf = np.empty((T, B), np.int64)
        logp_buf = np.empty((T, B), np.float32)
        val_buf = np.empty((T, B), np.float32)
        rew_buf = np.empty((T, B), np.float32)
        done_buf = np.empty((T, B), np.float32)
        term_buf = np.empty((T, B), np.float32)
        for t in range(T):
            self._key, sub = jax.random.split(self._key)
            if explore:
                action, logp, value = self._explore(params, self.obs, sub)
            else:
                action = self._infer(params, self.obs)
                logp = value = np.zeros(B, np.float32)
            action = np.asarray(action)
            obs_buf[t] = self.obs
            act_buf[t] = action
            logp_buf[t] = np.asarray(logp)
            val_buf[t] = np.asarray(value)
            nxt, rew, term, trunc, _ = self.env.step(action)
            done = np.logical_or(term, trunc)
            rew_buf[t] = rew
            done_buf[t] = done
            term_buf[t] = term  # truncation is NOT termination: TD targets
            # bootstrap through time limits (dones only cut episodes)
            self._ep_ret += rew
            self._ep_len += 1
            for i in np.nonzero(done)[0]:
                self.completed_returns.append(float(self._ep_ret[i]))
                self.completed_lengths.append(int(self._ep_len[i]))
                self._ep_ret[i] = 0.0
                self._ep_len[i] = 0
            self.obs = _flat(nxt)
        # Bootstrap value for the final obs (used by GAE/V-trace).
        self._key, sub = jax.random.split(self._key)
        _, _, last_val = self._explore(params, self.obs, sub)
        return {
            "obs": obs_buf, "actions": act_buf, "logp": logp_buf,
            "values": val_buf, "rewards": rew_buf, "dones": done_buf,
            "terminateds": term_buf,
            "last_values": np.asarray(last_val),
            "final_obs": self.obs.copy(),  # next_obs tail for TD targets
        }

    def get_metrics(self) -> dict:
        out = {
            "episode_return_mean": (float(np.mean(self.completed_returns[-100:]))
                                    if self.completed_returns else float("nan")),
            "episode_len_mean": (float(np.mean(self.completed_lengths[-100:]))
                                 if self.completed_lengths else float("nan")),
            "num_episodes": len(self.completed_returns),
        }
        return out

    def ping(self):
        return "ok"


class RunnerGroupBase:
    """Shared local/remote dispatch + fault handling for runner groups
    (parity: env_runner_group.py:71 local-worker mode; fault-awareness per
    restart_failed_env_runners / FaultAwareApply, env_runner.py:32).

    Subclasses set `runner_cls` and call `_init_runners(args, kw, ...)`;
    dead remote runners are replaced on the next sample round."""

    runner_cls: type = None

    def _init_runners(self, args: tuple, kw: dict, *, num_env_runners: int,
                      seed: int, restart_failed: bool):
        self._args = args
        self._kw = kw
        self.restart_failed = restart_failed
        self.num_env_runners = num_env_runners
        self._seed = seed
        if num_env_runners == 0:
            self.local = self.runner_cls(*args, seed=seed, **kw)
            self.remotes = []
        else:
            self.local = None
            self._cls = ray_tpu.remote(num_cpus=1)(self.runner_cls)
            self.remotes = [
                self._cls.remote(*args, seed=seed + i, **kw)
                for i in range(num_env_runners)]

    def _replace(self, idx: int):
        self.remotes[idx] = self._cls.remote(
            *self._args, seed=self._seed + 1000 + idx, **self._kw)

    def sample(self, params, num_steps: int) -> list[dict]:
        if self.local is not None:
            return [self.local.sample(params, num_steps)]
        params_ref = ray_tpu.put(params)
        refs = [(i, r.sample.remote(params_ref, num_steps))
                for i, r in enumerate(self.remotes)]
        out = []
        for i, ref in refs:
            try:
                out.append(ray_tpu.get(ref, timeout=120))
            except ray_tpu.RayTpuError:
                if not self.restart_failed:
                    raise
                self._replace(i)
        return out

    def sample_async(self, params_ref, num_steps: int):
        """One in-flight sample request per runner (IMPALA-style)."""
        return [(i, r.sample.remote(params_ref, num_steps))
                for i, r in enumerate(self.remotes)]

    def aggregate_metrics(self) -> dict:
        if self.local is not None:
            return self.local.get_metrics()
        rets, lens, n = [], [], 0
        for i, r in enumerate(self.remotes):
            try:
                m = ray_tpu.get(r.get_metrics.remote(), timeout=60)
            except ray_tpu.RayTpuError:
                if self.restart_failed:
                    self._replace(i)
                continue
            if m["num_episodes"]:
                rets.append(m["episode_return_mean"])
                lens.append(m["episode_len_mean"])
                n += m["num_episodes"]
        return {
            "episode_return_mean": float(np.mean(rets)) if rets else float("nan"),
            "episode_len_mean": float(np.mean(lens)) if lens else float("nan"),
            "num_episodes": n,
        }

    def stop(self):
        for r in self.remotes:
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass


class EnvRunnerGroup(RunnerGroupBase):
    runner_cls = SingleAgentEnvRunner

    def __init__(self, env_name: str, module, *, num_env_runners: int = 0,
                 num_envs_per_env_runner: int = 1, seed: int = 0,
                 env_config: dict | None = None, restart_failed: bool = True):
        self._init_runners(
            (env_name, module),
            dict(num_envs=num_envs_per_env_runner, env_config=env_config),
            num_env_runners=num_env_runners, seed=seed,
            restart_failed=restart_failed)
