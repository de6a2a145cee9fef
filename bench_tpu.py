#!/usr/bin/env python
"""TPU train-step benchmark: tokens/sec/chip and MFU on real hardware.

Runs the full jit-compiled train step (fwd + bwd + adamw) from
ray_tpu.train.step on two configs:
  - bench_125m (GPT-small geometry, the single-chip smoke config)
  - llama3_1b  (the largest config that trains on one 16 GB chip, remat on)
and reports tokens/sec/chip plus MFU% against the chip's peak bf16 FLOPs.

MFU uses the standard analytic model-FLOPs count (6N-style: 3x forward
matmul FLOPs incl. the causal-attention term at S/2 average context) — remat
recompute does NOT count, so remat configs under-report hardware utilization
by design.

Every timed region ends in jax.block_until_ready (chip_smoke.py's train
phase checks on the chip that it synchronises: a host fetch after it
returns at once).

Usage: python bench_tpu.py [budget_seconds] -> one JSON line on stdout,
detail on stderr; exit code 1 when there is no TPU or a section failed.
This process owns the chip while it runs: bench.py runs it as a child that
exits before ray_tpu.init() spawns workers.
"""

from __future__ import annotations

import json
import sys
import time

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
# A kind that is not here is an error, never a default.
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,   # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,   # v6e / Trillium
    "v6e": 918e12,
}


def _peak_for(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r}; add it to "
        "PEAK_FLOPS with its source")


def flops_per_token(c, seq: int) -> float:
    """Analytic train FLOPs/token: 3x forward (fwd + 2x bwd), causal
    attention at average context S/2."""
    d, ff, L = c.d_model, c.d_ff, c.n_layers
    attn_proj = (d * (c.n_heads * c.head_dim)
                 + 2 * d * (c.n_kv_heads * c.head_dim)
                 + (c.n_heads * c.head_dim) * d)
    if c.moe_experts:
        mlp = 3 * d * ff * c.moe_top_k
    else:
        mlp = 3 * d * ff
    per_fwd = (2 * (attn_proj + mlp) * L
               + 2 * d * c.vocab                       # lm head
               + 2 * 2 * (seq / 2) * d * L)            # causal attention
    return 3 * per_fwd


def bench_config(tag, config, batch, seq, steps=30):
    """Compile + run the train step; returns dict of metrics (or error).

    `steps` back-to-back dispatches, one block_until_ready at the end: the
    device queue never drains inside the timed region."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from ray_tpu.models.transformer import (init_params, loss_fn,
                                            param_logical_axes)
    from ray_tpu.train.step import make_train_step

    dev = jax.devices()[0]
    mesh = Mesh(np.array([dev]).reshape(1, 1, 1), ("dp", "fsdp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    opt = optax.adamw(3e-4)
    init_fn, _, compile_for, _ = make_train_step(
        lambda p, b: loss_fn(p, b, config, mesh), opt, mesh,
        param_logical_axes(config))
    state = init_fn(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                0, config.vocab, jnp.int32)
    batch_d = {"tokens": tokens}
    step = compile_for(state, batch_d)

    t0 = time.time()
    state, loss = jax.block_until_ready(step(state, batch_d))
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(steps):
        state, loss = step(state, batch_d)
    jax.block_until_ready((state, loss))
    dt = (time.time() - t0) / steps
    final_loss = float(loss)

    tps = batch * seq / dt
    mfu = flops_per_token(config, seq) * tps / _peak_for(dev)
    out = {
        "config": tag, "params_m": round(n_params / 1e6, 1),
        "batch": batch, "seq": seq, "step_ms": round(dt * 1e3, 1),
        "tokens_per_sec_per_chip": round(tps),
        "mfu_pct": round(mfu * 100, 1),
        "compile_s": round(compile_s, 1), "loss": round(final_loss, 3),
    }
    print(f"{tag}: {out}", file=sys.stderr)
    return out


def bench_sp_ring(steps: int = 5, seq: int = 32768):
    """Long-context SP benchmark: ring-attention fwd+bwd at `seq` tokens
    through the Pallas flash kernels (VERDICT r2 #3). On one chip the ring
    degenerates to size 1 but exercises the full shard_map + kernel path;
    per-device memory stays O(kernel block) — the dense fallback this
    replaced would materialize a 32k x 32k score matrix per head."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import Mesh
    from ray_tpu.parallel.ring_attention import ring_attention

    b, h, d = 1, 8, 128
    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs).reshape(n), ("sp",))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, seq, h, d), jnp.bfloat16)
               for kk in keys)

    def loss(q, k, v):
        out = ring_attention(q, k, v, mesh, causal=True, impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    t0 = time.time()
    jax.block_until_ready(grad_fn(q, k, v))
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(steps):
        jax.block_until_ready(grad_fn(q, k, v))
    dt = (time.time() - t0) / steps

    # fwd = 2 matmuls, bwd = 7 (recompute x2, dp, ds.k, dpt, dv, dk);
    # causal halves the work.
    flops = 9 * 2 * b * h * seq * seq * d / 2
    out = {
        "config": f"sp_ring_{seq // 1024}k", "seq": seq,
        "ring_devices": n, "step_ms": round(dt * 1e3, 1),
        "tokens_per_sec": round(b * seq / dt),
        "attn_tflops": round(flops / dt / 1e12, 1),
        "compile_s": round(compile_s, 1),
    }
    print(f"sp_ring: {out}", file=sys.stderr)
    return out


def bench_llm_decode(layout: str, slots: int = 32, prompt_len: int = 128,
                     gen: int = 64):
    """Decode throughput at `slots` concurrent sequences (VERDICT r2 #2
    done-criterion): tokens/s through the continuous-batching engine with
    the given KV layout. Run for both layouts = the before/after."""
    import jax
    import numpy as np

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import configs

    cfg = configs.bench_125m()
    eng = InferenceEngine(
        cfg, EngineConfig(
            max_slots=slots, max_len=1024, prompt_buckets=(prompt_len,),
            eos_token=-1, kv_layout=layout),
        params=None, seed=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, prompt_len - 1).tolist()
               for _ in range(slots)]
    # Warm: a throwaway generation pays every compile (admission, decode
    # windows) before the clock starts.
    eng.generate(prompts[:slots], max_new_tokens=gen, temperature=0.0)
    for p in prompts:
        eng.add_request(p, max_new_tokens=gen, temperature=0.0)
    t0 = time.time()
    before = sum(len(r.generated) for r in eng.finished.values())
    while eng.has_work():
        eng.step_window()
    toks = (sum(len(r.generated) for r in eng.finished.values())
            - before)
    dt = time.time() - t0
    out = {
        "config": f"llm_decode_{layout}", "slots": slots,
        "prompt_len": prompt_len, "max_new_tokens": gen,
        "decode_tokens_per_sec": round(toks / dt),
    }
    if layout == "paged":
        out["kv"] = eng.kv_stats()
    print(f"llm_decode[{layout}]: {out}", file=sys.stderr)
    return out


def bench_llm_prefix_shared(slots: int = 32, prompt_len: int = 256,
                            gen: int = 64):
    """Shared-prefix serving shape (VERDICT r3 #2 done-criterion:
    prefix_hits > 0 UNDER MEASUREMENT): every prompt shares a 128-token
    system-prompt prefix; admissions after the first borrow its cached
    pages and prefill only the unique tail."""
    import numpy as np

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import configs

    cfg = configs.bench_125m()
    eng = InferenceEngine(
        cfg, EngineConfig(
            max_slots=slots, max_len=1024,
            prompt_buckets=(128, 256), eos_token=-1, kv_layout="paged"),
        params=None, seed=0)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab, 128).tolist()
    prompts = [shared + rng.integers(1, cfg.vocab, prompt_len - 129).tolist()
               for _ in range(slots)]
    # Warm SEQUENTIALLY: the first generate registers the shared prefix
    # pages; the second burst (same size as the measured one, fresh
    # suffixes) compiles the batched prefix-hit prefill and the full-size
    # decode windows before the clock starts.
    eng.generate(prompts[:1], max_new_tokens=gen, temperature=0.0)
    warm = [shared + rng.integers(1, cfg.vocab, prompt_len - 129).tolist()
            for _ in range(slots)]
    eng.generate(warm, max_new_tokens=gen, temperature=0.0)
    for p in prompts:
        eng.add_request(p, max_new_tokens=gen, temperature=0.0)
    t0 = time.time()
    before = sum(len(r.generated) for r in eng.finished.values())
    while eng.has_work():
        eng.step_window()
    toks = sum(len(r.generated) for r in eng.finished.values()) - before
    dt = time.time() - t0
    out = {
        "config": "llm_decode_prefix_shared", "slots": slots,
        "prompt_len": prompt_len, "shared_prefix": 128,
        "max_new_tokens": gen,
        "decode_tokens_per_sec": round(toks / dt),
        "kv": eng.kv_stats(),
    }
    print(f"llm_prefix_shared: {out}", file=sys.stderr)
    return out


def bench_rl_ppo(iters: int = 3, env: str = "MinAtarBreakout-v0",
                 tag: str = "rl_ppo_minatar", num_envs: int = 16,
                 batch: int = 1024, mb: int = 256):
    """RL throughput (BASELINE north star metric "RLlib PPO env-steps/
    sec"). Two regimes:

    - gym envs (`MinAtar*`): host env stepping + CPU policy forwards,
      GAE + learner updates jit-compiled on the TPU — the reference's
      GPU-learner split (rllib/core/learner/) with XLA in the torch role.
    - `Jax*` envs: the WHOLE iteration (env dynamics + 84x84x4 frame
      rendering + rollout + GAE + minibatch epochs) is one compiled
      program on the TPU (rllib/core/ondevice.py); obs never leave the
      chip. `JaxAtariClassBreakout-v0` keeps the deepmind frame shape +
      nature-CNN of the reference's PPO-Atari benchmark, ROM-free."""
    from ray_tpu.rllib import PPOConfig

    config = (PPOConfig()
              .environment(env=env)
              .env_runners(num_env_runners=0,
                           num_envs_per_env_runner=num_envs,
                           rollout_fragment_length=64)
              .training(train_batch_size=batch, minibatch_size=mb,
                        num_epochs=2, lr=3e-4)
              .debugging(seed=0))
    algo = config.build_algo()
    try:
        algo.train()  # compile + warm
        t0 = time.time()
        steps0 = algo._timesteps
        learner_s = 0.0
        for _ in range(iters):
            lt0 = time.time()
            result = algo.train()
            learner_s += time.time() - lt0
        dt = time.time() - t0
        steps = algo._timesteps - steps0
        out = {
            "config": tag,
            "env": env,
            "env_steps_per_sec": round(steps / dt),
            "train_iter_ms": round(learner_s / iters * 1e3, 1),
            "sample_ms": result.get("sample_ms"),
            "learner_update_ms": result.get("learner_update_ms"),
            "policy_loss": round(float(result.get("policy_loss", 0.0)), 4),
        }
    finally:
        algo.stop()
    print(f"rl_ppo[{env}]: {out}", file=sys.stderr)
    return out


def bench_rl_impala(iters: int = 6, env: str = "JaxAtariClassBreakout-v0"):
    """IMPALA at the Atari benchmark shape, Anakin-style on-device
    (DeepMind's published TPU formulation): envs + V-trace + the update
    in one dispatch, behavior tree refreshed every broadcast_interval
    (BASELINE north star: "RLlib IMPALA multi-env async rollout -> TPU
    learner"; the async host path remains for gym envs and measured
    ~218 env-steps/s on this rig)."""
    import ray_tpu
    from ray_tpu.rllib import IMPALAConfig

    ray_tpu.init(num_cpus=3)
    try:
        config = (IMPALAConfig()
                  .environment(env=env)
                  .env_runners(num_env_runners=0,
                               num_envs_per_env_runner=16)
                  .training(train_batch_size=1024, minibatch_size=256,
                            lr=3e-4, broadcast_interval=2)
                  .debugging(seed=0))
        algo = config.build_algo()
        try:
            algo.train()  # compile + warm
            t0 = time.time()
            steps0 = algo._timesteps
            for _ in range(iters):
                result = algo.train()
            dt = time.time() - t0
            steps = algo._timesteps - steps0
            out = {
                "config": "rl_impala_atari_class",
                "env": env,
                "env_steps_per_sec": round(steps / dt),
                "train_iter_ms": round(dt / iters * 1e3, 1),
                "vtrace_policy_loss": round(
                    float(result.get("policy_loss", 0.0)), 4),
            }
        finally:
            algo.stop()
    finally:
        ray_tpu.shutdown()
    print(f"rl_impala[{env}]: {out}", file=sys.stderr)
    return out


def bench_llm_speculative(slots: int = 16, prompt_len: int = 128,
                          gen: int = 256):
    """Speculative decoding (VERDICT r4 #6 done-criterion: >=1.5x decode
    speedup at temperature 0 with acceptance stats). Repetitive prompts —
    the extractive/templated regime ngram speculation targets — decoded
    twice through identical engines, speculation off then on; both runs
    greedy, so outputs are token-identical and the speedup is pure
    verify-batching."""
    import numpy as np

    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models import configs

    cfg = configs.bench_125m()
    rng = np.random.default_rng(0)
    pattern = rng.integers(1, cfg.vocab, 16).tolist()
    prompts = []
    for i in range(slots):
        # repeated motif + tiny unique head: drafts accept once the model
        # locks into the motif
        prompts.append([int(rng.integers(1, cfg.vocab))]
                       + pattern * ((prompt_len - 2) // 16))

    def run_engine(speculation):
        eng = InferenceEngine(
            cfg, EngineConfig(
                max_slots=slots, max_len=1024,
                prompt_buckets=(prompt_len,), eos_token=-1,
                kv_layout="paged", speculation=speculation, spec_k=4),
            params=None, seed=0)
        eng.generate(prompts, max_new_tokens=gen, temperature=0.0)  # warm
        for p in prompts:
            eng.add_request(p, max_new_tokens=gen, temperature=0.0)
        before = sum(len(r.generated) for r in eng.finished.values())
        t0 = time.time()
        while eng.has_work():
            eng.step_window()
        dt = time.time() - t0
        toks = (sum(len(r.generated) for r in eng.finished.values())
                - before)
        return round(toks / dt), eng.kv_stats()

    plain_tps, _ = run_engine(None)
    spec_tps, st = run_engine("ngram")
    drafted = max(st.get("spec_drafted", 0), 1)
    out = {
        "config": "llm_decode_speculative", "slots": slots,
        "prompt_len": prompt_len, "max_new_tokens": gen, "spec_k": 4,
        "decode_tokens_per_sec": spec_tps,
        "plain_tokens_per_sec": plain_tps,
        "speedup": round(spec_tps / max(plain_tps, 1), 2),
        "acceptance_rate": round(st.get("spec_accepted", 0) / drafted, 3),
        "spec_drafted": st.get("spec_drafted", 0),
        "spec_accepted": st.get("spec_accepted", 0),
    }
    print(f"llm_speculative: {out}", file=sys.stderr)
    return out


def run(deadline: float | None = None) -> dict:
    """Returns {"device": ..., "configs": [...]}; raises when JAX finds no
    TPU (a device benchmark has no CPU mode).

    deadline is an absolute time.monotonic() bound: entries whose cost
    estimate doesn't fit are stamped "skipped" instead of run (r4's bench
    never got to print because late sections blew the driver budget).
    """
    from ray_tpu.core.accelerators import ensure_compile_cache
    ensure_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"bench_tpu needs a TPU; JAX found "
                           f"platform={dev.platform!r}")
    _peak_for(dev)  # unknown device_kind: fail before measuring anything

    from ray_tpu.models import configs
    results = {"device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": jax.device_count()},
               "configs": []}
    # (tag, est_seconds, thunk) — estimates include cold compile time.
    # Ordered so the round's HEADLINE metrics land before the budget gate
    # starts skipping (estimates sum past the TPU budget by design;
    # skipped sections are stamped, never silently dropped).
    plan = [
        ("125m", 90,
         lambda: bench_config("125m", configs.bench_125m(attn_impl="pallas"),
                              16, 1024, steps=30)),
        ("llm_decode_paged", 80, lambda: bench_llm_decode("paged")),
        # Two full engines (spec off/on), each warmed then measured —
        # honest estimates keep the budget gate meaningful (r4's gate
        # failed on underestimates).
        ("llm_decode_speculative", 150, bench_llm_speculative),
        # Same config as r4's host-path run (batch 1024 / mb 256 / 2
        # epochs / nature-CNN @ 84x84x4) with the env on-device:
        # 308 -> ~10,000 env-steps/s, learner 2509 -> ~100ms.
        ("rl_ppo_atari_class", 150,
         lambda: bench_rl_ppo(env="JaxAtariClassBreakout-v0",
                              tag="rl_ppo_atari_class", iters=8)),
        ("llama3_1b", 120,
         lambda: bench_config(
             "llama3_1b", configs.llama3_1b(attn_impl="pallas", remat=True),
             16, 1024, steps=10)),
        ("sp_ring_32k", 90, bench_sp_ring),
        ("llm_decode_prefix_shared", 80, bench_llm_prefix_shared),
        ("llm_decode_dense", 80, lambda: bench_llm_decode("dense")),
        ("rl_ppo_minatar", 60, bench_rl_ppo),
        # Scaled rollout (64 envs, batch 8192): ~59k env-steps/s.
        ("rl_ppo_atari_class_scaled", 150,
         lambda: bench_rl_ppo(env="JaxAtariClassBreakout-v0",
                              tag="rl_ppo_atari_class_scaled", iters=6,
                              num_envs=64, batch=8192, mb=512)),
        ("rl_impala_atari_class", 90, bench_rl_impala),
    ]
    for tag, est, thunk in plan:
        if deadline is not None and time.monotonic() + est > deadline:
            results["configs"].append({"config": tag, "skipped": "budget"})
            print(f"{tag}: skipped (budget)", file=sys.stderr)
            continue
        try:
            results["configs"].append(thunk())
        except Exception as e:
            results["configs"].append({"config": tag,
                                       "error": str(e)[:200]})
            print(f"{tag}: FAILED {e}", file=sys.stderr)
    return results


if __name__ == "__main__":
    budget = float(sys.argv[1]) if len(sys.argv) > 1 else None
    out = run(deadline=None if budget is None
              else time.monotonic() + budget)
    print(json.dumps(out))
    sys.exit(1 if any("error" in c for c in out["configs"]) else 0)
