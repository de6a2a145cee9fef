#!/usr/bin/env python
"""Chip smoke: the train and serve main paths, once, on the TPU.

    python chip_smoke.py               # one chip, full widths (driver's run)
    python chip_smoke.py --tiny        # same code at configs.tiny() widths
    python chip_smoke.py --four-chips  # sharded train + its one-device
                                       # comparison, and no other phase

A driver calls ray_tpu.init(); a worker process that reserved TPUs runs the
jitted program. This script is that driver and NEVER initialises a JAX
backend itself (asserted before the last line): a chip belongs to one
process at a time, and every phase runs in its own worker, which must have
released the chip before the next phase asks for it.

Phases (one JSON object per line, each with a "checks" map):
  probe  chips found by ray_tpu.init() == chips a num_tpus=1 task sees; a
         num_tpus=0 task stays on the CPU; task, task, actor in a row all
         get the chip (a pooled worker that kept it would fail the second).
  train  JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True)) ->
         make_train_step(adamw) at qwen2_7b widths, depth cut.
  serve  serve.run(build_openai_app(LLMConfig(num_tpus_per_replica=1)))
         at the same widths; concurrent requests through the deployment
         handle (cold) and the OpenAI HTTP route (warm); the engine's
         log-probabilities against models.transformer.forward.

The LAST stdout line is {"ok": bool, "device": {platform, kind, count}} with
the device as a chip-holding worker reported it. Exit code 0 only when every
check of every phase passed. There is no CPU mode that reports success:
under JAX_PLATFORMS=cpu the `device_*` checks are false — with --tiny those
are the ONLY false checks (the rehearsal); without it the run stops at the
probe rather than push 7B-wide matmuls through a CPU.

Widths are never cut; depth and batch are, and each phase prints what it
used. Smoke, not a benchmark: the wall times it prints are for finding a
cold compile or a hung phase, not for comparing commits.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import math
import os
import re
import sys
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 0
HTTP_PORT = 18473  # not serve's default: nothing else on the host owns it
MODEL_ID = "qwen2-7b-smoke"

# Log-probabilities from two independent programs over the same bf16
# weights: the engine (Pallas flash prefill, Pallas paged decode, KV
# pool in bf16) against models.transformer.forward (Pallas flash, one
# pass). Both accumulate in fp32; what differs is where activations round
# to bf16 (2^-8 relative) across the layers, which moves a unit-variance
# logit by ~1e-2. A wrong token or position is off by whole units
# (a non-argmax token sits ~4 nats lower). float32 (--tiny) agrees to 5e-7
# on the CPU, but the chip multiplies float32 in bf16 passes at JAX's
# default precision: 1e-3 there (chip run, --tiny), hence 1e-2.
LOGPROB_TOL = {"bfloat16": 0.1, "float32": 1e-2}
# Same math on one device and on fsdp=4; only the reduction order and bf16
# rounding points differ: per-shard partial sums, then a collective, and
# since PR 36 the loss head's log-sum-exp is summed per VOCABULARY slice
# (a max and a sum of exponentials a chip, then two [tokens]-sized
# reductions) where one device sums a whole row, and dx is reduce-
# scattered from per-slice partials: at most 1.8e-4 on losses of 5-12
# over three steps (four-chip run, PR 36; 3e-4 with the GSPMD head).
FSDP_LOSS_TOL = {"bfloat16": 1e-2, "float32": 1e-2}


def sizes(tiny: bool) -> dict:
    """Every size the phases use. Widths are configs.qwen2_7b()'s (or
    configs.tiny()'s); only depth, batch and traffic are chosen here."""
    if tiny:
        return dict(
            preset="tiny", model_kw=dict(vocab=512),  # byte tokenizer: 258 ids
            train=dict(n_layers=2, batch=2, seq=64, steps=2),
            serve=dict(n_layers=2, max_slots=2, max_len=256, page_size=128,
                       prompt_tokens=[20, 40], new_tokens=4),
            four=dict(n_layers=2, deep_layers=4, batch=4, seq=64, steps=3))
    return dict(
        preset="qwen2_7b", model_kw={},
        # 2 layers x (2 x 2048 tokens): 1.56 B params; params + adam state
        # 8.7 GiB + 4.9 GiB of temporaries = 13.6 GiB of the chip's 16 GB
        # (described-chip compile, memory_analysis()).
        train=dict(n_layers=2, batch=2, seq=2048, steps=6),
        # 12 layers: 7.2 GiB of bf16 weights + 0.4 GiB of KV pool; the
        # decode program adds 3.6 GiB (an fp32 copy of the head and the
        # fused-QKV / gate-up concats), prefill of 4 x 1024 tokens 2.4 GiB
        # of fp32 logits: ~11.3 GiB at the peak (described-chip compile).
        serve=dict(n_layers=12, max_slots=8, max_len=2048, page_size=128,
                   prompt_tokens=[300, 500, 700, 900], new_tokens=32),
        # (a) the train phase's size with 4 sequences so the batch divides
        # over fsdp=4; (b) 20 layers = 5.75 B params, 34.5 GB of params +
        # adam state: 8.0 + 5.7 GiB per chip, what the single chip held.
        four=dict(n_layers=2, deep_layers=20, batch=4, seq=2048, steps=3))


# ---------------------------------------------------------------- output


class Report:
    """Collects phase lines; the verdict is the AND of every check."""

    def __init__(self):
        self.ok = True
        self.device = None
        os.makedirs(OUT_DIR, exist_ok=True)
        self._log = open(os.path.join(OUT_DIR, "phases.jsonl"), "w")

    def phase(self, name: str, checks: dict, **info):
        checks = {k: bool(v) for k, v in checks.items()}
        line = json.dumps({"phase": name, "checks": checks, **info},
                          default=float)
        print(line, flush=True)
        self._log.write(line + "\n")
        self._log.flush()
        self.ok = self.ok and all(checks.values())

    def saw_device(self, dev: dict):
        """The last line carries the device as the latest chip-holding
        worker reported it (each phase's `device_platform_tpu` check is
        what fails the run on any other platform)."""
        self.device = {k: dev[k] for k in ("platform", "kind", "count")}


def leak_checks() -> dict:
    """After ray_tpu.shutdown(): no arena of this driver in /dev/shm and
    no worker process of this driver alive (bounded wait: children die
    with their parent asynchronously)."""
    import psutil
    me = psutil.Process()
    deadline = time.monotonic() + 10.0
    while True:
        shm = glob.glob(f"/dev/shm/ray_tpu_{os.getpid()}_*")
        procs = [p for p in me.children(recursive=True)
                 if p.is_running() and p.status() != psutil.STATUS_ZOMBIE]
        if not (shm or procs) or time.monotonic() > deadline:
            return {"no_shm_left": not shm, "no_process_left": not procs}
        time.sleep(0.1)


# ------------------------------------------------- code that runs on a chip
# Everything below this line until the phases runs inside worker processes
# (pickled by value from __main__); the driver only ships it.


def pallas_kernels(lowered_text: str) -> list:
    """Names of the Pallas TPU kernels a lowered (StableHLO) program
    calls: each is a `tpu_custom_call` carrying its kernel's name. Empty
    for a program that took a reference, XLA or interpreted path."""
    if "tpu_custom_call" not in lowered_text:
        return []
    return sorted(set(re.findall(r'kernel_name = "(\w+)"', lowered_text)))


def device_report() -> dict:
    """What JAX reports in THIS process, plus which pid it is."""
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(), "pid": os.getpid(),
            "bytes_limit": stats.get("bytes_limit"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


class ChipActor:
    def info(self) -> dict:
        return device_report()


def _model(preset: str, model_kw: dict, n_layers: int):
    from ray_tpu.models import configs
    return getattr(configs, preset)(n_layers=n_layers, **model_kw)


def _run_steps(cfg, mesh, batch: int, seq: int, steps: int, seed: int):
    """Init, compile (timed apart), run `steps` steps on a repeated seeded
    batch; every timing ends in block_until_ready. Returns plain data: the
    losses and timings, the kernels in the lowered step, the collectives in
    the compiled one, how the state ended up laid out over the mesh."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.transformer import (init_params, loss_fn,
                                            param_logical_axes)
    from ray_tpu.train.step import make_train_step
    # 1e-4: on a repeated batch adam at 3e-4 memorises it in four steps
    # and then bounces (chip run: 12.4, 8.5, 1.9, 0.06, 0.16, 2.4).
    init_fn, _, compile_for, shardings = make_train_step(
        lambda p, b: loss_fn(p, b, cfg, mesh), optax.adamw(1e-4), mesh,
        param_logical_axes(cfg))
    t0 = time.perf_counter()
    # Params are BORN sharded (jit with out_shardings): a model no single
    # chip holds never passes through one.
    state = jax.block_until_ready(init_fn(jax.jit(
        partial(init_params, cfg), out_shardings=shardings)(
            jax.random.PRNGKey(seed))))
    init_s = time.perf_counter() - t0
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab, jnp.int32)
    batch_d = {"tokens": jax.device_put(
        tokens, NamedSharding(mesh, P(("dp", "fsdp"))))}
    t0 = time.perf_counter()
    lowered = compile_for(state, batch_d).lower(state, batch_d)
    step = lowered.compile()
    compile_s = time.perf_counter() - t0
    compiled_text = step.as_text()
    losses, step_s, fetch_s = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = jax.block_until_ready(step(state, batch_d))
        t1 = time.perf_counter()
        losses.append(float(loss))  # after the fence: must cost ~nothing
        step_s.append(t1 - t0)
        fetch_s.append(time.perf_counter() - t1)
    # Every param leaf parallel/sharding.py declares sharded must be split
    # in n pieces on n distinct devices (a replicated leaf also has n
    # addressable shards — each of them whole).
    n = mesh.devices.size
    unsplit = [
        jax.tree_util.keystr(path) for (path, x), sh in zip(
            jax.tree_util.tree_leaves_with_path(state.params),
            jax.tree.leaves(shardings))
        if any(a is not None for a in sh.spec) and (
            len({s.device for s in x.addressable_shards}) != n
            or any(s.data.size * n != x.size for s in x.addressable_shards))]
    return {
        "losses": losses, "init_s": init_s, "compile_s": compile_s,
        "step_s": step_s, "fetch_after_fence_s": fetch_s,
        "n_params": sum(x.size for x in jax.tree.leaves(state.params)),
        "kernels": pallas_kernels(lowered.as_text()),
        "collectives": [k for k in ("all-gather", "reduce-scatter",
                                    "all-reduce") if k in compiled_text],
        "declared_sharded_leaves_not_split": unsplit,
        # with the state still live; None on the CPU backend
        "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                         for d in mesh.devices.flat],
    }


def _loss_checks(r: dict, vocab: int, steps: int) -> dict:
    losses = r["losses"]
    return {
        "steps_ran": len(losses) == steps,
        # unit-variance logits at init put the loss a little above ln(V)
        "loss0_near_ln_vocab": abs(losses[0] - math.log(vocab)) < 1.0,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_falls_on_repeated_batch": losses[-1] < losses[0],
        # block_until_ready really waited: the fetch after it is instant
        "fence_synchronises": max(r["fetch_after_fence_s"]) < max(
            0.05, 0.1 * min(r["step_s"])),
    }


def _sharded_checks(r: dict, vocab: int, steps: int) -> dict:
    """What both four-chip programs must show on top of the loss checks."""
    return {
        **_loss_checks(r, vocab, steps),
        "declared_sharded_leaves_split_over_all_chips": not r[
            "declared_sharded_leaves_not_split"],
        "all_gather": "all-gather" in r["collectives"],
        "reduce_scatter_or_all_reduce": bool(
            {"reduce-scatter", "all-reduce"} & set(r["collectives"])),
    }


def train_loop(config: dict):
    """The one-chip train phase, inside the JaxTrainer's worker."""
    import jax

    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.train import session
    s = config["train"]
    cfg = _model(config["preset"], config["model_kw"], s["n_layers"])
    # The one-chip phase: the worker owns every chip of its host (one,
    # where the driver runs this) and trains on the first.
    mesh = make_mesh(devices=jax.devices()[:1])
    r = _run_steps(cfg, mesh, s["batch"], s["seq"], s["steps"], SEED)
    checks = _loss_checks(r, cfg.vocab, s["steps"])
    checks["device_train_step_tpu_custom_call"] = bool(r["kernels"])
    session.report({"device": device_report(), "checks": checks,
                    "mesh": dict(mesh.shape), **r})


def four_chip_loop(config: dict):
    """--four-chips: (a) the one-chip size on fsdp=N against a one-device
    mesh over devices[:1], same seed; (b) a depth no single chip could
    train, on fsdp=N only. Both programs in this one worker."""
    import jax

    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import session
    s = config["four"]
    n = jax.device_count()
    one = make_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])
    full = make_mesh(MeshConfig(fsdp=n))
    cfg = _model(config["preset"], config["model_kw"], s["n_layers"])
    deep = _model(config["preset"], config["model_kw"], s["deep_layers"])
    tol = FSDP_LOSS_TOL[cfg.dtype]

    r1 = _run_steps(cfg, one, s["batch"], s["seq"], s["steps"], SEED)
    rn = _run_steps(cfg, full, s["batch"], s["seq"], s["steps"], SEED)
    rb = _run_steps(deep, full, s["batch"], s["seq"], s["steps"], SEED)
    diffs = [abs(a - b) for a, b in zip(r1["losses"], rn["losses"])]
    in_use = rb["bytes_in_use"]
    checks = {
        **{f"a_{k}": v for k, v in
           _sharded_checks(rn, cfg.vocab, s["steps"]).items()},
        "a_losses_agree_with_one_device": max(diffs) <= tol,
        "device_a_tpu_custom_call": bool(rn["kernels"]),
        **{f"b_{k}": v for k, v in
           _sharded_checks(rb, deep.vocab, s["steps"]).items()},
        "device_b_tpu_custom_call": bool(rb["kernels"]),
        # a program that put everything on device 0 fails this
        "device_b_memory_within_2x_across_chips": all(in_use)
        and max(in_use) <= 2 * min(in_use),
    }
    session.report({
        "checks": checks, "device": device_report(), "n_devices": n,
        "loss_tol": tol, "a_loss_diffs": diffs, "a_one_device": r1,
        "a_fsdp": rn, "b_fsdp": rb})


def reference_logprobs(preset: str, model_kw: dict, n_layers: int,
                       seed: int, prompt_ids: list, generated: list,
                       pad_to: int) -> dict:
    """log-softmax of models.transformer.forward over prompt + generated at
    the positions that predict each generated token; same seed, so the
    same weights the replica served. Right-padding is invisible to the
    causal positions before it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward, init_params
    cfg = _model(preset, model_kw, n_layers)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    seq = list(prompt_ids) + list(generated)
    tokens = jnp.zeros((1, pad_to), jnp.int32).at[0, :len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    logp = jax.nn.log_softmax(
        jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)[0], axis=-1)
    pos = jnp.arange(len(prompt_ids) - 1, len(seq) - 1)
    got = logp[pos, jnp.asarray(generated, jnp.int32)]
    return {"logprobs": [float(x) for x in got], "device": device_report()}


# ---------------------------------------------------------------- phases


def phase_probe(rep: Report, init_kw: dict, detected: int):
    import ray_tpu
    t0 = time.perf_counter()
    ray_tpu.init(**init_kw)
    init_s = time.perf_counter() - t0
    chips = ray_tpu.cluster_resources().get("TPU", 0)
    on_chip = ray_tpu.remote(num_tpus=1)(device_report)
    on_cpu = ray_tpu.remote(num_tpus=0)(device_report)
    steps = []

    def timed(ref_fn):
        t0 = time.perf_counter()
        out = ray_tpu.get(ref_fn(), timeout=300)
        steps.append(round(time.perf_counter() - t0, 2))
        return out

    first = timed(on_chip.remote)
    cpu = timed(on_cpu.remote)
    second = timed(on_chip.remote)
    actor = ray_tpu.remote(num_tpus=1)(ChipActor).remote()
    third = timed(actor.info.remote)
    ray_tpu.kill(actor)
    ray_tpu.shutdown()
    rep.saw_device(first)
    holders = [first, second, third]
    rep.phase(
        "probe",
        {
            # detect_tpus() found what the machine has: the cluster's TPU
            # resource is what a chip-holding task counts
            "device_chips_detected": detected > 0 and all(
                h["count"] == chips == detected for h in holders),
            "device_platform_tpu": all(
                h["platform"] == "tpu" for h in holders),
            "cpu_task_stays_on_cpu": cpu["platform"] == "cpu",
            # one owner per chip: the worker that held it was retired, so
            # task, task, actor are three processes, none of them the
            # num_tpus=0 task's pooled worker
            "each_chip_holder_is_a_fresh_process": len(
                {h["pid"] for h in holders} | {cpu["pid"]}) == 4,
            **leak_checks(),
        },
        chips_detected=detected, cluster_tpu=chips, init_s=round(init_s, 2),
        seconds_task_cpu_task_actor=steps, first=first, cpu_task=cpu,
        second=second, actor=third)
    return first["platform"] == "tpu"


def phase_train(rep: Report, cfg: dict, init_kw: dict):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    ray_tpu.init(**init_kw)
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop, train_loop_config=cfg,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="chip_smoke_train",
                             storage_path=os.path.join(OUT_DIR, "train")),
    ).fit()
    fit_s = time.perf_counter() - t0
    if result.error is not None:
        raise RuntimeError(f"train phase failed: {result.error}")
    # The trainer's worker must have let go of the chip: no sleep here.
    after = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(device_report).remote(), timeout=300)
    ray_tpu.shutdown()
    m = result.metrics
    rep.saw_device(m["device"])
    rep.phase(
        "train",
        {**m["checks"],
         "device_platform_tpu": m["device"]["platform"] == "tpu",
         "chip_free_after_fit": after["platform"] == m["device"]["platform"]
         and after["pid"] != m["device"]["pid"],
         **leak_checks()},
        used={"preset": cfg["preset"], **cfg["train"]}, mesh=m["mesh"],
        n_params=m["n_params"], losses=m["losses"],
        init_s=round(m["init_s"], 2), compile_s=round(m["compile_s"], 2),
        step_s=[round(x, 4) for x in m["step_s"]],
        fetch_after_fence_s=[round(x, 5) for x in m["fetch_after_fence_s"]],
        fit_s=round(fit_s, 2), pallas_kernels_in_lowered_step=m["kernels"],
        device=m["device"])


def _prompt(rng, n_tokens: int) -> str:
    """A seeded printable-ASCII prompt that the byte tokenizer turns into
    exactly n_tokens ids (one BOS + one id per byte)."""
    return "".join(chr(c) for c in rng.integers(32, 127, n_tokens - 1))


def _http_completion(body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{HTTP_PORT}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.load(r)


def phase_serve(rep: Report, cfg: dict, init_kw: dict):
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, LLMConfig
    from ray_tpu.llm.serve import build_openai_app
    s = cfg["serve"]
    model = _model(cfg["preset"], cfg["model_kw"], s["n_layers"])
    ir_dir = os.path.join(OUT_DIR, "ir_serve")
    for old in glob.glob(os.path.join(ir_dir, "*")):
        os.unlink(old)
    # Every process of THIS cluster dumps the StableHLO of what it lowers:
    # the proof of which kernel the replica really ran is read from the
    # replica's own programs, not from a flag.
    os.environ["JAX_DUMP_IR_TO"] = ir_dir
    ray_tpu.init(**init_kw)
    t0 = time.perf_counter()
    serve.run(build_openai_app(LLMConfig(
        model_id=MODEL_ID, model=model, num_tpus_per_replica=1, seed=SEED,
        engine=EngineConfig(max_slots=s["max_slots"], max_len=s["max_len"],
                            page_size=s["page_size"]))),
        http_port=HTTP_PORT, blocking_timeout_s=600)
    deploy_s = time.perf_counter() - t0
    server = serve.get_deployment_handle(f"LLMServer:{MODEL_ID}")
    rng = np.random.default_rng(SEED)
    waves = [[_prompt(rng, n) for n in s["prompt_tokens"]] for _ in range(2)]
    new = s["new_tokens"]
    with concurrent.futures.ThreadPoolExecutor(len(waves[0])) as pool:
        # Wave 1 through the deployment handle: pays the cold compiles
        # (the HTTP proxy gives one request 60 s).
        t0 = time.perf_counter()
        cold = [f.result() for f in [
            pool.submit(lambda p=p: server.completions.remote(
                p, max_tokens=new).result(timeout_s=900))
            for p in waves[0]]]
        cold_s = time.perf_counter() - t0
        # Wave 2 through the OpenAI HTTP route; the first asks for
        # log-probabilities.
        t0 = time.perf_counter()
        warm = [f.result() for f in [
            pool.submit(_http_completion,
                        {"prompt": p, "max_tokens": new, "temperature": 0.0,
                         "logprobs": i == 0})
            for i, p in enumerate(waves[1])]]
        warm_s = time.perf_counter() - t0
    replica = server.device.remote().result(timeout_s=60)
    serve.shutdown()
    # The replica has released the chip (no sleep): the reference runs in
    # a num_tpus=1 task of the same cluster.
    lp = warm[0]["choices"][0]["logprobs"]
    prompt_ids = [256] + list(waves[1][0].encode())  # BOS + bytes
    longest = max(s["prompt_tokens"]) + new
    ref = ray_tpu.get(ray_tpu.remote(num_tpus=1)(reference_logprobs).remote(
        cfg["preset"], cfg["model_kw"], s["n_layers"], SEED, prompt_ids,
        lp["token_ids"], -(-longest // 128) * 128), timeout=600)
    ray_tpu.shutdown()
    del os.environ["JAX_DUMP_IR_TO"]
    rep.saw_device(replica)
    lowered = [open(p).read() for p in glob.glob(
        os.path.join(ir_dir, "*.mlir"))]
    kernels = sorted({k for t in lowered for k in pallas_kernels(t)})
    diffs = [abs(a - b) for a, b in zip(lp["token_logprobs"],
                                        ref["logprobs"])]
    tol = LOGPROB_TOL[model.dtype]
    usage = [r["usage"] for r in cold + warm]
    rep.phase(
        "serve",
        {
            "every_request_returned_exactly_the_tokens_asked": all(
                u["completion_tokens"] == new for u in usage),
            "prompt_token_counts_as_sent": [
                u["prompt_tokens"] for u in usage] == s["prompt_tokens"] * 2,
            "logprobs_one_per_token": len(lp["token_logprobs"]) == new
            == len(lp["token_ids"]) == len(ref["logprobs"]),
            "logprobs_agree_with_forward": bool(diffs) and max(diffs) <= tol,
            "device_platform_tpu": replica["platform"] == "tpu"
            and ref["device"]["platform"] == "tpu",
            # the paged-decode DMA kernel (ops/paged_attention.py
            # _dma_kernel) in a program this cluster lowered — not
            # _paged_decode_xla, not the interpreter
            "device_decode_tpu_custom_call": "_dma_kernel" in kernels,
            "programs_were_dumped": bool(lowered),
            **leak_checks(),
        },
        used={"preset": cfg["preset"], **s}, deploy_s=round(deploy_s, 2),
        cold_wave_s=round(cold_s, 2), warm_wave_s=round(warm_s, 2),
        compile_s_estimate=round(cold_s - warm_s, 2),
        completion_tokens=[u["completion_tokens"] for u in usage],
        logprob_max_abs_diff=max(diffs, default=None), logprob_tol=tol,
        engine_logprobs=lp["token_logprobs"],
        forward_logprobs=ref["logprobs"], programs_dumped=len(lowered),
        pallas_kernels_in_lowered_programs=kernels, replica=replica,
        reference_device=ref["device"])


def phase_four_chips(rep: Report, cfg: dict, init_kw: dict):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    ray_tpu.init(**init_kw)
    t0 = time.perf_counter()
    result = JaxTrainer(
        four_chip_loop, train_loop_config=cfg,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="chip_smoke_four",
                             storage_path=os.path.join(OUT_DIR, "four")),
    ).fit()
    fit_s = time.perf_counter() - t0
    ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"four-chip phase failed: {result.error}")
    m = dict(result.metrics)
    checks = m.pop("checks")
    rep.saw_device(m["device"])
    rep.phase(
        "four_chips",
        {**checks,
         "device_four_chips": m["n_devices"] == 4,
         "device_platform_tpu": m["device"]["platform"] == "tpu",
         **leak_checks()},
        used={"preset": cfg["preset"], **cfg["four"]},
        fit_s=round(fit_s, 2), **m)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="configs.tiny() widths: the CPU rehearsal, or the "
                    "cheap first call on a chip")
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the sharded-train phase and its "
                    "one-device comparison (needs four chips)")
    args = ap.parse_args()
    cfg = sizes(args.tiny)

    from ray_tpu.core.accelerators import detect_tpus
    detected = detect_tpus()
    init_kw = {}
    if args.tiny and not detected:
        # The rehearsal on a machine without chips declares the logical
        # TPUs the phases reserve; device_chips_detected stays false.
        init_kw["num_tpus"] = 4 if args.four_chips else 1

    rep = Report()
    try:
        if args.four_chips:
            phase_four_chips(rep, cfg, init_kw)
        elif phase_probe(rep, init_kw, detected) or args.tiny:
            phase_train(rep, cfg, init_kw)
            phase_serve(rep, cfg, init_kw)
        else:
            print("chip_smoke: the probe found no TPU; not running the "
                  "full-width phases on another backend", file=sys.stderr)
    except BaseException:  # noqa: BLE001 — reported, then exit code 1
        traceback.print_exc()
        rep.ok = False
    finally:
        import ray_tpu
        from ray_tpu import serve
        if ray_tpu.is_initialized():  # a phase died mid-way: stop it all
            try:
                serve.shutdown()
            finally:
                ray_tpu.shutdown()

    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        print("chip_smoke: the DRIVER initialised a JAX backend",
              file=sys.stderr)
        rep.ok = False
    print(json.dumps({"ok": rep.ok, "device": rep.device}), flush=True)
    return 0 if rep.ok else 1


if __name__ == "__main__":
    sys.exit(main())
