"""A training cell: JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True))
-> make_train_step, the entry point a training user calls. The benchmark's
process is the driver and never touches JAX (a chip belongs to one
process); the trainer's worker holds the chips, times every step to
block_until_ready, traces a slice of the window and reports plain data.
Sizes and the loop's shape are chip_smoke.py's `_run_steps`, copied.
"""

from __future__ import annotations

import math
import os
import statistics
import time

# Same math as the plain float32 reference; what differs is bf16 rounding
# points, the Pallas flash kernel's blockwise softmax and fsdp's reduction
# order: chip_smoke.py's FSDP_LOSS_TOL, on losses near 12.
LOSS_TOL = {"bfloat16": 1e-2, "float32": 1e-2}


def worker_loop(config: dict):
    """Runs inside the trainer's worker. Everything it needs is in
    `config` (plain data): the benchmark's files were read by the driver."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perfbench.harness import cells, modelcfg, tracered
    from ray_tpu import diagnostics
    from ray_tpu.models.transformer import (init_params, loss_fn,
                                            param_logical_axes)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import session
    from ray_tpu.train.step import make_train_step

    traffic, seconds = config["traffic"], float(config["seconds"])
    cfg = modelcfg.model_config(config["cfg"], "train_job",
                                config["rehearsal"])
    reference = cells.load_reference(config["root"], config["cfg"])
    flops_per_token = cells.load_flops(config["root"], config["cfg"])
    dev = jax.devices()[0]
    n = jax.device_count()
    if n < config["chips"] or (dev.platform != "tpu"
                               and not config["rehearsal"]):
        session.report({"error": f"found {n} x {dev.platform}, the cell "
                        f"needs {config['chips']} TPU chips"})
        return
    mesh = make_mesh(MeshConfig(fsdp=config["chips"]),
                     devices=jax.devices()[:config["chips"]])
    batch, seq = int(traffic["global_batch"]), int(traffic["seq_len"])
    if config["rehearsal"]:
        seq = int(traffic["rehearsal"]["seq_len"])
    seed = int(config["seed"]) % (2**31 - 5)
    init_fn, _, compile_for, shardings = make_train_step(
        lambda p, b: loss_fn(p, b, cfg, mesh),
        optax.adamw(float(traffic["learning_rate"])), mesh,
        param_logical_axes(cfg))
    # Params are born sharded, in one jitted call from the seed.
    state = jax.block_until_ready(init_fn(jax.jit(
        partial(init_params, cfg), out_shardings=shardings)(
            jax.random.PRNGKey(seed))))
    data_sh = NamedSharding(mesh, P(("dp", "fsdp")))
    n_batches = int(traffic["distinct_batches"])
    batches = [{"tokens": jax.device_put(jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed + 1), i),
        (batch, seq + 1), 0, cfg.vocab, jnp.int32), data_sh)}
        for i in range(n_batches)]
    # The reference's loss on step 0's parameters and batch, before the
    # step donates them.
    ref_loss = reference.mean_loss(state.params, cfg, batches[0]["tokens"])
    step = compile_for(state, batches[0]).lower(state, batches[0]).compile()
    losses = []
    for i in range(int(traffic["warmup_steps"])):
        state, loss = jax.block_until_ready(step(state, batches[i % n_batches]))
        losses.append(float(loss))
    check = {
        "loss0": losses[0], "reference_loss0": ref_loss,
        "ln_vocab": math.log(cfg.vocab),
        "ok": bool(abs(losses[0] - math.log(cfg.vocab)) < 1.0
                   and abs(losses[0] - ref_loss) <= LOSS_TOL[cfg.dtype])}

    trace_dir = config.get("trace_dir")
    trace_from, trace_steps = int(traffic.get("trace_from_step", 3)), int(
        traffic.get("trace_steps", 3))
    misses0 = diagnostics.jit_misses() + diagnostics.jit_traces()
    opened_wall = time.time()
    t0 = time.perf_counter()
    step_ms, ended, k, tracing, traced = [], [], 0, False, None
    inside_last = 0.0
    was_traced = False
    while True:
        if trace_dir and k == trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
            ann = jax.profiler.TraceAnnotation("bench.window")
            ann.__enter__()
        a = time.perf_counter()
        if a - t0 >= seconds:
            break
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, loss = jax.block_until_ready(
                step(state, batches[(k + len(losses)) % n_batches]))
        b = time.perf_counter()
        losses.append(float(loss))
        k += 1
        if b - t0 <= seconds:
            step_ms.append((b - a) * 1e3)
            ended.append(math.isfinite(losses[-1]))
        elif a - t0 < seconds:
            # the step the window's end cuts: the share of it inside
            inside_last = (t0 + seconds - a) / (b - a)
        if tracing and k == trace_from + trace_steps:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, was_traced = False, True
    if tracing:
        jax.profiler.stop_trace()
    if was_traced:
        traced = tracered.reduce_trace(tracered.read_xplane(trace_dir))
        if config.get("debug_dir"):
            _dump(config["debug_dir"], trace_dir)
    # All the work of the window over all its time: whole steps that ended
    # in it, and of the step its end cuts the share that lay inside (whole
    # steps alone move the rate in quanta of one step in ~45: 2.2 %).
    tokens = batch * seq * (len(step_ms) + inside_last)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    session.report({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n},
        "memory_peak_bytes": max([p for p in peaks if p], default=None),
        "opened_wall": opened_wall, "check": check,
        "step_ms": step_ms, "steps_ok": ended,
        "tokens": tokens, "seq": seq, "batch": batch,
        "losses": losses, "trace": traced,
        "trace_steps": trace_steps if traced else 0,
        "compiles_in_window": (diagnostics.jit_misses()
                               + diagnostics.jit_traces() - misses0),
        "flops_per_token": flops_per_token(cfg, seq),
        "n_params": sum(x.size for x in jax.tree.leaves(state.params)),
    })


def _dump(debug_dir: str, trace_dir: str):
    import json

    from perfbench.harness import tracered
    os.makedirs(debug_dir, exist_ok=True)
    tr = tracered.read_xplane(trace_dir, full_names=True)
    with open(os.path.join(debug_dir, "trace_summary.json"), "w") as f:
        json.dump(tracered.summarize(
            tr, 40, "custom|kernel|flash|all-|reduce-|collective"), f,
            indent=1)


def run(cell: dict, cfg: dict, traffic: dict, cellp: dict, args, rec,
        proc_start_wall: float, trace_dir: str | None,
        out_dir: str) -> dict:
    import ray_tpu
    from perfbench.harness import modelcfg
    from perfbench.harness.peaks import peaks_for
    from ray_tpu.core.accelerators import detect_tpus
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    detected = detect_tpus()
    init_kw = {}
    if detected < cell["chips"]:
        if not args.rehearsal:
            raise SystemExit(
                f"perfbench: {detected} TPU chips here, the cell needs "
                f"{cell['chips']}")
        init_kw["num_tpus"] = cell["chips"]
    ray_tpu.init(**init_kw)
    try:
        result = JaxTrainer(
            worker_loop,
            train_loop_config={
                "cfg": cfg, "root": os.path.abspath(args.benchmark_root),
                "traffic": traffic, "chips": cell["chips"],
                "seed": args.seed, "seconds": args.seconds,
                "rehearsal": args.rehearsal, "trace_dir": trace_dir,
                "debug_dir": args.debug_dir},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(name="perfbench_train",
                                 storage_path=os.path.join(out_dir, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError(f"the trainer failed: {result.error}")
    m = result.metrics
    if "error" in m:
        raise SystemExit("perfbench: " + str(m["error"]))
    chips = cell["chips"]
    rec.samples["train_step_ms"] = m["step_ms"]
    rec.values["setup_s"] = m["opened_wall"] - proc_start_wall
    rec.values["tok_per_s_chip"] = m["tokens"] / args.seconds / chips
    rec.counters["new_programs"] = m["compiles_in_window"]
    if m["memory_peak_bytes"]:
        rec.values["hbm_peak_gib"] = m["memory_peak_bytes"] / 2**30
    rec.trace = m["trace"]
    model_cfg = modelcfg.model_config(cfg, "train_job", args.rehearsal)
    rec.context.update(model=model_cfg, cfg=cfg, traffic=traffic,
                       chips=chips, seq=m["seq"], batch=m["batch"],
                       trace_steps=m["trace_steps"])
    if m["device"]["platform"] == "tpu":
        rec.context["peaks"] = peaks_for(m["device"]["kind"])
        # from the median step, so that a traced run (which stops to start
        # and stop the profiler inside the window) reads the same
        step_s = statistics.median(m["step_ms"]) / 1e3
        rec.values["mfu_pct"] = (
            100.0 * m["batch"] * m["seq"] * m["flops_per_token"]
            / (step_s * chips * rec.context["peaks"]["bf16_flops_per_s"]))
    failed = sum(1 for ok in m["steps_ok"] if not ok)
    return {"correct": bool(m["check"]["ok"]) and failed == 0,
            "attempted": len(m["steps_ok"]), "failed": failed,
            "check": m["check"], "device": m["device"],
            "memory_peak_bytes": m["memory_peak_bytes"],
            "n_params": m["n_params"]}
