"""Tier-1's hold on the code that decides a cell's `correct`: the
benchmark's look-ups (perfbench/tests/test_lookups.py's cases, collected
here because tier-1 collects `tests/` alone; perfbench/tests/
test_program_spans.py's and test_ring_steps.py's beside them), the `deepseek_v2-serve-longdoc`
and `nemotron3_nano_30b-serve-reasoning` cells end to end at their rehearsal
sizes, their references handed a fault, and the latent kernel's and the
state update kernel's cost functions against counts made by hand."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from perfbench.tests.test_lookups import *  # noqa: F401,F403 — its cases
from perfbench.tests.test_program_spans import *  # noqa: F401,F403 — too
from perfbench.tests.test_ring_steps import *  # noqa: F401,F403 — too

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepseek_v2-serve-longdoc"
HYBRID_CELL = "nemotron3_nano_30b-serve-reasoning"


@pytest.mark.parametrize("over,exc,names", [
    # test_lookups.py's own first case names `kv_lora_rank` as a field that
    # ModelConfig lacks; ModelConfig has it now, and that file is the
    # benchmark's (not edited here): the same check with a field it lacks.
    ({"model_fields": {"conv_kernel": {"key": "conv_kernel"}},
      "conv_kernel": 4}, ValueError, ("other.json", "conv_kernel")),
    ({"model_fields": {"d_ff": {"key": "moe_intermediate_size"}}},
     KeyError, ("other.json", "d_ff", "moe_intermediate_size")),
    ({"model_fields": {"d_ff": {"key": "a", "value": 1}}},
     ValueError, ("other.json", "d_ff")),
    ({"model_fields": {"d_ff": {"key": "intermediate_size",
                                "cast": "tuple"}}},
     ValueError, ("other.json", "d_ff", "tuple")),
])
def test_a_bad_table_is_an_error_that_names_file_field_and_key(  # noqa: F811
        over, exc, names):
    from perfbench.harness import cells, modelcfg
    cfg = cells.load_cell(ROOT, "qwen2_7b-serve-chat")["cfg"]
    with pytest.raises(exc) as e:
        modelcfg.model_config(
            {**cfg, "_file": "perfbench/configs/other.json", **over},
            "open_loop")
    assert all(n in str(e.value) for n in names), str(e.value)


@pytest.mark.parametrize("cell", [CELL, HYBRID_CELL])
def test_the_longdoc_cell_rehearses_end_to_end(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 1234), "--seconds", "3",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert out["metrics"] == {}          # never a device metric from a CPU
    assert out["check"]["tokens"] == (96 if cell == HYBRID_CELL else 48)
    assert out["check"]["max_abs_diff"] < 1e-3
    got = out["rehearsal_only_not_device_numbers"]
    assert "decode_step_ms_p50.tput" in got and "prefill_chunk_ms_p50" in got


def _replica_of(cell):
    """A cell's replica at rehearsal sizes, as serve_cell.run builds it."""
    from perfbench.harness import cells, modelcfg, serve_cell
    from perfbench.harness.record import Record
    found = cells.load_cell(ROOT, cell, rehearsal=True)
    cfg = found["cfg"]
    model = modelcfg.model_config(cfg, found["traffic"]["kind"], True)
    engine = modelcfg.engine_config(cfg, found["cellp"], True)
    rep = serve_cell.Replica(model, engine, 11, Record(tracing=False))
    return rep, model, cells.load_reference(ROOT, cfg), cfg


@pytest.fixture(scope="module")
def replica():
    made = _replica_of(CELL)
    yield made
    made[0].stop()


@pytest.fixture(scope="module")
def hybrid_replica():
    made = _replica_of(HYBRID_CELL)
    yield made
    made[0].stop()


FAULTS = {
    "none": lambda c, ids: (c, ids),
    "one_expert_fewer": lambda c, ids: (
        dataclasses.replace(c, moe_top_k=c.moe_top_k - 1), ids),
    "another_rope_theta": lambda c, ids: (
        dataclasses.replace(c, rope_theta=500.0), ids),
    "one_group_fewer": lambda c, ids: (
        dataclasses.replace(c, moe_topk_group=c.moe_topk_group - 1), ids),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_reference_handed_a_fault_turns_correct_false(replica, fault):
    from perfbench.harness import modelcfg, serve_cell
    rep, model, reference, cfg = replica
    d = serve_cell.reference_diffs(rep, reference, model, 11,
                                   n_new=cfg["check"]["new_tokens"],
                                   fault=FAULTS[fault])
    verdict = serve_cell.judge(d, modelcfg.LOGPROB_TOL[model.dtype],
                               cfg["check"])
    assert verdict["ok"] == (fault == "none"), verdict


HYBRID_FAULTS = {
    "none": lambda c, ids: (c, ids),
    "a_dropped_convolution_tap": lambda c, ids: (
        dataclasses.replace(c, ssm_conv_width=c.ssm_conv_width - 1), ids),
    "no_routed_scaling": lambda c, ids: (
        dataclasses.replace(c, moe_routed_scale=1.0), ids),
    "one_expert_fewer": lambda c, ids: (
        dataclasses.replace(c, moe_top_k=c.moe_top_k - 1), ids),
}


@pytest.mark.parametrize("fault", list(HYBRID_FAULTS))
def test_the_hybrid_reference_handed_a_fault_turns_correct_false(
        hybrid_replica, fault):
    from perfbench.harness import modelcfg, serve_cell
    rep, model, reference, cfg = hybrid_replica
    d = serve_cell.reference_diffs(rep, reference, model, 11,
                                   n_new=cfg["check"]["new_tokens"],
                                   fault=HYBRID_FAULTS[fault])
    verdict = serve_cell.judge(d, modelcfg.LOGPROB_TOL[model.dtype],
                               cfg["check"])
    assert verdict["ok"] == (fault == "none"), verdict


def test_a_state_kept_in_bf16_shows_in_the_comparison(hybrid_replica):
    """Float32 against float32 the rehearsal's tolerance (1e-2) is loose;
    what the chip's rule must refuse (tools/checkdist_faults.py there) is
    seen here as a median many times the honest one."""
    from perfbench.harness import serve_cell
    rep, model, reference, cfg = hybrid_replica

    def median(fault):
        d = serve_cell.reference_diffs(rep, reference, model, 11, n_new=48,
                                       fault=fault)
        return sorted(d["diffs"])[24]

    honest = median(None)
    rounded = median(lambda c, ids: (
        dataclasses.replace(c, ssm_state_dtype="bfloat16"), ids))
    assert honest < 1e-5 and rounded > 50 * honest, (honest, rounded)


@pytest.mark.parametrize("fault", ["none", "state_bf16", "wrong_snapshot"])
def test_the_state_check_refuses_what_the_log_probabilities_let_pass(
        hybrid_replica, fault):
    """tools/checkstate.py on the rehearsal replica, by the configuration's
    own `check.state` rule: a prompt of two chunks through the pump, then
    the slot's row and the snapshot's row against the plain recurrence."""
    from perfbench.harness import traffic
    from perfbench.tools import checkstate
    rep, model, reference, cfg = hybrid_replica
    ids = traffic.prompt_ids(11, 10**6 + 7, 400, model.vocab)
    req = checkstate.run_one(rep, ids, 8)
    d = checkstate.state_diffs(rep.engine, reference, model, ids, req, fault)
    assert sorted(d["rows"]) == ["slot", "snapshot_256"]
    verdict = checkstate.judge(d, cfg["check"]["state"], model.dtype)
    assert verdict["ok"] == (fault == "none"), verdict
    assert fault == "none" or verdict["worst"] > 20 * verdict["limit"]


def test_ssm_update_cost_by_hand():
    from perfbench.harness import cells, modelcfg
    found = cells.load_cell(ROOT, HYBRID_CELL)
    model = modelcfg.model_config(found["cfg"], "closed_loop")
    mod = cells.load_module(os.path.join(ROOT, "perfbench", "kernels",
                                         "ssm_update.py"))
    assert mod.cost({"steps": [], "model": model}) is None
    # a model without Mamba layers (an older program's): nothing to read
    assert mod.cost({"steps": [{"lengths": [5]}], "model": object()}) is None
    # one active slot: 64 heads x 64 x 128 of float32 state in and out,
    # three [64, 64] float32 tiles, B and C [8, 128] float32
    state = 64 * 64 * 128
    flops, nbytes = mod.cost_of_step(1, model)
    assert flops == 6 * state
    assert nbytes == 2 * state * 4 + 3 * 64 * 64 * 4 + 2 * 8 * 128 * 4
    # a window of two steps with 3 and 64 active slots, 12 Mamba layers
    ctx = {"model": model, "steps": [{"lengths": [9, 9, 9]},
                                     {"lengths": [7] * 64}]}
    assert mod.cost(ctx) == (12 * 67 * flops, 12 * 67 * nbytes)
    # 0.74 operations a byte: far under the v5e's ridge, memory-bound
    assert 0.7 < flops / nbytes < 0.8


def test_latent_attn_cost_by_hand():
    from perfbench.harness import cells, modelcfg
    found = cells.load_cell(ROOT, CELL)
    model = modelcfg.model_config(found["cfg"], "closed_loop")
    engine = modelcfg.engine_config(found["cfg"], found["cellp"])
    mod = cells.load_module(os.path.join(ROOT, "perfbench", "kernels",
                                         "latent_attn.py"))
    assert mod.cost({"steps": []}) is None
    # one slot of 300 tokens: 3 pages of 128; 128 heads; 576 = 512 + 64
    flops, nbytes = mod.cost_of_step([300], model, 128)
    assert flops == 2 * 128 * 300 * (576 + 512)
    assert nbytes == 3 * 128 * 576 * 2 + 128 * (576 + 512) * 2
    # a window of two steps, 8 layers each
    ctx = {"model": model, "engine": engine,
           "steps": [{"lengths": [300]}, {"lengths": [300, 5000]}]}
    f2, b2 = mod.cost_of_step([300, 5000], model, 128)
    assert mod.cost(ctx) == (8 * (flops + f2), 8 * (nbytes + b2))
    # 242 operations a byte at long lengths: the v5e's ridge (197e12 / 819e9)
    f, b = mod.cost_of_step([8192], model, 128)
    assert 225 < f / b < 245


# ------------------------------------------- the program's spans, reduced


def test_the_spans_tool_prints_the_programs_readings_beside_the_old_ones():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "tools",
                                      "spans_run.py"),
         "--workload", "qwen2_7b-serve-chat", "--seed", str(2**31 + 77),
         "--seconds", "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0
    assert out["compiles_in_window"] == 0 and out["metrics"] == {}
    old = out["rehearsal_only_not_device_numbers"]
    assert {"queue_wait_p50_ms", "batch_occupancy_pct", "decode_step_ms_p50",
            "admit_gap_share_pct", "admit_step_ms_p50"} <= set(old)
    prog = {k for k in out["extra"] if k.startswith("prog.")}
    assert prog >= {"prog.step_host_ms_p50", "prog.admit_unfed_ms_p50",
                    "prog.steps_ahead_pct", "prog.req_queue_ms_p50",
                    "prog.spans_dropped"}
    # since PR 51 no admission of this traffic waits for its first tokens
    # (`fenced` is 0 on every `engine.admit` span), so the reading of the
    # fenced ones has nothing to read
    assert "prog.prefill_fenced_ms_per_krow_p50" not in prog
    assert out["extra"]["prog.spans_dropped"] == 0
    # no device plane in a CPU's trace: like device_idle_pct.*, absent
    assert "prog.idle_with_work_pct" not in prog
    assert out["extra"]["prog.req_queue_ms_p50"] <= (
        old["queue_wait_p50_ms"]["value"])
