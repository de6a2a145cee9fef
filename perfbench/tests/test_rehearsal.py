"""The command end to end on the CPU at the configurations' tiny rehearsal
sizes. A rehearsal proves paths, control flow and counts; it prints no
device metric, and the real command fails without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "perfbench", "run.py")


def _run(*argv, env=None, root=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = [sys.executable, RUN, *argv]
    if root:
        cmd += ["--benchmark-root", root]
    p = subprocess.run(cmd, cwd=ROOT, env=e, capture_output=True, text=True,
                       timeout=600)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    return p.returncode, last, p.stderr


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_real_command_refuses_a_machine_without_a_chip():
    rc, last, err = _run("--workload", "qwen2_7b-serve-chat", "--seed", "1",
                         "--seconds", "2", "--trace", "0")
    assert rc != 0 and last == ""
    assert "TPU" in err


@pytest.mark.parametrize("cell,trace", [
    ("qwen2_7b-serve-chat", 0), ("qwen2_7b-serve-chat", 1),
    ("mixtral_8x7b-serve-chat", 1), ("qwen2_7b-serve-longprompt", 1)])
def test_serve_cells_rehearse(cell, trace):
    rc, last, err = _run("--workload", cell, "--seed", str(2**31 + 77),
                         "--seconds", "3", "--trace", str(trace),
                         "--rehearsal")
    assert rc == 0, err[-2000:]
    out = json.loads(last)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {}          # never a device metric from a CPU
    assert out["compiles_in_window"] == 0
    assert out["check"]["max_abs_diff"] < 1e-3
    got = out["rehearsal_only_not_device_numbers"]
    bench = _bench()
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[group]
             if "workloads" not in m or cell in m["workloads"]}
    assert set(got) <= names
    if not trace:
        assert set(got) == names


def test_train_cell_rehearses_on_four_virtual_devices():
    rc, last, err = _run(
        "--workload", "qwen2_7b-train-fsdp4", "--seed", "5", "--seconds",
        "3", "--trace", "0", "--rehearsal",
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert rc == 0, err[-2000:]
    out = json.loads(last)
    assert out["correct"] and out["device"]["count"] == 4
    assert abs(out["check"]["loss0"] - out["check"]["reference_loss0"]) < 1e-3
    assert out["compiles_in_window"] == 0


def test_a_cell_is_added_with_files_and_one_entry(tmp_path):
    """A throw-away cell, traffic mix and per-layer metric: new files and
    new entries only; nothing that exists is edited."""
    root, pb, before = _copy_of_the_benchmark(tmp_path)
    with open(os.path.join(pb, "traffic", "serve-tiny-burst.json"), "w") as f:
        json.dump({"kind": "open_loop", "max_total_tokens": 512,
                   "prompt_tokens": {"dist": "uniform_grid", "min": 10,
                                     "max": 60},
                   "output_tokens": {"dist": "fixed", "value": 5, "min": 5,
                                     "max": 5}}, f)
    with open(os.path.join(pb, "cells", "mixtral_8x7b-serve-tiny-burst.json"),
              "w") as f:
        json.dump({"rate_rps": 5.0, "ramp_s": 0.4, "warm_admit_together": 2,
                   "trace_at_s": 0.3, "trace_s": 0.5}, f)
    with open(os.path.join(pb, "metrics", "itl_p90_ms.json"), "w") as f:
        json.dump({"reducer": "percentile", "series": "itl_ms", "q": 90}, f)
    bench = _bench()
    bench["workloads"].append({
        "name": "mixtral_8x7b-serve-tiny-burst", "config": "mixtral_8x7b",
        "traffic": "serve-tiny-burst", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "itl_p90_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "replica pump (llm/serve.py)",
        "moves": "itl_p99_ms",
        "workloads": ["mixtral_8x7b-serve-tiny-burst"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"].startswith(("itl", "ttft")):
            m["workloads"].append("mixtral_8x7b-serve-tiny-burst")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, last, err = _run("--workload", "mixtral_8x7b-serve-tiny-burst",
                         "--seed", "9", "--seconds", "2", "--trace", "1",
                         "--rehearsal", root=root)
    assert rc == 0, err[-2000:]
    out = json.loads(last)
    assert out["correct"] and out["attempted"] == 10
    assert "itl_p90_ms" in out["rehearsal_only_not_device_numbers"]
    _nothing_that_existed_was_edited(before)


@pytest.mark.parametrize("shift,correct", [(0.0, True), (1.0, False)])
def test_a_configuration_is_added_with_files_and_entries(tmp_path, shift,
                                                         correct):
    """A throw-away configuration of its own field table and its own
    reference: new files and new entries only. Its table sets a ModelConfig
    field outside the default thirteen, which its reference insists on
    seeing; with its log-probabilities shifted by 1.0 the cell is not
    correct, so the named file decided, not reference/decoder.py."""
    root, pb, before = _copy_of_the_benchmark(tmp_path)
    with open(os.path.join(pb, "configs", "qwen2_7b.json")) as f:
        cfg = json.load(f)
    cfg.update(model_fields={"attn_impl": {"value": "reference"},
                             "unroll_layers": {"key": "unroll"},
                             "remat": None},
               unroll=True, reference="perfbench/reference/throwaway.py")
    with open(os.path.join(pb, "configs", "throwaway.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(pb, "reference", "decoder.py")) as f:
        plain = f.read()
    with open(os.path.join(pb, "reference", "throwaway.py"), "w") as f:
        f.write(plain + f"""

_plain_logprobs_of = logprobs_of


def logprobs_of(params, c, prompt, generated):
    assert c.attn_impl == "reference" and c.unroll_layers is True, c
    logp, margin = _plain_logprobs_of(params, c, prompt, generated)
    return [x - {shift} for x in logp], margin
""")
    shutil.copy(os.path.join(pb, "cells", "qwen2_7b-serve-chat.json"),
                os.path.join(pb, "cells", "throwaway-serve-chat.json"))
    bench = _bench()
    bench["configs"].append({
        "name": "throwaway", "source": cfg["source"],
        "file": "perfbench/configs/throwaway.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-serve-chat", "config": "throwaway",
        "traffic": "serve-chat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"].startswith("itl"):
            m["workloads"].append("throwaway-serve-chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, last, err = _run("--workload", "throwaway-serve-chat", "--seed", "11",
                         "--seconds", "2", "--trace", "0", "--rehearsal",
                         root=root)
    assert rc == 0, err[-2000:]
    out = json.loads(last)
    assert out["correct"] is correct and out["failed"] == 0
    assert abs(out["check"]["max_abs_diff"] - shift) < 1e-3
    _nothing_that_existed_was_edited(before)


def _copy_of_the_benchmark(tmp_path):
    """BENCHMARK.json and the directories of data files and of modules found
    by name, copied: (root, its perfbench/, every file's text)."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "cells", "metrics", "reference",
              "kernels"):
        shutil.copytree(os.path.join(ROOT, "perfbench", d),
                        os.path.join(root, "perfbench", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return (root, os.path.join(root, "perfbench"),
            {p: open(p).read() for p in _files(root)})


def _nothing_that_existed_was_edited(before):
    assert all(open(p).read() == s for p, s in before.items()
               if not p.endswith("BENCHMARK.json"))


def _files(root):
    return [os.path.join(d, f) for d, _s, fs in os.walk(root) for f in fs]
