"""bench.py resilience: the headline ALWAYS lands, parseable, <2048B.

r04 died rc=124 when one hung get() ate the whole run; r05 exited 0 but
the driver parsed null out of the tail. These tests pin the fixes: a
per-section SIGALRM watchdog (injected hanging section), crash
containment (injected throwing section), and the final-line byte cap
under adversarially bloated extras.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hanging_and_crashing_sections_still_emit_headline(tmp_path):
    """One bench run with a forever-hanging section AND a throwing
    section: the watchdog reaps the hang, the suite stamps both as
    skipped, rc is 0, and the last stdout line is a parseable <2048B
    headline."""
    out_path = tmp_path / "bench_out.json"
    env = {
        **os.environ,
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "BENCH_OUT": str(out_path),
        # Shield the test harness's own clusters from the preflight
        # sweep (it kills every ray_tpu daemon on the box otherwise).
        "RAY_TPU_BENCH_NO_PREFLIGHT": "1",
        "RAY_TPU_BENCH_TEST_HANG": "1",
        "RAY_TPU_BENCH_TEST_CRASH": "1",
        "RAY_TPU_BENCH_SECTIONS": "_hang,_crash",
        "RAY_TPU_BENCH_SECTION_TIMEOUT_S": "3",
        "RAY_TPU_BENCH_BUDGET_S": "600",
    }
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert lines, r.stderr[-3000:]
    headline = json.loads(lines[-1])           # parseable, full stop
    assert len(lines[-1]) < 2048
    assert headline["metric"] == "core_microbenchmark_geomean_vs_ray"
    assert headline["status"] == "partial"     # not "complete": skips
    assert headline["n_skipped"] == 2
    # The watchdog fired within its budget (not the driver's timeout).
    assert '"partial": "_watchdog"' in r.stderr
    detail = json.loads(out_path.read_text())
    skipped = detail["skipped_sections"]
    assert any(s.startswith("_hang: watchdog timeout") for s in skipped), \
        skipped
    assert any(s.startswith("_crash: injected section crash")
               for s in skipped), skipped


def test_boot_crash_still_emits_degraded_headline(tmp_path):
    """Even a crash BEFORE any section (init failure) must emit the
    headline — forced by pointing the object store at an unwritable
    path via a zero budget sections run + bad store size env is fragile,
    so instead inject via RAY_TPU_BENCH_SECTIONS with a budget of 0:
    every section skips, and the suite completes degraded-but-parseable."""
    out_path = tmp_path / "bench_out.json"
    env = {
        **os.environ,
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "BENCH_OUT": str(out_path),
        "RAY_TPU_BENCH_NO_PREFLIGHT": "1",
        "RAY_TPU_BENCH_SECTIONS": "tasks",
        # Budget already burned: the section must skip, not run.
        "RAY_TPU_BENCH_BUDGET_S": "0",
    }
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    headline = json.loads(lines[-1])
    assert len(lines[-1]) < 2048
    assert headline["status"] == "partial"


def test_final_line_stays_under_2048_with_bloated_extras(tmp_path,
                                                         capsys,
                                                         monkeypatch):
    """Adversarial headline: giant host strings, hundreds of metrics —
    the trim ladder must land a parseable <2048B line, never assert."""
    monkeypatch.setenv("BENCH_OUT", str(tmp_path / "out.json"))
    sys.path.insert(0, REPO)
    import bench
    monkeypatch.setattr(bench, "_FINAL_PRINTED", False)
    monkeypatch.setattr(bench, "RESULTS",
                        {f"fake_metric_{i}": 123.456 for i in range(400)})
    monkeypatch.setattr(bench, "SKIPPED", [f"sec{i}: boom" * 10
                                           for i in range(50)])
    monkeypatch.setattr(bench, "EXTRAS", {
        "host": {"cpu_count": 1, "memcpy_gbps": 10.0,
                 "junk": "y" * 3000},
        "adag_pipeline": {"tensor_speedup_x": "z" * 2000},
    })
    bench.final_line("partial")
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert len(out) < 2048
    parsed = json.loads(out)
    assert parsed["metric"] == "core_microbenchmark_geomean_vs_ray"
