"""The ways a user asks for the chip must give it or fail loudly, and one
process owns it at a time. CPU-side halves of what chip_smoke.py checks on
the TPU: resource resolution, worker retirement, the zygote staying off
every backend, the compile-cache placement, and the smoke's own rehearsal.
"""

import json
import os
import subprocess
import sys
import warnings

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_use_tpu_on_a_cluster_without_chips_raises():
    """Before the module's four-chip cluster exists: its own head."""
    from ray_tpu.core.status import ResourceError
    from ray_tpu.train import JaxTrainer, ScalingConfig
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        with pytest.raises(ResourceError, match="use_tpu=True"):
            JaxTrainer(lambda cfg: None,
                       scaling_config=ScalingConfig(use_tpu=True)).fit()
    finally:
        ray_tpu.shutdown()


@pytest.fixture(scope="module")
def four_chip_cluster():
    """A head that declares four (logical) chips. JAX_PLATFORMS=cpu is the
    suite's explicit platform, so a worker that reserves them re-latches
    onto the CPU — everything but the device itself runs as on a chip."""
    rt = ray_tpu.init(num_cpus=4, num_tpus=4)
    yield rt
    ray_tpu.shutdown()


def _born_clean():
    """(backends initialised?, parent's argv) from inside a pooled worker
    that has run nothing yet: what it sees is what the zygote had when it
    forked, plus whatever worker boot did."""
    import psutil
    from jax._src import xla_bridge
    return (xla_bridge.backends_are_initialized(),
            psutil.Process(os.getppid()).cmdline())


def test_zygote_forks_before_any_backend(four_chip_cluster):
    """The zygote may import jax but must never initialise a backend: every
    worker would inherit it, and a chip opened there could be opened by
    none of them. Must run first in this module (fresh pool)."""
    initialised, parent = ray_tpu.get(
        ray_tpu.remote(_born_clean).remote(), timeout=60)
    assert "--zygote" in parent, parent
    assert not initialised


def _pid():
    return os.getpid()


class _Holder:
    def pid(self):
        return os.getpid()


def test_chip_holder_is_retired_not_pooled(four_chip_cluster):
    """task, task, actor that each reserve every chip: three different
    processes, none of them a pooled worker afterwards, and the chips are
    free again once the last holder is gone."""
    cpu_pid = ray_tpu.get(ray_tpu.remote(_pid).remote(), timeout=60)
    chip_task = ray_tpu.remote(num_tpus=4)(_pid)
    first = ray_tpu.get(chip_task.remote(), timeout=60)
    second = ray_tpu.get(chip_task.remote(), timeout=60)
    actor = ray_tpu.remote(num_tpus=4)(_Holder).remote()
    third = ray_tpu.get(actor.pid.remote(), timeout=60)
    assert len({cpu_pid, first, second, third}) == 4
    ray_tpu.kill(actor)
    # The reservation returns only after the holder's process is gone.
    fourth = ray_tpu.get(chip_task.remote(), timeout=60)
    assert fourth not in (first, second, third)
    import psutil
    assert not any(psutil.pid_exists(p) and psutil.Process(p).status()
                   != psutil.STATUS_ZOMBIE for p in (first, second, third))
    pooled = {ray_tpu.get(ray_tpu.remote(_pid).remote(), timeout=60)
              for _ in range(8)}
    assert not pooled & {first, second, third, fourth}


def test_use_tpu_reserves_every_chip_of_the_host(four_chip_cluster):
    from ray_tpu.train import JaxTrainer, ScalingConfig
    trainer = JaxTrainer(lambda cfg: None,
                         scaling_config=ScalingConfig(use_tpu=True))
    assert trainer._per_worker_req()["TPU"] == 4
    explicit = JaxTrainer(lambda cfg: None, scaling_config=ScalingConfig(
        use_tpu=True, chips_per_worker=2))
    assert explicit._per_worker_req()["TPU"] == 2


def test_cpu_replica_on_a_chip_cluster_warns_once(four_chip_cluster):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serve import build_disagg_deployment, build_llm_deployment
    from ray_tpu.models import configs
    cpu = LLMConfig(model_id="tiny-cpu", model=configs.tiny())
    with pytest.warns(RuntimeWarning, match="num_tpus_per_replica=0"):
        build_llm_deployment(cpu)
    with pytest.warns(RuntimeWarning, match="serve from the CPU backend"):
        build_disagg_deployment(cpu)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        build_llm_deployment(LLMConfig(model_id="tiny-chip",
                                       model=configs.tiny(),
                                       num_tpus_per_replica=1))


def test_compile_cache_placed_from_outside_or_fixed_in_checkout(monkeypatch):
    import jax

    from ray_tpu.core.accelerators import ensure_compile_cache
    live = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert ensure_compile_cache() == "/some/dir"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == live  # no path set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert ensure_compile_cache() == fixed
        assert ensure_compile_cache() == fixed  # nothing from pid or time
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", live)


def test_preset_overrides_win():
    from ray_tpu.models import configs
    cut = configs.qwen2_7b(n_layers=2)
    assert (cut.n_layers, cut.d_model, cut.vocab) == (2, 3584, 152064)


def test_chip_smoke_tiny_rehearsal_fails_only_on_device_checks():
    """`chip_smoke.py --tiny` on the CPU: every phase runs through the
    same code as on the chip, the run FAILS (there is no CPU mode that
    reports success), and the only false checks are the `device_` ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_NUM_TPUS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        "--tiny"], capture_output=True, text=True, env=env,
                       timeout=300, cwd=REPO)
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.strip()]
    assert r.returncode != 0, r.stdout[-2000:]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = {x["phase"]: x["checks"] for x in lines[:-1]}
    assert list(phases) == ["probe", "train", "serve"], r.stderr[-3000:]
    false = {f"{p}.{k}" for p, c in phases.items()
             for k, v in c.items() if not v}
    assert false and all(k.split(".")[1].startswith("device_")
                         for k in false), false
