"""TPU chip detection and topology helpers.

Parity: reference `python/ray/_private/accelerators/tpu.py:109`
(TPUAcceleratorManager; /dev/accel* & /dev/vfio detection at :135,
TPU_VISIBLE_CHIPS, pod-slice `TPU-{type}-head` resource at :422). TPUs are
first-class schedulable resources here: the head counts chips at boot and the
mesh layer (ray_tpu.parallel) maps logical TPU resource slots to jax devices.
"""

from __future__ import annotations

import glob
import os
import sys

_GKE_TPU_ENV = "TPU_WORKER_ID"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache for this process and every
    process it spawns; returns the directory.

    `JAX_COMPILATION_CACHE_DIR` set from outside wins and nothing is set
    in code. Otherwise the cache lives at ONE fixed path inside the
    checkout: the path is part of the cache key's environment, so a
    directory made from a temporary name, a pid or a time never hits.
    Thresholds stay at JAX's defaults. Workers get the variable through
    `build_worker_env`; a process that compiles on its own calls this
    before its first jit."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before the variable existed
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def detect_tpus() -> int:
    """Number of TPU chips attached to this host (0 if none)."""
    env = os.environ.get("RAY_TPU_NUM_TPUS")
    if env:
        return int(env)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    vfio = glob.glob("/dev/vfio/[0-9]*")
    if vfio:
        return len(vfio)
    return 0


def tpu_pod_name() -> str | None:
    """Pod-slice identity for gang scheduling (parity: tpu.py:422 and
    `ray.util.accelerators.tpu.get_current_pod_name`)."""
    name = os.environ.get("TPU_NAME") or os.environ.get("TPU_POD_NAME")
    return name or None


def tpu_accelerator_type() -> str | None:
    return os.environ.get("TPU_ACCELERATOR_TYPE") or None


def tpu_worker_count() -> int:
    return int(os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") + 1) \
        if os.environ.get("TPU_WORKER_HOSTNAMES") else 1
