"""Head runtime: object directory, scheduler, worker pool, actor lifecycle.

This process plays the roles that the reference splits across three daemons:
- GCS (`src/ray/gcs/gcs_server/`): actor lifecycle FSM + restarts
  (gcs_actor_manager.h:328), named-actor registry, KV.
- raylet (`src/ray/raylet/`): worker pool with prestart + idle cache
  (worker_pool.h:228), local scheduler with resource accounting
  (local_task_manager.h:65), dependency manager (dependency_manager.h).
- core worker submission side (`src/ray/core_worker/transport/`): task queues,
  inlined-dependency resolution (dependency_resolver.h), actor call ordering
  (actor_task_submitter.h:78), retries + owner failure handling
  (task_manager.h:216).

Single-node they share one event loop (the listener thread) + one lock, which
removes two process hops from the reference's submit path; the multi-node
split reintroduces a GCS process but keeps this object as the per-node brain.
"""

from __future__ import annotations

import collections
import concurrent.futures
import os
import pickle
import selectors
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import traceback
import uuid

from ray_tpu.core import chaos, serialization, task_events
from ray_tpu.core.jobs import (DEFAULT_JOB, current_job_id,
                               ledger_from_config)
from ray_tpu.core.config import Config, get_config, set_config
from ray_tpu.core.ids import ActorID, ObjectID, WorkerID
from ray_tpu.core.object_store import SharedMemoryStore, default_store_size
from ray_tpu.core.refcount import ReferenceCounter
from ray_tpu.core.status import (
    ActorDiedError,
    GetTimeoutError,
    RayTpuError,
    ResourceError,
    TaskError,
    WorkerCrashedError,
)
from ray_tpu.core.task import ActorCreationSpec, TaskSpec
from ray_tpu.core.transport import (FrameBuffer, encode_frame,
                                    encode_payload, send_msg)

def _reap_stale_stores(shm_dir: str):
    """Unlink arenas whose head process died without shutdown(), and kill
    worker processes orphaned by such a death — a SIGKILLed driver leaves
    zygote workers holding the (unlinked) arena mapping forever otherwise
    (observed: 3 zygotes + a 20GB arena surviving a killed test run)."""
    import glob as _glob

    def _driver_pid(name: str) -> int | None:
        parts = name.split("_")
        if len(parts) < 3:
            return None
        try:
            return int(parts[2])
        except ValueError:
            return None  # old unversioned name; leave it

    def _alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
            return True
        except ProcessLookupError:
            return False
        except PermissionError:
            return True  # alive, owned by someone else

    for path in _glob.glob(os.path.join(shm_dir, "ray_tpu_*")):
        pid = _driver_pid(os.path.basename(path))
        if pid is not None and not _alive(pid):
            try:
                os.unlink(path)
            except OSError:
                pass
    # Orphaned workers: cmdline `... -m ray_tpu.core.worker [--zygote]
    # <arena path>`; reap when the arena's driver pid is dead.
    try:
        pids = [int(d) for d in os.listdir("/proc") if d.isdigit()]
    except OSError:
        return
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
        except OSError:
            continue
        if b"ray_tpu.core.worker" not in argv:
            continue
        for arg in argv:
            name = os.path.basename(arg.decode("utf-8", "replace"))
            if not name.startswith("ray_tpu_"):
                continue
            drv = _driver_pid(name)
            if drv is not None and not _alive(drv):
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
                break


IDLE, BUSY, ASSIGNED_ACTOR, DEAD = "idle", "busy", "actor", "dead"
A_PENDING, A_ALIVE, A_RESTARTING, A_DEAD = "pending", "alive", "restarting", "dead"


def build_worker_env(config, node_id_hex: str,
                     is_head: bool = False) -> dict:
    """Environment for spawned worker processes (shared head/agent)."""
    from ray_tpu.core.accelerators import ensure_compile_cache
    ensure_compile_cache()  # every worker compiles into the same cache
    env = dict(os.environ)
    env.update(config.to_env())
    env["RAY_TPU_NODE_ID"] = node_id_hex
    env["RAY_TPU_IS_HEAD_NODE"] = "1" if is_head else "0"
    # Accelerator visibility (parity: the reference assigns
    # CUDA_VISIBLE_DEVICES / TPU_VISIBLE_CHIPS per worker): pooled workers
    # boot on the CPU backend — a chip belongs to one process at a time,
    # so a CPU-bound task must never open it. The spawner's own
    # JAX_PLATFORMS (possibly unset: JAX then picks the TPU by itself) is
    # kept aside so a worker executing a num_tpus>0 task can re-latch
    # onto it (worker._ensure_accelerator_platform).
    platform = config.worker_jax_platform
    if platform:
        env["RAY_TPU_HOST_JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "")
        env["JAX_PLATFORMS"] = platform
    env.setdefault("PYTHONPATH", "")
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo_root + os.pathsep + env["PYTHONPATH"]
    return env


def apply_pip_env(env: dict, zygote, pip: list | None):
    """Prepare a worker spawn for a package runtime env (pip/uv/conda/
    container): build/reuse the env, point the worker at it, and force a
    cold spawn (the zygote's env is baked at fork-server start). Returns
    (env, zygote, env_key). Shared by the head runtime and node agents."""
    if not pip:
        return env, zygote, None
    from ray_tpu.core.runtime_env import (
        _norm_spec,
        ensure_conda_env,
        ensure_pip_env,
        pip_env_key,
    )
    tool, pkgs = _norm_spec(pip)
    env = dict(env)
    if tool == "conda":
        # A whole-interpreter env: the worker runs the env's own python
        # (parity: runtime_env/conda.py activating the env for the worker).
        prefix = ensure_conda_env(pkgs)
        env["RAY_TPU_PYTHON"] = os.path.join(prefix, "bin", "python")
        env["CONDA_PREFIX"] = prefix
    elif tool == "container":
        # spawn_worker_process wraps the worker in `podman run` (it owns
        # the session dir needed for the mounts).
        env["RAY_TPU_CONTAINER_IMAGE"] = pkgs[0]
    else:
        env["RAY_TPU_VENV_SITE"] = ensure_pip_env(pip)
    env_key = pip_env_key(pip)
    env["RAY_TPU_ENV_KEY"] = env_key
    return env, None, env_key


# All cold worker forks go through ONE long-lived spawner thread. The
# workers arm PR_SET_PDEATHSIG, and on Linux the "parent" whose death
# delivers the signal is the THREAD that forked the child — a worker
# forked from a transient spawn thread is SIGKILLed the moment that
# thread exits, IF it armed the prctl while the thread was still alive.
# That race is why warm (fast-booting) env-pool workers died silently at
# boot with empty logs while cold boots survived: a slow child armed
# after the spawn thread was already gone (prctl then never fires).
# Forking from a thread that lives as long as the process makes the
# pdeathsig mean what it was always meant to mean.
_spawn_exec = None
_spawn_exec_lock = threading.Lock()


def _on_spawner_thread(fn):
    global _spawn_exec
    if threading.current_thread() is threading.main_thread():
        return fn()  # main thread outlives everything: fork directly
    with _spawn_exec_lock:
        if _spawn_exec is None:
            import concurrent.futures
            _spawn_exec = concurrent.futures.ThreadPoolExecutor(
                1, thread_name_prefix="rtpu-spawn")
    return _spawn_exec.submit(fn).result()


def spawn_worker_process(worker_id: WorkerID, store_path: str, env: dict,
                         zygote: "_Zygote | None", session_dir: str):
    """Fork a worker from the warm zygote, or cold-exec as fallback.
    Returns (parent_sock, proc). Shared by the head runtime and node agents
    (parity: WorkerPool::StartWorkerProcess, worker_pool.h:228)."""
    import socket as socket_mod
    log_path = os.path.join(session_dir, "logs",
                            f"worker-{worker_id.hex()[:8]}.out")
    # Fallback runs on a FRESH socketpair: a zygote that died mid-spawn may
    # have forked a child that already holds the first pair's worker end.
    parent = child = proc = None
    if zygote is not None:
        parent, child = socket_mod.socketpair(
            socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        pid = zygote.spawn(worker_id.hex(), child, log_path)
        if pid:
            proc = _ForkedProc(pid, zygote)
        else:
            parent.close()
            child.close()
            parent = child = None
    if proc is None:
        parent, child = socket_mod.socketpair(
            socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        python = env.get("RAY_TPU_PYTHON") or sys.executable
        image = env.get("RAY_TPU_CONTAINER_IMAGE", "")
        # Popen dups stdout into the child, so the parent's copy closes
        # right after the spawn — one leaked log fd per spawn otherwise.
        logf = open(log_path, "ab")
        try:
            if image:
                # Container wrapper (podman --preserve-fds=1 maps fd 3):
                # the worker's socketpair end must sit at exactly fd 3
                # inside. close_fds=False + preexec dup2: dup2's result fd
                # has no CLOEXEC so it survives exec, while every other
                # parent fd is CLOEXEC by Python default (pass_fds can't
                # express "keep the fd I will only create in the child's
                # preexec").
                from ray_tpu.core.runtime_env import container_worker_argv
                repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))
                fd = child.fileno()
                cmd = (container_worker_argv(image, session_dir, repo_root)
                       + ["python", "-m", "ray_tpu.core.worker",
                          store_path, worker_id.hex(), "3"])
                proc = _on_spawner_thread(lambda: subprocess.Popen(
                    cmd, env=env, close_fds=False,
                    preexec_fn=lambda: os.dup2(fd, 3),
                    stdout=logf, stderr=subprocess.STDOUT))
            else:
                proc = _on_spawner_thread(lambda: subprocess.Popen(
                    [python, "-m", "ray_tpu.core.worker",
                     store_path, worker_id.hex(), str(child.fileno())],
                    pass_fds=[child.fileno()], env=env,
                    close_fds=True, stdout=logf,
                    stderr=subprocess.STDOUT))
        finally:
            logf.close()
    child.close()
    return parent, proc


class WorkerHandle:
    kind = "worker"

    def __init__(self, worker_id: WorkerID, sock, proc, node_id: bytes = b""):
        self.worker_id = worker_id
        self.sock = sock
        self.send_lock = threading.Lock()
        self.proc = proc
        self.node_id = node_id
        self.state = IDLE
        self.connected = threading.Event()
        self.registered_fns: set[bytes] = set()
        # FIFO of specs dispatched to this worker and not yet completed:
        # [0] is executing, the rest are pipelined behind it (depth-K
        # dispatch, parity: max_tasks_in_flight_per_worker lease reuse).
        self.assigned: collections.deque[TaskSpec] = collections.deque()
        self.actor_id: bytes | None = None
        # Per-env worker pools (parity: worker_pool.h:228): None = default
        # pool; otherwise the pip env key the worker booted with.
        self.env_key: str | None = None
        # Worker peer plane: UDS path where this (head-node) worker
        # accepts direct actor calls from sibling workers.
        self.peer_path: str | None = None
        self.buffer = FrameBuffer()
        # Cached {"node","worker"} hex pair for DISPATCHED task events
        # (built once; per-dispatch hex() measurably hit the storm path).
        self.tev_data: dict | None = None
        # One process owns a chip at a time. Set when TPU work is
        # dispatched here (the worker re-latches onto the chip and keeps
        # it for its lifetime): such a worker never re-enters the idle
        # pool — it is retired when that work ends, and `chip_token` (the
        # reservation the work held) is released only once the process is
        # gone, so whoever is granted the chips next finds them free.
        self.owns_chip = False
        self.chip_token = None

    @property
    def current_task(self) -> "TaskSpec | None":
        return self.assigned[0] if self.assigned else None

    def send(self, msg):
        send_msg(self.sock, msg, self.send_lock)

    def kill(self) -> bool:
        """Force-kill the worker process. Returns True if a kill was issued."""
        if self.proc is None:
            return False
        try:
            self.proc.kill()
        except ProcessLookupError:
            pass
        return True


class RemoteWorkerHandle(WorkerHandle):
    """A worker on another node; every message relays through its node agent
    (parity: the reference pushes tasks to remote workers over the worker's
    own gRPC service, `core_worker.proto:457` — here the per-node agent is
    the remote endpoint and fans in/out to its local workers)."""

    def __init__(self, worker_id: WorkerID, node_conn: "NodeConn",
                 node_id: bytes):
        super().__init__(worker_id, None, None, node_id)
        self.node_conn = node_conn
        self.connected.set()

    def send(self, msg):
        self.node_conn.send(("to_worker", self.worker_id.binary(), msg))

    def kill(self) -> bool:
        try:
            self.node_conn.send(("kill_worker", self.worker_id.binary()))
        except OSError:
            pass
        return True


class NodeConn:
    """Head-side handle for one node agent's TCP connection."""

    kind = "node"

    def __init__(self, sock):
        self.sock = sock
        self.send_lock = threading.Lock()
        self.buffer = FrameBuffer()
        self.node_id: bytes | None = None  # set on register_node
        self.client_handle = None  # set on client_hello (client mode)
        # Native head core bookkeeping (cpp/head_core.cc): the pump tag
        # this conn's fd rides, and — once register_node lands — its
        # native node index (grant outbox + completion-ledger key).
        self._htag: int | None = None
        self._nidx: int | None = None

    def send(self, msg):
        send_msg(self.sock, msg, self.send_lock)


class _Acceptor:
    """Selector sentinel for the cluster's listening socket."""

    kind = "accept"
    sock = None       # set in enable_cluster (the native pump accepts
    _htag = None      # through Python, so the handle carries the socket)


class NodeState:
    """Per-node resource/worker bookkeeping (parity: a `GcsNodeManager` row
    plus that node's view in `ClusterResourceManager`,
    `scheduling/cluster_resource_data.h`)."""

    def __init__(self, node_id: bytes, resources: dict, conn: NodeConn | None,
                 peer_addr=None, hostname: str = "", pid: int = 0,
                 ctrl_addr=None):
        self.node_id = node_id
        self.conn = conn  # None for the head node
        self.peer_addr = peer_addr  # (host, port) serving cross-node pulls
        # (host, port) of the agent's peer CONTROL listener: direct
        # agent<->agent actor-call frames ride it (parity: the reference's
        # worker-to-worker CoreWorkerService gRPC, actor_task_submitter.h:78
        # — here hoisted to one channel per agent pair).
        self.ctrl_addr = ctrl_addr
        self.hostname = hostname or socket.gethostname()
        self.pid = pid
        self.total = dict(resources)
        self.available = dict(resources)
        self.idle: collections.deque[WorkerHandle] = collections.deque()
        self.workers: dict[bytes, WorkerHandle] = {}
        self.pending_actor_assign: collections.deque[bytes] = collections.deque()
        self.state = "ALIVE"
        self.last_heartbeat = time.monotonic()
        self.last_spawn_req = 0.0
        # --- node-lease dispatch (the raylet-local scheduling split,
        # parity: cluster_task_manager.h:45 / local_task_manager.h:65) ---
        # Plain dep-free tasks are LEASED to the node as a whole: the
        # agent owns per-worker dispatch, the head only debits node
        # resources and banks completions per batch. task_id -> spec.
        self.leases: dict[bytes, "TaskSpec"] = {}
        # Grant timestamps + re-drive counts for the lease watchdog:
        # task_id -> [sent_monotonic, redrives]. A node_exec frame lost on
        # the wire (or dropped by chaos) would otherwise park its lease in
        # `leases` forever while the agent sits idle.
        self.lease_sent: dict[bytes, list] = {}
        # fn_ids whose blob this node's agent already caches.
        self.lease_fns: set[bytes] = set()
        # Agent-reported load view (versioned deltas riding heartbeats —
        # the ray_syncer.h:20 role): {"v", "idle", "backlog"}.
        self.load_view: dict = {}
        self.last_reclaim = 0.0
        # Cluster-view broadcast cursor: the head-global view version this
        # agent has been sent up to. Broadcasts carry only entries newer
        # than the cursor (TCP FIFO makes advancing it at send time safe);
        # a re-registration resets it to 0, which is the full-view resend.
        self.cview_cursor = 0


class _ForkedProc:
    """Popen-shaped handle for a worker forked by the zygote. We are not its
    parent: kills are routed through the zygote, which only signals pids that
    are still its own live-or-unreaped children (pid-recycling safe). poll()
    probes the pid directly — it can momentarily mis-report a recycled pid as
    'our' worker, so it is only used in bounded wait loops (shutdown), never
    for kill decisions."""

    def __init__(self, pid: int, zygote: "_Zygote"):
        self.pid = pid
        self._zygote = zygote

    def kill(self):
        self._zygote.kill(self.pid)

    terminate = kill

    def poll(self):
        try:
            os.kill(self.pid, 0)
            return None
        except (ProcessLookupError, PermissionError):
            return 0

    def wait(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("forked-worker", timeout)
            time.sleep(0.01)
        return 0


class _Zygote:
    """Forkserver client. One subprocess pays the interpreter+jax import once;
    each worker spawn is then a fork (~ms) instead of a cold exec (~2s, worse
    under concurrent-import CPU contention). Spawn protocol: JSON request +
    SCM_RIGHTS socket fd out, 4-byte child pid back."""

    def __init__(self, session_dir: str, store_path: str, env: dict):
        import socket as socket_mod
        parent, child = socket_mod.socketpair(
            socket_mod.AF_UNIX, socket_mod.SOCK_STREAM)
        # Parent's log-fd copy closes after the spawn (Popen dup'd it).
        logf = open(os.path.join(session_dir, "logs", "zygote.out"), "ab")
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.worker", "--zygote",
                 store_path, str(child.fileno())],
                pass_fds=[child.fileno()], env=env, close_fds=True,
                stdout=logf, stderr=subprocess.STDOUT)
        finally:
            logf.close()
        child.close()
        self.sock = parent
        self.lock = threading.Lock()
        self._ready = threading.Event()
        self._dead = False
        threading.Thread(target=self._wait_ready, daemon=True,
                         name="rtpu-zygote-ready").start()

    def _recv_exact(self, n: int) -> bytes | None:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def _wait_ready(self):
        try:
            if self._recv_exact(4) == b"RDY0":
                self._ready.set()
            else:
                self._dead = True
        except OSError:
            self._dead = True

    def _roundtrip(self, req: bytes, rights=None) -> int | None:
        import struct
        with self.lock:
            if self._dead:
                return None
            try:
                # Bounded: a wedged zygote must not freeze spawning/kills
                # forever while we hold the lock — poison and fall back.
                self.sock.settimeout(15.0)
                # staticcheck: ok blocking-under-lock — self.lock IS this
                # channel's serialization lock (one req/reply in flight),
                # and the settimeout above bounds the stall.
                self.sock.sendmsg([req], rights or [])
                buf = self._recv_exact(4)
                if buf is None:
                    self._dead = True
                    return None
                return struct.unpack("<I", buf)[0]
            except OSError:
                self._dead = True
                return None

    def _wait_usable(self, timeout: float) -> bool:
        if self._dead:
            return False
        if not self._ready.wait(timeout):
            # Hung during import: poison so later spawns fall back immediately.
            self._dead = True
            return False
        return not self._dead

    def spawn(self, worker_id_hex: str, child_sock, log_path: str,
              timeout: float = 60.0) -> int | None:
        if not self._wait_usable(timeout):
            return None
        import array
        import json
        import socket as socket_mod
        req = json.dumps({"worker_id": worker_id_hex, "log": log_path}).encode()
        rights = [(socket_mod.SOL_SOCKET, socket_mod.SCM_RIGHTS,
                   array.array("i", [child_sock.fileno()]).tobytes())]
        return self._roundtrip(req, rights)

    def kill(self, pid: int):
        """Ask the zygote to SIGKILL its child; no-ops on recycled pids."""
        import json
        if self._roundtrip(json.dumps({"kill": pid}).encode()) is None:
            # Zygote gone: its children were reparented; signal directly as a
            # last resort (small recycle risk only in this rare path).
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def close(self):
        self._dead = True
        try:
            self.sock.close()
        except OSError:
            pass
        try:
            self.proc.kill()
            self.proc.wait(timeout=2.0)
        except Exception:  # noqa: BLE001
            pass


def _pip_key_of(spec) -> str | None:
    """Per-env worker-pool key of a spec (None = the default pool)."""
    from ray_tpu.core.runtime_env import env_spec, pip_env_key
    pip = env_spec(getattr(spec, "runtime_env", None))
    return pip_env_key(pip) if pip else None


def _journal_safe_spec(spec):
    """Copy a task/actor spec with memoryview buffers flattened to bytes so
    it can ride the plain-pickle persistence journal."""
    import copy
    out = copy.copy(spec)
    if getattr(out, "buffers", None):
        out.buffers = [bytes(b) for b in out.buffers]
    if getattr(out, "inline_deps", None):
        out.inline_deps = {
            k: (p, [bytes(b) for b in (bufs or [])])
            for k, (p, bufs) in out.inline_deps.items()}
    return out


class _JournaledDict(dict):
    """Dict that writes every mutation through to the head's persistence
    store (a no-op append when persistence is off). Covers the direct
    `rt.kv[...] = v` mutation style used across the control plane."""

    def __init__(self, table: str, store):
        super().__init__()
        self._table = table
        self._store = store

    def __setitem__(self, key, value):
        dict.__setitem__(self, key, value)
        self._store.append(self._table, key, value)

    def __delitem__(self, key):
        dict.__delitem__(self, key)
        self._store.delete(self._table, key)

    def pop(self, key, *default):
        had = key in self
        out = dict.pop(self, key, *default)
        if had:
            self._store.delete(self._table, key)
        return out

    def setdefault(self, key, default=None):
        if key not in self:
            self[key] = default
        return dict.__getitem__(self, key)

    def update(self, *args, **kw):
        for k, v in dict(*args, **kw).items():
            self[k] = v

    def load_silent(self, entries: dict):
        """Restore replayed state without re-journaling it."""
        dict.update(self, entries)


class ActorState:
    def __init__(self, cspec: ActorCreationSpec):
        self.cspec = cspec
        self.state = A_PENDING
        self.worker: WorkerHandle | None = None
        self.queued: collections.deque[TaskSpec] = collections.deque()
        self.inflight: dict[bytes, TaskSpec] = {}  # task_id -> spec
        self.death_cause = None
        self.seq = 0
        self.resources_reserved: dict[str, float] = {}
        self.node_id: bytes | None = None
        # True for actors rebuilt from the persistence journal after a head
        # restart: they sit in RESTARTING until an agent re-registration
        # adopts their still-running worker (or the adopt grace expires).
        self.restored = False


class ObjectDirectory:
    """Owner's object table: where every object is and who is waiting.

    Parity: memory store + ownership-based object directory
    (`store_provider/memory_store/memory_store.h`,
    `ownership_based_object_directory.h:39`).
    """

    def __init__(self):
        self.entries: dict[bytes, tuple] = {}  # oid -> ("inline", v)|("shm",)|("err", e)
        self.callbacks: dict[bytes, list] = {}
        self.lock = threading.Lock()
        # Global ready-event pulse: wait() re-probes on each pulse instead
        # of registering per-ref callbacks — pop-one-ref wait loops over N
        # refs would otherwise pile up O(N^2) ghost callbacks.
        self.ready_cv = threading.Condition()
        self.ready_gen = 0
        # Optional write-through hooks (head WAL "dir" table + the shard
        # mirror): on_location(oid, node_id, merged_locs) after a shm
        # location lands, on_discard(oid) after an entry drops. Called
        # OUTSIDE self.lock; None (the default) costs one attribute read.
        self.on_location = None
        self.on_discard = None

    def _pulse_ready(self):
        with self.ready_cv:
            self.ready_gen += 1
            self.ready_cv.notify_all()

    def put(self, oid: bytes, entry: tuple):
        with self.lock:
            self.entries[oid] = entry
            cbs = self.callbacks.pop(oid, [])
        for cb in cbs:
            cb(entry)
        self._pulse_ready()

    def lookup(self, oid: bytes):
        with self.lock:
            return self.entries.get(oid)

    def split_ready(self, oids: list) -> tuple[list, list]:
        """(ready, pending) under ONE lock acquisition, single pass —
        wait() probes thousands of refs per call."""
        ready: list = []
        pending: list = []
        with self.lock:
            entries = self.entries
            for o in oids:
                (ready if o in entries else pending).append(o)
        return ready, pending

    def add_location(self, oid: bytes, node_id: bytes):
        """Merge a replica location into a shm entry, creating it if absent.
        No-op for non-shm entries (inline/err outrank locations)."""
        hook = self.on_location
        merged = entry = None
        cbs: list = []
        with self.lock:
            e = self.entries.get(oid)
            if e is not None:
                if e[0] == "shm" and node_id not in e[1]:
                    e[1].add(node_id)
                    merged = sorted(e[1]) if hook is not None else None
            else:
                entry = ("shm", {node_id})
                self.entries[oid] = entry
                merged = [node_id] if hook is not None else None
                cbs = self.callbacks.pop(oid, [])
        if merged is not None:
            hook(oid, node_id, merged)
        if entry is None:
            return
        for cb in cbs:
            cb(entry)
        self._pulse_ready()

    def on_ready(self, oid: bytes, cb):
        with self.lock:
            entry = self.entries.get(oid)
            if entry is None:
                self.callbacks.setdefault(oid, []).append(cb)
                return None
        cb(entry)
        return entry

    def discard(self, oid: bytes):
        hook = self.on_discard
        with self.lock:
            e = self.entries.pop(oid, None)
        if hook is not None and e is not None and e[0] == "shm":
            hook(oid)


class PlacementGroupState:
    """Head-side record of a placement group.

    Parity: `gcs_placement_group_manager.h:232` (lifecycle) +
    `gcs_placement_group_scheduler.h:288` (2PC reserve, collapsed to one
    atomic carve-out on the single-node pool). `bundle_avail` tracks the
    unconsumed remainder of each bundle's reservation.
    """

    __slots__ = ("pg_id", "bundles", "strategy", "name", "state",
                 "bundle_avail", "bundle_nodes", "ready_oid")

    def __init__(self, pg_id: bytes, bundles, strategy: str, name: str):
        self.pg_id = pg_id
        self.bundles = bundles
        self.strategy = strategy
        self.name = name
        self.state = "PENDING"  # PENDING/CREATED/REMOVED/INFEASIBLE
        self.bundle_avail = [dict(b) for b in bundles]
        self.bundle_nodes: list[bytes] = []  # bundle i -> hosting node id
        self.ready_oid = os.urandom(16)


def _sum_bundles(bundles) -> dict[str, float]:
    total: dict[str, float] = {}
    for b in bundles:
        for k, v in b.items():
            total[k] = total.get(k, 0.0) + v
    return total


def _kv_key_bytes(k) -> bytes:
    return k.encode() if isinstance(k, str) else k


# Process-global emission ring, bound once (record() runs per task state
# transition — a ring() call per record showed up in the task storm).
_TEV_RING = task_events.ring()


class TaskEventBuffer:
    """Bounded ring of task state transitions (parity: task_event_buffer.h:225).

    `record` sits on the per-call hot path, so it stores the spec's two name
    fields (not the spec itself — that would pin payload/buffer memory in
    the ring) and defers string formatting to read time (`snapshot`).

    This legacy ring holds the HEAD's scheduling-path view only (it backs
    `state.list_tasks` and the bypass-evidence tests); the cluster-wide
    task-event pipeline (core/task_events.py) is fed by the forward in
    `record` — `pipeline_state`/`data` let a call site give the pipeline a
    richer transition (LEASE_GRANTED with node + lease_seq, DISPATCHED
    with the worker) while the legacy ring keeps its coarse state."""

    def __init__(self, maxlen: int, export=None):
        self.events = collections.deque(maxlen=maxlen)
        self.finished_total = 0  # monotonic, survives ring eviction
        self._export = export  # ExportEventWriter | None (off the hot path
        # unless the export_events config flag is set)

    def record(self, task_id: bytes, spec, state: str,
               pipeline_state: str | None = None,
               data: dict | None = None):
        now = time.time()
        name = spec if isinstance(spec, str) else (spec.name, spec.method_name)
        self.events.append((now, task_id, name, state))
        if state == "FINISHED":
            self.finished_total += 1
        ring = _TEV_RING
        if ring.enabled and not isinstance(spec, str):
            # Inlined ring emit (this is a per-transition hot path; the
            # extra call frames + second clock read measurably moved the
            # task storm).
            ev = ring.events
            if len(ev) >= ring.capacity:
                ring.dropped += 1
            ev.append((task_id,
                       max(0, (spec.max_retries or 0)
                           - (spec.retries_left or 0)),
                       pipeline_state or state, now, name, data))
        if self._export is not None:
            lease_seq = (None if isinstance(spec, str)
                         else getattr(spec, "lease_seq", None))
            self._export.emit("TASK", task_id=task_id.hex(),
                              name=self._name(name), state=state,
                              lease_seq=lease_seq)

    @staticmethod
    def _name(name) -> str:
        if isinstance(name, str):
            return name
        base, method = name
        return f"{base}.{method}" if method else (base or "task")

    def snapshot(self) -> list:
        """Events with names formatted: [(ts, task_id, name, state)]."""
        return [(ts, tid, self._name(s), st) for ts, tid, s, st in self.events]

    def summary(self) -> dict:
        counts: dict[str, int] = {}
        for _, _, s, state in self.events:
            key = f"{self._name(s)}:{state}"
            counts[key] = counts.get(key, 0) + 1
        return counts


class Runtime:
    """The head-node runtime singleton (driver side)."""

    def __init__(self, num_cpus=None, num_tpus=None, resources=None,
                 object_store_memory=None, system_config=None):
        cfg = Config(system_config)
        set_config(cfg)
        self.config = cfg
        self.session_id = uuid.uuid4().hex[:12]
        from ray_tpu.core.session import new_session_dir
        self.session_dir = new_session_dir("session")

        store_size = object_store_memory or default_store_size(cfg)
        shm_dir = "/dev/shm" if os.path.isdir("/dev/shm") else self.session_dir
        _reap_stale_stores(shm_dir)
        # pid in the name lets the next init reap arenas of crashed drivers.
        self.store_path = os.path.join(
            shm_dir, f"ray_tpu_{os.getpid()}_{self.session_id}")
        self.store = SharedMemoryStore(
            self.store_path, size=store_size,
            num_slots=cfg.object_store_hash_slots, create=True,
            num_shards=cfg.object_store_shards)
        from ray_tpu.core.object_store import configure_store
        configure_store(self.store, cfg)
        # Reservation refills make room through the spill machinery once
        # per EXTENT instead of a stats probe + spill pass per put.
        self.store.spill_hook = self._ensure_headroom
        # Serializes the health loop's orphan-reservation sweep against
        # shutdown()'s arena unmap (a sweep over freed shm segfaults).
        self._store_close_lock = threading.Lock()

        # logical resources (parity: scheduling/resource_set.h)
        from ray_tpu.core.accelerators import detect_tpus
        detected_tpus = detect_tpus()
        self.total_resources: dict[str, float] = {
            "CPU": float(num_cpus if num_cpus is not None else (os.cpu_count() or 1)),
            "TPU": float(num_tpus if num_tpus is not None else detected_tpus),
        }
        for k, v in (resources or {}).items():
            self.total_resources[k] = float(v)

        self.directory = ObjectDirectory()
        self.refcount = ReferenceCounter(free_callback=self._free_object)
        # Actor execs relayed by the head (diagnostics; the direct
        # worker<->worker plane keeps this flat under actor storms).
        self.actor_head_dispatches = 0
        # Export API (parity: export_api/ durable event stream): opt-in
        # JSONL writer fed by task/actor/node state transitions.
        self.export_events = None
        if cfg.export_events:
            from ray_tpu.util.event_export import ExportEventWriter
            self.export_events = ExportEventWriter(self.session_dir)
        self.task_events = TaskEventBuffer(cfg.task_events_buffer_size,
                                           export=self.export_events)
        # Task-event pipeline (parity: task_event_buffer.h:225 emission +
        # gcs_task_manager.h:94 head storage): the head's own emissions go
        # through the process ring like every other process; agents and
        # workers ship theirs on frames they already send, and everything
        # merges per (task_id, attempt) in task_store.
        task_events.configure(cfg)
        self.task_store = task_events.TaskEventStorage(
            max_tasks=cfg.task_events_max_tasks,
            max_per_job=getattr(cfg, "task_events_max_per_job", 0),
            export=self.export_events)
        # Arriving event batches park here and merge on a dedicated
        # thread — the listener must never pay the ingest (a storm ships
        # thousands of events/s, and merging them inline measurably
        # slowed the dispatch loop). Bounded: overflow evicts the oldest
        # parked batch, counted as source drops, never blocks.
        self._tev_pending: collections.deque = collections.deque()
        self._tev_overflow = 0
        # Guards _tev_overflow's read-modify-writes: the listener's
        # overflow bump races the ingest thread's swap-and-reset, and an
        # unguarded swap LOSES drop counts — the one thing drop
        # accounting must never do. Both touches are cold (overflow only
        # fires with 512 batches parked; the drain ticks at 4 Hz).
        self._tev_overflow_lock = threading.Lock()
        # Worker-process metric registries, merged at scrape time tagged
        # WorkerId (parity: the per-node metrics agent aggregating worker
        # metrics, _private/metrics_agent.py:492). wid -> {name: snapshot}.
        self._worker_metrics: dict[bytes, dict] = {}

        self.lock = threading.RLock()
        # --- node table (parity: gcs_node_manager) ---
        self.head_node_id = os.urandom(8)
        self.head_node = NodeState(self.head_node_id,
                                   self.total_resources, conn=None,
                                   pid=os.getpid())
        self.nodes: dict[bytes, NodeState] = {self.head_node_id: self.head_node}
        self._node_order: list[bytes] = [self.head_node_id]
        self.cluster_addr: str | None = None
        self.client_proto_addr: str | None = None
        self._cluster_srv = None
        self._spread_idx = 0
        # (dest_nid, oid) -> {"cbs": [done cbs], "src": src_nid,
        #                     "attempt": n} — attempt correlates completions
        # to the live attempt so a stale failure from an aborted attempt
        # can't kill a retried fetch.
        self._fetches: dict[tuple, dict] = {}
        self._fetch_attempts = 0
        # Diagnostics (under self.lock): cross-node object movements the
        # head orchestrated (one per registered (dest, oid) fetch) + the
        # agent-initiated object_src pulls. The data-plane locality tests
        # assert a co-located pipeline keeps these flat.
        self.cross_node_fetches = 0
        # fetch_many frames sent (vectored same-source pull batches).
        self.fetch_batches_sent = 0
        # On-demand worker profiling (dashboard /api/profile): token ->
        # future resolved when the worker's sampler report arrives.
        self._profile_futs: dict[bytes, "object"] = {}

        self.workers: dict[bytes, WorkerHandle] = {}
        # Per-scheduling-key task queues (parity: normal_task_submitter.h:58
        # SchedulingKey — one reserve probe covers every queued sibling).
        self.task_queues: dict[tuple, collections.deque] = {}
        # scheduling-key -> busy workers executing that key (pipelining
        # candidates); pruned lazily as workers go idle/die.
        self._sig_workers: dict[tuple, set] = {}
        # return-oid -> live TaskSpec (cancel() resolves refs to tasks);
        # entries drop when the task finishes or fails.
        self._rid_to_spec: dict[bytes, TaskSpec] = {}
        self._cancelled: set[bytes] = set()  # task_ids
        # --- lineage (parity: reference_count.h:72 lineage pinning,
        #     object_recovery_manager.h:43): specs of FINISHED normal tasks,
        #     retained so lost plasma-tier outputs can be recomputed.
        self._lineage: dict[bytes, TaskSpec] = {}        # return-oid -> spec
        self._lineage_live: dict[bytes, set] = {}        # task_id -> live rids
        self._lineage_pins: dict[bytes, int] = {}        # oid -> #dependents
        self._lineage_freed: set[bytes] = set()          # freed while pinned
        self._reconstructing: set[bytes] = set()         # task_ids in flight
        self._reconstruct_count: dict[bytes, int] = {}   # task_id -> attempts
        self._streams: dict[bytes, dict] = {}  # streaming task state
        self.waiting_deps: dict[bytes, list] = {}  # oid -> [pending items]
        # Pluggable head persistence (parity: gcs store_client tier):
        # journaled dicts write through; everything else stays volatile.
        from ray_tpu.core.persistence import make_store
        self._persist = bool(cfg.head_persistence_path)
        self._pstore = make_store(cfg.head_persistence_path)
        # Full control-plane WAL (beyond the durable tables): in-flight
        # lease grants, object-directory locations, PG reservations and
        # stream specs/cursors — the state a head.kill SIGKILL must
        # replay. Same store, more tables.
        self._wal = self._persist and cfg.head_wal
        self.actors: dict[bytes, ActorState] = {}
        self.named_actors: dict[str, bytes] = _JournaledDict(
            "named", self._pstore)
        self.fn_table: dict[bytes, bytes] = _JournaledDict(
            "fn", self._pstore)  # fn_id -> blob
        self.remote_subs: dict[bytes, list[bytes]] = {}  # oid -> [worker ids]
        self.actors_waiting_resources: collections.deque[bytes] = collections.deque()
        self._shutdown = False
        self.kv: dict = _JournaledDict("kv", self._pstore)  # gcs_kv_manager.h
        self.placement_groups: dict[bytes, PlacementGroupState] = {}
        self.pgs_waiting: collections.deque[bytes] = collections.deque()
        # The control loop allocates ~10 small objects per message; the
        # default gen-0 threshold (700) runs a collection — and the gc
        # callback jax registers when it is imported (`import ray_tpu`
        # imports it) — every ~70 messages, visibly sampling in the hot
        # relay path.
        if cfg.gc_gen0_threshold > 0:
            import gc
            gc.set_threshold(cfg.gc_gen0_threshold)  # gens 1-2 untouched
        if cfg.gc_freeze_init:
            # Move the boot-time universe (jax + imports) to the
            # permanent generation: full collections stop re-scanning
            # ~1M immortal objects (a gen-2 pass over them ran 100ms+
            # here and surfaced as bimodal task-storm rates once the
            # task-event ring raised the allocation rate).
            import gc
            gc.freeze()
        self._reservations: dict[bytes, tuple] = {}  # task_id -> token
        # --- multi-tenant job ledger (core/jobs.py): per-job quota
        # admission at BOTH grant paths (_schedule_now worker/lease pops,
        # _lease_refill_locked) and the weighted-DRF fair-share order the
        # grant loops iterate keys in. Charges settle through the same
        # funnels every lease/assignment pop already goes through.
        self.jobs = ledger_from_config(cfg)
        # Scale-up demand the task queues cannot see (elastic trainer
        # capacity-wait, serve shed pressure, explicit hints) — posted by
        # request_scale_up, drained by autoscaler/policy.py's collector
        # each reconcile. Bounded: a hot wait loop must not grow it.
        self._scale_requests: collections.deque = collections.deque(
            maxlen=256)
        # Generic pubsub hub (parity: src/ray/pubsub/publisher.h:300 —
        # channelized publisher with per-key subscriptions). Workers
        # subscribe over their head socket; driver-side subscribers are
        # local callbacks. Delivery is at-most-once doorbell semantics;
        # durable state (KV, directory) carries the payload of record.
        self._pubsub_subs: dict[tuple, set] = {}    # (chan, key) -> wids
        self._pubsub_local: dict[tuple, list] = {}  # (chan, key) -> cbs
        # Two-phase steal: specs pulled off a busy worker's backlog await the
        # origin's drop-ack before re-dispatch (exactly-once absent failures;
        # the reference never duplicates execution without a failure).
        # task_id -> (origin WorkerHandle, TaskSpec)
        self._pending_steals: dict[bytes, tuple] = {}
        # --- cluster-view broadcast (the missing half of the resource
        # syncer, parity: ray_syncer.h:20 — agents report deltas up via
        # heartbeats; the head broadcasts the merged, versioned cluster
        # view back down so agents can spill leases peer-to-peer without
        # a per-task head round trip, cluster_task_manager.cc:187). Each
        # entry carries the global version it last changed at; per-agent
        # cursors (NodeState.cview_cursor) turn every broadcast into a
        # delta.
        self._cview_lock = threading.Lock()
        self._cview_version = 0
        self._cview: dict[bytes, dict] = {}  # nid -> view entry (versioned)
        self.lease_spills_total = 0  # agent->agent lease moves observed

        self._selector = selectors.DefaultSelector()
        self._sel_lock = threading.Lock()
        self._tl_out = threading.local()  # listener drain-pass send batch
        # --- native head core (cpp/head_core.cc) --- the listener's
        # frame pump, the node_done_raw completion parse + (task_id,
        # lease_seq) ledger and the node_exec_raw grant builds run in C++
        # when `native_head` is on and the module builds; any failure
        # degrades to the pure-Python listener below, never to an error.
        # Chaos-armed processes keep the native ledger but skip native
        # consumption and route every send through per-frame send_msg so
        # the seeded transport sites fire exactly as scheduled.
        self._hnat = None
        self._htag: dict[int, object] = {}   # pump tag -> handle
        self._nidx_conn: dict[int, NodeConn] = {}
        if cfg.native_head:
            try:
                from ray_tpu._native.head_core import HeadCore
                self._hnat = HeadCore()
            except Exception:  # noqa: BLE001 — pure-Python fallback
                traceback.print_exc()
                self._hnat = None
        self._listener = threading.Thread(
            target=(self._listen_loop_native if self._hnat is not None
                    else self._listen_loop),
            daemon=True, name="rtpu-listener")
        self._listener.start()
        if cfg.task_events:
            # Started here (not at task_store creation): the loop reads
            # _shutdown, which is only assigned a few blocks above.
            threading.Thread(target=self._tev_ingest_loop, daemon=True,
                             name="rtpu-tev-ingest").start()
        # Dedicated scheduler thread (see _schedule): submission bursts
        # coalesce into few passes; dispatch sendalls leave the
        # submitting/listener threads.
        self._sched_cv = threading.Condition()
        self._sched_gen = 0
        self._last_sched_req = 0.0
        # Lease refills computed on the listener thread, sent by the
        # scheduler thread (blocking sendalls must stay off the listener).
        self._pending_lease_sends: collections.deque = collections.deque()
        threading.Thread(target=self._sched_loop, daemon=True,
                         name="rtpu-scheduler").start()
        if cfg.cluster_view_broadcast_ms > 0:
            threading.Thread(target=self._cview_broadcast_loop, daemon=True,
                             name="rtpu-cview").start()

        pool = cfg.num_workers or int(self.total_resources["CPU"])
        self.pool_size = max(1, pool)
        self._zygote = _Zygote(self.session_dir, self.store_path,
                               self._worker_env())

        def prestart():
            for _ in range(self.pool_size):
                try:
                    self._spawn_worker()
                except Exception:  # noqa: BLE001 — keep filling the pool
                    traceback.print_exc()

        threading.Thread(target=prestart, daemon=True,
                         name="rtpu-pool-prestart").start()
        # Stream worker logs to the driver (parity: log_monitor.py).
        self._log_monitor = None
        if cfg.log_to_driver:
            from ray_tpu.core.log_monitor import LogMonitor
            self._log_monitor = LogMonitor(
                os.path.join(self.session_dir, "logs")).start()
        if cfg.memory_monitor_refresh_ms > 0:
            threading.Thread(target=self._memory_monitor_loop, daemon=True,
                             name="rtpu-oom-monitor").start()
        self.spill_dir = cfg.object_spill_dir or os.path.join(
            self.session_dir, "spill")
        self._spilled: dict[bytes, str] = {}  # oid -> spill file path
        # oid -> monotonic restore time; the spill pass leaves freshly
        # restored objects alone so their pending reader can finish.
        self._restored_at: dict[bytes, float] = {}
        # RLock: _restore_spilled holds it across write+add_location while
        # its full-arena fallback re-enters _spill_bytes.
        self._spill_lock = threading.RLock()
        if cfg.object_spill_threshold < 1.0:
            threading.Thread(target=self._spill_monitor_loop, daemon=True,
                             name="rtpu-spill-monitor").start()
        # --- head shards (core/head_shards.py): N subprocesses own
        # disjoint id-space slices of the object directory (durable
        # per-shard WAL mirror) and task-event ingest; the head keeps
        # lease policy and stays the lookup authority. The shard map
        # rides the cluster-view broadcast as a reserved pseudo-entry.
        self._shards = None
        if cfg.head_shards > 0:
            from ray_tpu.core import head_shards as _head_shards
            self._shards = _head_shards.ShardManager(
                cfg.head_shards, cfg.head_persistence_path or None,
                chaos_env=cfg.to_env())
            self._publish_shard_map()
            threading.Thread(target=self._shard_health_loop, daemon=True,
                             name="rtpu-shard-health").start()
        if self._wal or self._shards is not None:
            self.directory.on_location = self._on_dir_location
            self.directory.on_discard = self._on_dir_discard
        if self._persist:
            self._restore_persisted()

    # ---------------- head shards (manager side) ----------------

    def _on_dir_location(self, oid: bytes, nid: bytes, merged: list):
        """Directory write-through: the WAL's "dir" table records the
        full merged location list (restart re-seeds without waiting for
        agent re-registration inventories); the shard mirror gets the
        incremental (oid, nid) via the manager's batched flusher."""
        if self._wal:
            self._pstore.append("dir", oid, merged)
        if self._shards is not None:
            self._shards.dir_add(oid, nid)

    def _on_dir_discard(self, oid: bytes):
        if self._wal:
            self._pstore.delete("dir", oid)
        if self._shards is not None:
            self._shards.dir_discard(oid)

    def _publish_shard_map(self):
        """Stamp the current shard map into the cluster view under the
        reserved pseudo-key — distribution, delta encoding and the
        cursor-0 full catch-up are the broadcast's existing machinery.
        Agent-side consumers of real node entries skip it naturally (it
        has neither a state nor a ctrl address)."""
        from ray_tpu.core.head_shards import SHARD_MAP_KEY
        self._cview_update(SHARD_MAP_KEY, smap=self._shards.shard_map())

    def _shard_health_loop(self):
        while not self._shutdown:
            time.sleep(1.0)
            try:
                shards = self._shards
                if shards is not None and shards.check_and_heal():
                    self._publish_shard_map()
            except Exception:  # noqa: BLE001 — the healer must not die
                traceback.print_exc()

    # ---------------- head restart / persistence restore ----------------

    def _seed_locations(self, located: dict):
        """Replay {oid: [node_id]} into the directory as shm entries
        without re-journaling them (direct entry writes, under the
        directory lock — add_location would write the WAL back)."""
        with self.directory.lock:
            for oid, locs in located.items():
                if locs and oid not in self.directory.entries:
                    self.directory.entries[oid] = ("shm", set(locs))

    def _restore_persisted(self):
        """Replay the persistence journal into head tables (parity:
        GcsInitData reload, gcs_init_data.h). Restored actors sit in
        RESTARTING until an agent re-registration adopts their still-running
        worker; unclaimed ones respawn after the adopt grace."""
        tables = self._pstore.load()
        if self._shards is not None:
            # Shard mirror re-seed: every shard replayed its own WAL on
            # boot, so the merged snapshot rebuilds shm locations BEFORE
            # any agent has re-registered its arena inventory (which
            # still merges in later, idempotently).
            self._seed_locations(self._shards.snapshot_all())
        if not tables:
            return
        import cloudpickle
        self.kv.load_silent(tables.get("kv", {}))
        self.fn_table.load_silent(tables.get("fn", {}))
        self.named_actors.load_silent(tables.get("named", {}))
        # WAL "dir" table: shm locations the dead head had merged.
        self._seed_locations(tables.get("dir", {}))
        restored_actors = []
        for aid, blob in tables.get("actor", {}).items():
            try:
                cspec = cloudpickle.loads(blob)
            except Exception:  # noqa: BLE001 — skip unloadable actors
                continue
            st = ActorState(cspec)
            st.state = A_RESTARTING
            st.restored = True
            self.actors[aid] = st
            restored_actors.append(aid)
        for pg_id, rec in tables.get("pg", {}).items():
            # 3-tuple (pre-WAL) or 4-tuple with the reserved bundle_nodes
            # rider; placement re-derives when nodes rejoin either way.
            bundles, strategy, name = rec[0], rec[1], rec[2]
            try:
                self.create_placement_group(pg_id, bundles, strategy, name)
            except Exception:  # noqa: BLE001 — infeasible until nodes rejoin
                pass
        dep_tasks: list[tuple] = []
        task_table = tables.get("task", {})
        # WAL "stream" table: admitted streaming specs (spec, cursor-at-
        # admit); resubmission regenerates their yields deterministically,
        # so a reconnected consumer continues at its absolute index.
        stream_cur = tables.get("stream_cur", {})
        stream_specs: dict = {}
        for tid, rec in tables.get("stream", {}).items():
            stream_specs[tid] = (rec[0] if isinstance(rec, (tuple, list))
                                 else rec)
        # WAL "lease" table: grants in flight at the kill. A surviving
        # agent's dedup ledger may still hold (task, lease_seq) from the
        # dead head's grant — the replayed spec must re-grant PAST that
        # seq or the re-send is swallowed and the task hangs forever.
        lease_table = dict(tables.get("lease", {}))
        for tid in list(lease_table):
            if tid not in task_table and tid not in stream_specs:
                # Task completed; the crash landed between its task-table
                # delete and the lease delete. Retire the orphan.
                self._pstore.delete("lease", tid)
                lease_table.pop(tid)
        # Return ids the replay will actually (re-)produce: only tasks that
        # really resubmitted may vouch for a dependent's dep — a producer
        # whose replay failed must not, or its consumers hang ungated.
        replayed_outputs: set[bytes] = set()
        for tid, spec in [*task_table.items(), *stream_specs.items()]:
            granted = lease_table.get(tid)
            if granted is not None:
                spec.lease_seq = max(spec.lease_seq or 0, granted[1])
            if spec.dependencies:
                # The object directory died with the old head. The deps may
                # still exist (agents re-register with an arena inventory
                # that rebuilds the directory) or be reproducible (their
                # producer is also journaled and will re-run): park the
                # task until the adopt grace has let nodes resync, then
                # decide (parity: GCS reload + owner resubmission,
                # gcs_init_data.h / task_manager.h:216).
                dep_tasks.append((tid, spec))
                continue
            try:
                self.submit_task(spec)
                replayed_outputs.update(spec.return_ids or [])
            except Exception:  # noqa: BLE001 — drop unreplayable tasks
                pass
        if stream_specs:
            with self.lock:
                for tid in stream_specs:
                    st = self._streams.get(tid)
                    if st is not None and tid in stream_cur:
                        st["consumed"] = stream_cur[tid]
        grace = self.config.head_restart_adopt_grace_s
        if restored_actors:

            def respawn_unclaimed():
                time.sleep(grace)
                for aid in restored_actors:
                    st = self.actors.get(aid)
                    if (st is not None and st.restored
                            and st.state == A_RESTARTING
                            and st.worker is None):
                        st.restored = False
                        threading.Thread(target=self._create_actor_now,
                                         args=(st.cspec,),
                                         daemon=True).start()

            threading.Thread(target=respawn_unclaimed, daemon=True).start()
        if dep_tasks:

            def resolve_dep_tasks():
                time.sleep(grace)
                from ray_tpu.core.status import ObjectLostError
                # A dep is satisfiable when it already exists (directory
                # rebuilt from the agents' arena inventories) or a task
                # that actually resubmitted will re-produce it (lineage
                # re-execution repopulates the SAME return ids). Parked
                # tasks may chain, so close over the promise set until
                # fixpoint; the remainder is unrecoverable.
                promised = set(replayed_outputs)
                pending = list(dep_tasks)
                submit = []
                changed = True
                while changed:
                    changed = False
                    for item in list(pending):
                        _tid, spec = item
                        if all(self.directory.lookup(d) is not None
                               or d in promised
                               for d in spec.dependencies):
                            pending.remove(item)
                            submit.append(spec)
                            promised.update(spec.return_ids or [])
                            changed = True
                for spec in submit:
                    try:
                        self.submit_task(spec)
                    except Exception as e:  # noqa: BLE001
                        # Neither produced nor silently dropped: tombstone
                        # so waiters see the resubmission failure.
                        self._fail_returns(spec, e)
                for _tid, spec in pending:
                    # Unrecoverable: a dep lived only in the dead head's
                    # arena (or its producer failed to replay). Tombstone
                    # the returns so adopted workers blocked in get() fail
                    # fast instead of hanging forever. A node registering
                    # between the fixpoint and here can resolve the dep
                    # after all — submit in that case instead.
                    lost = next(
                        (d for d in spec.dependencies
                         if self.directory.lookup(d) is None
                         and d not in promised), None)
                    if lost is None:
                        try:
                            self.submit_task(spec)
                        except Exception as e:  # noqa: BLE001
                            self._fail_returns(spec, e)
                        continue
                    self._fail_returns(spec, ObjectLostError(
                        ObjectID(lost),
                        msg=f"dependency of journaled task "
                            f"{spec.describe()} was lost with the old "
                            f"head and cannot be re-executed"))

            threading.Thread(target=resolve_dep_tasks, daemon=True).start()

    def _adopt_actor_worker(self, aid: bytes, w: "WorkerHandle") -> bool:
        """An agent re-registered a worker that still hosts `aid`: wire it
        back in as ALIVE without restarting (the in-memory actor state in
        the worker process survived the head restart). Returns False when
        the actor is not adoptable — e.g. it was already restarted
        elsewhere, leaving this worker a stale duplicate."""
        st = self.actors.get(aid)
        if st is None or not (st.restored and st.state == A_RESTARTING):
            return st is not None and st.worker is w
        w.actor_id = aid
        with self.lock:
            st.worker = w
            st.node_id = w.node_id
            st.state = A_ALIVE
            st.restored = False
            # Re-reserve the actor's resources on its node so scheduling
            # accounting stays truthful after the restart — EXCEPT for
            # actors living inside a placement group: the journal-restored
            # PG re-carves its bundles itself, and a node-level reservation
            # here would double-count and park the PG in PENDING forever.
            if getattr(st.cspec, "placement_group_id", None) is None:
                node = self.nodes.get(w.node_id)
                req = self._actor_resources(st.cspec)
                if node is not None:
                    for k, v in req.items():
                        node.available[k] = node.available.get(k, 0.0) - v
                    st.resources_reserved = ("node", w.node_id, req)
            queued = list(st.queued)
            st.queued.clear()
        self._export_actor(st, "ALIVE")
        for spec in queued:
            self._send_actor_task(st, spec)
        return True

    # ---------------- object spilling ----------------
    #
    # Parity: LocalObjectManager::SpillObjects -> ExternalStorage
    # (raylet/local_object_manager.h:111, _private/external_storage.py) —
    # the persistence tier of the object plane. The head spills its own
    # store's oldest unpinned owner-tracked objects to files BEFORE the
    # arena's last-resort LRU eviction would drop them, and restores on
    # demand. Node-agent stores rely on arena eviction only (v1).

    def _spill_monitor_loop(self):
        """Keep arena usage under object_spill_threshold so bursty puts hit
        prepared headroom instead of evicting live objects."""
        while not self._shutdown:
            time.sleep(1.0)
            if self._shutdown:
                return  # store is closing: its mmap must not be touched
            try:
                stats = self.store.stats()
                cap = stats["capacity"] or 1
                frac = stats["allocated"] / cap
                threshold = self.config.object_spill_threshold
                low_water = max(0.0, threshold - 0.2)
                if frac > threshold:
                    self._spill_bytes(int((frac - low_water) * cap))
            except Exception:  # noqa: BLE001 — monitoring must not die
                traceback.print_exc()

    def _spill_bytes(self, needed: int) -> bool:
        """Spill oldest unpinned head-local objects until `needed` bytes are
        freed. Returns whether that much was freed."""
        if needed <= 0:
            return True
        os.makedirs(self.spill_dir, exist_ok=True)
        freed = 0
        with self._spill_lock:
            with self.directory.lock:
                candidates = [
                    oid for oid, e in self.directory.entries.items()
                    if e[0] == "shm" and len(e) > 1
                    and self.head_node_id in e[1]]
            for oid in candidates:
                if freed >= needed:
                    break
                freed += self._spill_one_locked(oid)
        return freed >= needed

    def _spill_job_bytes(self, job_id: str, needed: int) -> int:
        """Per-job blast radius: spill the offending job's coldest
        head-local objects (the ledger's insertion order is put order)
        until `needed` bytes are freed — other tenants' hot objects are
        never touched, so one job's quota breach applies disk pressure
        only to itself. Returns bytes freed (spill-accounted to the
        job)."""
        if needed <= 0:
            return 0
        os.makedirs(self.spill_dir, exist_ok=True)
        freed = 0
        with self._spill_lock:
            for oid in self.jobs.coldest_objects(job_id, limit=1024):
                if freed >= needed:
                    break
                freed += self._spill_one_locked(oid)
        if freed:
            self.jobs.note_spilled(job_id, freed)
        return freed

    def _spill_one_locked(self, oid: bytes) -> int:
        """Spill one head-local shm object to disk (caller holds
        _spill_lock). Returns bytes freed from the arena — 0 when the
        object is pinned, already gone, or too freshly restored."""
        with self.refcount._lock:
            if oid in self.refcount._pins:
                return 0  # an in-flight task depends on it
        prior = self._spilled.get(oid)
        if prior is not None and os.path.exists(prior):
            # Restored earlier: the spill file is still valid, so
            # dropping the in-arena copy costs nothing — EXCEPT for
            # a just-restored object whose reader (a get/push that
            # triggered the restore) may not have read it yet.
            if time.monotonic() - self._restored_at.get(oid, 0.0) < 10.0:
                return 0
            with self.directory.lock:
                e = self.directory.entries.get(oid)
                if e is None or e[0] != "shm":
                    return 0
                e[1].discard(self.head_node_id)
            self.store.delete(ObjectID(oid))
            return os.path.getsize(prior)
        res = self.store.get_raw(ObjectID(oid), timeout=0)
        if res is None:
            return 0
        data, meta = res
        path = os.path.join(self.spill_dir, oid.hex())
        try:
            with open(path, "wb") as f:
                # Spill file = [u32 meta_len][meta][data]: the
                # tagged-object meta (arrow blocks, tensor
                # frames, cross-language values) must survive the
                # disk round trip or the restored copy decodes as
                # the wrong layout.
                f.write(struct.pack("<I", len(meta)))
                if meta:
                    f.write(meta)
                f.write(data)
        finally:
            data.release()
            self.store.release(ObjectID(oid))
        size = os.path.getsize(path)
        with self.directory.lock:
            e = self.directory.entries.get(oid)
            if e is None or e[0] != "shm":
                os.unlink(path)
                return 0
            self._spilled[oid] = path
            e[1].discard(self.head_node_id)
        self.store.delete(ObjectID(oid))
        return size

    def _restore_spilled(self, oid: bytes) -> bool:
        """Bring a spilled object back into the head store (blocking IO —
        never call on the listener thread)."""
        from ray_tpu.core import objxfer
        path = self._spilled.get(oid)
        if path is None:
            return False
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return False
        (meta_len,) = struct.unpack_from("<I", raw, 0)
        meta = bytes(raw[4:4 + meta_len])
        blob = memoryview(raw)[4 + meta_len:]
        # Under _spill_lock: a concurrent spill pass must not 'cheap-drop'
        # the arena copy between our write and add_location (it would leave
        # the directory claiming a head copy that is gone).
        with self._spill_lock:
            self._ensure_headroom(len(blob))
            try:
                objxfer.write_blob(self.store, oid, blob, meta=meta)
            except Exception:  # noqa: BLE001 — arena full: make room, retry
                if not self._spill_bytes(int(len(blob) * 1.2)):
                    return False
                objxfer.write_blob(self.store, oid, blob, meta=meta)
            self._restored_at[oid] = time.monotonic()
            self.directory.add_location(oid, self.head_node_id)
        return True

    def _ensure_headroom(self, nbytes: int):
        """Spill-BEFORE-pressure: the arena's last-resort LRU eviction
        silently destroys owned objects, so every head-store write makes
        room under the spill threshold first. Under pressure, dead
        clients' stranded reservations are reclaimed BEFORE spilling live
        objects to disk — leaked extents are free headroom. Jobs already
        past their object quota pay next (per-job blast radius: their
        coldest objects go to disk before any within-quota tenant's)."""
        stats = self.store.stats()
        cap = stats["capacity"] or 1
        limit = self.config.object_spill_threshold * cap
        if stats["allocated"] + nbytes > limit:
            if self.store.reclaim_orphans() > 0:
                stats = self.store.stats()
                if stats["allocated"] + nbytes <= limit:
                    return
            needed = int(stats["allocated"] + nbytes - limit) + (4 << 20)
            for jid, over in self.jobs.over_quota_objects():
                if needed <= 0:
                    break
                needed -= self._spill_job_bytes(jid, min(over, needed))
            if needed > 0:
                self._spill_bytes(needed)

    def _account_put(self, oid: bytes, nbytes: int,
                     job_id: str | None = None) -> None:
        """Attribute a sealed head-local object to its tenant; on object
        quota breach spill that job's OWN coldest objects — the offender
        pays the disk penalty at its own put site, other tenants' arena
        residency is untouched."""
        jid = job_id or current_job_id(rt=self)
        self.jobs.charge_object(jid, oid, nbytes)
        over = self.jobs.object_overage(jid)
        if over > 0:
            self._spill_job_bytes(jid, over)

    def put_in_store(self, oid: "ObjectID", value,
                     job_id: str | None = None) -> None:
        from ray_tpu.core.object_store import arrow_block_of
        from ray_tpu.core.status import ObjectStoreFullError
        table = arrow_block_of(value)
        approx = int(getattr(value, "nbytes", 0) or (1 << 20))
        # Reservation-backed puts carve no global memory: the refill path
        # already ran the headroom check (store.spill_hook), so the
        # per-put stats probe + spill pass is skipped.
        if not self.store.reservation_fits(approx):
            self._ensure_headroom(approx)
        try:
            if table is not None:
                self.store.put_arrow(oid, table)
            else:
                self.store.put_serialized(oid, value)
        except ObjectStoreFullError:
            if not self._spill_bytes(int(approx * 1.5) + (1 << 20)):
                raise
            if table is not None:
                self.store.put_arrow(oid, table)
            else:
                self.store.put_serialized(oid, value)
        self._account_put(oid.binary(), approx, job_id)

    # ---------------- OOM monitor ----------------

    @staticmethod
    def _memory_usage() -> float:
        """Fraction of system memory in use (parity: memory_monitor.h:52)."""
        info = {}
        with open("/proc/meminfo") as f:
            for line in f:
                k, _, rest = line.partition(":")
                info[k] = int(rest.split()[0])
        total = info.get("MemTotal", 1)
        return 1.0 - info.get("MemAvailable", total) / total

    def _memory_monitor_loop(self):
        """Above the usage threshold, kill one busy worker whose task can
        retry (parity: retriable-FIFO WorkerKillingPolicy,
        worker_killing_policy_retriable_fifo.h:34 — the kill converts host
        OOM death-by-kernel into a retryable task failure)."""
        period = self.config.memory_monitor_refresh_ms / 1000.0
        while not self._shutdown:
            time.sleep(period)
            if self._shutdown:
                return
            try:
                if self._memory_usage() < self.config.memory_usage_threshold:
                    continue
                with self.lock:
                    busy = [(w, w.current_task)
                            for w in self.head_node.workers.values()
                            if w.state == BUSY and w.current_task is not None]
                    retriable = [(w, t) for w, t in busy
                                 if (t.retries_left or 0) > 0]
                    pool = retriable or busy
                    victim, vtask = pool[-1] if pool else (None, None)
                    if victim is not None:
                        # Still on the SELECTED task? A completion racing
                        # this sweep must not get an unrelated worker (or a
                        # fresh non-retriable task) killed in its place.
                        if (victim.state != BUSY
                                or victim.current_task is not vtask):
                            victim = None
                if victim is not None:
                    self.task_events.record(vtask.task_id, vtask,
                                            "OOM_KILLED")
                    victim.kill()
            except Exception:  # noqa: BLE001 — monitoring must not die
                traceback.print_exc()

    # ---------------- worker pool ----------------

    def _worker_env(self) -> dict:
        return build_worker_env(self.config, self.head_node_id.hex(),
                                is_head=True)

    def _spawn_worker(self, pip: list | None = None) -> WorkerHandle:
        if self._shutdown:
            return None
        worker_id = WorkerID.from_random()
        env, zygote, env_key = apply_pip_env(
            self._worker_env(), self._zygote, pip)
        parent, proc = spawn_worker_process(
            worker_id, self.store_path, env, zygote,
            self.session_dir)
        handle = WorkerHandle(worker_id, parent, proc,
                              node_id=self.head_node_id)
        handle.env_key = env_key
        with self.lock:
            if self._shutdown:
                # Raced with shutdown(): it won't see this handle, so clean
                # up here instead of leaking an orphan worker.
                proc.kill()
                parent.close()
                return None
            self.workers[worker_id.binary()] = handle
            self.head_node.workers[worker_id.binary()] = handle
        self._pump_register(parent, handle)
        return handle

    def _replenish_pool_async(self):
        def run():
            with self.lock:
                # Head DEFAULT pool only: remote workers are the agents'
                # business, env-pool workers are demand-spawned.
                n_pool = sum(1 for w in self.head_node.workers.values()
                             if w.state in (IDLE, BUSY)
                             and w.env_key is None)
                need = self.pool_size - n_pool
            for _ in range(max(0, need)):
                self._spawn_worker()
        threading.Thread(target=run, daemon=True).start()

    # ---------------- listener / message handling ----------------

    def _pump_register(self, sock, handle, accept: bool = False):
        """Register a readable fd with the listener: the native head
        pump when it owns the select round, the Python selector
        otherwise. `handle` is the routing object (WorkerHandle /
        NodeConn / _Acceptor)."""
        nat = self._hnat
        if nat is None:
            with self._sel_lock:
                self._selector.register(sock, selectors.EVENT_READ, handle)
            return
        tag = nat.alloc_tag()
        handle._htag = tag
        handle._hfd = sock.fileno()
        self._htag[tag] = handle
        nat.add_fd(handle._hfd, tag, accept=accept)

    def _pump_unregister(self, sock, handle=None):
        nat = self._hnat
        if nat is None:
            with self._sel_lock:
                try:
                    self._selector.unregister(sock)
                except (KeyError, ValueError):
                    pass
            return
        tag = getattr(handle, "_htag", None)
        fd = getattr(handle, "_hfd", None)
        if fd is None:
            try:
                fd = sock.fileno()
            except (OSError, AttributeError):
                fd = -1
        if fd is not None and fd >= 0:
            try:
                nat.del_fd(fd)
            except OSError:
                pass
        if tag is not None:
            self._htag.pop(tag, None)
            handle._htag = None

    def _accept_pending(self, acc):
        """Drain the listening socket (native pump surfaced readiness)."""
        from ray_tpu.core.transport import enable_nodelay
        srv = acc.sock
        while True:
            try:
                conn_sock, _addr = srv.accept()
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return
            conn_sock.setblocking(True)
            enable_nodelay(conn_sock)
            nc = NodeConn(conn_sock)
            self._pump_register(conn_sock, nc)

    def _drain_native_completions(self, nat):
        """Feed the round's natively parsed node_done_raw records into
        the SAME per-batch completion pass as the Python path — grouped
        per node conn, entry shape (task_id, outs, tev, whex). The
        C++ side already popped the (task_id, lease_seq) ledger;
        _on_node_done's _pop_lease_locked stays the authoritative pop."""
        groups: dict = {}
        order: list = []
        for nidx, _known, tid, whex, outs, tev in nat.completions():
            if nidx not in groups:
                groups[nidx] = []
                order.append(nidx)
            groups[nidx].append((tid, outs, tev, whex))
        for nidx in order:
            conn = self._nidx_conn.get(nidx)
            if conn is None:
                continue
            try:
                self._on_node_done(conn, groups[nidx], native_popped=True)
            except Exception:
                traceback.print_exc()

    def _listen_loop_native(self):
        """The head's select round on the native pump (cpp/head_core.cc):
        C++ owns readiness, frame split and node_done_raw consumption;
        Python handles the cold frames, runs accepts, and performs every
        send (the out-batch coalescing is unchanged). Chaos-armed rounds
        skip native consumption so every frame takes the Python path and
        its seeded sites."""
        from ray_tpu._native.head_core import (KIND_ACCEPT, KIND_EOF,
                                               KIND_PROTO)
        from ray_tpu.core.transport import _decode_proto
        nat = self._hnat
        while not self._shutdown:
            try:
                n = nat.poll(50)
            except OSError:
                continue
            if n <= 0:
                continue
            nat.split()
            consumed = 0
            if chaos._armed is None:
                consumed = nat.consume_hot()
            dead: list = []
            self._begin_out_batch()
            try:
                if consumed:
                    self._drain_native_completions(nat)
                for tag, kind, _pt, payload, bufs, _whole in nat.frames():
                    handle = self._htag.get(tag)
                    if handle is None:
                        continue
                    try:
                        if kind == KIND_ACCEPT:
                            self._accept_pending(handle)
                            continue
                        if kind == KIND_EOF:
                            dead.append(handle)
                            continue
                        msg = (_decode_proto(bytes(payload))
                               if kind == KIND_PROTO
                               else pickle.loads(payload, buffers=bufs))
                        if handle.kind == "node":
                            if handle.client_handle is not None:
                                self._handle_msg(handle.client_handle, msg)
                            else:
                                self._handle_node_msg(handle, msg)
                        else:
                            self._handle_msg(handle, msg)
                    except Exception:
                        traceback.print_exc()
            finally:
                self._flush_out_batch()
            nat.round_end()  # frame views die here
            for handle in dead:
                try:
                    if handle.kind == "node":
                        self._on_node_conn_closed(handle)
                    else:
                        self._on_worker_death(handle)
                except Exception:
                    traceback.print_exc()

    def _listen_loop(self):
        while not self._shutdown:
            with self._sel_lock:
                try:
                    events = self._selector.select(timeout=0.05)
                except OSError:
                    continue
            # One out-batch per select round, spanning every ready
            # connection: a single done_batch frame can fan out dozens of
            # result pushes, and under load several conns are ready at
            # once — coalescing across the whole round turns those into
            # one sendall per destination.
            self._begin_out_batch()
            try:
                for key, _mask in events:
                    handle = key.data
                    if handle.kind == "accept":
                        try:
                            conn_sock, _addr = key.fileobj.accept()
                        except OSError:
                            continue
                        conn_sock.setblocking(True)
                        from ray_tpu.core.transport import enable_nodelay
                        enable_nodelay(conn_sock)
                        nc = NodeConn(conn_sock)
                        with self._sel_lock:
                            self._selector.register(
                                conn_sock, selectors.EVENT_READ, nc)
                        continue
                    try:
                        data = key.fileobj.recv(1 << 20)
                    except (BlockingIOError, InterruptedError):
                        continue
                    except OSError:
                        data = b""
                    if handle.kind == "node":
                        if not data:
                            self._on_node_conn_closed(handle)
                            continue
                        handle.buffer.feed(data)
                        for msg in handle.buffer.frames():
                            try:
                                if handle.client_handle is not None:
                                    self._handle_msg(handle.client_handle,
                                                     msg)
                                else:
                                    self._handle_node_msg(handle, msg)
                            except Exception:
                                traceback.print_exc()
                        continue
                    if not data:
                        self._on_worker_death(handle)
                        continue
                    handle.buffer.feed(data)
                    for msg in handle.buffer.frames():
                        try:
                            self._handle_msg(handle, msg)
                        except Exception:
                            traceback.print_exc()
            finally:
                self._flush_out_batch()

    # The select-round out-batch: outbound frames produced while handling
    # this round's inbound frames coalesce per destination into one
    # sendall (the worker side unpacks "batch" frames). Listener-thread
    # only — other threads send inline.

    def _begin_out_batch(self):
        self._tl_out.batch = {}
        self._tl_out.order = []

    def _buffered_send(self, w, frame) -> bool:
        """Queue a frame on the current drain pass's batch; False when no
        batch is active (caller sends inline)."""
        batch = getattr(self._tl_out, "batch", None)
        if batch is None:
            return False
        if w not in batch:
            batch[w] = []
            self._tl_out.order.append(w)
        batch[w].append(frame)
        return True

    def _flush_out_batch(self):
        batch = getattr(self._tl_out, "batch", None)
        if batch is None:
            return
        self._tl_out.batch = None
        for w in self._tl_out.order:
            frames = batch[w]
            try:
                w.send(frames[0] if len(frames) == 1
                       else ("batch", frames))
            except OSError:
                for frame in frames:
                    if frame[0] == "exec":
                        self._actor_exec_send_failed(frame[1])

    def _handle_msg(self, w: WorkerHandle, msg):
        op = msg[0]
        if op == "done":
            self._on_task_done(w, msg[1], msg[2], msg[3],
                               msg[4] if len(msg) > 4 else None)
        elif op == "done_batch":
            # Coalesced replies from a pipelined sync actor (worker-side
            # _flush_replies): one frame, many task completions. Entries
            # optionally carry the packed exec-span record as a 4th
            # element (task-event pipeline piggyback).
            for entry in msg[1]:
                self._on_task_done(w, entry[0], entry[1], entry[2],
                                   entry[3] if len(entry) > 3 else None)
        elif op == "stream_item":
            # One yield from a streaming (generator) task.
            task_id, (rid, status, payload, bufs) = msg[1], msg[2]
            if status == "inline":
                self.directory.put(rid, ("raw", payload, bufs, True))
            elif status == "err":
                self.directory.put(rid, ("raw", payload, bufs, False))
            else:
                self.directory.add_location(rid, w.node_id)
            self._stream_append(task_id, rid)
        elif op == "ready":
            w.connected.set()
            if len(msg) > 3 and msg[3]:
                w.env_key = msg[3]  # env-pool worker (remote agents spawn
                # them; the key rides the ready frame)
            if len(msg) > 4 and msg[4]:
                w.peer_path = msg[4]  # worker peer-plane UDS listener
            with self.lock:
                if w.state == DEAD:
                    return
                node = self.nodes.get(w.node_id)
                if node is not None and node.pending_actor_assign:
                    # First pending actor whose env pool matches this
                    # worker (default actors <-> default workers).
                    for i, aid in enumerate(node.pending_actor_assign):
                        st = self.actors.get(aid)
                        if (st is not None and
                                _pip_key_of(st.cspec) == w.env_key):
                            del node.pending_actor_assign[i]
                            if not self._assign_actor_locked(st, w):
                                # Worker died on handoff: re-park in place;
                                # the death path replenishes the pool.
                                node.pending_actor_assign.insert(i, aid)
                            return
                w.state = IDLE
                if node is not None:
                    node.idle.append(w)
            self._schedule()
        elif op == "wait_obj":
            oid = msg[1]
            wid = w.worker_id.binary()

            def push(entry, oid=oid, wid=wid):
                self._push_obj_to_worker(wid, oid, entry)

            self.directory.on_ready(oid, push)
        elif op == "wait_objs":
            # Vectored dependency subscribe: one frame, many oids; ready
            # same-source remote objects pull as ONE fetch_many batch.
            self._on_wait_objs(w, msg[1])
        elif op == "put_notify":
            self.directory.add_location(msg[1], w.node_id)
            self._on_object_ready(msg[1])
        elif op == "drop_ack":
            self._on_drop_ack(w, msg[1], msg[2])
        elif op == "subscribe":
            with self.lock:
                self._pubsub_subs.setdefault(
                    (msg[1], msg[2]), set()).add(w.worker_id.binary())
        elif op == "unsubscribe":
            with self.lock:
                subs = self._pubsub_subs.get((msg[1], msg[2]))
                if subs is not None:
                    subs.discard(w.worker_id.binary())
                    if not subs:
                        self._pubsub_subs.pop((msg[1], msg[2]), None)
        elif op == "publish":
            self.pubsub_publish(msg[1], msg[2], msg[3])
        elif op == "profile_result":
            entry = self._profile_futs.pop(msg[1], None)
            if entry is not None:
                entry[0].set_result(msg[2])
        elif op == "task_events":
            # A worker's ring flush (piggybacked on its reply channel;
            # agent-node workers' frames ride the agent's select-round
            # relay batch). msg: (op, events, dropped_delta).
            self._queue_task_events(msg[1], w.node_id,
                                    w.worker_id.binary(), msg[2])
        elif op == "metrics_update":
            # Dirty-metric registry delta from a worker process: merged
            # at scrape time into /metrics tagged WorkerId.
            self._merge_worker_metrics(w.worker_id.binary(), msg[1])
        elif op == "free_put":
            # Owning worker dropped the last local handle of its own put()
            # and the ref never escaped — safe to free cluster-wide, unless
            # a task referencing it is in flight (pinned).
            if not self.refcount.is_pinned(msg[1]):
                self._free_object(msg[1])
        elif op == "submit":
            spec: TaskSpec = msg[1]
            if spec.actor_id is None and spec.owner is None:
                spec.owner = w.worker_id.binary()  # see _pipeline_locked
            self.submit_task(spec, fn_blob=None)
        elif op == "direct_actor":
            # Agent-plane routing frame that landed on the head (a client
            # or misrouted caller): degrade to a normal submission rather
            # than killing the connection's listener pass.
            self.submit_task(msg[3])
        elif op == "direct_fail":
            # A worker-plane direct call's channel died after the exec
            # frame was sent and the actor permits no retries: the only
            # safe outcome is failing the returns (replaying could
            # double-execute). Parity: the at-most-once arm of the
            # reference's actor-death handling.
            spec = msg[1]
            st = self.actors.get(spec.actor_id)
            cause = getattr(st, "death_cause", None) if st else None
            self._fail_returns(
                spec, cause if isinstance(cause, Exception)
                else ActorDiedError(
                    msg="actor's worker died with the call in flight"))
        elif op == "direct_actor_head":
            # Thin actor dispatch from a head-node worker (the agent-node
            # direct path's counterpart; see actor.py). Dep-free by
            # construction, so it goes straight to _send_actor_task —
            # which parks on RESTARTING actors and fails on DEAD ones,
            # exactly like the full path after gating.
            spec = msg[1]
            st = self.actors.get(spec.actor_id)
            if st is None:
                self.submit_task(spec)  # full path surfaces the failure
            else:
                self._send_actor_task(st, spec)
        elif op == "export_fn":
            _, fn_id, blob = msg
            with self.lock:
                self.fn_table[fn_id] = blob
        elif op == "create_actor":
            self.create_actor(msg[1], from_worker=True)
        elif op == "actor_ready":
            self._on_actor_ready(msg[1])
        elif op == "actor_err":
            self._on_actor_init_error(msg[1], msg[2], msg[3])
        elif op == "request":
            self._on_request(w, msg[1], msg[2], msg[3])
        else:
            raise RayTpuError(f"head: unknown message {op}")

    def kv_keys(self, prefix=b"") -> list:
        with self.lock:
            return [k for k in self.kv
                    if isinstance(k, (bytes, str))
                    and (not prefix or _kv_key_bytes(k).startswith(
                        _kv_key_bytes(prefix)))]

    def kv_take(self, key):
        """Atomic get+delete: exactly one caller consumes a one-shot value
        (the primitive behind workflow event consumption)."""
        with self.lock:
            return self.kv.pop(key, None)

    def kv_putnx(self, key, value) -> bool:
        """Atomic put-if-absent; returns True if the key already existed
        (and was left untouched). The worker-side overwrite=False path must
        go through this — a get-then-put over two RPCs lets two workers
        both observe absence and both write."""
        with self.lock:
            existed = key in self.kv
            if not existed:
                self.kv[key] = value
            return existed

    def kv_incr(self, key) -> int:
        """Atomic counter increment (serialized by the head lock); the
        primitive behind barriers/rendezvous — a get-then-put from N workers
        would lose counts."""
        with self.lock:
            n = int(self.kv.get(key, b"0")) + 1
            self.kv[key] = str(n).encode()
            return n

    def _on_request(self, w: WorkerHandle, req_id, what, arg):
        """Small synchronous control-plane queries from workers."""
        if what == "get_actor":
            aid = self.named_actors.get(arg)
            resp = None
            if aid is not None:
                st = self.actors.get(aid)
                resp = (aid, st.cspec.name if st else "")
        elif what == "kv_get":
            resp = self.kv.get(arg)
        elif what == "kv_put":
            with self.lock:
                resp = arg[0] in self.kv  # 'existed', the API's return value
                self.kv[arg[0]] = arg[1]
        elif what == "kv_putnx":
            resp = self.kv_putnx(arg[0], arg[1])
        elif what == "stream_next":
            # Parked callback, not a thread: the reply fires from
            # _stream_append/_stream_close when the yield lands (one parked
            # entry per consumed item instead of one thread per RPC).
            task_id, idx, _timeout = arg

            def reply(rid, w=w, req_id=req_id):
                try:
                    w.send(("resp", req_id, rid))
                except OSError:
                    pass

            self.stream_item_or_park(task_id, idx, reply)
            return
        elif what == "stream_finished":
            resp = self.stream_finished(arg)
        elif what == "stream_release":
            self.release_stream(arg)
            resp = True
        elif what == "kv_del":
            self.kv.pop(arg, None)
            resp = True
        elif what == "kv_incr":
            resp = self.kv_incr(arg)
        elif what == "kv_take":
            resp = self.kv_take(arg)
        elif what == "kv_keys":
            resp = self.kv_keys(arg)
        elif what == "state":
            # Heavy queries (100k-row task lists) must not stall the
            # listener thread — compute + pickle the reply off-thread
            # (same rule as the spill branch below).
            def state_and_reply(arg=arg, w=w, req_id=req_id):
                from ray_tpu.util.state import _dispatch
                kind, sarg = arg
                try:
                    resp = _dispatch(self, kind, sarg)
                except Exception as e:  # noqa: BLE001 — report, don't die
                    resp = RayTpuError(f"state query {kind!r} failed: {e}")
                try:
                    w.send(("resp", req_id, resp))
                except OSError:
                    pass

            threading.Thread(target=state_and_reply, daemon=True).start()
            return
        elif what == "spill":
            # Only head-node workers share the head's arena; a remote
            # worker's store is its agent's (arena LRU eviction applies).
            # Spilling is bulk disk IO — never run it on the listener
            # thread (it would freeze the whole control plane); reply
            # asynchronously from the spill thread.
            if w.node_id != self.head_node_id:
                w.send(("resp", req_id, False))
                return

            def spill_and_reply(n=int(arg), w=w, req_id=req_id):
                try:
                    ok = self._spill_bytes(n)
                except Exception:  # noqa: BLE001
                    traceback.print_exc()
                    ok = False
                try:
                    w.send(("resp", req_id, ok))
                except OSError:
                    pass

            threading.Thread(target=spill_and_reply, daemon=True).start()
            return
        elif what == "client_put":
            # Deserialize + store off the listener thread; reply async.
            def put_and_reply(arg=arg, w=w, req_id=req_id):
                try:
                    value = serialization.deserialize(arg[0], arg[1])
                    oid = ObjectID.from_random()
                    # arg[2] = client's job id (absent from old clients).
                    self.put_in_store(
                        oid, value,
                        job_id=arg[2] if len(arg) > 2 else None)
                    self.directory.put(oid.binary(),
                                       ("shm", {self.head_node_id}))
                    resp = oid.binary()
                except Exception as e:  # noqa: BLE001 — ship to client
                    resp = RayTpuError(f"client_put failed: {e}")
                try:
                    w.send(("resp", req_id, resp))
                except OSError:
                    pass

            threading.Thread(target=put_and_reply, daemon=True).start()
            return
        elif what == "client_wait":
            def wait_and_reply(arg=arg, w=w, req_id=req_id):
                oids, num_returns, timeout = arg
                try:
                    resp = self._wait_oids(oids, num_returns, timeout)
                except Exception as e:  # noqa: BLE001
                    resp = RayTpuError(f"client_wait failed: {e}")
                try:
                    w.send(("resp", req_id, resp))
                except OSError:
                    pass

            threading.Thread(target=wait_and_reply, daemon=True).start()
            return
        elif what == "job_register":
            # JobSupervisor/JobSubmissionClient registrar: (job_id,
            # weight, quota dict, object_quota), Nones keep defaults.
            jid, weight, quota, object_quota = arg
            self.jobs.register(jid, weight=weight, quota=quota,
                               object_quota=object_quota)
            resp = True
        elif what == "job_stop":
            # Queue/lease teardown can fail hundreds of returns — keep
            # it off the listener thread (same rule as "state").
            def stop_and_reply(jid=arg, w=w, req_id=req_id):
                try:
                    resp = self.stop_job(jid)
                except Exception as e:  # noqa: BLE001 — report, don't die
                    resp = RayTpuError(f"job_stop {jid!r} failed: {e}")
                try:
                    w.send(("resp", req_id, resp))
                except OSError:
                    pass

            threading.Thread(target=stop_and_reply, daemon=True).start()
            return
        elif what == "scale_up":
            self.request_scale_up(arg[0], source=arg[1])
            resp = True
        elif what == "cancel":
            resp = self.cancel_task(arg[0], force=arg[1])
        elif what == "kill_actor":
            self.kill_actor_by_id(arg, no_restart=True)
            resp = True
        elif what == "actor_methods":
            st = self.actors.get(arg)
            resp = (st.cspec.methods_meta or {}) if st else {}
        elif what == "actor_location":
            # Direct-call resolution (parity: the GCS actor-table lookup
            # that seeds actor_task_submitter.h:78): (node_id, worker_id)
            # of a live actor on an AGENT node, else None (head-local
            # actors and unstable states go through the head path).
            st = self.actors.get(arg)
            resp = None
            requester_on_head = w.node_id == self.head_node_id
            if (st is not None and st.state == A_ALIVE
                    and st.worker is not None and st.worker.state != DEAD):
                if (st.worker.node_id == w.node_id
                        and not requester_on_head
                        and getattr(st.worker, "peer_path", None)
                        and w.kind == "worker"
                        and not getattr(w, "is_client", False)
                        and self.config.worker_direct_calls):
                    # Same AGENT node: hand out the hosting worker's UDS
                    # so actor->actor calls skip the agent relay both
                    # ways (call and reply) — the agent only sees the
                    # async put_notify/task-event bookkeeping.
                    resp = ("uds", st.worker.peer_path,
                            bool(st.cspec.max_task_retries))
                elif (st.worker.node_id != self.head_node_id
                        and not requester_on_head):
                    # Agent-plane location — only meaningful to a caller
                    # that has an agent to route through; a head-node
                    # worker must keep the thin head dispatch instead.
                    resp = (st.worker.node_id,
                            st.worker.worker_id.binary(),
                            bool(st.cspec.max_task_retries))
                elif (st.worker.node_id == self.head_node_id
                      and getattr(st.worker, "peer_path", None)
                      and w.kind == "worker"
                      and not getattr(w, "is_client", False)
                      and requester_on_head
                      and self.config.worker_direct_calls):
                    # Worker peer plane: the requester shares this
                    # machine with the hosting worker — hand it the UDS
                    # so calls skip the head relay entirely (the role of
                    # the reference's direct worker-to-worker gRPC,
                    # actor_task_submitter.h:78).
                    resp = ("uds", st.worker.peer_path,
                            bool(st.cspec.max_task_retries))
        elif what == "my_peer_addr":
            # The requester's node object-plane endpoint: p2p host
            # collectives rendezvous through this once per group, then
            # move every payload agent<->agent (util/collective).
            node = self.nodes.get(w.node_id)
            resp = tuple(node.peer_addr) if (
                node is not None and node.peer_addr) else None
        elif what == "create_pg":
            pg_id, bundles, strategy, name = arg
            resp = self.create_placement_group(pg_id, bundles, strategy, name)
        elif what == "remove_pg":
            self.remove_placement_group(arg)
            resp = True
        elif what == "pg_table":
            resp = self.placement_group_table()
        elif what == "cluster_resources":
            resp = dict(self.total_resources)
        elif what == "available_resources":
            resp = self.available_resources()
        elif what == "nodes":
            resp = self.nodes_table()
        else:
            resp = RayTpuError(f"unknown request {what}")
        w.send(("resp", req_id, resp))

    # ---------------- generic pubsub (publisher side) ----------------

    def pubsub_publish(self, channel: str, key: str, message):
        """Fan a message out to every subscriber of (channel, key):
        worker subscribers get a pubsub_msg push; driver-side local
        callbacks fire inline."""
        with self.lock:
            wids = list(self._pubsub_subs.get((channel, key), ()))
            cbs = list(self._pubsub_local.get((channel, key), ()))
        frame = ("pubsub_msg", channel, key, message)
        for wid in wids:
            w = self.workers.get(wid)
            if w is None or w.state == DEAD:
                with self.lock:
                    subs = self._pubsub_subs.get((channel, key))
                    if subs is not None:
                        subs.discard(wid)
                continue
            try:
                if not self._buffered_send(w, frame):
                    w.send(frame)
            except OSError:
                pass  # death path prunes
        for cb in cbs:
            try:
                cb(message)
            except Exception:  # noqa: BLE001 — one bad cb can't stop fan-out
                traceback.print_exc()

    def pubsub_subscribe(self, channel: str, key: str, callback):
        with self.lock:
            self._pubsub_local.setdefault((channel, key),
                                          []).append(callback)

    def pubsub_unsubscribe(self, channel: str, key: str, callback):
        with self.lock:
            cbs = self._pubsub_local.get((channel, key))
            if cbs is not None:
                try:
                    cbs.remove(callback)
                except ValueError:
                    pass
                if not cbs:
                    self._pubsub_local.pop((channel, key), None)

    def _push_obj_to_worker(self, wid: bytes, oid: bytes, entry):
        w = self.workers.get(wid)
        if w is None or w.state == DEAD:
            return
        def send_or_buffer(frame):
            # Ride the listener's per-drain-pass out-batch when one is
            # active: a fan-out waiter gets thousands of these pushes, and
            # one coalesced sendall beats one syscall (plus one receiver
            # wakeup) per result. Client-mode drivers never get batch
            # frames — their handle_push has no "batch" arm.
            if getattr(w, "is_client", False) or not self._buffered_send(
                    w, frame):
                w.send(frame)

        kind = entry[0]
        if kind == "raw":
            send_or_buffer(("obj", oid, "inline" if entry[3] else "err",
                            entry[1], entry[2]))
        elif kind == "inline":
            payload, bufs, _ = serialization.serialize_value(entry[1])
            send_or_buffer(("obj", oid, "inline", payload, bufs))
        elif kind == "err":
            payload, bufs, _ = serialization.serialize_value(entry[1])
            send_or_buffer(("obj", oid, "err", payload, bufs))
        else:
            if getattr(w, "is_client", False):
                # Clients have no store: materialize on the head and ship
                # the value inline (off-thread — may restore/fetch + read).
                threading.Thread(target=self._push_inline_to_client,
                                 args=(w, oid), daemon=True).start()
                return
            locs = entry[1] if len(entry) > 1 else {self.head_node_id}
            if w.node_id in locs:
                w.send(("obj", oid, "shm", None, None))
                return
            node = self.nodes.get(w.node_id)
            if node is None:
                return

            def done(ok, err, wid=wid, oid=oid, nid=w.node_id):
                if ok:
                    self._push_obj_to_worker(wid, oid, ("shm", {nid}))
                else:
                    w2 = self.workers.get(wid)
                    if w2 is not None and w2.state != DEAD:
                        from ray_tpu.core.status import ObjectLostError
                        payload, bufs, _ = serialization.serialize_value(
                            err or ObjectLostError(ObjectID(oid)))
                        w2.send(("obj", oid, "err", payload, bufs))

            self._fetch_to_node(node, oid, done)

    def _on_wait_objs(self, w: WorkerHandle, oids: list):
        """Batched wait_obj (the vectored dependency fetch): ready shm
        objects that need a pull to w's agent node are routed through the
        fetch collector and grouped per SOURCE into one fetch_many frame
        — a reduce partition's many small exchange pieces cross the wire
        in one batched objxfer round instead of N serial gets. Pending /
        inline / err / local oids take the per-oid wait_obj path."""
        wid = w.worker_id.binary()
        node = self.nodes.get(w.node_id)
        batch: list = []
        for oid in oids:
            entry = self.directory.lookup(oid)
            if (node is not None and node.conn is not None
                    and not getattr(w, "is_client", False)
                    and entry is not None and entry[0] == "shm"
                    and w.node_id not in (entry[1] if len(entry) > 1
                                          else {self.head_node_id})):
                batch.append(oid)
                continue

            def push(entry, oid=oid, wid=wid):
                self._push_obj_to_worker(wid, oid, entry)

            self.directory.on_ready(oid, push)
        if not batch:
            return
        collector: list = []
        for oid in batch:

            def done(ok, err, wid=wid, oid=oid, nid=w.node_id):
                if ok:
                    self._push_obj_to_worker(wid, oid, ("shm", {nid}))
                else:
                    w2 = self.workers.get(wid)
                    if w2 is not None and w2.state != DEAD:
                        from ray_tpu.core.status import ObjectLostError
                        payload, bufs, _ = serialization.serialize_value(
                            err or ObjectLostError(ObjectID(oid)))
                        w2.send(("obj", oid, "err", payload, bufs))

            self._fetch_to_node(node, oid, done, collector=collector)
        self._send_fetch_batches(node, collector)

    def _send_fetch_batches(self, node: NodeState, collector: list):
        """Ship collected (oid, attempt, src_addr) fetch routes: same-source
        groups of >=2 ride ONE fetch_many frame, singletons the classic
        fetch frame. A send failure is recoverable — each entry's armed
        watchdog re-drives it as an individual fetch."""
        groups: dict = {}
        for oid, attempt, src_addr in collector:
            groups.setdefault(tuple(src_addr), []).append((oid, attempt))
        for src_addr, entries in groups.items():
            try:
                if len(entries) == 1:
                    oid, attempt = entries[0]
                    node.conn.send(("fetch", oid, src_addr, attempt))
                else:
                    node.conn.send(("fetch_many", entries, src_addr))
                    with self.lock:
                        self.fetch_batches_sent += 1
            except OSError:
                pass  # watchdog re-drives per-oid

    def _push_inline_to_client(self, w: WorkerHandle, oid: bytes):
        try:
            entry = self.directory.lookup(oid)
            if entry is None or entry[0] != "shm":
                raise RayTpuError("object entry changed under the push")
            locs = entry[1] if len(entry) > 1 else {self.head_node_id}
            if self.head_node_id not in locs:
                if not (oid in self._spilled and self._restore_spilled(oid)):
                    self._pull_to_head(oid, timeout=60.0)
            found, value = self.store.get_deserialized(ObjectID(oid),
                                                       timeout=5.0)
            if not found:
                from ray_tpu.core.status import ObjectLostError
                raise ObjectLostError(ObjectID(oid))
            payload, bufs, _ = serialization.serialize_value(value)
            w.send(("obj", oid, "inline", payload, bufs))
        except Exception as e:  # noqa: BLE001 — ship the failure inline
            try:
                payload, bufs, _ = serialization.serialize_value(e)
                w.send(("obj", oid, "err", payload, bufs))
            except OSError:
                pass

    # ---------------- cluster plane (multi-node) ----------------
    #
    # Parity map: enable_cluster ≈ the GCS server socket
    # (gcs_server_main.cc:50); node agents ≈ raylets registering over gRPC;
    # the heartbeat monitor ≈ GcsHealthCheckManager
    # (gcs_health_check_manager.h:45); cross-node object movement ≈
    # PullManager/PushManager chunked transfer (pull_manager.h:57,
    # push_manager.h:32), carried here as whole-blob frames between
    # node-local shm stores.

    def enable_cluster(self, host: str = "127.0.0.1", port: int = 0) -> str:
        """Open the head's TCP endpoint for node agents; returns addr."""
        with self.lock:
            if self.cluster_addr:
                return self.cluster_addr
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind((host, port or self.config.gcs_port))
            srv.listen(128)
            srv.setblocking(False)
            self._cluster_srv = srv
            # racecheck: ok thread-escape written exactly once while
            # cluster mode boots — no agent exists to race the readers
            # until enable_cluster returns the address they dial
            self.cluster_addr = f"{host}:{srv.getsockname()[1]}"
            # The head serves its own objects to nodes over a dedicated
            # peer port (native C++ server; big blobs must never ride the
            # control link).
            from ray_tpu.core import objxfer
            self._peer_server = objxfer.start_peer_server(self.store, host)
            # racecheck: ok thread-escape same boot-once publication as
            # cluster_addr above
            self.head_peer_addr = (host, self._peer_server.port)
            # Visible through the node table too (p2p collective ranks on
            # the head resolve their endpoint the same way workers do).
            self.head_node.peer_addr = self.head_peer_addr
            # Protobuf client plane on its own port (parity: the dedicated
            # Ray Client server port): non-Python frontends connect here.
            try:
                from ray_tpu.core.client_server import ClientProtoServer
                self._proto_clients = ClientProtoServer(self, host)
                self.client_proto_addr = (
                    f"{host}:{self._proto_clients.addr[1]}")
            except Exception as e:  # noqa: BLE001 — protobuf runtime absent
                import sys
                print(f"ray_tpu: proto client plane unavailable ({e!r})",
                      file=sys.stderr)
                self.client_proto_addr = None
        acc = _Acceptor()
        acc.sock = srv
        self._pump_register(srv, acc, accept=True)
        threading.Thread(target=self._health_loop, daemon=True,
                         name="rtpu-node-health").start()
        return self.cluster_addr

    def _health_loop(self):
        period = self.config.health_check_period_ms / 1000.0
        deadline = period * self.config.health_check_failure_threshold
        reclaim_every = self.config.orphan_reclaim_interval_s
        last_reclaim = time.monotonic()
        while not self._shutdown:
            time.sleep(period)
            now = time.monotonic()
            for node in list(self.nodes.values()):
                if (node.conn is not None and node.state == "ALIVE"
                        and now - node.last_heartbeat > deadline):
                    self._on_node_death(node)
                elif node.conn is not None and node.state == "ALIVE":
                    self._redrive_lost_leases(node, now)
            if (reclaim_every > 0
                    and now - last_reclaim >= reclaim_every):
                # Head-arena liveness sweep: reservations stranded by
                # SIGKILLed head-node workers return to the free list.
                # Under the close gate: shutdown() unmaps the arena, and
                # a sweep dereferencing freed shm is a segfault, not an
                # exception.
                last_reclaim = now
                with self._store_close_lock:
                    if not self._shutdown:
                        try:
                            self.store.reclaim_orphans()
                        except Exception:  # noqa: BLE001 — sweep must
                            traceback.print_exc()  # not kill the loop

    def _redrive_lost_leases(self, node: NodeState, now: float):
        """Lease watchdog: a granted lease whose node_exec frame was lost
        on the wire parks in node.leases forever while the agent idles.
        When the agent reports ITSELF fully idle (no backlog, nothing in
        flight) and a lease is older than lease_redrive_timeout_s, resend
        the grant — the agent dedups (task_id, lease_seq), so a re-drive
        racing a slow original delivery cannot double-queue."""
        timeout = self.config.lease_redrive_timeout_s
        if timeout <= 0 or not node.leases:
            if not node.leases:
                node.lease_sent.clear()
            return
        view = node.load_view
        if view.get("backlog", 0) or view.get("inflight", 0):
            return  # the agent is busy: its leases are simply running
        resend = []
        with self.lock:
            for tid in list(node.lease_sent):
                if tid not in node.leases:
                    node.lease_sent.pop(tid, None)  # completed/moved
                    continue
                ent = node.lease_sent[tid]
                if now - ent[0] < timeout or ent[1] >= 5:
                    continue
                ent[0] = now
                ent[1] += 1
                spec = node.leases[tid]
                # Re-attach the blob unconditionally: the lost frame may
                # have been the one carrying it (lease_fns was already
                # credited at the original grant).
                resend.append((spec.fn_id, self.fn_table.get(spec.fn_id),
                               spec))
        if not resend:
            return
        self.task_events.record(
            resend[0][2].task_id, resend[0][2], "RETRY",
            data={"redrive": "lease"})
        try:
            node.conn.send(("node_exec", resend))
        except OSError:
            pass  # node death handling owns the requeue

    def _handle_node_msg(self, conn: NodeConn, msg):
        op = msg[0]
        if op == "wmsg":
            _, wid, inner = msg
            w = self.workers.get(wid)
            if w is None:
                if conn.node_id is None:
                    return  # agent never registered
                w = RemoteWorkerHandle(WorkerID(wid), conn, conn.node_id)
                with self.lock:
                    self.workers[wid] = w
                    node = self.nodes.get(conn.node_id)
                    if node is not None:
                        node.workers[wid] = w
            self._handle_msg(w, inner)
        elif op == "register_node":
            _, nid, resources, peer_addr, hostname, pid = msg[:6]
            inventory = msg[6] if len(msg) > 6 else []
            ctrl_addr = msg[7] if len(msg) > 7 else None
            obj_inventory = msg[8] if len(msg) > 8 else []
            with self.lock:
                prev = self.nodes.get(nid)
                if prev is not None and prev.state == "ALIVE":
                    # Re-registration (agent reconnected after a head
                    # restart or link flap): adopt the connection without
                    # double-counting resources. Every existing worker
                    # handle must follow — they route through the node conn.
                    prev.conn = conn
                    conn.node_id = nid
                    node = prev
                    if ctrl_addr:
                        prev.ctrl_addr = ctrl_addr
                    for wh in prev.workers.values():
                        if isinstance(wh, RemoteWorkerHandle):
                            wh.node_conn = conn
                else:
                    node = NodeState(nid, resources, conn=conn,
                                     peer_addr=peer_addr, hostname=hostname,
                                     pid=pid, ctrl_addr=ctrl_addr)
                    conn.node_id = nid
                    self.nodes[nid] = node
                    if nid not in self._node_order:
                        self._node_order.append(nid)
                    for k, v in resources.items():
                        self.total_resources[k] = (
                            self.total_resources.get(k, 0.0) + v)
                # New capacity may unblock queued PGs/actors.
                self._kick_waiters()
            if (self._hnat is not None and conn._htag is not None
                    and conn._nidx is None):
                # Native node slot: keys the grant outbox and the
                # completion ledger for this conn. A reconnected agent
                # arrives on a FRESH conn (fresh tag, fresh slot); the
                # old conn's slot retires on its EOF.
                conn._nidx = self._hnat.node_add(conn._htag)
                self._nidx_conn[conn._nidx] = conn
            # (Re-)registration resets the broadcast cursor: the agent's
            # view cache died with its old process/link, so the next
            # broadcast pass resends the full cluster view.
            node.cview_cursor = 0
            self._cview_update(
                nid, state="ALIVE",
                cpu=float((resources or {}).get("CPU", 0.0)),
                ctrl=tuple(ctrl_addr) if ctrl_addr else None)
            # Worker inventory: rebuild handles for surviving workers and
            # adopt the actors they still host (head-restart resync,
            # parity: raylets resyncing with a restarted GCS).
            for item in inventory:
                wid, aid = item[0], item[1]
                env_key = item[2] if len(item) > 2 else None
                language = item[3] if len(item) > 3 else None
                if language not in (None, "python"):
                    # Non-Python workers are agent-local executors on the
                    # lease plane; the head never dispatches to them
                    # directly, so no handle is built (adopting one into
                    # the Python pool would wedge the first pickle exec).
                    continue
                w = self.workers.get(wid)
                if w is None:
                    w = RemoteWorkerHandle(WorkerID(wid), conn, nid)
                    w.connected.set()
                    w.env_key = env_key  # adopted env workers keep their
                    # pip pool — a default task must not land on them
                    with self.lock:
                        self.workers[wid] = w
                        node.workers[wid] = w
                        if not aid:
                            # Surviving pool worker: back into the idle
                            # pool (a mid-task worker just queues behind
                            # its current work).
                            w.state = IDLE
                            node.idle.append(w)
                if aid and not self._adopt_actor_worker(aid, w):
                    # Not adoptable: the actor was restarted elsewhere (or
                    # permanently died) while this node was away — its old
                    # worker is a stale duplicate that must not keep
                    # mutating state.
                    try:
                        conn.send(("kill_worker", wid))
                    except OSError:
                        pass
            # Object inventory: merge surviving arena contents into the
            # directory. On a fresh head this repopulates locations the
            # journal could not carry, resolving replayed dep-gated tasks.
            for oid in obj_inventory:
                self.directory.add_location(oid, nid)
            conn.send(("node_ack", self.head_node_id))
            if self.export_events is not None:
                self.export_events.emit("NODE", node_id=nid.hex(),
                                        state="ALIVE", hostname=hostname)
            self._schedule()
        elif op == "heartbeat":
            node = self.nodes.get(conn.node_id)
            if node is not None:
                node.last_heartbeat = time.monotonic()
                if len(msg) > 2 and isinstance(msg[2], dict):
                    # Agent-local load view rides every heartbeat as a
                    # versioned delta (the ray_syncer.h:20 role): applied
                    # off the scheduling lock, read by the reclaimer and
                    # the state API. TCP FIFO keeps versions monotonic.
                    if msg[2].get("v", 0) >= node.load_view.get("v", -1):
                        node.load_view = msg[2]
                        view = node.load_view
                        self._cview_update(
                            conn.node_id,
                            idle=int(view.get("idle", 0)),
                            backlog=int(view.get("backlog", 0)),
                            inflight=int(view.get("inflight", 0)))
                    if node.load_view.get("backlog"):
                        self._maybe_reclaim_leases(node)
        elif op == "agent_req":
            # Small synchronous agent->head queries (peer discovery).
            _, req_id, what, arg = msg
            resp = None
            if what == "node_ctrl_addr":
                n = self.nodes.get(arg)
                if (n is not None and n.state == "ALIVE"
                        and n.ctrl_addr):
                    resp = tuple(n.ctrl_addr)
            elif what == "object_src":
                # Peer address of a node holding `arg` in its arena — the
                # agent-side dep staging for cpp leases pulls from here.
                with self.lock:
                    self.cross_node_fetches += 1
                e = self.directory.lookup(arg)
                if e is not None and e[0] == "shm":
                    for nid2 in e[1]:
                        n2 = self.nodes.get(nid2)
                        if (n2 is not None and n2.state == "ALIVE"
                                and n2.peer_addr):
                            resp = tuple(n2.peer_addr)
                            break
                    else:
                        head_pa = getattr(self, "head_peer_addr", None)
                        if self.head_node_id in e[1] and head_pa:
                            resp = tuple(head_pa)
            try:
                conn.send(("agent_resp", req_id, resp))
            except OSError:
                pass
        elif op == "node_done":
            self._on_node_done(conn, msg[1])
        elif op == "node_done_raw":
            # Native-agent completion batch: the agent forwarded the
            # workers' done frames RAW (no agent-side unpickle/repickle);
            # the head decodes them here, where the payloads are consumed
            # anyway. msg = (op, worker_hex, [raw outer frames]).
            self._on_node_done_raw(conn, msg[1], msg[2])
        elif op == "lease_fail":
            self._on_lease_fail(conn.node_id, msg[1])
        elif op == "lease_spilled":
            # Async spillback notice: leases moved agent->agent; the head
            # only re-points its bookkeeping (no scheduling pass).
            self._on_lease_spilled(conn.node_id, msg[1])
        elif op == "lease_return":
            self._on_lease_return(conn.node_id, msg[1])
        elif op == "task_events":
            # The agent's OWN ring (spill hops, node-local dispatch),
            # flushed on its select-round head batch / heartbeats.
            self._queue_task_events(msg[1], conn.node_id, None, msg[2])
        elif op == "worker_death":
            w = self.workers.get(msg[1])
            if w is not None:
                self._on_worker_death(w)
        elif op == "fetched":
            _, oid, ok, attempt = msg
            nid = conn.node_id
            err = None
            if ok:
                self.directory.add_location(oid, nid)
            else:
                from ray_tpu.core.status import ObjectLostError
                err = ObjectLostError(ObjectID(oid))
            self._finish_fetch((nid, oid), ok, err, attempt=attempt)
        elif op == "fetched_many":
            # One reply frame for a vectored fetch_many batch.
            nid = conn.node_id
            for oid, ok, attempt in msg[1]:
                err = None
                if ok:
                    self.directory.add_location(oid, nid)
                else:
                    from ray_tpu.core.status import ObjectLostError
                    err = ObjectLostError(ObjectID(oid))
                self._finish_fetch((nid, oid), ok, err, attempt=attempt)
        elif op == "client_hello":
            # A client-mode driver (parity: Ray Client `ray://` sessions):
            # acts like a worker whose every object value travels inline.
            wid = msg[1]
            w = WorkerHandle(WorkerID(wid), conn.sock, None,
                             node_id=self.head_node_id)
            w.send_lock = conn.send_lock  # one TCP writer lock
            w.state = "client"  # never enters the idle pool
            w.is_client = True
            w.connected.set()
            # Client sends ride a dedicated writer thread: a large value
            # push (a client get() of a GB object is one inline frame)
            # must never run sendall on the LISTENER thread — it would
            # stall the whole control plane for the transfer (parity: the
            # reference chunks client values through a dedicated client
            # server, util/client/server/).
            import queue as _queue
            outq: "_queue.Queue" = _queue.Queue(maxsize=256)
            direct_send = w.send

            def _client_writer(outq=outq, direct_send=direct_send,
                               sock=conn.sock):
                while True:
                    m = outq.get()
                    if m is None:
                        return
                    try:
                        direct_send(m)
                    except Exception:  # noqa: BLE001 — ANY failure ends
                        # the stream: close the socket so the listener's
                        # EOF path runs full client cleanup (a silently
                        # dead writer would black-hole every later reply).
                        try:
                            sock.close()
                        except OSError:
                            pass
                        return

            threading.Thread(target=_client_writer, daemon=True,
                             name="rtpu-client-tx").start()

            def _client_send(m, outq=outq, sock=conn.sock):
                try:
                    # Bounded: a client that stops draining multi-GB
                    # replies is disconnected rather than buffering the
                    # head into OOM (sendall's old backpressure stalled
                    # the listener instead; neither tail is kept).
                    outq.put_nowait(m)
                except _queue.Full:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    raise OSError("client send queue overflow")

            w.send = _client_send
            w._client_outq = outq
            conn.client_handle = w
            with self.lock:
                self.workers[wid] = w
        else:
            raise RayTpuError(f"head: unknown node message {op}")

    def _park_fetch_for_reconstruction(self, dest: NodeState, oid: bytes,
                                       key) -> bool:
        """If `oid` is being recomputed from lineage, park this fetch's
        callbacks until the fresh copy lands, then re-route them. Returns
        True when parked (the caller must not fail the fetch)."""
        with self.lock:
            spec = self._lineage.get(oid)
            if spec is None or spec.task_id not in self._reconstructing:
                return False
            info = self._fetches.pop(key, None)
        cbs = (info or {}).get("cbs", [])
        if not cbs:
            return True

        def on_entry(entry, dest=dest, oid=oid, cbs=cbs):
            from ray_tpu.core.status import ObjectLostError
            for cb in cbs:
                if entry[0] == "shm":
                    self._fetch_to_node(dest, oid, cb)
                elif entry[0] == "err":
                    cb(False, entry[1])
                else:
                    # Deterministic re-execution should reproduce the same
                    # storage tier; a raw/inline rebirth is unexpected here.
                    cb(False, ObjectLostError(ObjectID(oid)))

        self.directory.on_ready(oid, on_entry)
        return True

    def _fetch_to_node(self, dest: NodeState, oid: bytes, done_cb,
                       collector: list | None = None):
        """Materialize `oid` in `dest`'s store; done_cb(ok, err) when done.
        Non-blocking; safe to call from the listener thread.

        With `collector`, an agent-bound fetch frame is appended as
        (oid, attempt, src_addr) instead of being sent — _on_wait_objs
        groups same-source entries into ONE fetch_many frame (the
        vectored pull plane); the per-oid watchdog still arms, so a
        dropped batch frame degrades to individual re-driven fetches."""
        with self.lock:
            key = (dest.node_id, oid)
            info = self._fetches.get(key)
            if info is not None:
                info["cbs"].append(done_cb)
                return
            self._fetch_attempts += 1
            self.cross_node_fetches += 1
            info = {"cbs": [done_cb], "src": None,
                    "attempt": self._fetch_attempts}
            self._fetches[key] = info
        entry = self.directory.lookup(oid)
        from ray_tpu.core.status import ObjectLostError
        if entry is None or entry[0] != "shm":
            if entry is None and self._park_fetch_for_reconstruction(
                    dest, oid, key):
                return
            self._finish_fetch(key, False, ObjectLostError(ObjectID(oid)))
            return
        locs = entry[1] if len(entry) > 1 else {self.head_node_id}
        srcs = [n for nid in locs
                if (n := self.nodes.get(nid)) is not None
                and n.state == "ALIVE"]
        if not srcs:
            if oid in self._spilled:
                # Restore from disk off-thread, then re-route the fetch
                # (the restored copy lands on the head).
                def restore():
                    if self._restore_spilled(oid):
                        with self.lock:
                            info2 = self._fetches.pop(key, None)
                        for cb in (info2 or {}).get("cbs", []):
                            if dest.node_id == self.head_node_id:
                                cb(True, None)
                            else:
                                self._fetch_to_node(dest, oid, cb)
                    else:
                        self._finish_fetch(key, False,
                                           ObjectLostError(ObjectID(oid)))
                threading.Thread(target=restore, daemon=True).start()
                return
            # Discard BEFORE deciding (same ordering as the node-death
            # path): a reconstruction completing mid-decision re-adds its
            # fresh entry after, instead of having it wiped.
            self.directory.discard(oid)
            if self._maybe_reconstruct(oid):
                if self._park_fetch_for_reconstruction(dest, oid, key):
                    return
                # Raced to completion between the two calls: re-drive.
                with self.lock:
                    info2 = self._fetches.pop(key, None)
                for cb in (info2 or {}).get("cbs", []):
                    self._fetch_to_node(dest, oid, cb)
                return
            self.directory.put(oid, ("err", ObjectLostError(ObjectID(oid))))
            self._on_object_ready(oid)
            self._finish_fetch(key, False, ObjectLostError(ObjectID(oid)))
            return
        src = srcs[0]
        info["src"] = src.node_id
        try:
            if dest.conn is None:
                # Head-bound pull rides the source's dedicated peer port (a
                # per-pull connection), NOT the agent's control link — a big
                # blob on the control link would head-of-line-block every
                # worker message relay on that node.
                threading.Thread(target=self._pull_via_peer,
                                 args=(src, oid, info["attempt"]),
                                 daemon=True).start()
            else:
                if src.conn is not None:
                    src_addr = tuple(src.peer_addr)
                else:
                    src_addr = self.head_peer_addr
                if collector is not None:
                    collector.append((oid, info["attempt"], src_addr))
                else:
                    dest.conn.send(("fetch", oid, src_addr,
                                    info["attempt"]))
        except OSError as e:
            self._finish_fetch(key, False, e)
            return
        if dest.conn is not None:
            # Frame-based agent fetch only: the head-bound peer pull runs in
            # its own thread and always resolves itself.
            self._arm_fetch_watchdog(key, info["attempt"])

    def _arm_fetch_watchdog(self, key, attempt):
        """A fetch whose frame (or reply) was dropped would otherwise park
        every co-waiter forever. RESEND the frame periodically (bounded,
        same attempt id — a slow but healthy transfer keeps its attempt and
        its eventual completion stays valid; a duplicate pull on the agent
        is idempotent). Truly-lost objects are failed by the node-death /
        no-source paths, never by the watchdog itself."""
        period = self.config.fetch_retry_timeout_s
        if period <= 0:
            return

        def check():
            from ray_tpu.core.status import ObjectLostError
            with self.lock:
                info = self._fetches.get(key)
                if info is None or info["attempt"] != attempt:
                    return  # completed or superseded
                retries = info.get("retries", 0)
                info["retries"] = retries + 1
            dest = self.nodes.get(key[0])
            if dest is None or dest.state != "ALIVE" or dest.conn is None:
                # Dest died between pops and probes: fail the waiters —
                # the stale-dest sweep may already have missed this entry.
                with self.lock:
                    info2 = self._fetches.pop(key, None)
                for cb in (info2 or {}).get("cbs", []):
                    cb(False, ObjectLostError(ObjectID(key[1])))
                return
            if retries >= 5:
                return  # stop resending; other failure paths own it now
            entry = self.directory.lookup(key[1])
            src = None
            if entry is not None and entry[0] == "shm" and len(entry) > 1:
                src = next((n for nid in entry[1]
                            if (n := self.nodes.get(nid)) is not None
                            and n.state == "ALIVE"), None)
            if src is None:
                # No live source anymore: re-drive through the normal path
                # (spill restore / reconstruction / loss).
                with self.lock:
                    info2 = self._fetches.pop(key, None)
                for cb in (info2 or {}).get("cbs", []):
                    self._fetch_to_node(dest, key[1], cb)
                return
            try:
                src_addr = (tuple(src.peer_addr) if src.conn is not None
                            else self.head_peer_addr)
                dest.conn.send(("fetch", key[1], src_addr, attempt))
            except OSError:
                pass
            self._arm_fetch_watchdog(key, attempt)

        t = threading.Timer(period, check)
        t.daemon = True
        t.start()

    def _pull_via_peer(self, src: NodeState, oid: bytes, attempt=None):
        """Worker thread: pull one object from src's peer port to the head
        store (parity: PullManager issuing a chunked pull)."""
        from ray_tpu.core import objxfer
        from ray_tpu.core.status import ObjectLostError
        key = (self.head_node_id, oid)
        ok, err = False, None
        try:
            self._ensure_headroom(1 << 20)  # size unknown until received
            if objxfer.fetch_from_peer(self.store, src.peer_addr, oid):
                self.directory.add_location(oid, self.head_node_id)
                ok = True
            else:
                err = ObjectLostError(ObjectID(oid))
        except Exception as e:  # noqa: BLE001 — conn reset, store full, ...
            err = e
        self._finish_fetch(key, ok, err, attempt=attempt)

    def _finish_fetch(self, key, ok: bool, err=None, attempt=None):
        with self.lock:
            info = self._fetches.get(key)
            if info is None:
                return
            if attempt is not None and info.get("attempt") != attempt:
                return  # stale completion from a superseded attempt
            self._fetches.pop(key, None)
        for cb in (info["cbs"] if info else []):
            try:
                cb(ok, err)
            except Exception:  # noqa: BLE001
                traceback.print_exc()

    def _pull_to_head(self, oid: bytes, timeout: float | None = None):
        """Blocking: fetch a remote object into the head store (driver get).
        Honors the caller's get() timeout (None = wait for the transfer —
        fetch *failures* still resolve promptly via node-death callbacks).
        Must NOT run on the listener thread (see as_future)."""
        ev = threading.Event()
        box = []

        def done(ok, err):
            box.append((ok, err))
            ev.set()

        self._fetch_to_node(self.head_node, oid, done)
        if not ev.wait(timeout):
            # Abandon only THIS caller: the transfer (and any co-waiters)
            # stay live; popping the whole record would fail them spuriously.
            with self.lock:
                info = self._fetches.get((self.head_node_id, oid))
                if info is not None:
                    try:
                        info["cbs"].remove(done)
                    except ValueError:
                        pass
            raise GetTimeoutError(
                f"timed out pulling object {oid.hex()[:16]} to the head")
        ok, err = box[0]
        if not ok:
            from ray_tpu.core.status import ObjectLostError
            raise err if isinstance(err, Exception) else ObjectLostError(
                ObjectID(oid))

    def _on_node_conn_closed(self, conn: NodeConn):
        self._pump_unregister(conn.sock, conn)
        if self._hnat is not None and conn._nidx is not None:
            # Retire the native node slot: drops its staged grants and
            # (task_id, lease_seq) mirror entries — Python requeues the
            # leases themselves from node.leases below.
            self._hnat.node_remove(conn._nidx)
            self._nidx_conn.pop(conn._nidx, None)
            conn._nidx = None
        try:
            conn.sock.close()
        except OSError:
            pass
        if conn.client_handle is not None:
            outq = getattr(conn.client_handle, "_client_outq", None)
            if outq is not None:
                try:  # retire the writer; a full queue means it already
                    outq.put_nowait(None)  # exited — never block the
                except Exception:  # noqa: BLE001 — listener thread here
                    pass
            self._on_worker_death(conn.client_handle)
            return
        if conn.node_id is not None:
            node = self.nodes.get(conn.node_id)
            # A reconnected agent already swapped in a fresh conn: the OLD
            # socket's EOF must not kill the re-registered live node.
            if node is not None and node.conn is conn:
                self._on_node_death(node)

    def _on_node_death(self, node: NodeState):
        """Node failure: fail/retry its tasks, restart its actors elsewhere,
        scrub its object locations (parity: GCS node-death publish +
        owner-side recovery, gcs_health_check_manager.h:45)."""
        with self.lock:
            if node.state == "DEAD":
                return
            node.state = "DEAD"
            for k, v in node.total.items():
                self.total_resources[k] = max(
                    0.0, self.total_resources.get(k, 0.0) - v)
            orphaned_assigns = list(node.pending_actor_assign)
            node.pending_actor_assign.clear()
        conn = node.conn
        if (self._hnat is not None and conn is not None
                and conn._nidx is not None):
            # Health-timeout death (no conn EOF yet): retire the native
            # node slot NOW so its staged grants and inflight mirror
            # entries can't outlive the lease requeue below.
            self._hnat.node_remove(conn._nidx)
            self._nidx_conn.pop(conn._nidx, None)
            conn._nidx = None
        if self.export_events is not None:
            self.export_events.emit("NODE", node_id=node.node_id.hex(),
                                    state="DEAD")
        # Broadcast the death: agents must stop spilling leases (and
        # dialing direct-call channels) toward this node.
        self._cview_update(node.node_id, state="DEAD")
        for w in list(node.workers.values()):
            self._on_worker_death(w)
        # Leased tasks died with the node: same policy as a dead worker's
        # running task — each MAY have started, so replays consume a retry.
        leased = list(node.leases.values())
        node.leases.clear()
        for spec in leased:
            # The bulk clear bypasses _pop_lease_locked (so the
            # _on_lease_fail below finds nothing to pop): settle the
            # grant's quota charge here or the retry's re-charge trips
            # the double-grant guard and the key parks forever.
            self.jobs.settle(getattr(spec, "job_id", None) or DEFAULT_JOB,
                             spec.task_id)
        if leased:
            self._on_lease_fail(node.node_id, leased)
        # Actors queued for assignment on this node never get a worker now:
        # release their dead-node reservation and re-place them.
        for aid in orphaned_assigns:
            st = self.actors.get(aid)
            if st is None or st.state == A_DEAD:
                continue
            with self.lock:
                if st.resources_reserved:
                    self._release_token(st.resources_reserved)
                    st.resources_reserved = None
            threading.Thread(target=self._create_actor_now,
                             args=(st.cspec,), daemon=True).start()
        # Scrub object locations; sole-copy objects are lost — recompute
        # them from lineage where possible, else poison their entries.
        from ray_tpu.core.status import ObjectLostError
        lost = []
        with self.directory.lock:
            for oid, e in self.directory.entries.items():
                if e[0] == "shm" and len(e) > 1 and node.node_id in e[1]:
                    e[1].discard(node.node_id)
                    if not e[1] and oid not in self._spilled:
                        lost.append(oid)
        for oid in lost:
            # Drop the location-less entry first: readers block on the
            # absent entry while reconstruction decides/runs, and a sibling
            # reconstruction finishing mid-loop re-adds it afterwards.
            self.directory.discard(oid)
            if self._maybe_reconstruct(oid):
                continue
            self.directory.put(oid, ("err", ObjectLostError(ObjectID(oid))))
            self._on_object_ready(oid)
        # In-flight fetches: dest died -> fail them; source died -> retry
        # from a surviving replica (directory is already scrubbed).
        with self.lock:
            stale_dest = [k for k in self._fetches if k[0] == node.node_id]
            stale_src = [k for k, info in self._fetches.items()
                         if info.get("src") == node.node_id
                         and k[0] != node.node_id]
        for key in stale_dest:
            self._finish_fetch(key, False, ObjectLostError(ObjectID(key[1])))
        for key in stale_src:
            with self.lock:
                info = self._fetches.pop(key, None)
            if info is None:
                continue
            dest = self.nodes.get(key[0])
            if dest is None or dest.state != "ALIVE":
                for cb in info["cbs"]:
                    cb(False, ObjectLostError(ObjectID(key[1])))
                continue
            for cb in info["cbs"]:
                self._fetch_to_node(dest, key[1], cb)
        self._schedule()

    def nodes_table(self) -> list[dict]:
        out = []
        for nid in list(self._node_order):
            node = self.nodes.get(nid)
            if node is None:
                continue
            out.append({
                "node_id": nid.hex(),
                "alive": node.state == "ALIVE",
                "is_head": node.conn is None,
                "hostname": node.hostname,
                "resources": dict(node.total),
                "available": dict(node.available),
            })
        return out

    # ---------------- object plane ----------------

    def node_of_object(self, oid: bytes) -> str | None:
        """Hex node id of a live node holding `oid` in its arena, or None
        for inline/err/unknown entries. The data executor's locality
        hints resolve block owners through this (soft NodeAffinity: the
        head's placement still falls back when the owner is saturated or
        dead)."""
        e = self.directory.lookup(oid)
        if e is None or e[0] != "shm":
            return None
        locs = e[1] if len(e) > 1 else {self.head_node_id}
        with self.lock:
            for nid in locs:
                n = self.nodes.get(nid)
                if n is not None and n.state == "ALIVE":
                    return nid.hex()
        return None

    def put(self, value) -> "ObjectRef":
        from ray_tpu.core.object_ref import ObjectRef
        oid = ObjectID.from_random()
        self.put_in_store(oid, value)
        self.directory.put(oid.binary(), ("shm", {self.head_node_id}))
        return ObjectRef(oid)

    def put_tagged(self, value) -> "ObjectRef":
        """put() in the language-neutral tagged arena layout (see
        object_store.TAGGED_META): the sealed object is readable by
        non-Python workers zero-copy — and by Python readers through the
        normal get path. Raises if `value` has no tagged encoding (the
        no-pickle assertion runs at the sender)."""
        from ray_tpu.core import proto_wire
        from ray_tpu.core.object_ref import ObjectRef
        fmt, data = proto_wire.encode_tagged(value, allow_pickle=False)
        oid = ObjectID.from_random()
        self.put_tagged_store(oid, fmt, data)
        self.directory.put(oid.binary(), ("shm", {self.head_node_id}))
        return ObjectRef(oid)

    def put_tagged_store(self, oid: "ObjectID", fmt: str, data,
                         job_id: str | None = None) -> None:
        """Seal (format, bytes) into the head arena with spill headroom —
        the tagged-layout sibling of put_in_store."""
        from ray_tpu.core.status import ObjectStoreFullError
        self._ensure_headroom(len(data) + 64)
        try:
            self.store.put_tagged(oid, fmt, data)
        except ObjectStoreFullError:
            if not self._spill_bytes(int(len(data) * 1.5) + (1 << 20)):
                raise
            self.store.put_tagged(oid, fmt, data)
        self._account_put(oid.binary(), len(data), job_id)

    def put_arg_object(self, value, nbytes) -> bytes:
        """Store one offloaded-args pack (serialization.maybe_offload_args)
        from the driver. Listed in the spec's dependencies, so submit_task
        pins it; _unpin_deps frees it after the final completion."""
        oid = ObjectID.from_random()
        self.put_in_store(oid, value)
        self.directory.put(oid.binary(), ("shm", {self.head_node_id}))
        return oid.binary()

    def get(self, refs, timeout=None):
        from ray_tpu.core.object_ref import ObjectRef
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        deadline = None if timeout is None else time.monotonic() + timeout
        out = []
        for r in refs:
            remain = None if deadline is None else max(0.0, deadline - time.monotonic())
            out.append(self._get_one(r, remain))
        return out[0] if single else out

    def _get_one(self, ref, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        entry = self.directory.lookup(ref.id.binary())
        if entry is None:
            ev = threading.Event()
            box = []

            def cb(e):
                box.append(e)
                ev.set()

            self.directory.on_ready(ref.id.binary(), cb)
            if not ev.wait(timeout):
                raise GetTimeoutError(f"get() timed out on {ref}")
            entry = box[0]
        remain = (None if deadline is None
                  else max(1e-3, deadline - time.monotonic()))
        return self._entry_value(ref, entry, timeout=remain)

    def _entry_value(self, ref, entry, timeout=None):
        kind = entry[0]
        if kind == "raw":
            value = serialization.deserialize(entry[1], entry[2])
            if entry[3]:
                return value
            entry = ("err", value)
            kind = "err"
        if kind == "inline":
            return entry[1]
        if kind == "err":
            e = entry[1]
            if isinstance(e, TaskError) and e.cause is not None:
                raise e.cause
            raise e
        locs = entry[1] if len(entry) > 1 else {self.head_node_id}
        if self.head_node_id not in locs:
            if not (ref.id.binary() in self._spilled
                    and self._restore_spilled(ref.id.binary())):
                self._pull_to_head(ref.id.binary(), timeout=timeout)
        found, value = self.store.get_deserialized(ref.id, timeout=5.0)
        if not found:
            from ray_tpu.core.status import ObjectLostError
            raise ObjectLostError(ref.id)
        return value

    def _wait_oids(self, oids: list, num_returns: int,
                   timeout) -> list:
        """wait() over raw oid bytes (client mode) — same ready-pulse
        re-probe as Runtime.wait (no per-ref ghost callbacks)."""
        ready, pending = self.directory.split_ready(oids)
        ready_set: set = set(ready)
        deadline = None if timeout is None else time.monotonic() + timeout
        cv = self.directory.ready_cv
        with cv:
            while len(ready_set) < num_returns:
                gen = self.directory.ready_gen
                fresh, pending = self.directory.split_ready(pending)
                ready_set.update(fresh)
                if len(ready_set) >= num_returns:
                    break
                remain = (None if deadline is None
                          else deadline - time.monotonic())
                if remain is not None and remain <= 0:
                    break
                if self.directory.ready_gen == gen:
                    cv.wait(min(remain, 0.1) if remain is not None
                            else 0.1)
        return [oid for oid in oids if oid in ready_set]

    def wait(self, refs, num_returns=1, timeout=None):
        if num_returns > len(refs):
            raise ValueError("num_returns > len(refs)")
        # Fastest path: wait()'s contract returns AT MOST num_returns ready
        # refs — everything else goes to not_ready regardless of its actual
        # state (same as the reference, `ray.wait`). So probe in order and
        # STOP as soon as num_returns are found: the canonical
        # pop-one-ref-per-call drain loop costs O(1) probes per call when
        # completions keep pace, instead of O(N) probes of every pending
        # ref on every call.
        entries = self.directory.entries
        with self.directory.lock:
            found = []
            for i, r in enumerate(refs):
                if r.id.binary() in entries:
                    found.append(i)
                    if len(found) == num_returns:
                        break
        if len(found) == num_returns:
            fset = set(found)
            ready = [refs[i] for i in found]
            not_ready = [r for i, r in enumerate(refs) if i not in fset]
            return ready, not_ready
        # Not enough ready. The scan above only breaks on success, so it
        # covered every ref — reuse its partition instead of re-probing
        # (split_ready here would double the lock-held probe cost exactly
        # when the caller is about to block).
        oids = [r.id.binary() for r in refs]
        fset = set(found)
        ready_set: set[bytes] = {oids[i] for i in found}
        pending = [o for i, o in enumerate(oids) if i not in fset]
        if len(ready_set) < num_returns:
            # Slow path: sleep on the directory's global ready pulse and
            # re-probe only the still-pending refs on each pulse (one lock
            # per probe batch). No per-ref callbacks: a pop-one-ref wait
            # loop over N refs costs O(N^2) cheap dict probes total, not
            # O(N^2) callback registrations + firings.
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            cv = self.directory.ready_cv
            with cv:
                while True:
                    gen = self.directory.ready_gen
                    fresh, pending = self.directory.split_ready(pending)
                    ready_set.update(fresh)
                    if len(ready_set) >= num_returns:
                        break
                    remain = (None if deadline is None
                              else deadline - time.monotonic())
                    if remain is not None and remain <= 0:
                        break
                    if self.directory.ready_gen == gen:
                        cv.wait(min(remain, 0.1) if remain is not None
                                else 0.1)
        ready = [r for r, o in zip(refs, oids) if o in ready_set]
        not_ready = [r for r, o in zip(refs, oids) if o not in ready_set]
        overflow = ready[num_returns:]
        return ready[:num_returns], overflow + not_ready

    def as_future(self, ref) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def cb(entry):
            def resolve():
                try:
                    fut.set_result(self._entry_value(ref, entry))
                except BaseException as e:  # noqa: BLE001
                    fut.set_exception(e)

            # A remote-only shm entry makes _entry_value block in
            # _pull_to_head; the ready-callback may be running on the
            # listener thread, which must stay free to process the pull's
            # completion — hand the blocking resolve to a thread.
            if (entry[0] == "shm" and len(entry) > 1
                    and self.head_node_id not in entry[1]):
                threading.Thread(target=resolve, daemon=True).start()
            else:
                resolve()

        self.directory.on_ready(ref.id.binary(), cb)
        return fut

    def _free_object(self, oid: bytes):
        entry = self.directory.lookup(oid)
        self.directory.discard(oid)
        self.jobs.release_object(oid)
        # Only shm-backed (or unknown — maybe mid-seal) entries touch the
        # native store: a delete miss there linear-probes the slot table,
        # which is pure waste for the inline-result common case.
        if entry is None or entry[0] == "shm":
            self.store.delete(ObjectID(oid))
        path = self._spilled.pop(oid, None)
        if path is not None:
            try:
                os.unlink(path)
            except OSError:
                pass
        if entry is not None and entry[0] == "shm" and len(entry) > 1:
            for nid in entry[1]:
                n = self.nodes.get(nid)
                if n is not None and n.conn is not None:
                    try:
                        n.conn.send(("free_obj", oid))
                    except OSError:
                        pass
        self._lineage_release(oid)

    # ---------------- lineage reconstruction ----------------
    #
    # Parity map: _lineage_register ≈ lineage retention in the owner's
    # ReferenceCounter (reference_count.h:72); _maybe_reconstruct ≈
    # ObjectRecoveryManager::RecoverObject (object_recovery_manager.h:43)
    # driving TaskManager lineage resubmission (task_manager.h:216). Specs of
    # finished normal tasks are retained while any of their return objects
    # (or a downstream retained spec's dependency chain) is alive; when a
    # node death wipes the only copy of a plasma-tier object, the producing
    # task is transparently re-executed — recursively, since its own inputs
    # may be gone too.

    def _lineage_register(self, spec: TaskSpec):
        """Retain a finished task's spec for object recovery."""
        cap = self.config.lineage_cache_entries
        if not cap or spec.actor_id is not None:
            return
        with self.lock:
            first = spec.task_id not in self._lineage_live
            if first and len(self._lineage) >= cap:
                return  # cache full — outputs are simply not recoverable
            live = self._lineage_live.setdefault(spec.task_id, set())
            for rid in spec.return_ids:
                self._lineage[rid] = spec
                live.add(rid)
            if first:
                for d in spec.dependencies or []:
                    self._lineage_pins[d] = self._lineage_pins.get(d, 0) + 1

    def _lineage_release(self, oid: bytes):
        """The object was freed (refcount zero): its lineage entry can go —
        unless a retained downstream spec still lists it as a dependency, in
        which case the drop is deferred (lineage pinning)."""
        with self.lock:
            if self._lineage_pins.get(oid, 0) > 0:
                if oid in self._lineage:
                    self._lineage_freed.add(oid)
                return
            self._drop_lineage_locked(oid)

    def _drop_lineage_locked(self, oid: bytes):
        self._lineage_freed.discard(oid)
        spec = self._lineage.pop(oid, None)
        if spec is None:
            return
        live = self._lineage_live.get(spec.task_id)
        if live is not None:
            live.discard(oid)
            if live:
                return
        # Spec fully dead: unpin its dependencies (cascading drops for deps
        # that were themselves freed while pinned).
        self._lineage_live.pop(spec.task_id, None)
        self._reconstruct_count.pop(spec.task_id, None)
        for d in spec.dependencies or []:
            n = self._lineage_pins.get(d, 0) - 1
            if n <= 0:
                self._lineage_pins.pop(d, None)
                if d in self._lineage_freed:
                    self._drop_lineage_locked(d)
            else:
                self._lineage_pins[d] = n

    def _maybe_reconstruct(self, oid: bytes) -> bool:
        """Try to recover a lost plasma-tier object by re-executing its
        producing task. Returns True if a reconstruction is running (the
        object's directory entry must then stay absent so readers block
        until the re-execution lands a fresh copy)."""
        with self.lock:
            spec = self._lineage.get(oid)
            if spec is None:
                return False
            if spec.task_id in self._reconstructing:
                return True
            n = self._reconstruct_count.get(spec.task_id, 0)
            if n >= self.config.max_object_reconstructions:
                return False
            self._reconstruct_count[spec.task_id] = n + 1
            self._reconstructing.add(spec.task_id)
        # Inputs may be gone too (freed after use, or lost in the same node
        # death): kick their recovery first. An unrecoverable dep means the
        # resubmitted task would gate forever — give up on this object.
        for d in spec.dependencies or []:
            if d in self._spilled:
                continue  # restorable from the spill tier, not lost
            entry = self.directory.lookup(d)
            missing = entry is None or (entry[0] == "shm" and len(entry) > 1
                                        and not entry[1])
            if missing:
                if entry is not None:
                    self.directory.discard(d)
                if not self._maybe_reconstruct(d):
                    with self.lock:
                        self._reconstructing.discard(spec.task_id)
                    return False
        # Fresh worker-crash retry budget for the re-execution.
        spec.retries_left = spec.max_retries
        self.task_events.record(spec.task_id, spec, "RECONSTRUCTING")
        self.submit_task(spec)
        return True

    def _on_object_ready(self, oid: bytes):
        """Unblock tasks waiting on this dependency + remote subscribers.
        Schedules only when something actually became ready — the no-waiter
        common case (every task completion) otherwise forces a dispatch
        pass per result, defeating the refill batching in _on_task_done."""
        ready_items = []
        with self.lock:
            for item in self.waiting_deps.pop(oid, []):
                # Decrement under the lock: listener and driver threads can
                # complete different deps of the same item concurrently.
                item["pending"] -= 1
                if item["pending"] == 0:
                    ready_items.append(item)
        if ready_items:
            for item in ready_items:
                self._enqueue_ready(item)
            self._schedule()

    # ---------------- task submission / scheduling ----------------

    def export_function(self, fn_id: bytes, blob: bytes):
        # Every submission ships the blob; all but the first are repeats.
        # The unlocked membership probe is safe (same fn_id -> same blob,
        # and dict reads are atomic) and keeps the submit path off the
        # scheduling lock — under a 64-agent storm this lock acquire
        # sampled hotter than the actual export.
        if fn_id in self.fn_table:
            return
        with self.lock:
            self.fn_table[fn_id] = blob

    def submit_task(self, spec: TaskSpec, fn_blob: bytes | None = None):
        if fn_blob is not None:
            self.export_function(spec.fn_id, fn_blob)
        if self._persist and spec.actor_id is None and not spec.streaming:
            # Journal normal tasks so a restarted head re-queues them
            # (removed again on completion/failure). Out-of-band buffers
            # become plain bytes for the pickle journal.
            self._pstore.append("task", spec.task_id,
                                _journal_safe_spec(spec))
        elif self._wal and spec.actor_id is None and spec.streaming:
            # WAL: an ADMITTED stream survives a head SIGKILL — restore
            # resubmits the spec (yields regenerate deterministically)
            # and the reconnected consumer continues at its absolute
            # index. Retired when the stream is exhausted or abandoned.
            self._pstore.append("stream", spec.task_id,
                                (_journal_safe_spec(spec), 0))
        # Job attribution of record: the spec's stamped tenant (falling
        # back to the default driver job) keys the task-event storage's
        # per-job accounting AND the ledger's submit counters — the
        # owner-hex pseudo-jobs of the pre-tenancy era are gone.
        jid = getattr(spec, "job_id", None) or DEFAULT_JOB
        self.jobs.note_submitted(jid)
        self.task_events.record(
            spec.task_id, spec, "SUBMITTED", data={"job": jid})
        if spec.streaming:
            self._register_stream(spec.task_id)
            with self.lock:
                # Keyed by task_id (no return ids): ray_tpu.cancel on the
                # generator resolves through the same table.
                self._rid_to_spec[spec.task_id] = spec
        with self.lock:
            for rid in spec.return_ids:
                self._rid_to_spec[rid] = spec
        # Pin dependencies for the task's lifetime so the owner cannot free
        # them between submit and execution (conservative borrower counting).
        for oid in spec.dependencies or []:
            self.refcount.pin(oid)
        item = {"kind": "task", "spec": spec, "pending": 0}
        ready = self._gate_on_deps(item, spec.dependencies or [])
        if (not ready and spec.actor_id is not None
                and getattr(spec, "caller_seq", None) is not None):
            # A seq-stamped actor call parked on pending deps: tell the
            # executing agent to release the slot now so later calls from
            # this caller don't stall behind it. The call itself delivers
            # when its deps resolve — exactly the reference's semantics,
            # where the submission slot is claimed at dependency
            # resolution time (dependency_resolver.h), not submit time.
            self._send_seq_skip(spec)

    def _broadcast_actor_moved(self, actor_id: bytes):
        """Poison cached direct-call locations for a dying/moving/
        restarted actor on every pooled worker — head-node workers
        directly, agent-node workers through their node relay (their
        cached UDS paths and negative "head-hosted" entries both go
        stale the moment the actor moves). The caller-side UDS EOF is
        the belt, this the braces."""
        with self.lock:
            targets = [w for w in self.workers.values()
                       if not getattr(w, "is_client", False)
                       and getattr(w, "kind", "worker") == "worker"]
        for w in targets:
            try:
                w.send(("actor_moved", actor_id))
            except OSError:
                pass

    def _send_seq_skip(self, spec: TaskSpec):
        st = self.actors.get(spec.actor_id)
        if st is None:
            return
        skip = ("seq_skip", spec.owner, spec.actor_id, spec.caller_seq)
        if (st.node_id == self.head_node_id and st.worker is not None):
            # Head-node actor: the gate lives in the hosting worker
            # (worker peer plane).
            try:
                st.worker.send(skip)
            except OSError:
                pass  # gap timeout at the worker resyncs
            return
        node = self.nodes.get(st.node_id)
        if node is not None and node.conn is not None:
            try:
                node.conn.send(skip)
            except OSError:
                pass  # gap timeout at the agent resyncs

    # ---------------- streaming tasks (ObjectRefGenerator) ----------------
    #
    # Parity: reference `num_returns="streaming"` generator tasks
    # (_raylet.pyx:280,295 ObjectRefGenerator). The executing worker sends
    # one "stream_item" per yield; the consumer's generator blocks in
    # next_stream_item until the item lands (or the stream closes).

    def _register_stream(self, task_id: bytes):
        with self.lock:
            self._streams[task_id] = {
                "items": [], "done": False, "consumed": 0,
                "abandoned": False,
                "cv": threading.Condition(self.lock),
                "parked": [],  # [(idx, cb)] worker-side stream_next waiters
            }

    def _journal_stream_cursor(self, task_id: bytes, consumed: int):
        """WAL the consumer's cursor so a restarted head restores the
        stream's consumed mark (abandon-drop bookkeeping stays correct
        across the restart). No-op unless the full WAL is on."""
        if self._wal:
            self._pstore.append("stream_cur", task_id, consumed)

    def _journal_stream_drop(self, task_id: bytes):
        """Retire a stream's WAL records: it is exhausted or abandoned —
        no longer 'admitted', so a restart must not resubmit it."""
        if self._wal:
            self._pstore.delete("stream", task_id)
            self._pstore.delete("stream_cur", task_id)

    def _stream_append(self, task_id: bytes, rid: bytes):
        with self.lock:
            st = self._streams.get(task_id)
            if st is None or st["abandoned"]:
                # No consumer will ever read this yield: drop it now so an
                # abandoned stream cannot grow driver memory unboundedly.
                self.directory.discard(rid)
                return
            st["items"].append(rid)
            st["cv"].notify_all()
            fired = self._pop_parked_locked(st)
        for cb, rid_or_none in fired:
            cb(rid_or_none)

    def _pop_parked_locked(self, st) -> list:
        """Collect parked stream_next callbacks that can now be answered
        (item arrived, or the stream closed). Fire OUTSIDE the lock."""
        ready, still = [], []
        for idx, cb in st["parked"]:
            if idx < len(st["items"]):
                st["consumed"] = max(st["consumed"], idx + 1)
                ready.append((cb, st["items"][idx]))
            elif st["done"]:
                ready.append((cb, None))
            else:
                still.append((idx, cb))
        st["parked"] = still
        return ready

    def stream_item_or_park(self, task_id: bytes, idx: int, cb):
        """Non-blocking next_stream_item: answer immediately when possible,
        else park `cb` until the yield lands or the stream closes. One
        parked entry replaces the thread-per-RPC a blocking wait would
        need (stream_next arrives once per consumed item)."""
        advanced = 0
        exhausted = False
        with self.lock:
            st = self._streams.get(task_id)
            if st is None:
                rid = None
            elif idx < len(st["items"]):
                if idx + 1 > st["consumed"]:
                    st["consumed"] = advanced = idx + 1
                rid = st["items"][idx]
            elif st["done"]:
                self._streams.pop(task_id, None)  # exhausted
                exhausted = True
                rid = None
            else:
                st["parked"].append((idx, cb))
                return
        if exhausted:
            self._journal_stream_drop(task_id)
        elif advanced:
            self._journal_stream_cursor(task_id, advanced)
        cb(rid)

    def release_stream(self, task_id: bytes):
        """Consumer dropped its ObjectRefGenerator: discard unconsumed
        yields, drop future ones on arrival, and (best effort) cancel the
        producing task."""
        with self.lock:
            st = self._streams.get(task_id)
            if st is None:
                return
            st["abandoned"] = True
            unread = st["items"][st["consumed"]:]
            st["cv"].notify_all()
            fired = [(cb, None) for _i, cb in st["parked"]]
            st["parked"] = []
        self._journal_stream_drop(task_id)  # no longer admitted
        for cb, none in fired:
            cb(none)
        for rid in unread:
            self.directory.discard(rid)
        try:
            self.cancel_task(task_id, force=False)
        except Exception:  # noqa: BLE001 — cleanup is best effort
            pass
        with self.lock:
            st = self._streams.get(task_id)
            if st is not None and st["done"]:
                self._streams.pop(task_id, None)

    def _stream_close(self, task_id: bytes):
        with self.lock:
            st = self._streams.get(task_id)
            if st is None:
                return
            st["done"] = True
            st["cv"].notify_all()
            fired = self._pop_parked_locked(st)
            if st.get("abandoned"):
                # The consumer already dropped its generator; nobody will
                # ever read this stream again — drop the state now or it
                # leaks for the life of the driver.
                self._streams.pop(task_id, None)
        for cb, rid_or_none in fired:
            cb(rid_or_none)

    def next_stream_item(self, task_id: bytes, idx: int,
                         timeout: float | None = None):
        """Blocks until yield #idx exists; returns its rid, or None when
        the stream closed before producing it."""
        with self.lock:
            st = self._streams.get(task_id)
            if st is None:
                return None  # fully consumed + closed earlier
            while len(st["items"]) <= idx and not st["done"]:
                # staticcheck: ok cv-wait-foreign-lock — st["cv"] is
                # Condition(self.lock), so wait() releases the held lock.
                if not st["cv"].wait(timeout):
                    from ray_tpu.core.status import GetTimeoutError
                    raise GetTimeoutError(
                        f"streaming task {task_id.hex()[:12]} produced no "
                        f"item #{idx} in time")
            if idx < len(st["items"]):
                if idx + 1 > st["consumed"]:
                    st["consumed"] = idx + 1
                    self._journal_stream_cursor(task_id, idx + 1)
                return st["items"][idx]
            # closed and exhausted: drop the state
            self._streams.pop(task_id, None)
            self._journal_stream_drop(task_id)
            return None

    def stream_finished(self, task_id: bytes) -> bool:
        with self.lock:
            st = self._streams.get(task_id)
            return st is None or st["done"]

    def cancel_task(self, rid: bytes, force: bool = False) -> bool:
        """Cancel the task owning return-oid `rid` (parity: ray.cancel,
        core_worker.h CancelTask). Queued/dep-gated tasks (and actor calls
        still parked in the actor's queue) fail immediately with
        TaskCancelledError; a RUNNING plain task is only interrupted with
        force=True (its worker is killed; the task does not retry). A
        no-effect call (already finished / running without force / actor
        call already executing) returns False WITHOUT mutating the task."""
        from ray_tpu.core.status import TaskCancelledError
        err = None
        notify_worker = None  # socket I/O deferred until the lock drops
        kill_worker = None
        with self.lock:
            spec = self._rid_to_spec.get(rid)
            if spec is None:
                return False  # already finished (or not a task ref)
            if spec.actor_id is not None:
                # Actor call: definite cancel while parked head-side
                # (actor PENDING/RESTARTING) or still dep-gated;
                # best-effort once pushed to the worker — it drops the call
                # if not yet started (interrupting a RUNNING method would
                # mean killing the actor, so that stays out of scope).
                st = self.actors.get(spec.actor_id)
                if st is None:
                    return False
                try:
                    st.queued.remove(spec)
                    err = TaskCancelledError(
                        f"actor task {spec.describe()} was cancelled")
                except ValueError:
                    if (spec.task_id in st.inflight
                            and st.worker is not None
                            and st.worker.state != DEAD):
                        notify_worker = st.worker
                    elif self.directory.lookup(rid) is None:
                        # Dep-gated actor call: tombstone drops it when the
                        # deps arrive (same path as plain tasks).
                        self._cancelled.add(spec.task_id)
                        err = TaskCancelledError(
                            f"actor task {spec.describe()} was cancelled")
                    else:
                        return False  # already finished
            else:
                q = self.task_queues.get(self._sched_key(spec))
                queued = False
                if q is not None:
                    try:
                        q.remove(spec)
                        queued = True
                    except ValueError:
                        pass
                if queued:
                    err = TaskCancelledError(
                        f"task {spec.describe()} was cancelled")
                else:
                    holder, is_running = None, False
                    for w in self.workers.values():
                        if w.state != BUSY:
                            continue
                        for i, t in enumerate(w.assigned):
                            if t.task_id == spec.task_id:
                                holder, is_running = w, (i == 0)
                                break
                        if holder is not None:
                            break
                    if holder is not None and not is_running:
                        # Pipelined behind the worker's running task — it
                        # never started: definite cancel. The worker's
                        # cancelled-set drops it when it reaches the front.
                        holder.assigned.remove(spec)
                        self._cancelled.add(spec.task_id)
                        notify_worker = holder
                        err = TaskCancelledError(
                            f"task {spec.describe()} was cancelled")
                    elif holder is not None:
                        if self.directory.lookup(rid) is not None:
                            # Completed; the worker just hasn't been marked
                            # idle yet — killing it would murder a healthy
                            # process over a finished task.
                            return False
                        if not force:
                            return False  # running; nothing was mutated
                        # Force: mark so the death handler fails (not
                        # retries) it, then kill the worker.
                        self._cancelled.add(spec.task_id)
                        spec.retries_left = 0
                        kill_worker = holder
                    elif self.directory.lookup(rid) is not None:
                        return False  # completed while we looked
                    else:
                        # Dep-gated: tombstone so _enqueue_ready drops it
                        # when its deps arrive (returns fail right now).
                        self._cancelled.add(spec.task_id)
                        err = TaskCancelledError(
                            f"task {spec.describe()} was cancelled")
        if notify_worker is not None:
            try:
                notify_worker.send(("cancel_task", spec.task_id))
            except OSError:
                if err is None:
                    return False
            if err is None:
                return True  # best-effort; the worker reports the fate
        if kill_worker is not None:
            kill_worker.kill()
            return True
        self._fail_returns(spec, err)
        return True

    # ---------------- multi-tenant job platform ----------------

    def stop_job(self, job_id: str) -> dict:
        """Tear down a tenant's in-flight footprint at the head (the
        JobSubmissionClient.stop_job release path — without it a stopped
        job's queued work still dispatches): mark the ledger stopped so
        every future charge refuses, fail the job's queued and dep-gated
        normal tasks with TaskCancelledError, pop its granted-but-
        unfinished leases (an agent-side zombie execution completes into
        a popped lease and no-ops, the same staleness contract as node
        death), and reclaim reservation tails the job's killed client
        processes stranded in the arena."""
        from ray_tpu.core.status import TaskCancelledError
        self.jobs.stop(job_id)
        to_fail: list = []
        leases: list = []
        with self.lock:
            # Queued specs: sig[3] carries the tenant, so whole keys go.
            for sig in list(self.task_queues):
                if (((sig[3] if len(sig) > 3 else None) or DEFAULT_JOB)
                        != job_id):
                    continue
                to_fail.extend(self.task_queues.pop(sig))
            # Dep-gated specs: tombstone + fail now (same contract as
            # cancel_task's dep-gated branch — _enqueue_ready drops the
            # spec when its deps finally arrive).
            gated: set = set()
            for items in self.waiting_deps.values():
                for item in items:
                    spec = item.get("spec")
                    if (item.get("kind") != "task" or spec is None
                            or (getattr(spec, "job_id", None)
                                or DEFAULT_JOB) != job_id
                            or spec.task_id in gated):
                        continue
                    gated.add(spec.task_id)
                    self._cancelled.add(spec.task_id)
                    to_fail.append(spec)
            # In-flight leases: pop + release the reservation. The
            # settle rides _pop_lease_locked's funnel; a completion
            # racing this finds the lease gone and no-ops.
            for node in self.nodes.values():
                for tid, spec in list(node.leases.items()):
                    if (getattr(spec, "job_id", None)
                            or DEFAULT_JOB) == job_id:
                        leases.append((tid, node))
            for tid, node in leases:
                spec = self._pop_lease_locked(tid, node)
                self._release_token(self._reservations.pop(tid, None))
                if spec is not None:
                    to_fail.append(spec)
            # Worker-assigned specs (head-local dispatch): one pipelined
            # behind a running task never started — definite cancel; the
            # front (RUNNING) spec gets its worker killed, same contract
            # as cancel_task(force=True): the death handler fails it (no
            # retry) and its settle/reservation release ride that path.
            notify: list = []
            kill: list = []
            for w in self.workers.values():
                if w.state != BUSY or not w.assigned:
                    continue
                mine = [t for t in w.assigned
                        if (getattr(t, "job_id", None)
                            or DEFAULT_JOB) == job_id]
                if not mine:
                    continue
                running = w.assigned[0]
                for t in mine:
                    self._cancelled.add(t.task_id)
                    if t is running:
                        t.retries_left = 0
                        kill.append(w)
                    else:
                        w.assigned.remove(t)
                        to_fail.append(t)
                        notify.append((w, t.task_id))
        for w, tid in notify:
            try:
                w.send(("cancel_task", tid))
            except OSError:
                pass  # staticcheck: ok recovery-swallow — worker already dead
        for w in kill:
            w.kill()
        for spec in to_fail:
            self._fail_returns(spec, TaskCancelledError(
                f"job {job_id!r} was stopped"))
        # Reservation tails: the supervisor killed the job's client
        # processes before this ran; their stranded write-reservation
        # extents are dead-pid orphans the arena sweep returns.
        reclaimed = self.store.reclaim_orphans()
        if to_fail or leases or kill:
            self._schedule()  # freed capacity: let other tenants in
        return {"job_id": job_id, "cancelled": len(to_fail) + len(kill),
                "leases_released": len(leases),
                "workers_killed": len(kill),
                "reservations_reclaimed": reclaimed}

    def request_scale_up(self, bundles: list, source: str = "") -> None:
        """Post scale-up demand the task queues cannot see — the elastic
        trainer's capacity-wait (PR 9's shrink loop finally gets its
        scale-UP signal), serve shed pressure, explicit hints. Drained by
        autoscaler/policy.py each reconcile; the deque bounds a hot wait
        loop's reposts."""
        self._scale_requests.append(
            {"bundles": [dict(b) for b in bundles if b],
             "source": source, "ts": time.time()})

    def take_scale_requests(self) -> list:
        """Drain posted scale-up requests (autoscaler policy core)."""
        out = []
        while True:
            try:
                out.append(self._scale_requests.popleft())
            except IndexError:
                return out

    def drain_node_leases(self, node_id_hex: str) -> int:
        """Scale-down drain: requeue every un-started lease still booked
        on the node through the same funnel as a lease return, so the
        autoscaler's terminate never relies on the node-death replay for
        work that never began there. Only called for nodes the
        autoscaler is about to terminate (idle by the resource view —
        anything that raced a grant in requeues here)."""
        requeued = 0
        with self.lock:
            node = next((n for n in self.nodes.values()
                         if n.node_id.hex() == node_id_hex), None)
            if node is None:
                return 0
            for tid in list(node.leases):
                spec = self._pop_lease_locked(tid, node)
                self._release_token(self._reservations.pop(tid, None))
                if spec is not None:
                    self._enqueue_task_locked(spec, front=True)
                    requeued += 1
        if requeued:
            self._schedule()
        return requeued

    def job_state(self) -> list[dict]:
        """Per-job platform view (/api/jobs): dominant share over the
        live cluster, quota usage, blast-radius counters, task-event
        drops."""
        with self.lock:
            totals = self._cluster_totals_locked()
        rows = self.jobs.snapshot(totals)
        drops = dict(getattr(self.task_store, "dropped_per_job", {}) or {})
        for row in rows:
            row["task_event_drops"] = drops.get(row["job_id"], 0)
        return rows

    def _unpin_deps(self, spec: TaskSpec):
        for oid in spec.dependencies or []:
            self.refcount.unpin(oid)
        aref = getattr(spec, "args_ref", None)
        if aref is not None:
            # The offloaded arg pack exists only for this task: free it
            # cluster-wide now that no attempt can run again. (A later
            # lineage reconstruction of this spec will fail its args fetch
            # cleanly — same contract as a borrowed dep freed by its
            # owner.)
            try:
                self._free_object(aref)
            except Exception:  # noqa: BLE001 — cleanup is best effort
                pass

    def _gate_on_deps(self, item, deps) -> bool:
        """Returns True when the item was enqueued immediately (no pending
        deps); False when it parked waiting for objects."""
        with self.lock:
            for oid in deps:
                entry = self.directory.lookup(oid)
                if entry is None:
                    item["pending"] += 1
                    self.waiting_deps.setdefault(oid, []).append(item)
            ready = item["pending"] == 0
        if ready:
            self._enqueue_ready(item)
        return ready

    def _enqueue_ready(self, item):
        if item["kind"] == "task":
            spec = item["spec"]
            self._inline_ready_deps(spec)
            with self.lock:
                # Tombstone check atomic with the enqueue: a cancel racing
                # this either lands its tombstone before the check (we drop
                # here) or finds the spec already in its queue (it removes
                # it there) — no window where both miss.
                if spec.task_id in self._cancelled:
                    # Returns already failed (and deps already unpinned by
                    # that failure); running it anyway would overwrite the
                    # cancellation error.
                    self._cancelled.discard(spec.task_id)
                    return
                if spec.actor_id is None:
                    fresh_key = self._enqueue_task_locked(spec)
                    # Burst debounce: with no idle worker anywhere AND an
                    # already-parked key, this enqueue waits for the next
                    # completion (which always reschedules AND is the only
                    # event that frees pipeline depth) or a worker-ready
                    # event. A FRESH key must still pass through
                    # _schedule — that is the only path that requests a
                    # worker spawn for it. Skipping the no-op passes keeps
                    # a 10k-submit burst O(dispatches), not
                    # O(submissions * scan). NOTE: if a depth-freeing path
                    # that does NOT reschedule is ever added, this skip
                    # must learn about it.
                    has_idle = any(
                        n.idle and n.state == "ALIVE"
                        for n in self.nodes.values())
            if spec.actor_id is not None:
                self._submit_actor_task(spec)
                return
            if has_idle or fresh_key:
                self._schedule()
        else:
            self._create_actor_now(item["cspec"])

    def _inline_ready_deps(self, spec: TaskSpec):
        """Ship owner-memory values with the spec (parity: dependency_resolver.h
        inlines small owner-local objects into the TaskSpec)."""
        for oid in spec.dependencies or []:
            entry = self.directory.lookup(oid)
            if entry is None:
                continue
            if entry[0] == "raw":
                spec.inline_deps[oid] = (entry[1], entry[2])
            elif entry[0] in ("inline", "err"):
                payload, bufs, _ = serialization.serialize_value(entry[1])
                spec.inline_deps[oid] = (payload, bufs)

    def _resources_of(self, spec: TaskSpec) -> dict[str, float]:
        req = dict(spec.resources or {})
        if spec.num_cpus:
            req["CPU"] = req.get("CPU", 0.0) + spec.num_cpus
        if spec.num_tpus:
            req["TPU"] = req.get("TPU", 0.0) + spec.num_tpus
        return req

    @staticmethod
    def _fits(avail: dict[str, float], req: dict[str, float]) -> bool:
        return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in req.items())

    def _alive_nodes(self) -> list[NodeState]:
        return [self.nodes[nid] for nid in self._node_order
                if self.nodes[nid].state == "ALIVE"]

    def _pick_node(self, strategy, req: dict[str, float],
                   deps=None) -> NodeState | None:
        """Scheduling policy (parity: policy/hybrid_scheduling_policy.h:50,
        spread_scheduling_policy.h:27, node-affinity). Hybrid order: data
        locality (most deps already node-local) > head-local > most
        available CPU. Raises for a hard affinity to a dead node."""
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        if isinstance(strategy, NodeAffinitySchedulingStrategy):
            nid = strategy.node_id
            if isinstance(nid, str):
                try:
                    nid = bytes.fromhex(nid)
                except ValueError:
                    raise ResourceError(
                        f"malformed node_id {strategy.node_id!r} in "
                        f"NodeAffinitySchedulingStrategy") from None
            node = self.nodes.get(nid)
            if node is not None and node.state == "ALIVE":
                if self._fits(node.available, req):
                    return node
                if not self._fits(node.total, req) and not strategy.soft:
                    raise ResourceError(
                        f"request {req} exceeds the pinned node's total "
                        f"{node.total} (hard NodeAffinity)")
                if not strategy.soft:
                    return None  # wait for capacity on the pinned node
            elif not strategy.soft:
                raise ResourceError(
                    f"node {strategy.node_id} is dead or unknown "
                    f"(hard NodeAffinity)")
            # soft affinity to a dead node: fall through to hybrid
        candidates = [n for n in self._alive_nodes()
                      if self._fits(n.available, req)]
        if not candidates:
            return None
        if strategy == "SPREAD":
            self._spread_idx += 1
            return candidates[self._spread_idx % len(candidates)]
        if deps:
            def local_deps(n):
                c = 0
                for oid in deps:
                    e = self.directory.lookup(oid)
                    if (e is not None and e[0] == "shm" and len(e) > 1
                            and n.node_id in e[1]):
                        c += 1
                return c
            return max(candidates, key=lambda n: (
                local_deps(n), n.node_id == self.head_node_id,
                n.available.get("CPU", 0.0)))
        for n in candidates:
            if n.node_id == self.head_node_id:
                return n
        return max(candidates, key=lambda n: n.available.get("CPU", 0.0))

    def _try_reserve_on(self, node: NodeState, req: dict[str, float]) -> bool:
        if node is None or node.state != "ALIVE":
            return False
        if not self._fits(node.available, req):
            return False
        for k, v in req.items():
            node.available[k] = node.available.get(k, 0.0) - v
        return True

    @staticmethod
    def _pg_of(strategy) -> tuple[bytes | None, int]:
        """(pg_id, bundle_index) from a scheduling strategy, if any."""
        pg = getattr(strategy, "placement_group", None)
        if pg is None:
            return None, -1
        bidx = getattr(strategy, "placement_group_bundle_index", -1)
        return pg.id.binary(), (-1 if bidx is None else bidx)

    def _try_reserve_pg(self, pg_id: bytes, bidx: int,
                        req: dict[str, float]):
        """Reserve `req` out of a placement-group bundle. Returns a token,
        None (retry when capacity frees / the PG finishes creating), or
        raises when the request can never be satisfied."""
        st = self.placement_groups.get(pg_id)
        if st is None or st.state == "REMOVED":
            raise RayTpuError(
                f"placement group {pg_id.hex()[:12]} was removed or never "
                f"created")
        if st.state == "INFEASIBLE":
            raise ResourceError(
                f"placement group {pg_id.hex()[:12]} is infeasible on this "
                f"cluster (strategy={st.strategy}, bundles={st.bundles})")
        if st.state != "CREATED":
            return None
        if bidx < -1 or bidx >= len(st.bundles):
            raise RayTpuError(
                f"bundle_index {bidx} out of range for placement group with "
                f"{len(st.bundles)} bundles")
        idxs = range(len(st.bundles)) if bidx == -1 else [bidx]
        if not any(all(st.bundles[i].get(k, 0.0) + 1e-9 >= v
                       for k, v in req.items())
                   for i in idxs):
            raise ResourceError(
                f"request {req} exceeds every candidate bundle spec of "
                f"placement group {pg_id.hex()[:12]}")
        for i in idxs:
            b = st.bundle_avail[i]
            if all(b.get(k, 0.0) + 1e-9 >= v for k, v in req.items()):
                for k, v in req.items():
                    b[k] = b.get(k, 0.0) - v
                return ("pg", pg_id, i, req)
        return None

    def _reserve_placement(self, strategy, req: dict[str, float], deps=None):
        """Reserve `req` per a scheduling strategy. Returns (node, token),
        None to retry later, or raises when never satisfiable. Caller must
        hold the runtime lock."""
        pg_id, bidx = self._pg_of(strategy)
        if pg_id is None:
            node = self._pick_node(strategy, req, deps)
            if node is None:
                return None
            for k, v in req.items():
                node.available[k] = node.available.get(k, 0.0) - v
            return node, ("node", node.node_id, req)
        token = self._try_reserve_pg(pg_id, bidx, req)
        if token is None:
            return None
        st = self.placement_groups[pg_id]
        node = self.nodes.get(st.bundle_nodes[token[2]])
        if node is None or node.state != "ALIVE":
            # The bundle's node died; PG rescheduling is not yet implemented,
            # so surface the loss instead of dispatching into the void.
            self._release_token(token)
            raise ResourceError(
                f"placement group {pg_id.hex()[:12]} bundle {token[2]} was "
                f"on a dead node")
        return node, token

    def _release_token(self, token):
        if not token:
            return
        if token[0] == "node":
            _, nid, req = token
            self._release_on(nid, req)
            return
        _, pg_id, i, req = token
        st = self.placement_groups.get(pg_id)
        if st is not None and st.state == "CREATED":
            b = st.bundle_avail[i]
            for k, v in req.items():
                b[k] = b.get(k, 0.0) + v
            # Freed bundle capacity may unblock queued PG tasks/actors.
            self._kick_waiters()
        else:
            # PG gone: its carve-out returns to the hosting node piecewise as
            # consumers finish.
            nid = (st.bundle_nodes[i] if st is not None and st.bundle_nodes
                   else self.head_node_id)
            self._release_on(nid, req)

    def _release_on(self, node_id: bytes, req: dict[str, float]):
        node = self.nodes.get(node_id)
        if node is not None and node.state == "ALIVE":
            for k, v in req.items():
                node.available[k] = node.available.get(k, 0.0) + v
        self._kick_waiters()

    def _kick_waiters(self):
        # Freed capacity may unblock queued placement groups — they reserve
        # whole bundles atomically, so retry them first (FIFO).
        created_pgs = []
        if self.pgs_waiting:
            still = collections.deque()
            for pg_id in self.pgs_waiting:
                st = self.placement_groups.get(pg_id)
                if st is None or st.state != "PENDING":
                    continue
                if self._try_create_pg_locked(st):
                    created_pgs.append(st)
                else:
                    still.append(pg_id)
            self.pgs_waiting = still
        if created_pgs:
            def fulfill():
                for st in created_pgs:
                    self._fulfill_pg_ready(st)
            threading.Thread(target=fulfill, daemon=True).start()
        # Freed capacity may unblock queued actor creations — retry ALL of
        # them, not just one: the freed block may fit several small waiters
        # and no later release is guaranteed to come. _create_actor_now
        # re-queues any that still don't fit. (Caller holds the runtime lock;
        # hand the retries to a thread to avoid re-entrancy.)
        if self.actors_waiting_resources:
            waiters = list(self.actors_waiting_resources)
            self.actors_waiting_resources.clear()

            def retry():
                for aid in waiters:
                    st = self.actors.get(aid)
                    if st is not None and st.state != A_DEAD:
                        self._create_actor_now(st.cspec)

            threading.Thread(target=retry, daemon=True).start()

    # ---------------- placement groups ----------------

    def create_placement_group(self, pg_id: bytes, bundles, strategy: str,
                               name: str = "") -> bytes:
        """Reserve `bundles` atomically; returns the ready-ObjectRef id.

        On one node STRICT_SPREAD with >1 bundle can never be satisfied
        (each bundle needs a distinct node) — marked INFEASIBLE, mirroring
        the reference's forever-pending semantics but failing ready() fast.
        """
        st = PlacementGroupState(pg_id, bundles, strategy, name)
        # The PG record owns its ready-object for the PG's lifetime; without
        # the pin the first ready() handle to be GC'd would free the entry.
        self.refcount.pin(st.ready_oid)
        if self._persist:
            self._pstore.append("pg", pg_id, (list(bundles), strategy, name))
        created = False
        with self.lock:
            self.placement_groups[pg_id] = st
            alive = self._alive_nodes()
            infeasible = any(
                not any(self._fits(n.total, b) for n in alive)
                for b in bundles)
            if strategy == "STRICT_SPREAD" and len(bundles) > len(alive):
                infeasible = True
            if strategy == "STRICT_PACK":
                # All bundles must fit ONE node together.
                total = _sum_bundles(bundles)
                if not any(self._fits(n.total, total) for n in alive):
                    infeasible = True
            if infeasible and self.cluster_addr is not None:
                # Multi-node mode: nodes may still join (add_node/autoscaler
                # race) — stay PENDING like the reference instead of failing
                # against a point-in-time node snapshot.
                infeasible = False
            if infeasible:
                st.state = "INFEASIBLE"
            else:
                created = self._try_create_pg_locked(st)
                if not created and st.state == "PENDING":
                    self.pgs_waiting.append(pg_id)
        if created:
            self._fulfill_pg_ready(st)
        elif st.state == "INFEASIBLE":
            self.directory.put(st.ready_oid, ("err", ResourceError(
                f"placement group (strategy={strategy}, bundles={bundles}) "
                f"is infeasible: cluster total is {self.total_resources}")))
            self._on_object_ready(st.ready_oid)
        return st.ready_oid

    def _place_bundles(self, bundles, strategy: str) -> list[bytes] | None:
        """Map bundles onto alive nodes per the PG strategy against current
        availability (parity: bundle_scheduling_policy.h:31-106; 2PC
        collapses to one atomic assignment under the head lock).
        ICI_CONTIGUOUS places bundles on a topologically contiguous run of
        TPU nodes (registration order ~ ICI ring order)."""
        alive = self._alive_nodes()
        avail = {n.node_id: dict(n.available) for n in alive}

        def take(nid, b):
            a = avail[nid]
            if not self._fits(a, b):
                return False
            for k, v in b.items():
                a[k] = a.get(k, 0.0) - v
            return True

        def pack_on_one():
            for n in alive:
                saved = dict(avail[n.node_id])
                if all(take(n.node_id, b) for b in bundles):
                    return [n.node_id] * len(bundles)
                avail[n.node_id] = saved
            return None

        if strategy in ("PACK", "STRICT_PACK"):
            assign = pack_on_one()
            if assign is not None or strategy == "STRICT_PACK":
                return assign
            # PACK fallback: greedy first-fit across nodes.
            assign = []
            for b in bundles:
                nid = next((n.node_id for n in alive if take(n.node_id, b)),
                           None)
                if nid is None:
                    return None
                assign.append(nid)
            return assign
        if strategy in ("SPREAD", "STRICT_SPREAD"):
            assign, used = [], set()
            for b in bundles:
                fresh = [n for n in alive if n.node_id not in used]
                pool = fresh if strategy == "STRICT_SPREAD" else (
                    fresh + [n for n in alive if n.node_id in used])
                nid = next((n.node_id for n in pool if take(n.node_id, b)),
                           None)
                if nid is None:
                    return None
                used.add(nid)
                assign.append(nid)
            return assign
        if strategy == "ICI_CONTIGUOUS":
            tpu_nodes = [n for n in alive if n.total.get("TPU", 0.0) > 0] or alive
            one = pack_on_one()
            if one is not None:
                return one
            # Sliding window of distinct consecutive TPU nodes.
            k = len(bundles)
            for s in range(len(tpu_nodes) - k + 1):
                win = tpu_nodes[s:s + k]
                saved = {n.node_id: dict(avail[n.node_id]) for n in win}
                if all(take(n.node_id, b) for n, b in zip(win, bundles)):
                    return [n.node_id for n in win]
                avail.update(saved)
            return None
        return pack_on_one()

    def _try_create_pg_locked(self, st: PlacementGroupState) -> bool:
        assign = self._place_bundles(st.bundles, st.strategy)
        if assign is None:
            return False
        for i, nid in enumerate(assign):
            na = self.nodes[nid].available
            for k, v in st.bundles[i].items():
                na[k] = na.get(k, 0.0) - v
        st.bundle_nodes = assign
        st.state = "CREATED"
        st.bundle_avail = [dict(b) for b in st.bundles]
        if self._wal:
            # WAL the landed reservation (4-tuple extends the PR-8 pg
            # record with bundle placements); restore tolerates both
            # arities and re-places when nodes rejoin.
            self._pstore.append("pg", st.pg_id,
                                (list(st.bundles), st.strategy, st.name,
                                 list(assign)))
        return True

    def _fulfill_pg_ready(self, st: PlacementGroupState):
        self.directory.put(st.ready_oid, ("inline", True))
        self._on_object_ready(st.ready_oid)
        with self.lock:
            self._kick_waiters()  # kick waiting actors/tasks gated on this PG

    def remove_placement_group(self, pg_id: bytes):
        self._pstore.delete("pg", pg_id)
        with self.lock:
            st = self.placement_groups.get(pg_id)
            if st is None or st.state == "REMOVED":
                return
            was = st.state
            if was == "CREATED":
                # Return the unconsumed remainder now; amounts held by
                # running tasks/actors flow back via _release_token.
                for i, b in enumerate(st.bundle_avail):
                    node = self.nodes.get(st.bundle_nodes[i])
                    if node is None or node.state != "ALIVE":
                        continue
                    for k, v in b.items():
                        node.available[k] = node.available.get(k, 0.0) + v
            try:
                self.pgs_waiting.remove(pg_id)
            except ValueError:
                pass
            st.state = "REMOVED"
            st.bundle_avail = [{} for _ in st.bundles]
        # Overwrite the ready entry with an error so any ready()/wait() call
        # issued after removal resolves instead of hanging. The entry stays
        # pinned for the runtime's lifetime — freeing it would strand handles
        # created later (ready() makes its ObjectRef lazily); the ~100-byte
        # tombstone per PG mirrors the reference keeping REMOVED rows in the
        # placement-group table.
        self.directory.put(st.ready_oid, ("err", RayTpuError(
            "placement group was removed")))
        self._on_object_ready(st.ready_oid)
        with self.lock:
            self._kick_waiters()
        self._schedule()

    def placement_group_table(self) -> dict:
        with self.lock:
            return {
                pg_id.hex(): {
                    "name": st.name,
                    "strategy": st.strategy,
                    "state": st.state,
                    "bundles": {i: dict(b) for i, b in enumerate(st.bundles)},
                }
                for pg_id, st in self.placement_groups.items()
            }

    def _check_feasible(self, req: dict[str, float], what: str):
        """A request must fit on some single node's total (not the cluster
        sum — a 8-CPU task cannot run on two 4-CPU nodes). Fail-fast only in
        single-node mode: with clustering on, a bigger node may register any
        moment and _kick_waiters will place the queued work."""
        if self.cluster_addr is not None:
            return
        for k, v in req.items():
            best = max((n.total.get(k, 0.0) for n in self._alive_nodes()),
                       default=0.0)
            if best < v:
                raise ResourceError(
                    f"{what} requires {{{k}: {v}}} but the largest node has "
                    f"{{{k}: {best}}}")

    @staticmethod
    def _sched_key(spec: TaskSpec) -> tuple:
        req = {}
        if spec.num_cpus:
            req["CPU"] = req.get("CPU", 0.0) + spec.num_cpus
        if spec.num_tpus:
            req["TPU"] = req.get("TPU", 0.0) + spec.num_tpus
        for k, v in (spec.resources or {}).items():
            req[k] = req.get(k, 0.0) + v
        strat = spec.scheduling_strategy
        # The job id rides the key (sig[3]): tenants never share a queue,
        # which is what lets the grant loops order KEYS by dominant share
        # and park one tenant's backlog without touching another's.
        return (tuple(sorted(req.items())),
                strat if isinstance(strat, str) or strat is None
                else id(strat),
                _pip_key_of(spec),
                getattr(spec, "job_id", None) or DEFAULT_JOB)

    @staticmethod
    def _pip_env_of(spec):
        from ray_tpu.core.runtime_env import env_spec
        return env_spec(getattr(spec, "runtime_env", None))

    def _enqueue_task_locked(self, spec: TaskSpec,
                             front: bool = False) -> bool:
        """Returns True when this key's queue was empty (a fresh key must
        always get a scheduling pass — it may need a worker spawned)."""
        q = self.task_queues.setdefault(self._sched_key(spec),
                                        collections.deque())
        was_empty = not q
        (q.appendleft if front else q.append)(spec)
        return was_empty

    @property
    def task_queue(self) -> list:
        """Flat view of all pending task specs (introspection/autoscaler).
        Includes pipelined-but-not-started tasks queued on busy workers:
        they are real unmet demand — hiding them would stop the autoscaler
        from scaling out under a pipelined backlog."""
        with self.lock:
            out = [s for q in self.task_queues.values() for s in q]
            for w in list(self.workers.values()):
                if w.state == BUSY and len(w.assigned) > 1:
                    out.extend(list(w.assigned)[1:])
            return out

    def _schedule(self):
        """Request a scheduling pass. Single-node clusters run it inline
        (a pass sends to at most the local worker pool — the thread hop
        would only add ~100us to every sync call). With agents attached,
        the pass is debounced onto the dedicated scheduler thread: a
        submission burst coalesces into a handful of passes whose
        dispatch frames batch per agent, instead of every submit paying a
        full pass plus one sendall per agent on the submitting thread (a
        64-agent profile put ~37% of the head core in exactly that).
        Concurrent passes are safe — queue pops and reservations are
        under the lock — the debounce exists for throughput, not
        correctness.

        Single-node burst debounce: a LONE request still runs inline
        (sync-call latency unchanged), but when the previous request was
        <150us ago — an async submit loop, or the listener draining a
        completion storm — the pass defers to the scheduler thread, where
        back-to-back requests coalesce into one pass and the dispatch
        sendalls leave the submitting/listener threads (profiled at ~45%
        of the listener's busy time on the 10k-nop bench)."""
        if len(self.nodes) <= 1:
            now = time.monotonic()
            burst = now - self._last_sched_req < 150e-6
            # racecheck: ok thread-escape burst-coalescing heuristic: a
            # torn stamp misclassifies one request, whose worst case is
            # one extra (idempotent) inline pass or one deferred hop to
            # the scheduler thread it was built to take anyway
            self._last_sched_req = now
            if not burst:
                self._schedule_now()
                return
        with self._sched_cv:
            self._sched_gen += 1
            self._sched_cv.notify()

    def _sched_loop(self):
        gen_done = 0
        while not self._shutdown:
            with self._sched_cv:
                while self._sched_gen == gen_done and not self._shutdown:
                    # The timeout is a safety net only: every state change
                    # that can unblock scheduling must call _schedule().
                    self._sched_cv.wait(0.2)
                gen_done = self._sched_gen
            if self._shutdown:
                return
            try:
                if self._pending_lease_sends:
                    # Merge everything queued since the last drain: one
                    # sendall per NODE instead of one per completion
                    # batch (at 64 agents the un-merged refill sends ate
                    # ~30% of this thread in blocking sendalls).
                    merged: list = []
                    while self._pending_lease_sends:
                        merged.extend(self._pending_lease_sends.popleft())
                    self._send_leases(merged)
                self._schedule_now()
            except Exception:
                traceback.print_exc()

    def _cluster_totals_locked(self) -> dict:
        """Live cluster capacity (alive nodes' totals) — the denominator
        of every DRF dominant-share computation. Caller holds self.lock."""
        totals: dict[str, float] = {}
        for n in self.nodes.values():
            if n.state != "ALIVE":
                continue
            for k, v in n.total.items():
                totals[k] = totals.get(k, 0.0) + v
        return totals

    def _sig_order(self, sigs: list) -> list:
        """Fair-share iteration order for the grant loops: weighted
        dominant share ascending (DRF — the most-starved tenant's keys
        first) when `fair_share` is on; submission (dict) order — plain
        FIFO over keys, the pre-tenancy behavior and the multi_tenant
        bench's A/B collapse mode — when it is off. The sort is stable,
        so keys of one job keep their FIFO order. Caller holds
        self.lock."""
        if not self.config.fair_share or len(sigs) < 2:
            return sigs
        totals = self._cluster_totals_locked()
        shares: dict[str, float] = {}

        def share(sig) -> float:
            jid = (sig[3] if len(sig) > 3 else None) or DEFAULT_JOB
            if jid not in shares:
                shares[jid] = self.jobs.dominant_share(jid, totals)
            return shares[jid]

        return sorted(sigs, key=share)

    def _schedule_now(self):
        """Dispatch every feasible queued task to an idle worker.

        Per-scheduling-key queues (parity: normal_task_submitter.h:58):
        a pass costs O(keys + dispatches), not O(queued tasks) — one failed
        reserve probe parks the entire key, so a 10k-task burst stays cheap
        on every completion event.

        Tenancy rides the same structure: keys are visited in weighted-DRF
        order (_sig_order) and every pop passes the job ledger's quota
        gate first — a refused charge parks the key exactly like a failed
        reserve probe, so an over-quota job queues without starving the
        keys behind it."""
        dispatches = []
        failures = []
        lease_dispatches: list = []  # (node, spec) — agent-local dispatch
        with self.lock:
            for sig in self._sig_order(list(self.task_queues)):
                q = self.task_queues.get(sig)
                while q:
                    spec = q[0]
                    jid = getattr(spec, "job_id", None) or DEFAULT_JOB
                    if not self.jobs.charge(jid, spec.task_id,
                                            self._resources_of(spec)):
                        # Quota gate: over quota or job stopped. The key
                        # parks with its backlog (a completion's settle
                        # re-runs this pass); autoscaler/policy.py counts
                        # the parked backlog as queued-beyond-quota
                        # demand.
                        break
                    try:
                        res = self._reserve_placement(
                            spec.scheduling_strategy,
                            self._resources_of(spec), spec.dependencies)
                    except Exception as e:  # noqa: BLE001 — an escaping
                        # error would stall the queue, hanging every get()
                        self.jobs.settle(jid, spec.task_id)
                        q.popleft()
                        failures.append((spec, e))
                        continue
                    if res is None:
                        self.jobs.settle(jid, spec.task_id)
                        # Key blocked on resources: pipeline the backlog
                        # onto busy same-key workers (they ride those
                        # workers' existing reservations), then next key.
                        # (Lease-eligible backlog refills node-locally in
                        # _on_node_done instead — measurably faster than
                        # topping nodes up from scheduler passes.)
                        self._pipeline_locked(sig, q, dispatches)
                        break
                    node, token = res
                    env_key = sig[2]
                    if (node.conn is not None
                            and self._lease_ok(spec, env_key)):
                        # Node lease (raylet-local dispatch,
                        # cluster_task_manager.h:45): the head debits node
                        # resources and hands the task to the NODE; the
                        # agent picks the worker, spawns on demand, and
                        # reports completions in node_done batches — no
                        # per-worker bookkeeping (and no per-completion
                        # global-lock work) at the head.
                        q.popleft()
                        self._reservations[spec.task_id] = token
                        spec.lease_seq = (spec.lease_seq or 0) + 1
                        node.leases[spec.task_id] = spec
                        lease_dispatches.append((node, spec))
                        continue
                    w = self._take_idle_locked(node, env_key)
                    if w is None:
                        # Resources fit but no free matching worker on that
                        # node: quiet rollback (no _kick_waiters churn), ask
                        # for a worker (of the right env pool), park the
                        # key. Every key still gets its own probe this pass
                        # — a blocked key must not starve feasible keys
                        # behind it.
                        self.jobs.settle(jid, spec.task_id)
                        self._rollback_token_locked(token)
                        self._pipeline_locked(sig, q, dispatches)
                        self._request_worker_locked(
                            node, pip=self._pip_env_of(spec)
                            if env_key else None)
                        break
                    q.popleft()
                    self._reservations[spec.task_id] = token
                    w.state = BUSY
                    if spec.num_tpus:
                        w.owns_chip = True
                    w.assigned.append(spec)
                    self._sig_workers.setdefault(sig, set()).add(w)
                    dispatches.append((w, spec))
                if not self.task_queues.get(sig):
                    self.task_queues.pop(sig, None)
        for spec, e in failures:
            self._fail_returns(spec, e)
        # Coalesce per-worker: one frame carries every spec headed to the
        # same worker this pass; then per-NODE: one sendall carries every
        # worker's frame headed to the same agent (the head's send syscalls
        # are its hottest loop under many-agent load — a 16-agent profile
        # put ~2/3 of head CPU in sendall before this batching).
        per_worker: dict = {}
        order: list = []
        for w, spec in dispatches:
            if w not in per_worker:
                per_worker[w] = []
                order.append(w)
            per_worker[w].append(spec)
        per_conn: dict = {}
        conn_order: list = []
        for w in order:
            msg = self._dispatch_many(w, per_worker[w], defer_remote=True)
            if msg is None:
                continue
            conn = w.node_conn
            if conn not in per_conn:
                per_conn[conn] = []
                conn_order.append(conn)
            per_conn[conn].append((w.worker_id.binary(), msg))
        for conn in conn_order:
            pairs = per_conn[conn]
            try:
                if len(pairs) == 1:
                    conn.send(("to_worker", pairs[0][0], pairs[0][1]))
                else:
                    conn.send(("relay_batch", pairs))
            except OSError:
                pass  # node death handling reroutes via heartbeat/EOF
        if lease_dispatches:
            self._send_leases(lease_dispatches)
        if self._steal_for_idle():
            self._schedule()

    def _send_leases(self, lease_dispatches: list):
        """One node_exec frame per node carries the batch; fn blobs ride
        along the first time a node sees a function."""
        per_node: dict = {}
        node_order: list = []
        for node, spec in lease_dispatches:
            self.task_events.record(
                spec.task_id, spec, "RUNNING",
                pipeline_state="LEASE_GRANTED",
                data={"node": node.node_id.hex(),
                      "lease_seq": spec.lease_seq})
            blob = None
            if spec.fn_id and spec.fn_id not in node.lease_fns:
                blob = self.fn_table.get(spec.fn_id)
                node.lease_fns.add(spec.fn_id)
            if node not in per_node:
                per_node[node] = []
                node_order.append(node)
            per_node[node].append((spec.fn_id, blob, spec))
        native = self.config.native_sched
        # Native-head grant builder: armed processes fall back to the
        # Python frame path so head.lease_grant.lose and the transport
        # sites fire per frame, exactly as in the pure-Python loop.
        hnat = self._hnat if chaos._armed is None else None
        for node in node_order:
            now = time.monotonic()
            for _fid, _blob, spec in per_node[node]:
                node.lease_sent[spec.task_id] = [now, 0]
                if self._wal:
                    # WAL the in-flight grant BEFORE the send: a restart
                    # replays the task with lease_seq past this grant, so
                    # a surviving agent's (task, seq) dedup ledger can
                    # never swallow the re-grant.
                    self._pstore.append(
                        "lease", spec.task_id,
                        (node.node_id, spec.lease_seq or 0))
            # Crash-consistency probe: grants of this batch are committed
            # but unsent — recovery must re-drive every one of them from
            # the journal alone.
            chaos.kill("head.kill")
            nidx = node.conn._nidx if node.conn is not None else None
            if native and hnat is not None and nidx is not None:
                # Native grant plane, head half: stage each raw entry
                # into the C++ per-node outbox (the spec bytes were
                # pickled exactly once by encode_payload; the batch
                # frame itself is built natively — no second pickle of
                # the entry list) and ship it as ONE sendall under the
                # conn's write lock. cpp-language leases keep the
                # object form (their queue and protobuf dispatch stay
                # Python-side at the agent).
                obj_triples = []
                staged = 0
                for fid, blob, spec in per_node[node]:
                    if getattr(spec, "language", None) == "cpp":
                        obj_triples.append((fid, blob, spec))
                        continue
                    hnat.grant_add(nidx, spec.task_id, fid,
                                   spec.lease_seq or 0, blob,
                                   encode_payload(spec),
                                   task_events.attempt_of(spec),
                                   spec.name)
                    staged += 1
                if obj_triples:
                    if not self._buffered_send(node.conn,
                                               ("node_exec", obj_triples)):
                        try:
                            node.conn.send(("node_exec", obj_triples))
                        except OSError:
                            hnat.grant_drop(nidx)
                            continue  # node-death requeues node.leases
                if staged:
                    try:
                        with node.conn.send_lock:
                            buf = hnat.grant_take(nidx)
                            if len(buf):
                                node.conn.sock.sendall(buf)
                    except OSError:
                        pass  # node-death handling requeues node.leases
                continue
            if native:
                # Native grant plane: each spec ships as raw pickle bytes
                # with (tid, fn, lease_seq, blob, spec, attempt, name)
                # sideband — the agent's C++ core ingests, dedups, queues
                # and dispatches them without a Python unpickle. cpp-
                # language leases keep the object form (their queue and
                # protobuf dispatch stay Python-side).
                raw_entries, obj_triples = [], []
                for fid, blob, spec in per_node[node]:
                    if getattr(spec, "language", None) == "cpp":
                        obj_triples.append((fid, blob, spec))
                    else:
                        raw_entries.append(
                            (spec.task_id, fid, spec.lease_seq, blob,
                             encode_payload(spec),
                             task_events.attempt_of(spec), spec.name))
                frames = []
                if raw_entries:
                    frames.append(("node_exec_raw", raw_entries))
                if obj_triples:
                    frames.append(("node_exec", obj_triples))
            else:
                frames = [("node_exec", per_node[node])]
            if chaos.site("head.lease_grant.lose"):
                continue  # injected grant loss: the lease watchdog in
                # _health_loop re-drives it against an idle agent
            sent_ok = True
            for frame in frames[:-1]:
                if self._buffered_send(node.conn, frame):
                    continue
                try:
                    node.conn.send(frame)
                except OSError:
                    sent_ok = False
                    break
            if not sent_ok:
                continue
            frame = frames[-1]
            # On the listener thread, ride the drain-pass out-batch: a
            # synchronous sendall here would stall the whole control
            # plane whenever one agent's socket back-pressures (with N
            # busy agents on few cores that is the common case, and it
            # serialized the lease plane at 16+ agents).
            if self._buffered_send(node.conn, frame):
                continue
            try:
                node.conn.send(frame)
            except OSError:
                pass  # node-death handling requeues node.leases

    # Lease pipeline depth per node CPU: how many tasks may ride one node
    # beyond its resource capacity (parity: max_tasks_in_flight_per_worker
    # lease reuse — here per NODE; without it every lease costs a full
    # head round-trip per task). 8 matches the worker pipeline depth —
    # measured optimum on the emulated many-agent rig (deeper caps let
    # early-finishing nodes hog the queue and collapse aggregate rate:
    # 12 -> 4x slower at 64 agents; shallower starves worker pipelines).
    _LEASE_DEPTH = 8

    @staticmethod
    def _lease_ok(spec: TaskSpec, env_key) -> bool:
        # cpp tasks lease WITH dependencies: their deps are ready
        # cluster-wide by queue time (the dep gate ran), and the agent
        # stages them into its local arena before dispatch — the cpp
        # worker has no object-plane RPC surface of its own.
        # TPU tasks never lease: the agent would return their worker to
        # its pool with the chip still open (see WorkerHandle.owns_chip),
        # and refills ride running leases without reserving chips.
        return (env_key is None and spec.actor_id is None
                and not spec.streaming and not spec.num_tpus
                and (not spec.dependencies
                     or getattr(spec, "language", None) == "cpp"))

    def _lease_refill_locked(self, node: NodeState,
                             completed: int = 1) -> list:
        """Pop lease-eligible backlog for `node` — called from
        _on_node_done so a completion hands the node new work DIRECTLY
        (one send, no scheduler pass), the lease-plane analogue of the
        worker path's local token handoff. Self-clocking: at most
        one-for-one with this batch's completions (plus the cap bound),
        so a fast node cannot monopolize the queue. No reservation:
        refills ride the node's running leases."""
        if node.state != "ALIVE":
            return []
        cap = int(self._LEASE_DEPTH * max(1.0, node.total.get("CPU", 1.0)))
        budget = min(cap - len(node.leases), completed)
        if budget <= 0:
            return []
        out = []
        for sig in self._sig_order(list(self.task_queues)):
            strat, env_key = sig[1], sig[2]
            if strat not in (None, "DEFAULT") or env_key is not None:
                continue
            # Capacity-type check (custom resources the node lacks).
            if any(node.total.get(k, 0.0) < v for k, v in sig[0]):
                continue
            q = self.task_queues[sig]
            while q and budget > 0:
                spec = q[0]
                if not self._lease_ok(spec, env_key):
                    break
                # Same quota gate as _schedule_now: the refill is the
                # second grant site, and a task-storm job must not ride
                # completion refills past its quota either.
                jid = getattr(spec, "job_id", None) or DEFAULT_JOB
                if not self.jobs.charge(jid, spec.task_id,
                                        self._resources_of(spec)):
                    break
                q.popleft()
                budget -= 1
                spec.lease_seq = (spec.lease_seq or 0) + 1
                node.leases[spec.task_id] = spec
                out.append((node, spec))
            if not self.task_queues.get(sig):
                self.task_queues.pop(sig, None)
            if budget <= 0:
                break
        return out

    def _maybe_reclaim_leases(self, node: NodeState):
        """Anti-straggler for the lease plane: a node reporting backlog
        while other nodes idle gets part of its UN-started lease queue
        pulled back for re-scheduling (cheap single-phase — the agent only
        returns tasks it never handed to a worker, so no execution race).
        Cooldown-paced: one reclaim per node per second is plenty."""
        now = time.monotonic()
        if now - node.last_reclaim < 5.0:
            return
        # Only a STUCK node (backlog with nothing in flight) is a
        # straggler; a node with execs in flight is making progress —
        # reclaiming from it just thrashes tasks between loaded nodes
        # (observed: 64 emulated agents on one core all report backlog
        # while their workers boot, and reclaim ping-pong halved the
        # aggregate rate).
        if node.load_view.get("inflight", 0) > 0:
            return
        with self.lock:
            if any(self.task_queues.values()):
                return
            idle = sum(len(n.idle) for n in self.nodes.values()
                       if n.state == "ALIVE" and n is not node)
        if idle <= 0:
            return
        node.last_reclaim = now
        try:
            node.conn.send(("lease_reclaim",
                            min(idle, int(node.load_view["backlog"]))))
        except OSError:
            pass

    # ------------- cluster-view broadcast (syncer downlink) -------------
    #
    # The uplink half (agents reporting versioned load deltas on
    # heartbeats) landed in round 5; this is the missing downlink
    # (parity: ray_syncer.h:20 both directions). The head merges every
    # node's delta into ONE versioned cluster view and periodically
    # broadcasts it back to the agents; per-agent cursors make each frame
    # a delta, so a quiet cluster costs zero broadcast bytes. Agents use
    # the view to spill leases peer-to-peer (node_agent._maybe_spill_leases,
    # parity: cluster_task_manager.cc:187) and to dial peer ctrl channels
    # without a head round trip.

    def _cview_update(self, nid: bytes, **fields):
        """Merge fields into a node's view entry, bumping the global
        version only when something actually changed — heartbeats with an
        unchanged load view must not generate broadcast traffic."""
        with self._cview_lock:
            e = self._cview.setdefault(nid, {})
            changed = False
            for k, v in fields.items():
                if e.get(k) != v:
                    e[k] = v
                    changed = True
            if changed:
                self._cview_version += 1
                e["v"] = self._cview_version

    def _cview_broadcast_loop(self):
        period = self.config.cluster_view_broadcast_ms / 1000.0
        while not self._shutdown:
            time.sleep(period)
            if self._shutdown:
                return
            try:
                self._broadcast_cluster_view()
            except Exception:  # noqa: BLE001 — the broadcaster must not die
                traceback.print_exc()

    def _broadcast_cluster_view(self):
        """One delta frame per agent that is behind the current version:
        exactly the entries newer than that agent's cursor. Cursors
        advance at send time; TCP FIFO per link makes that safe, and a
        link that dies mid-send re-registers, which resets the cursor to
        0 (the full-view catch-up).

        Encoded ONCE per distinct cursor (under a 16-agent storm every
        agent sits at the same cursor, so the tick costs one pickle +
        N raw sendalls instead of N pickles — the broadcaster was ~30%
        of head CPU in the HEADPROF_r06 storm before this). An agent's
        own entry rides along un-elided: every agent-side consumer
        already skips nid == self (the agent is the authority on its own
        load), so the shared bytes are semantically identical to the old
        per-agent frames. Chaos-armed processes keep per-agent send_msg
        so the seeded transport sites fire per frame."""
        with self._cview_lock:
            version = self._cview_version
            entries = [(nid, dict(e)) for nid, e in self._cview.items()]
        if version == 0:
            return
        armed = chaos._armed is not None
        by_cursor: dict = {}
        for node in list(self.nodes.values()):
            conn = node.conn
            if conn is None or node.state != "ALIVE":
                continue
            cursor = node.cview_cursor
            if cursor >= version:
                continue
            node.cview_cursor = version
            by_cursor.setdefault(cursor, []).append(node)
        for cursor, nodes in by_cursor.items():
            delta = [(nid, e) for nid, e in entries
                     if e.get("v", 0) > cursor]
            if not delta:
                continue
            if armed:
                for node in nodes:
                    try:
                        node.conn.send(("cluster_view", version, delta))
                    except OSError:
                        pass  # node-death handling owns the cleanup
                continue
            blob = encode_frame(("cluster_view", version, delta))
            for node in nodes:
                try:
                    with node.conn.send_lock:
                        node.conn.sock.sendall(blob)
                except OSError:
                    pass  # node-death handling owns the cleanup

    def _find_lease_locked(self, task_id: bytes, node):
        """Locate a lease by task id under self.lock WITHOUT popping it:
        the reporting node first, then every node — a spilled lease can
        complete on its peer before the origin's lease_spilled notice
        arrives (the two frames ride different TCP links). Returns
        (holder_node, spec), both None when the lease is gone."""
        if node is not None:
            spec = node.leases.get(task_id)
            if spec is not None:
                return node, spec
        for n in self.nodes.values():
            if n is node:
                continue
            spec = n.leases.get(task_id)
            if spec is not None:
                return n, spec
        return None, None

    def _pop_lease_locked(self, task_id: bytes, node,
                          native_popped: bool = False):
        """_find_lease_locked, destructively. Also retires the native
        head core's (task_id, lease_seq) mirror entry so the cold paths
        (lease_fail / reclaim / node death) can never leak it —
        `native_popped=True` skips that call on the hot completion path,
        where consume_hot already popped (or never held) the entry."""
        holder, spec = self._find_lease_locked(task_id, node)
        if holder is not None:
            holder.leases.pop(task_id, None)
            # Quota release: every lease pop (completion, failure,
            # requeue, node death, job stop) funnels through here, so the
            # ledger's inflight charge can never outlive the grant.
            self.jobs.settle(getattr(spec, "job_id", None) or DEFAULT_JOB,
                             task_id)
            if self._hnat is not None and not native_popped:
                self._hnat.inflight_pop(task_id)
            if self._wal:
                # Grant retired (completed/failed/requeued): every pop
                # funnels through here, so this is the WAL "lease"
                # table's single delete chokepoint.
                self._pstore.delete("lease", task_id)
        return spec

    def _on_lease_return(self, from_nid: bytes, specs: list):
        """Reclaimed (or back-pressure-refused spilled) un-started
        leases: back into the queues verbatim (no retry consumed — they
        never ran). Global find: a spilled lease returned by the
        RECEIVING agent may still be booked on its origin node.

        A return only counts while the lease it names is CURRENT — still
        booked somewhere AND the same grant generation (lease_seq). The
        spill-to-a-dead-peer case races the head's own requeue
        (_on_lease_spilled) against the origin agent's lease_return
        fallback, and by the time the loser's frame lands the task may
        already be re-queued, re-granted (seq bumped), or failed with
        retries exhausted; acting on the stale frame anyway would enqueue
        a second copy (duplicate execution) and double-release the
        reservation token. The loser must be a no-op."""
        node = self.nodes.get(from_nid)
        requeued = False
        with self.lock:
            for spec in specs:
                holder, cur = self._find_lease_locked(spec.task_id, node)
                if (cur is None
                        or (cur.lease_seq or 0) != (spec.lease_seq or 0)):
                    continue  # already requeued / completed / re-granted
                holder.leases.pop(spec.task_id, None)
                self.jobs.settle(
                    getattr(cur, "job_id", None) or DEFAULT_JOB,
                    spec.task_id)
                if self._hnat is not None:
                    self._hnat.inflight_pop(spec.task_id)
                self._release_token(
                    self._reservations.pop(spec.task_id, None))
                # Carry the hop count home: bouncing through the head
                # does not reset the anti-ping-pong budget.
                cur.spill_hops = spec.spill_hops
                self._enqueue_task_locked(cur, front=True)
                requeued = True
        if requeued:
            self._schedule()

    def _on_lease_spilled(self, from_nid: bytes, moves: list):
        """An agent forwarded leases to a peer agent (decentralized
        spillback): move head-side lease ownership to the executing node
        so node_done accounting and node-death replay stay truthful.
        Advisory and async — the head is OFF the per-task path here; a
        completion racing this frame simply wins (_find_lease_locked
        comes up empty).

        Two staleness guards, because these notices ride a different TCP
        link than returns/completions: (1) a notice whose lease_seq does
        not match the current lease names a PREVIOUS grant — the lease
        was returned and re-granted before the notice landed, and
        re-pointing the new grant would strand it (dest death replays
        spuriously, real holder's death replays never); (2) within one
        grant, the spill_hops position orders a multi-hop chain's notices
        (A->B and B->C may arrive reversed) — only a move further along
        the chain than what is already applied wins."""
        requeue = []
        with self.lock:
            for task_id, seq, hops, to_nid in moves:
                holder, spec = self._find_lease_locked(
                    task_id, self.nodes.get(from_nid))
                if (spec is None
                        or (spec.lease_seq or 0) != (seq or 0)):
                    continue  # completed / failed / returned + re-granted
                if (spec.spill_hops or 0) >= (hops or 0):
                    continue  # a later hop's notice already applied
                holder.leases.pop(task_id, None)
                spec.spill_hops = hops
                dest = self.nodes.get(to_nid)
                if dest is None or dest.state != "ALIVE":
                    requeue.append(spec)
                    continue
                dest.leases[task_id] = spec
                self.lease_spills_total += 1
        if requeue:
            # Destination died before the notice arrived: same policy as a
            # node death mid-lease — the task MAY have started there. The
            # origin agent's own lease_return fallback (its dial to the
            # dead peer fails too) lands on a popped lease and no-ops.
            self._on_lease_fail(None, requeue)

    def _steal_for_idle(self) -> bool:
        """Anti-straggler: with idle workers and empty queues, reclaim
        pipelined tasks that have not started (queued behind a long task on
        a busy worker) back into the scheduling queues.

        Two-phase by default: the spec is parked in _pending_steals and only
        re-enqueued once the origin worker acks that the task had not begun
        (drop_ack True). If the origin already started it, the steal aborts
        and the running execution stands — exactly-once absent failures, the
        reference's invariant. Tasks explicitly marked idempotent=True (and
        retriable) keep the cheaper one-phase path: enqueue immediately; a
        lost drop race is a benign duplicate of a task the user declared
        safe to replay."""
        stolen: list[tuple] = []
        with self.lock:
            if any(self.task_queues.values()):
                return False
            idle = sum(len(n.idle) for n in self.nodes.values()
                       if n.state == "ALIVE")
            # Each in-flight pending steal has already claimed an idle slot;
            # without this, every _schedule pass re-steals the same backlog
            # for the same idle workers while acks are in flight.
            idle -= len(self._pending_steals)
            if idle <= 0:
                return False
            for w in self.workers.values():
                if w.state != BUSY or len(w.assigned) <= 1:
                    continue
                while len(w.assigned) > 1 and idle > 0:
                    spec = w.assigned[-1]
                    if (not getattr(spec, "idempotent", False)
                            and (spec.retries_left or 0) <= 0):
                        # Even two-phase stealing leaves a worker-death
                        # window where "queued" vs "just begun" cannot be
                        # distinguished — resolving it costs a retry, so a
                        # task with no budget left must not be stolen.
                        break
                    # Steal only what can actually be placed RIGHT NOW on a
                    # node with a free worker — otherwise the spec would
                    # bounce queue -> pipeline -> steal forever.
                    try:
                        res = self._reserve_placement(
                            spec.scheduling_strategy,
                            self._resources_of(spec), spec.dependencies)
                    except Exception:  # noqa: BLE001 — unplaceable: leave it
                        break
                    if res is None:
                        break
                    node, token = res
                    self._rollback_token_locked(token)
                    # The idle worker must be from the spec's env pool —
                    # stealing onto a mismatched pool parks the task.
                    ek = _pip_key_of(spec)
                    if not any(iw.env_key == ek for iw in node.idle):
                        break
                    w.assigned.pop()
                    stolen.append((w, spec))
                    idle -= 1
                if idle <= 0:
                    break
            one_phase = []
            for w, spec in reversed(stolen):
                if getattr(spec, "idempotent", False):
                    self._enqueue_task_locked(spec, front=True)
                    one_phase.append((w, spec))
                else:
                    self._pending_steals[spec.task_id] = (w, spec)
        for w, spec in stolen:
            try:
                w.send(("drop_task", spec.task_id))
            except OSError:
                # Ack will never come; the worker-death path requeues
                # whatever is still parked in _pending_steals for w.
                pass
        return bool(one_phase)

    def _on_drop_ack(self, w: WorkerHandle, task_id: bytes, dropped: bool):
        """Phase two of a steal. dropped=True: the origin never started the
        task — re-dispatch it. dropped=False: the origin had already begun
        (or finished) it — abort the steal and let that execution stand."""
        with self.lock:
            entry = self._pending_steals.pop(task_id, None)
            if entry is None:
                # Completion beat the ack (task finished at the origin while
                # the steal was pending) — nothing left to do.
                return
            _w, spec = entry
            fail_spec = None
            if dropped:
                self._enqueue_task_locked(spec, front=True)
            elif w.state == DEAD:
                # Origin began the task and died before finishing it (its
                # death raced this ack): same retry-or-fail as the orphan
                # block in the death handler — never silently drop the spec
                # (its return futures would hang forever).
                if (spec.retries_left or 0) > 0:
                    spec.retries_left -= 1
                    self.task_events.record(task_id, spec, "RETRY")
                    self._enqueue_task_locked(spec, front=True)
                    dropped = True  # trigger the _schedule below
                else:
                    fail_spec = spec
            else:
                # The origin is executing the spec right now: restore the
                # in-flight bookkeeping so its eventual done/death handling
                # finds it. The steal victim was the backlog tail, so every
                # earlier done was processed before this ack (same-socket
                # FIFO) and may have re-idled the worker — pull it back.
                if w.state == IDLE:
                    w.state = BUSY
                    node = self.nodes.get(w.node_id)
                    if node is not None:
                        try:
                            node.idle.remove(w)
                        except ValueError:
                            pass
                w.assigned.append(spec)
                self._sig_workers.setdefault(
                    self._sched_key(spec), set()).add(w)
        if fail_spec is not None:
            self._fail_returns(fail_spec, WorkerCrashedError(
                f"worker died executing stolen task {fail_spec.describe()}"))
        if dropped:
            self._schedule()

    @staticmethod
    def _take_idle_locked(node: NodeState, env_key: str | None):
        """Pop an idle worker from the right env pool: env tasks need an
        exact env match; default tasks run on default-pool workers only
        (keeps env workers available for their env)."""
        for i, w in enumerate(node.idle):
            if w.env_key == env_key:
                del node.idle[i]
                return w
        return None

    def _pipeline_locked(self, sig, q, dispatches):
        """Assign queued same-key tasks to busy workers already executing
        that key, up to max_tasks_in_flight_per_worker each. Pipelined tasks
        take no new reservation — the completion handler hands the running
        task's token to the next one in the worker's queue."""
        depth = self.config.max_tasks_in_flight_per_worker
        if self.config.fair_share and self.jobs.multi_tenant():
            # A pipelined backlog is invisible to the weighted-DRF grant
            # order AND the quota gate (it rides the running task's
            # reservation, uncharged) — a storm job would hold every
            # worker for depth x task-time while the victim's queued key
            # waits. With a second live tenant, every grant goes back
            # through the ordered _schedule_now pass instead.
            depth = 1
        if depth <= 1 or not q:
            return
        cands = self._sig_workers.get(sig)
        if not cands:
            return
        for w in list(cands):
            if w.state != BUSY or not w.assigned:
                cands.discard(w)
                continue
            while q and len(w.assigned) < depth:
                if q[0].owner == w.worker_id.binary():
                    # Submitted by the task this worker is running, which
                    # usually goes on to wait for it: queued behind its own
                    # submitter it could never start (a nested streaming
                    # task, not stealable, hung that way for good whenever
                    # the pool's next worker was a moment late).
                    break
                spec = q.popleft()
                w.assigned.append(spec)
                dispatches.append((w, spec))
            if not q:
                break

    def _rollback_token_locked(self, token):
        """Undo a just-taken reservation without waking PG/actor waiters."""
        if not token:
            return
        if token[0] == "node":
            node = self.nodes.get(token[1])
            if node is not None and node.state == "ALIVE":
                for k, v in token[2].items():
                    node.available[k] = node.available.get(k, 0.0) + v
            return
        _, pg_id, i, req = token
        st = self.placement_groups.get(pg_id)
        if st is not None and st.state == "CREATED":
            b = st.bundle_avail[i]
            for k, v in req.items():
                b[k] = b.get(k, 0.0) + v
        else:
            self._rollback_token_locked(
                ("node",
                 st.bundle_nodes[i] if st is not None and st.bundle_nodes
                 else self.head_node_id, req))

    def _request_worker_locked(self, node: NodeState, pip: list | None = None):
        """Grow a node's worker pool on demand (rate-limited). With `pip`,
        the new worker boots into that env's pool (worker_pool.h:228)."""
        now = time.monotonic()
        if now - node.last_spawn_req < 0.5:
            return
        node.last_spawn_req = now
        if node.conn is None:
            alive = sum(1 for w in node.workers.values() if w.state != DEAD)
            if alive < self.pool_size * 2 + 8:
                threading.Thread(target=self._spawn_worker,
                                 kwargs={"pip": pip}, daemon=True).start()
        else:
            try:
                node.conn.send(("spawn_worker", pip)
                               if pip else ("spawn_worker",))
            except OSError:
                pass

    def _dispatch(self, w: WorkerHandle, spec: TaskSpec):
        self._dispatch_many(w, [spec])

    def _dispatch_many(self, w: WorkerHandle, specs: list,
                       defer_remote: bool = False):
        """Ship a run of specs to one worker as a single frame.

        defer_remote=True: for workers behind a node agent, RETURN the
        worker-bound message instead of sending so the caller can pack
        several workers' frames into one agent sendall (_schedule's
        per-node batching). Local workers always send directly (None is
        returned)."""
        frames = []
        for spec in specs:
            if spec.fn_id and spec.fn_id not in w.registered_fns:
                blob = self.fn_table.get(spec.fn_id)
                if blob is None:
                    self._pop_assignment(w, spec.task_id)
                    self._fail_returns(spec, RayTpuError(
                        f"function {spec.fn_id.hex()} was never exported"))
                    continue
                frames.append(("reg_fn", spec.fn_id, blob))
                w.registered_fns.add(spec.fn_id)
            data = w.tev_data  # cached {"node","worker"} hex dict — a
            if data is None:   # per-dispatch hex() showed in the storm
                data = w.tev_data = {"node": (w.node_id or b"").hex(),
                                     "worker": w.worker_id.hex()}
            self.task_events.record(
                spec.task_id, spec, "RUNNING", pipeline_state="DISPATCHED",
                data=data)
            frames.append(("exec", spec))
        if not frames:
            return None
        msg = frames[0] if len(frames) == 1 else ("batch", frames)
        if defer_remote and isinstance(w, RemoteWorkerHandle):
            return msg
        try:
            w.send(msg)
        except OSError:
            # The worker died under this dispatch (chaos storms hit this
            # window constantly: SIGKILL between idle-pop and send). The
            # specs are already in w.assigned, so the death path replays
            # them — force the socket to EOF so the listener notices NOW
            # and owns recovery; raising here would kill whichever thread
            # happened to be scheduling (observed: the listener itself).
            try:
                w.sock.shutdown(socket.SHUT_RDWR)
            except (OSError, AttributeError):
                pass
        return None

    def _pop_assignment(self, w: WorkerHandle, task_id: bytes):
        """Remove a finished/failed task from the worker's in-flight queue.
        Its reservation is handed to the next pipelined task on the worker
        (which was dispatched without one); the worker goes back to the idle
        pool when the queue drains. Returns the spec, or None."""
        with self.lock:
            spec = None
            if w.assigned and w.assigned[0].task_id == task_id:
                spec = w.assigned.popleft()
            else:
                for t in w.assigned:
                    if t.task_id == task_id:
                        spec = t
                        w.assigned.remove(t)
                        break
            if spec is None:
                return None
            # Quota release for the worker-dispatch grant path (pipelined
            # specs were never charged — settle is idempotent).
            self.jobs.settle(getattr(spec, "job_id", None) or DEFAULT_JOB,
                             task_id)
            token = self._reservations.pop(task_id, None)
            if (w.assigned and w.state != DEAD and token is not None
                    and w.assigned[0].task_id not in self._reservations):
                self._reservations[w.assigned[0].task_id] = token
                token = None
            retire = (not w.assigned and w.owns_chip and w.state != DEAD)
            if retire:
                # Stays BUSY (never idle again) holding the reservation
                # until _on_worker_death sees the process gone.
                w.chip_token = token
            else:
                self._release_token(token)
            if not w.assigned:
                self._sig_workers.get(
                    self._sched_key(spec), set()).discard(w)
                if w.state != DEAD and not retire:
                    w.state = IDLE
                    node = self.nodes.get(w.node_id)
                    if node is not None:
                        node.idle.append(w)
        if retire:
            try:
                w.send(("shutdown",))
            except OSError:
                pass  # already gone: the death handler releases the token
        return spec

    def _on_node_done_raw(self, conn: "NodeConn", whex: str, raws: list):
        """Unpack raw worker done frames into node_done entries. Each raw
        item is one COMPLETE outer frame (header + payload + oob buffers)
        exactly as the worker sent it — the C++ agent loop only sniffed
        the task ids, so the single unpickle happens here, where the
        payloads are consumed anyway. Parsed in place (no FrameBuffer
        bytearray round trip: one header unpack + one loads per frame)."""
        import pickle as _pickle
        import struct as _struct
        entries = []
        for raw in raws:
            (n,) = _struct.unpack_from("<Q", raw, 0)
            (nbufs,) = _struct.unpack_from("<I", raw, 8)
            off = 12 + 8 * nbufs
            blens = _struct.unpack_from(f"<{nbufs}Q", raw, 12) if nbufs \
                else ()
            payload = memoryview(raw)[off:off + n]
            bufs = []
            boff = off + n
            for bl in blens:
                bufs.append(memoryview(raw)[boff:boff + bl])
                boff += bl
            m = _pickle.loads(payload, buffers=bufs)
            if m[0] == "done":
                entries.append((m[1], m[3],
                                m[4] if len(m) > 4 else None, whex))
            elif m[0] == "done_batch":
                for e in m[1]:
                    entries.append((e[0], e[2],
                                    e[3] if len(e) > 3 else None, whex))
        if entries:
            self._on_node_done(conn, entries)

    def _on_node_done(self, conn: "NodeConn", entries: list,
                      native_popped: bool = False):
        """Batched completions of node-leased tasks (the raylet-local
        dispatch path). ONE global-lock acquisition per BATCH — the
        per-completion lock work the 64-agent profile named as the head's
        ceiling (HEADPROF_r04) collapses into per-frame bookkeeping
        (directory/object puts use their own locks)."""
        nid = conn.node_id
        node = self.nodes.get(nid)
        nid_hex = nid.hex() if nid else None
        # Object publication first (directory has its own locking);
        # the locked waiter probe below then observes every entry —
        # same ordering contract as _on_object_ready. Entries:
        # (task_id, outs[, exec-span record, worker hex]).
        for entry in entries:
            task_id, outs = entry[0], entry[1]
            if len(entry) > 2 and entry[2] is not None:
                self._emit_exec_spans(task_id, entry[2], nid_hex,
                                      entry[3] if len(entry) > 3 else None)
            for rid, status, payload, bufs in outs:
                if status == "inline":
                    self.directory.put(rid, ("raw", payload, bufs, True))
                elif status == "err":
                    self.directory.put(rid, ("raw", payload, bufs, False))
                else:
                    self.directory.add_location(rid, nid)
        ready_items = []
        refill = []
        with self.lock:
            for task_id, outs, *_ in entries:
                # Global pop: a spilled lease completes on the EXECUTING
                # node's link, which may not be the node it was leased to
                # (and the lease_spilled notice may still be in flight).
                spec = self._pop_lease_locked(task_id, node,
                                              native_popped)
                self._release_token(
                    self._reservations.pop(task_id, None))
                for rid, _s, _p, _b in outs:
                    self._rid_to_spec.pop(rid, None)
                    for item in self.waiting_deps.pop(rid, []):
                        item["pending"] -= 1
                        if item["pending"] == 0:
                            ready_items.append(item)
                self._cancelled.discard(task_id)
                self._reconstructing.discard(task_id)
                if spec is not None:
                    self.jobs.note_finished(
                        getattr(spec, "job_id", None) or DEFAULT_JOB)
                    self.task_events.record(task_id, spec, "FINISHED")
                    if self._persist and not spec.streaming:
                        self._pstore.delete("task", task_id)
                    self._lineage_register(spec)
                    self._unpin_deps(spec)
            if node is not None:
                refill = self._lease_refill_locked(node,
                                                   completed=len(entries))
        if refill:
            # Hand the send to the scheduler thread: this runs on the
            # LISTENER thread, and a blocking sendall to one
            # back-pressured agent here stalls the entire control plane
            # (profiled at 16 agents: the listener spent ~100% of its
            # samples inside send_msg).
            self._pending_lease_sends.append(refill)
        for item in ready_items:
            self._enqueue_ready(item)
        self._schedule()

    def _on_lease_fail(self, nid: bytes, specs: list):
        """A leased task's worker died at the agent: mirror the
        worker-death retry policy — the task MAY have started, so a
        replay consumes a retry; exhausted ones fail their returns."""
        node = self.nodes.get(nid)
        requeued = False
        for spec in specs:
            with self.lock:
                self._pop_lease_locked(spec.task_id, node)
                self._release_token(
                    self._reservations.pop(spec.task_id, None))
            if spec.task_id in self._cancelled:
                from ray_tpu.core.status import TaskCancelledError
                self._fail_returns(spec, TaskCancelledError(
                    f"task {spec.describe()} was cancelled"))
                self._cancelled.discard(spec.task_id)
            elif (spec.retries_left or 0) > 0:
                spec.retries_left -= 1
                self.task_events.record(spec.task_id, spec, "RETRY")
                with self.lock:
                    self._enqueue_task_locked(spec, front=True)
                requeued = True
            else:
                self._fail_returns(spec, RayTpuError(
                    f"worker died executing {spec.describe()} "
                    "(leased; retries exhausted)"))
        if requeued:
            self._schedule()

    def _emit_exec_spans(self, task_id: bytes, tev, node_hex, worker_hex):
        """One inlined ring append for a done frame's piggybacked exec
        record ((attempt, exec_start, args_ready, exec_done, seal) from
        the executing worker) — the whole worker-side exec story costs
        the head one tuple here."""
        ring = _TEV_RING
        if not ring.enabled or tev is None:
            return
        ev = ring.events
        if len(ev) >= ring.capacity:
            ring.dropped += 1
        ev.append((task_id, tev[0], "EXEC_SPANS", tev[4], None,
                   (tev[1], tev[2], tev[3], worker_hex, node_hex)))

    def _on_task_done(self, w: WorkerHandle, task_id: bytes,
                      actor_id: bytes | None, outs, tev=None):
        if tev is not None:
            d = w.tev_data
            if d is None:
                d = w.tev_data = {"node": (w.node_id or b"").hex(),
                                  "worker": w.worker_id.hex()}
            self._emit_exec_spans(task_id, tev, d["node"], d["worker"])
        for rid, status, payload, bufs in outs:
            # Inline payloads stay pickled until someone reads them — the
            # listener thread must not burn CPU deserializing results that may
            # only ever be forwarded to another worker.
            if status == "inline":
                self.directory.put(rid, ("raw", payload, bufs, True))
            elif status == "err":
                self.directory.put(rid, ("raw", payload, bufs, False))
            else:
                self.directory.add_location(rid, w.node_id)
            self._on_object_ready(rid)
        with self.lock:
            for rid, _s, _p, _b in outs:
                self._rid_to_spec.pop(rid, None)
            self._cancelled.discard(task_id)  # force-cancel lost the race
            self._reconstructing.discard(task_id)
        if task_id in self._streams:
            self._stream_close(task_id)
            with self.lock:
                self._rid_to_spec.pop(task_id, None)
        if actor_id is not None:
            st = self.actors.get(actor_id)
            if st is not None:
                spec = st.inflight.pop(task_id, None)
                if spec is not None:
                    self.task_events.record(task_id, spec, "FINISHED")
                    self._unpin_deps(spec)
            return
        spec = self._pop_assignment(w, task_id)
        if spec is None:
            # A steal was pending on this task and the origin finished it
            # first: reap the steal, keep the result (exactly-once).
            with self.lock:
                entry = self._pending_steals.pop(task_id, None)
            if entry is not None:
                spec = entry[1]
        if spec is not None:
            self.jobs.note_finished(
                getattr(spec, "job_id", None) or DEFAULT_JOB)
            self.task_events.record(task_id, spec, "FINISHED")
            if self._persist and spec.actor_id is None and not spec.streaming:
                self._pstore.delete("task", task_id)
            if not spec.streaming:
                self._lineage_register(spec)
            self._unpin_deps(spec)
        # Refill hysteresis: this completion freed no capacity (the
        # reservation token passed to the worker's next pipelined spec), so
        # while the worker's backlog sits above the half-depth mark a
        # schedule pass cannot place anything it couldn't before. Waiting
        # for the mark batches the refill — one dispatch frame then carries
        # several specs, halving head send syscalls under storm load.
        if len(w.assigned) <= self.config.max_tasks_in_flight_per_worker // 2:
            self._schedule()

    def _fail_returns(self, spec: TaskSpec, exc: Exception):
        err = exc if isinstance(exc, TaskError) else TaskError(
            exc, str(exc), spec.describe())
        jid = getattr(spec, "job_id", None) or DEFAULT_JOB
        # A failed spec may die holding a charge (grant-site exception
        # paths, job stop); settle is idempotent for the never-charged.
        self.jobs.settle(jid, spec.task_id)
        self.jobs.note_finished(jid)
        self.task_events.record(spec.task_id, spec, "FAILED")
        self._unpin_deps(spec)
        if self._persist and spec.actor_id is None and not spec.streaming:
            self._pstore.delete("task", spec.task_id)
        with self.lock:
            self._reconstructing.discard(spec.task_id)
        if spec.streaming:
            # Surface the failure as the stream's final item, then close —
            # the consumer's next() returns a ref whose get() raises.
            rid = os.urandom(16)
            payload, bufs, _ = serialization.serialize_value(err)
            self.directory.put(rid, ("raw", payload, bufs, False))
            self._stream_append(spec.task_id, rid)
            self._stream_close(spec.task_id)
        with self.lock:
            # NOTE: _cancelled is NOT cleared here — a dep-gated cancelled
            # task still needs its tombstone when the deps arrive.
            for rid in spec.return_ids:
                self._rid_to_spec.pop(rid, None)
            if spec.streaming:
                # Streaming specs are keyed by task_id, not return ids.
                self._rid_to_spec.pop(spec.task_id, None)
        for rid in spec.return_ids:
            self.directory.put(rid, ("err", err))
            self._on_object_ready(rid)

    # ---------------- actors ----------------

    def _actor_resources(self, cspec: ActorCreationSpec) -> dict[str, float]:
        req = {"CPU": cspec.num_cpus or 0.0, "TPU": cspec.num_tpus or 0.0,
               **(cspec.resources or {})}
        return {k: v for k, v in req.items() if v}

    def create_actor(self, cspec: ActorCreationSpec, fn_blob: bytes | None = None,
                     dependencies=None, from_worker: bool = False):
        if fn_blob is not None:
            self.export_function(cspec.cls_id, fn_blob)
        try:
            self._check_feasible(self._actor_resources(cspec), cspec.name)
            with self.lock:
                if cspec.name and cspec.name in self.named_actors:
                    raise RayTpuError(
                        f"actor name {cspec.name!r} already taken")
                st = ActorState(cspec)
                self.actors[cspec.actor_id] = st
                if cspec.name:
                    self.named_actors[cspec.name] = cspec.actor_id
            if self._persist:
                import cloudpickle
                self._pstore.append(
                    "actor", cspec.actor_id,
                    cloudpickle.dumps(_journal_safe_spec(cspec)))
        except RayTpuError as e:
            if not from_worker:
                raise
            # Worker-originated create: record a dead actor so the caller's
            # method calls fail fast with the real cause instead of hanging.
            st = ActorState(cspec)
            st.state = A_DEAD
            self._export_actor(st, "DEAD")
            st.death_cause = e
            with self.lock:
                self.actors.setdefault(cspec.actor_id, st)
            return
        item = {"kind": "actor", "cspec": cspec, "pending": 0}
        self._gate_on_deps(item, dependencies or cspec.dependencies or [])

    def _create_actor_now(self, cspec: ActorCreationSpec):
        st = self.actors[cspec.actor_id]
        with self.lock:
            if st.state == A_DEAD:  # killed while the creation was queued
                return
            # Actors hold their resources for their lifetime; queue the
            # creation until the reservation fits (released on death/kill).
            req = self._actor_resources(cspec)
            try:
                if cspec.placement_group_id is not None:
                    bidx = cspec.bundle_index
                    token = self._try_reserve_pg(
                        cspec.placement_group_id,
                        -1 if bidx is None else bidx, req)
                    node = None
                    if token is not None:
                        pg = self.placement_groups[cspec.placement_group_id]
                        node = self.nodes.get(pg.bundle_nodes[token[2]])
                        if node is None or node.state != "ALIVE":
                            # PG rescheduling is not implemented: nothing can
                            # ever revive this bundle, so fail loudly like
                            # the task path does instead of parking forever.
                            self._release_token(token)
                            raise ResourceError(
                                f"placement group bundle {token[2]} was on "
                                f"a dead node")
                else:
                    strategy = getattr(cspec, "scheduling_strategy",
                                       None) or "DEFAULT"
                    res = self._reserve_placement(strategy, req, None)
                    node, token = (None, None) if res is None else res
            except RayTpuError as e:
                st.state = A_DEAD
                self._export_actor(st, "DEAD")
                st.death_cause = e
                if cspec.name and self.named_actors.get(cspec.name) == cspec.actor_id:
                    del self.named_actors[cspec.name]
                queued = list(st.queued)
                st.queued.clear()
                for qspec in queued:
                    self._fail_returns(qspec, e)
                return
            if token is None:
                self.actors_waiting_resources.append(cspec.actor_id)
                return
            st.resources_reserved = token
            st.node_id = node.node_id
            # Env-pool matching (worker_pool.h:228): an actor with a pip
            # runtime_env needs a worker from that env's pool, a default
            # actor must not consume (or contaminate itself on) one.
            w = self._take_idle_locked(node, _pip_key_of(cspec))
            spawn_new = w is not None and self._assign_actor_locked(st, w)
            if not spawn_new:
                # No idle worker (or the popped one was already dead):
                # park; the next ready worker picks the assignment up.
                node.pending_actor_assign.append(cspec.actor_id)
        # Keep the pool at size for plain tasks; new process feeds the pool
        # (or picks up the pending assignment on connect).
        pip = self._pip_env_of(cspec)
        if node.conn is not None:
            try:
                # When the actor is still waiting, the spawned worker must
                # come from its env pool; when it was assigned, replenish
                # the default pool.
                node.conn.send(("spawn_worker", pip)
                               if pip and not spawn_new
                               else ("spawn_worker",))
            except OSError:
                pass
        elif spawn_new:
            self._replenish_pool_async()
        else:
            threading.Thread(target=self._spawn_worker,
                             kwargs={"pip": pip}, daemon=True).start()

    def _assign_actor_locked(self, st: ActorState, w: WorkerHandle) -> bool:
        """Hand the actor creation to `w`. Returns False if the worker died
        between pool-pop and the handoff (send hit a closed pipe): the
        assignment is rolled back so the death notification reaps a plain
        worker — no restart budget consumed, no BrokenPipeError escaping
        into the caller's thread — and the caller re-parks the actor."""
        cspec = st.cspec
        w.state = ASSIGNED_ACTOR
        w.actor_id = cspec.actor_id
        w.owns_chip = bool(cspec.num_tpus)
        st.worker = w
        blob = self.fn_table.get(cspec.cls_id)
        try:
            w.send(("reg_fn", cspec.cls_id, blob))
            w.registered_fns.add(cspec.cls_id)
            w.send(("create_actor", cspec))
        except OSError:
            w.state = IDLE
            w.actor_id = None
            w.owns_chip = False
            st.worker = None
            return False
        return True

    def _export_actor(self, st: "ActorState", state: str):
        if state == "DEAD":
            # Permanently dead actors leave the persistence journal (every
            # terminal transition funnels through this export).
            self._pstore.delete("actor", st.cspec.actor_id)
        if self.export_events is not None:
            self.export_events.emit("ACTOR",
                                    actor_id=st.cspec.actor_id.hex(),
                                    name=st.cspec.name, state=state)

    def _on_actor_ready(self, actor_id: bytes):
        st = self.actors.get(actor_id)
        if st is None:
            return
        dead_worker = None
        with self.lock:
            was_restart = st.state == A_RESTARTING
            if st.state == A_DEAD:
                # Killed while starting up: do not resurrect; stop the worker
                # (outside the lock — zygote kills round-trip).
                dead_worker = st.worker
                queued = []
            else:
                st.state = A_ALIVE
                queued = list(st.queued)
                st.queued.clear()
        if st.state == A_ALIVE:
            self._export_actor(st, "ALIVE")
            if was_restart:
                # Restart landed (possibly on a new worker/node): poison
                # every caller's cached direct-call location — including
                # the NEGATIVE "head-hosted" entries callers latched while
                # the actor was restarting, which would otherwise pin them
                # to the slow head path (and any stale UDS path) forever.
                self._broadcast_actor_moved(actor_id)
        if dead_worker is not None:
            dead_worker.kill()
        for spec in queued:
            self._send_actor_task(st, spec)

    def _on_actor_init_error(self, actor_id: bytes, payload, bufs):
        st = self.actors.get(actor_id)
        if st is None:
            return
        err = serialization.deserialize(payload, bufs)
        st.state = A_DEAD
        self._export_actor(st, "DEAD")
        st.death_cause = err
        for spec in list(st.queued):
            self._fail_returns(spec, err)
        st.queued.clear()
        w = st.worker
        with self.lock:
            name = st.cspec.name
            if name and self.named_actors.get(name) == st.cspec.actor_id:
                del self.named_actors[name]
            if st.resources_reserved:
                if w is not None and w.owns_chip and w.state != DEAD:
                    # __init__ may have opened the chip before failing:
                    # the reservation goes back when the process is gone.
                    w.chip_token = st.resources_reserved
                else:
                    self._release_token(st.resources_reserved)
                st.resources_reserved = None
        # Reclaim the worker process: its only job was this actor.
        st.worker = None
        if w is not None and w.state != DEAD:
            try:
                w.send(("shutdown",))
            except OSError:
                pass

    def _submit_actor_task(self, spec: TaskSpec):
        st = self.actors.get(spec.actor_id)
        if st is None or st.state == A_DEAD:
            cause = st.death_cause if st else None
            self._fail_returns(spec, cause if isinstance(cause, Exception)
                               else ActorDiedError(msg="actor is dead"))
            return
        with self.lock:
            spec.seq_no = st.seq
            st.seq += 1
            if spec.retries_left is None or spec.retries_left == 0:
                spec.retries_left = st.cspec.max_task_retries or 0
            if st.state in (A_PENDING, A_RESTARTING):
                st.queued.append(spec)
                return
        self._send_actor_task(st, spec)

    def _send_actor_task(self, st: ActorState, spec: TaskSpec):
        with self.lock:
            # Diagnostic: every actor exec the HEAD relays (the direct
            # worker peer plane never passes through here — tests assert
            # this stays flat during a direct-call storm). Counted under
            # the lock: listener + submitter threads both land here, and
            # an unlocked += loses increments exactly when the count is
            # being compared against a storm's dispatch total.
            self.actor_head_dispatches += 1
            w = st.worker
            if st.state == A_DEAD:
                dead_cause = st.death_cause
            elif w is None or st.state != A_ALIVE:
                # Raced with a restart: park the call for replay.
                st.queued.append(spec)
                return
            else:
                st.inflight[spec.task_id] = spec
                dead_cause = None
        if dead_cause is not None or st.state == A_DEAD:
            # Death handler already ran and drained the queue; fail here.
            self._fail_returns(
                spec, dead_cause if isinstance(dead_cause, Exception)
                else ActorDiedError(msg="actor is dead"))
            return
        self.task_events.record(spec.task_id, spec, "RUNNING")
        if self._buffered_send(w, ("exec", spec)):
            return
        try:
            w.send(("exec", spec))
        except OSError:
            self._actor_exec_send_failed(spec)

    def _actor_exec_send_failed(self, spec):
        # Raced with the worker dying (socket already closed). Park the
        # call; the death handler replays/fails it with the actor's fate.
        # If that handler already ran, fail the call here instead — nobody
        # will drain the queue again.
        st = self.actors.get(spec.actor_id)
        if st is None:
            self._fail_returns(spec, ActorDiedError(msg="actor is dead"))
            return
        with self.lock:
            st.inflight.pop(spec.task_id, None)
            if st.state != A_DEAD:
                st.queued.append(spec)
                return
        cause = st.death_cause
        self._fail_returns(spec, cause if isinstance(cause, Exception)
                           else ActorDiedError(msg="actor is dead"))

    def kill_actor_by_id(self, actor_id: bytes, no_restart=True):
        st = self.actors.get(actor_id)
        if st is None:
            return
        st.cspec.max_restarts = 0 if no_restart else st.cspec.max_restarts
        with self.lock:
            # Read the worker under the lock: a kill racing the pending
            # assignment (listener setting st.worker) must see it, or we'd
            # take the no-worker branch and the actor would come alive later.
            w = st.worker
        if w is not None and w.kill():
            return
        # No worker yet: the creation is still queued (waiting on resources
        # or a pending assignment). Mark it dead so the queued create is
        # skipped, and fail anything already parked on it.
        with self.lock:
            if st.state == A_DEAD or st.worker is not None:
                # Re-check: assignment may have won the race after our read;
                # retry through the worker-kill branch.
                if st.worker is not None and st.state != A_DEAD:
                    st.worker.kill()
                return
            st.state = A_DEAD
            self._export_actor(st, "DEAD")
            st.death_cause = ActorDiedError(
                msg=f"actor {st.cspec.name} was killed before it started")
            try:
                self.actors_waiting_resources.remove(actor_id)
            except ValueError:
                pass
            for node in self.nodes.values():
                try:
                    node.pending_actor_assign.remove(actor_id)
                except ValueError:
                    pass
            if st.resources_reserved:
                self._release_token(st.resources_reserved)
                st.resources_reserved = None
            queued = list(st.queued)
            st.queued.clear()
        for spec in queued:
            self._fail_returns(spec, st.death_cause)

    # ---------------- failure handling ----------------

    def _on_worker_death(self, w: WorkerHandle):
        if w.state == DEAD:
            return
        if w.sock is not None:
            self._pump_unregister(w.sock, w)
            try:
                w.sock.close()
            except OSError:
                pass
        if w.owns_chip and w.proc is not None:
            # The socket closes a moment before the kernel has torn the
            # process down; the chip is free only after that. Every
            # reservation this worker held is released below, so whoever
            # is granted the chips next can open them without a sleep.
            try:
                w.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                w.kill()
        with self.lock:
            prev_state = w.state
            if prev_state == DEAD:
                return
            w.state = DEAD
            self._release_token(w.chip_token)
            w.chip_token = None
            self.workers.pop(w.worker_id.binary(), None)
            if getattr(w, "peer_path", None):
                try:
                    os.unlink(w.peer_path)
                except OSError:
                    pass
            wid_bin = w.worker_id.binary()
            for subs in self._pubsub_subs.values():
                subs.discard(wid_bin)
            node = self.nodes.get(w.node_id)
            if node is not None:
                try:
                    node.idle.remove(w)
                except ValueError:
                    pass
                node.workers.pop(w.worker_id.binary(), None)
        if prev_state == BUSY and w.assigned:
            assigned = list(w.assigned)
            w.assigned.clear()
            with self.lock:
                self._sig_workers.get(
                    self._sched_key(assigned[0]), set()).discard(w)
                for spec in assigned:
                    self._release_token(
                        self._reservations.pop(spec.task_id, None))
                    # Settle the worker-dispatch grant's quota charge
                    # BEFORE the retry requeue: the re-grant's charge
                    # would hit the double-grant guard and park the key
                    # forever. Pipelined tails were never charged —
                    # settle is idempotent.
                    self.jobs.settle(
                        getattr(spec, "job_id", None) or DEFAULT_JOB,
                        spec.task_id)
            # Requeue retriable tasks at the FRONT in original order
            # (reversed appendleft); the rest fail. Pipelined tasks queued
            # behind the running one never started — they requeue without
            # consuming a retry.
            running_id = assigned[0].task_id
            for spec in reversed(assigned):
                if spec.task_id != running_id:
                    if spec.task_id in self._cancelled:
                        from ray_tpu.core.status import TaskCancelledError
                        self._fail_returns(spec, TaskCancelledError(
                            f"task {spec.describe()} was cancelled"))
                        self._cancelled.discard(spec.task_id)
                        continue
                    with self.lock:
                        self._enqueue_task_locked(spec, front=True)
                elif (spec.retries_left or 0) > 0:
                    spec.retries_left -= 1
                    self.task_events.record(spec.task_id, spec, "RETRY")
                    with self.lock:
                        self._enqueue_task_locked(spec, front=True)
                elif spec.task_id in self._cancelled:
                    from ray_tpu.core.status import TaskCancelledError
                    self._fail_returns(spec, TaskCancelledError(
                        f"task {spec.describe()} was cancelled"))
                    self._cancelled.discard(spec.task_id)
                else:
                    self._fail_returns(spec, WorkerCrashedError(
                        f"worker died executing {spec.describe()}"))
        # Steals that never got their ack: the dying origin will not run
        # them (or died mid-run). Stolen specs are retriable by construction;
        # consume a retry — "queued tail" vs "just begun" cannot be told
        # apart once the worker is gone, and a begun task must not replay
        # for free.
        with self.lock:
            orphaned = [tid for tid, (ow, _s) in self._pending_steals.items()
                        if ow is w]
            requeue, fail = [], []
            for tid in orphaned:
                spec = self._pending_steals.pop(tid)[1]
                if (spec.retries_left or 0) > 0:
                    spec.retries_left -= 1
                    self.task_events.record(tid, spec, "RETRY")
                    self._enqueue_task_locked(spec, front=True)
                    requeue.append(spec)
                else:
                    fail.append(spec)
        for spec in fail:
            self._fail_returns(spec, WorkerCrashedError(
                f"worker died with stolen task {spec.describe()} unacked"))
        if requeue:
            self._schedule()
        for token, (fut, fwid) in list(self._profile_futs.items()):
            if fwid == w.worker_id.binary():
                self._profile_futs.pop(token, None)
                if not fut.done():
                    fut.set_exception(RayTpuError(
                        "worker died while being profiled"))
        if w.actor_id is not None:
            self._on_actor_worker_death(w.actor_id)
        if (prev_state in (IDLE, BUSY) and not self._shutdown
                and w.node_id == self.head_node_id):
            # Remote nodes replenish their own pools agent-side.
            self._replenish_pool_async()
        self._schedule()

    def _on_actor_worker_death(self, actor_id: bytes):
        st = self.actors.get(actor_id)
        if st is None or st.state == A_DEAD:
            return
        # Only head-hosted actors can have worker-plane location caches
        # (agents invalidate their own workers' caches themselves).
        if (st.node_id == self.head_node_id
                and self.config.worker_direct_calls):
            self._broadcast_actor_moved(actor_id)
        cspec = st.cspec
        inflight = list(st.inflight.values())
        st.inflight.clear()
        if cspec.restarts_used < (cspec.max_restarts or 0):
            cspec.restarts_used += 1
            st.state = A_RESTARTING
            st.worker = None
            retried = []
            for spec in inflight:
                if (spec.retries_left or 0) > 0:
                    spec.retries_left -= 1
                    retried.append(spec)
                else:
                    self._fail_returns(spec, ActorDiedError(
                        msg=f"actor {cspec.name} died; call retries exhausted"))
            # Replay ahead of anything queued later, preserving submission order.
            st.queued.extendleft(reversed(retried))
            # Release the old placement and re-run node selection: the death
            # may have been the node itself, so the restart must be free to
            # land anywhere (parity: GCS actor FSM re-schedules on restart,
            # gcs_actor_manager.h:328).
            with self.lock:
                if st.resources_reserved:
                    self._release_token(st.resources_reserved)
                    st.resources_reserved = None
            threading.Thread(target=self._create_actor_now,
                             args=(cspec,), daemon=True).start()
        else:
            st.state = A_DEAD
            self._export_actor(st, "DEAD")
            st.death_cause = ActorDiedError(msg=f"actor {cspec.name} died")
            st.worker = None
            for spec in inflight:
                self._fail_returns(spec, st.death_cause)
            for spec in list(st.queued):
                self._fail_returns(spec, st.death_cause)
            st.queued.clear()
            with self.lock:
                if cspec.name and self.named_actors.get(cspec.name) == actor_id:
                    del self.named_actors[cspec.name]
                if st.resources_reserved:
                    self._release_token(st.resources_reserved)
                    st.resources_reserved = None

    def profile_worker(self, worker_id_hex: str, duration_s: float = 1.0,
                       hz: float = 100.0) -> dict:
        """Sample a live worker's stacks on demand (parity: the dashboard
        reporter's py-spy endpoint; here a built-in cooperative sampler —
        ray_tpu/util/profiling.py). worker_id "head" samples this
        process."""
        import concurrent.futures

        from ray_tpu.util.profiling import sample_stacks
        if worker_id_hex in ("head", "driver", ""):
            return sample_stacks(duration_s, hz)
        wid = bytes.fromhex(worker_id_hex)
        w = self.workers.get(wid)
        if w is None or w.state == DEAD:
            raise RayTpuError(f"no live worker {worker_id_hex}")
        if getattr(w, "is_client", False):
            raise RayTpuError(
                f"{worker_id_hex} is a client-mode driver, not a worker")
        token = os.urandom(8)
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        self._profile_futs[token] = (fut, wid)
        try:
            w.send(("profile", token, float(duration_s), float(hz)))
            return fut.result(duration_s + 30.0)
        except concurrent.futures.TimeoutError:
            raise RayTpuError(
                f"profiling {worker_id_hex} timed out") from None
        finally:
            self._profile_futs.pop(token, None)

    # ---------------- introspection ----------------

    def cluster_resources(self) -> dict[str, float]:
        return dict(self.total_resources)

    def available_resources(self) -> dict[str, float]:
        with self.lock:
            out: dict[str, float] = {}
            for n in self._alive_nodes():
                for k, v in n.available.items():
                    out[k] = out.get(k, 0.0) + v
            return out

    def get_actor_state(self, actor_id: bytes) -> str:
        st = self.actors.get(actor_id)
        return st.state if st else "unknown"

    def timeline(self):
        return self.task_events.snapshot()

    def _queue_task_events(self, events, node, worker, dropped):
        """Park an arriving batch for the ingest thread (listener-thread
        fast path: one deque append)."""
        q = self._tev_pending
        if len(q) >= 512:  # bounded: count the evicted batch as drops
            try:
                old = q.popleft()
                with self._tev_overflow_lock:
                    self._tev_overflow += len(old[0]) + old[3]
            except IndexError:
                pass
        q.append((events, node, worker, dropped))

    def _tev_ingest_loop(self):
        while not self._shutdown:
            time.sleep(0.25)
            try:
                self._drain_tev_pending()
            except Exception:  # noqa: BLE001 — ingest must outlive glitches
                traceback.print_exc()

    def _drain_tev_pending(self):
        q = self._tev_pending
        while q:
            try:
                events, node, worker, dropped = q.popleft()
            except IndexError:
                break
            self.task_store.ingest(events, node=node, worker=worker,
                                   dropped=dropped)
        with self._tev_overflow_lock:
            n, self._tev_overflow = self._tev_overflow, 0
        if n:
            self.task_store.ingest([], dropped=n)

    def sync_task_store(self):
        """Merge everything pending — parked arrival batches plus the
        head process's OWN emission ring (head emissions are
        ring-buffered like every other process's, but there is no socket
        to flush over — queries pull them in)."""
        self._drain_tev_pending()
        if self._shards is not None:
            # Shard-held event slices merge lazily — agents shipped them
            # to the owning shards, keeping per-event work off the head's
            # storm path; queries pay the pull instead.
            for nid, batch, dropped in self._shards.drain_tev():
                self.task_store.ingest(batch, node=nid, dropped=dropped)
        batch, dropped = task_events.ring().drain(max_events=1 << 20)
        if batch or dropped:
            self.task_store.ingest(batch, node=None, dropped=dropped)

    def _merge_worker_metrics(self, wid: bytes, snapshots: list):
        """Latest registry snapshot per (worker, metric name): deltas only
        carry metrics that changed, so merge by name."""
        per = self._worker_metrics.setdefault(wid, {})
        for snap in snapshots:
            per[snap["name"]] = snap

    def worker_metric_snapshots(self) -> dict:
        """wid -> {metric name -> snapshot}, live workers only (a dead
        worker's counters would freeze into the scrape forever)."""
        out = {}
        for wid, per in list(self._worker_metrics.items()):
            w = self.workers.get(wid)
            if w is None or w.state == DEAD:
                self._worker_metrics.pop(wid, None)
                continue
            out[wid] = per
        return out

    # ---------------- shutdown ----------------

    def shutdown(self):
        with self.lock:
            if self._shutdown:
                return
            # Under the lock: any in-flight _spawn_worker either registered
            # its handle (we see it below) or will observe the flag and
            # self-clean.
            self._shutdown = True
        with self._sched_cv:
            self._sched_cv.notify_all()
        for node in list(self.nodes.values()):
            if node.conn is not None and node.state == "ALIVE":
                try:
                    node.conn.send(("shutdown_node",))
                except OSError:
                    pass
        if self._cluster_srv is not None:
            try:
                self._cluster_srv.close()
            except OSError:
                pass
        if self._shards is not None:
            self._shards.shutdown()
        self._pstore.close()
        if getattr(self, "_proto_clients", None) is not None:
            self._proto_clients.close()
        for w in list(self.workers.values()):
            if w.state != DEAD and w.sock is not None:
                try:
                    w.send(("shutdown",))
                except OSError:
                    pass
        deadline = time.monotonic() + 2.0
        for w in list(self.workers.values()):
            if w.proc is None:
                continue
            try:
                w.proc.wait(timeout=max(0.05, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                w.proc.kill()
                if w.owns_chip:
                    # The next init() in this process (or the next command
                    # on this host) must find the chip free.
                    try:
                        w.proc.wait(timeout=10.0)
                    except subprocess.TimeoutExpired:
                        pass
        if self._zygote is not None:
            self._zygote.close()
        # Stop the peer server BEFORE unmapping the arena: its native
        # threads read the mmap raw.
        if getattr(self, "_peer_server", None) is not None:
            self._peer_server.stop()
        if self.export_events is not None:
            self.export_events.close()
        if self._log_monitor is not None:
            self._log_monitor.stop()
        # Close gate: the health loop's orphan sweep walks the raw arena;
        # unmapping under it is a segfault. _shutdown is already set, so
        # once we hold the lock no further sweep can start.
        with self._store_close_lock:
            self.store.close()
            self.store.unlink()
        # Worker peer sockets (`<arena>_w<id>.sock`) belong to worker
        # processes we may have just killed mid-unlink; sweep them so a
        # clean shutdown leaves /dev/shm empty.
        import glob as _glob
        for p in _glob.glob(self.store.path + "_w*.sock"):
            try:
                os.unlink(p)
            except OSError:
                pass


# ---------------- global runtime plumbing ----------------

_runtime: Runtime | None = None
_worker_runtime = None


def set_worker_runtime(rt):
    global _worker_runtime
    _worker_runtime = rt


def current_runtime():
    """Driver Runtime, WorkerRuntime, or None — whatever this process has."""
    return _worker_runtime if _worker_runtime is not None else _runtime


def get_runtime():
    rt = current_runtime()
    if rt is None:
        from ray_tpu.core.status import RuntimeNotInitializedError
        raise RuntimeNotInitializedError()
    return rt


def init_runtime(**kw) -> Runtime:
    global _runtime
    if _runtime is not None:
        return _runtime
    _runtime = Runtime(**kw)
    return _runtime


def shutdown_runtime():
    global _runtime
    if _runtime is not None:
        _runtime.shutdown()
        _runtime = None
