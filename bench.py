#!/usr/bin/env python
"""Core microbenchmark vs the reference's checked-in numbers.

Mirrors the reference's `python/ray/_private/ray_perf.py:93` suite — the
FULL 21-metric regression-gate set in BASELINE.md, same workload semantics
(nested submission for multi-client, Client fan-out actors, threaded /
async actors, 10k-ref objects, wait loops, PG churn, client-mode RPCs).
Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
where vs_baseline is the geometric mean of (ours / reference) across all
metrics. Detail per-metric numbers go to stderr.

Process hygiene (r4 verdict #1 — the r4 artifact was empty, rc=124):
- every metric is emitted to stderr as JSONL the moment it completes, so
  a timeout yields a partial artifact, never nothing;
- SIGTERM/SIGINT print the final JSON line with whatever has been
  collected before exiting (the driver's `timeout` sends SIGTERM first);
- an internal wall budget (RAY_TPU_BENCH_BUDGET_S, default 1320s) gates
  every section — sections that don't fit are stamped "skipped", and the
  final line always lands before any external timeout;
- subprocess sections run in their own process GROUP and are killed with
  killpg on timeout (subprocess.run's timeout= kills only the direct
  child; r4 leaked a whole `start --head --block` cluster that starved
  the next section into GetTimeoutError);
- a preflight sweep kills ray_tpu daemons leaked by PRIOR runs (matching
  the reference's release-suite "always start from a clean node").
"""

import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np

# Reference numbers from BASELINE.md (release 2.44.0, 64-CPU instance).
BASELINE = {
    "single_client_tasks_sync": 969.8,
    "single_client_tasks_async": 7931.9,
    "multi_client_tasks_async": 23258.5,
    "1_1_actor_calls_sync": 1959.2,
    "1_1_actor_calls_async": 8173.7,
    "1_1_actor_calls_concurrent": 5130.6,
    "1_n_actor_calls_async": 8060.7,
    "n_n_actor_calls_async": 27209.7,
    "n_n_actor_calls_with_arg_async": 2693.5,
    "1_1_async_actor_calls_sync": 1426.2,
    "1_1_async_actor_calls_async": 4284.4,
    "n_n_async_actor_calls_async": 23555.1,
    "single_client_get_calls": 10529.2,
    "single_client_put_calls": 4968.8,
    "multi_client_put_calls": 16759.6,
    "single_client_put_gigabytes": 17.80,
    "multi_client_put_gigabytes": 40.39,
    "single_client_get_object_containing_10k_refs": 12.32,
    "single_client_wait_1k_refs": 5.01,
    "placement_group_create_removal": 743.6,
    "client_get_calls": 992.4,
    "client_put_calls": 824.2,
    # Reference release/benchmarks many_nodes.json: 215 tasks/s across the
    # cluster. Ours runs emulated node agents on ONE machine (the
    # reference used real nodes) — the comparison still gates regression.
    "many_nodes_tasks_s": 215.0,
}

PARALLEL = {"multi_client_tasks_async", "n_n_actor_calls_async",
            "n_n_async_actor_calls_async", "multi_client_put_calls",
            "multi_client_put_gigabytes"}

_T0 = time.monotonic()
_BUDGET = float(os.environ.get("RAY_TPU_BENCH_BUDGET_S", "1320"))
_REPO = os.path.dirname(os.path.abspath(__file__))

RESULTS: dict[str, float] = {}
SKIPPED: list[str] = []
EXTRAS: dict = {}
_FINAL_PRINTED = False


def _remaining() -> float:
    return _BUDGET - (time.monotonic() - _T0)


def emit(name: str, value: float):
    """Record a metric and stream it to stderr immediately (JSONL), so a
    killed bench still leaves per-metric evidence (r4 weak #7)."""
    RESULTS[name] = value
    base = BASELINE.get(name)
    line = {"partial": name, "value": round(value, 2),
            "t": round(time.monotonic() - _T0, 1)}
    if base:
        line["vs_ref"] = round(value / base, 3)
    print(json.dumps(line), file=sys.stderr, flush=True)


def _gm(rs):
    return math.exp(sum(math.log(x) for x in rs) / len(rs)) if rs else 0.0


def final_line(status: str = "complete"):
    """The ONE stdout JSON line, guaranteed parseable from a tail window.

    r5/r4 postmortem: the old final line carried the full 22-metric detail
    + TPU config dump and overflowed the driver's stdout tail, so the
    headline parsed as null two rounds running. Now the FULL results JSON
    is persisted to the BENCH_OUT file and the final stdout line is a
    short (<1 KB) headline: geomean, the split geomeans, the contended
    top metrics, and a pointer to the detail file."""
    global _FINAL_PRINTED
    if _FINAL_PRINTED:
        return
    _FINAL_PRINTED = True
    ratios, single_r, par_r, missing = [], [], [], []
    for key, base in BASELINE.items():
        ours = RESULTS.get(key, 0.0)
        if ours <= 0:
            missing.append(key)
            continue
        r = ours / base
        ratios.append(r)
        (par_r if key in PARALLEL else single_r).append(r)
    geomean = _gm(ratios)
    detail_path = os.environ.get(
        "BENCH_OUT", os.path.join(_REPO, "bench_out.json"))
    full = {
        "metric": "core_microbenchmark_geomean_vs_ray",
        "value": round(geomean, 3),
        "unit": f"x (geomean of {len(ratios)}/{len(BASELINE)} metrics "
                "vs Ray 2.44 on 64-CPU)",
        "vs_baseline": round(geomean, 3),
        "single_client_geomean": round(_gm(single_r), 3),
        "parallel_geomean": round(_gm(par_r), 3),
        "status": status,
        "wall_s": round(time.monotonic() - _T0, 1),
        "host": EXTRAS.get("host", {}),
        "many_nodes_scaling": EXTRAS.get("many_nodes_scaling", {}),
        "native_head_ab": EXTRAS.get("native_head_ab", {}),
        "cluster_scale": EXTRAS.get("cluster_scale", {}),
        "adag_pipeline": EXTRAS.get("adag_pipeline", {}),
        "data_pipeline": EXTRAS.get("data_pipeline", {}),
        "task_events": EXTRAS.get("task_events", {}),
        "cross_language": EXTRAS.get("cross_language", {}),
        "chaos_storm": EXTRAS.get("chaos_storm", {}),
        "elastic_train": EXTRAS.get("elastic_train", {}),
        "multi_tenant": EXTRAS.get("multi_tenant", {}),
        "serve_storm": EXTRAS.get("serve_storm", {}),
        "detail": {k: round(v, 1) for k, v in RESULTS.items()},
    }
    if missing:
        full["missing_metrics"] = missing
    if SKIPPED:
        full["skipped_sections"] = SKIPPED
    try:
        with open(detail_path, "w") as f:
            json.dump(full, f, indent=1)
        wrote_detail = True
    except OSError:
        wrote_detail = False
    headline = {
        "metric": full["metric"],
        "value": full["value"],
        "unit": "x vs Ray 2.44 (64-CPU baseline numbers)",
        "vs_baseline": full["vs_baseline"],
        "single_client_geomean": full["single_client_geomean"],
        "parallel_geomean": full["parallel_geomean"],
        "status": status,
        "wall_s": full["wall_s"],
        "n_metrics": len(ratios),
        "n_missing": len(missing),
        "n_skipped": len(SKIPPED),
        # The two data-plane gap rows (ROADMAP item 2): per-row ratio vs
        # ref right in the headline so the trajectory reads without
        # opening BENCH_OUT.
        "mc_put_x": (round(RESULTS["multi_client_put_gigabytes"]
                           / BASELINE["multi_client_put_gigabytes"], 3)
                     if RESULTS.get("multi_client_put_gigabytes")
                     else None),
        "nn_async_x": (round(RESULTS["n_n_async_actor_calls_async"]
                             / BASELINE["n_n_async_actor_calls_async"], 3)
                       if RESULTS.get("n_n_async_actor_calls_async")
                       else None),
        "adag_x": EXTRAS.get("adag_pipeline", {}).get("tensor_speedup_x"),
        # Data plane: arrow-native block hop speedup vs the pickle path
        # (the >=64MB map/iter A/B; full pipeline numbers in BENCH_OUT).
        "data_x": EXTRAS.get("data_pipeline", {}).get("arrow_speedup_x"),
        # Robustness headline: storm throughput as a fraction of the
        # clean run under the fixed-seed 1% fault schedule.
        "chaos_x": EXTRAS.get("chaos_storm", {}).get("chaos_x"),
        # Elastic train plane: seconds from mid-run worker SIGKILL to the
        # first post-restart report, and the bit-stability verdict of the
        # resumed loss trajectory (True = committed-manifest resume
        # restored exactly the pre-death state).
        "train_rec_s": EXTRAS.get("elastic_train", {}).get("recovery_s"),
        "train_bit": EXTRAS.get("elastic_train", {}).get("bit_stable"),
        # Disaggregated serving plane: the open-loop storm's latency
        # headline, the dense-vs-disagg p99 ratio, the mid-storm-kill
        # p99, and the zero-admitted-drops verdict (must be 0).
        "serve_p50_ms": EXTRAS.get("serve_storm", {}).get(
            "disagg", {}).get("p50_ms"),
        "serve_p99_ms": EXTRAS.get("serve_storm", {}).get(
            "disagg", {}).get("p99_ms"),
        "serve_dvd_x": EXTRAS.get("serve_storm", {}).get(
            "dense_vs_disagg_p99_x"),
        "serve_kill_p99_ms": EXTRAS.get("serve_storm", {}).get(
            "disagg_kill", {}).get("p99_ms"),
        "serve_drop": EXTRAS.get("serve_storm", {}).get(
            "disagg_kill", {}).get("dropped"),
        # Native head core (PR 14): best-of tasks-per-head-CPU-second
        # with the head core ON from the counterbalanced A/B — the
        # acceptance metric's headline copy (full samples in BENCH_OUT).
        "tphc_s": EXTRAS.get("native_head_ab", {}).get(
            "best", {}).get("on", {}).get("tasks_per_head_cpu_s"),
        # Control-plane scale-out (head shards): sharded-vs-single rates
        # at 256 emulated agents + the sharded view-fanout p95 (full
        # 64/256 curve in BENCH_OUT cluster_scale).
        "cscale": {
            "sh256_ts": EXTRAS.get("cluster_scale", {}).get(
                "curve", {}).get(256, {}).get("sharded", {}).get("tasks_s"),
            "sg256_ts": EXTRAS.get("cluster_scale", {}).get(
                "curve", {}).get(256, {}).get("single", {}).get("tasks_s"),
            "fan_p95_ms": EXTRAS.get("cluster_scale", {}).get(
                "curve", {}).get(256, {}).get("sharded", {}).get(
                    "fanout_p95_ms"),
            "cpu_sublin": EXTRAS.get("cluster_scale", {}).get(
                "head_cpu_sublinear"),
        } if EXTRAS.get("cluster_scale") else None,
        "tev_ovh_pct": EXTRAS.get("task_events", {}).get("overhead_pct"),
        "xlang_s": EXTRAS.get("cross_language", {}).get(
            "cpp_tasks_async_s"),
        "host": {k: EXTRAS.get("host", {}).get(k)
                 for k in ("cpu_count", "memcpy_gbps")},
        "top": {k: round(RESULTS[k], 1) for k in (
            "multi_client_put_gigabytes", "n_n_actor_calls_with_arg_async",
            "multi_client_tasks_async", "single_client_put_gigabytes",
            "single_client_tasks_async") if k in RESULTS},
        "detail_file": detail_path if wrote_detail else None,
    }
    line = json.dumps(headline)
    if len(line) > 1024:  # soft cap: trim optional fields first
        for key in ("top", "detail_file", "unit"):
            headline.pop(key, None)
            line = json.dumps(headline)
            if len(line) <= 1024:
                break
    # Hard invariant (r4/r5 postmortem: two rounds of parsed:null from an
    # overflowing final line): the headline must fit the driver's tail
    # window, full stop. An assert here would EAT the headline on the
    # oversize path — trim to the irreducible core instead of dying.
    if len(line) >= 2048:
        for key in ("host", "xlang_s", "tev_ovh_pct",
                    "adag_x", "data_x", "chaos_x", "train_bit",
                    "train_rec_s",
                    "serve_p50_ms", "serve_dvd_x", "serve_kill_p99_ms",
                    "serve_p99_ms", "serve_drop", "cscale",
                    "n_skipped", "n_missing",
                    "n_metrics", "wall_s", "status", "mc_put_x",
                    "nn_async_x"):
            headline.pop(key, None)
            line = json.dumps(headline)
            if len(line) < 2048:
                break
    if len(line) >= 2048:
        line = json.dumps({
            "metric": "core_microbenchmark_geomean_vs_ray",
            "value": round(geomean, 3),
            "vs_baseline": round(geomean, 3),
            "status": str(status)[:80]})
    print(line, flush=True)


def _on_term(signum, _frame):
    print(json.dumps({"partial": "_signal", "signum": signum}),
          file=sys.stderr, flush=True)
    final_line(status=f"interrupted by signal {signum}")
    sys.stdout.flush()
    # No clean shutdown on the way out (it can hang) — sweep our own
    # workers/agents the same way preflight sweeps a prior run's
    # (respects RAY_TPU_BENCH_NO_PREFLIGHT: an operator shielding a live
    # cluster shields it from the exit sweep too).
    try:
        preflight_kill_stale()
    except Exception:
        pass
    os._exit(0)


class SectionTimeout(Exception):
    """Raised in the main thread by the per-section SIGALRM watchdog."""


_ACTIVE_SUB: list = []  # Popen of the in-flight run_sub, for the watchdog


def _on_alarm(_signum, _frame):
    # Kill an in-flight subprocess group FIRST: the exception may unwind
    # past run_sub's own cleanup (r04's leaked `start --head --block`
    # cluster starved every later section).
    for p in _ACTIVE_SUB:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            pass
    raise SectionTimeout()


def run_sub(code: str, timeout: float, tag: str) -> str:
    """Run python -c CODE in its OWN process group; on timeout kill the
    whole group (grandchildren included) — never leak a cluster."""
    env = {**os.environ,
           "PYTHONPATH": _REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, start_new_session=True, env=env)
    _ACTIVE_SUB.append(p)
    try:
        out, err = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            pass
        p.communicate()
        raise TimeoutError(f"{tag}: subprocess timed out after {timeout}s")
    finally:
        try:
            _ACTIVE_SUB.remove(p)
        except ValueError:
            pass
    if p.returncode != 0:
        raise RuntimeError(
            f"{tag}: rc={p.returncode}: {err.strip()[-300:]}")
    return out


def preflight_kill_stale() -> list[int]:
    """Kill ray_tpu daemons leaked by prior runs (r4's root cause: an
    orphaned `start --head --block` cluster from hours earlier starved a
    1-CPU box into nop-task GetTimeouts). Matches by /proc cmdline with
    self+ancestors excluded — pkill patterns would match our own wrapper."""
    if os.environ.get("RAY_TPU_BENCH_NO_PREFLIGHT"):
        return []
    keep = {os.getpid()}
    p = os.getpid()
    while p > 1:
        try:
            with open(f"/proc/{p}/stat") as f:
                p = int(f.read().rsplit(")", 1)[1].split()[1])
            keep.add(p)
        except (OSError, ValueError, IndexError):
            break
    killed = []
    markers = ("ray_tpu.core.worker", "ray_tpu.core.node_agent",
               "ray_tpu start", "-m ray_tpu", "ray_tpu.util.many_agents")
    try:
        pids = [int(s) for s in os.listdir("/proc") if s.isdigit()]
    except OSError:
        return []
    for pid in pids:
        if pid in keep:
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode("utf-8", "replace").replace("\0", " ")
        except OSError:
            continue
        if "python" in cmd and any(m in cmd for m in markers):
            try:
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)
            except OSError:
                pass
    if killed:
        print(json.dumps({"partial": "_preflight_killed", "pids": killed}),
              file=sys.stderr, flush=True)
        time.sleep(0.5)
    return killed


def timeit(fn, number, trials=2, warm=None) -> float:
    """Warm run, then the mean of timed trials — the reference's
    microbenchmark does the same (ray_microbenchmark_helpers.py:15: 1s
    warmup, mean of four 2s windows), so cold-start transitions between
    phases don't land on any one metric. `warm` overrides the default
    10% warm pass: dispatch-storm metrics need ~1s of sustained load
    before the allocator/branch caches settle (measured: trial rates
    climb 6.3k -> 8.4k over the first ~20k nop tasks on the 1-CPU box)."""
    fn(max(1, warm if warm is not None else number // 10))  # warmup
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn(number)
        rates.append(number / (time.perf_counter() - t0))
    return sum(rates) / len(rates)


def main():
    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    signal.signal(signal.SIGALRM, _on_alarm)
    try:
        _main_inner()
    except BaseException as e:  # noqa: BLE001 — the headline MUST land
        # r05 postmortem: any escape path that skips final_line leaves
        # the driver parsing null. Crashes stamp a degraded headline.
        print(json.dumps({"partial": "_crash",
                          "error": f"{type(e).__name__}: {str(e)[:200]}"}),
              file=sys.stderr, flush=True)
        final_line(status=f"degraded: {type(e).__name__}: {str(e)[:100]}")


def _main_inner():
    preflight_kill_stale()

    import ray_tpu
    from ray_tpu.core.session import gc_stale_sessions
    gc_stale_sessions()

    ncpu = os.cpu_count() or 1
    EXTRAS["host"] = {"cpu_count": ncpu,
                      "memcpy_gbps": _memcpy_ceiling_gbps()}
    # 4GB arena: large puts recycle warm pages instead of faulting fresh ones.
    rt = ray_tpu.init(num_cpus=max(4, ncpu), object_store_memory=4 << 30,
                      resources={"custom": 100})

    @ray_tpu.remote
    def nop():
        pass

    @ray_tpu.remote
    def do_put_small(n):
        for _ in range(n):
            ray_tpu.put(0)

    @ray_tpu.remote
    def do_put_large(n):
        # One source buffer, reused across puts — the reference's
        # ray_perf.py puts the SAME array repeatedly; allocating a fresh
        # 80MB np.zeros per put measures mmap/fault cost, not the store
        # (measured: 2.4 vs 8.8 GB/s single-worker).
        buf = np.zeros(10 * (1 << 20), dtype=np.int64)  # 80 MB
        for _ in range(n):
            ray_tpu.put(buf)

    @ray_tpu.remote
    def make_10k_refs():
        return [ray_tpu.put(1) for _ in range(10000)]

    @ray_tpu.remote(num_cpus=0)
    class Submitter:
        def batch(self, n):
            ray_tpu.get([nop.remote() for _ in range(n)], timeout=120)

    @ray_tpu.remote(num_cpus=0)
    class Sink:
        def ping(self):
            pass

        def ping_arg(self, x):
            pass

        def batch(self, others, n, with_arg=False):
            if with_arg:
                x = ray_tpu.put(0)
                refs = [o.ping_arg.remote(x) for o in others
                        for _ in range(n)]
            else:
                refs = [o.ping.remote() for o in others for _ in range(n)]
            ray_tpu.get(refs, timeout=300)

    @ray_tpu.remote(num_cpus=0)
    class AsyncSink:
        async def ping(self):
            pass

        async def batch(self, others, n):
            refs = [o.ping.remote() for o in others for _ in range(n)]
            ray_tpu.get(refs, timeout=300)

    m = min(4, max(2, ncpu // 2))
    k = min(4, max(2, ncpu // 2))

    def sec_tasks():
        ray_tpu.get(nop.remote(), timeout=60)  # warm the pool

        def tasks_sync(n):
            for _ in range(n):
                ray_tpu.get(nop.remote(), timeout=60)

        emit("single_client_tasks_sync", timeit(tasks_sync, 2000))

        def tasks_async(n):
            ray_tpu.get([nop.remote() for _ in range(n)], timeout=120)

        emit("single_client_tasks_async", timeit(tasks_async, 10000,
                                             warm=8000))

        # multi client: m actors each submitting n nested tasks
        # (ray_perf.py "multi client tasks async").
        submitters = [Submitter.remote() for _ in range(m)]
        ray_tpu.get([s.batch.remote(1) for s in submitters], timeout=60)

        def multi_tasks(total):
            per = total // m
            ray_tpu.get([s.batch.remote(per) for s in submitters],
                        timeout=300)

        emit("multi_client_tasks_async", timeit(multi_tasks, 4000 * m))

    def sec_actors():
        a = Sink.remote()
        ray_tpu.get(a.ping.remote(), timeout=60)

        def actor_sync(n):
            for _ in range(n):
                ray_tpu.get(a.ping.remote(), timeout=60)

        emit("1_1_actor_calls_sync", timeit(actor_sync, 2000))

        def actor_async(n):
            ray_tpu.get([a.ping.remote() for _ in range(n)], timeout=120)

        emit("1_1_actor_calls_async", timeit(actor_async, 10000,
                                         warm=6000))

        ac = Sink.options(max_concurrency=16).remote()
        ray_tpu.get(ac.ping.remote(), timeout=60)

        def actor_concurrent(n):
            ray_tpu.get([ac.ping.remote() for _ in range(n)], timeout=120)

        emit("1_1_actor_calls_concurrent", timeit(actor_concurrent, 5000))

        # 1:n — one fan-out client actor driving k sink actors.
        sinks = [Sink.remote() for _ in range(k)]
        fan = Sink.remote()
        ray_tpu.get([s.ping.remote() for s in sinks] + [fan.ping.remote()],
                    timeout=60)

        def one_n(total):
            ray_tpu.get(fan.batch.remote(sinks, total // k), timeout=300)

        emit("1_n_actor_calls_async", timeit(one_n, 2000 * k,
                                             warm=4000))

        # n:n — m worker tasks each fanning to the k sinks.
        def n_n(total):
            per = total // (m * k)
            fans = [Sink.remote() for _ in range(m)]
            ray_tpu.get([f.ping.remote() for f in fans], timeout=60)
            ray_tpu.get([f.batch.remote(sinks, per) for f in fans],
                        timeout=300)

        emit("n_n_actor_calls_async", timeit(n_n, 10000))

        def n_n_arg(total):
            per = total // (m * k)
            fans = [Sink.remote() for _ in range(m)]
            ray_tpu.get([f.ping.remote() for f in fans], timeout=60)
            ray_tpu.get([f.batch.remote(sinks, per, True) for f in fans],
                        timeout=300)

        emit("n_n_actor_calls_with_arg_async", timeit(n_n_arg, 4000))

        aa = AsyncSink.remote()
        ray_tpu.get(aa.ping.remote(), timeout=60)

        def async_actor_sync(n):
            for _ in range(n):
                ray_tpu.get(aa.ping.remote(), timeout=60)

        emit("1_1_async_actor_calls_sync", timeit(async_actor_sync, 1000))

        def async_actor_async(n):
            ray_tpu.get([aa.ping.remote() for _ in range(n)], timeout=120)

        emit("1_1_async_actor_calls_async",
             timeit(async_actor_async, 5000))

        def n_n_async(total):
            asinks = [AsyncSink.remote() for _ in range(k)]
            fans = [Sink.remote() for _ in range(m)]
            ray_tpu.get([f.ping.remote() for f in fans]
                        + [s.ping.remote() for s in asinks], timeout=60)
            per = total // (m * k)
            ray_tpu.get([f.batch.remote(asinks, per) for f in fans],
                        timeout=300)

        emit("n_n_async_actor_calls_async", timeit(n_n_async, 10000))

    def sec_objects():
        small = np.zeros(1024, dtype=np.uint8)

        def put_calls(n):
            for _ in range(n):
                ray_tpu.put(small)

        emit("single_client_put_calls", timeit(put_calls, 10000))

        ref = ray_tpu.put(small)

        def get_calls(n):
            for _ in range(n):
                ray_tpu.get(ref, timeout=60)

        emit("single_client_get_calls", timeit(get_calls, 10000))

        def multi_put_calls(total):
            per = total // 10
            ray_tpu.get([do_put_small.remote(per) for _ in range(10)],
                        timeout=120)

        emit("multi_client_put_calls", timeit(multi_put_calls, 10000))

        gb = np.zeros(1 << 30, dtype=np.uint8)

        def put_gb(n):
            for _ in range(n):
                ray_tpu.put(gb)

        put_gb(3)  # fault in + warm the arena pages
        emit("single_client_put_gigabytes", timeit(put_gb, 8))
        del gb

        def multi_put_gb(n_gb):
            # 10 workers x n puts of 80MB
            per = max(1, int(n_gb * (1 << 30) / (10 * 80 * (1 << 20))))
            ray_tpu.get([do_put_large.remote(per) for _ in range(10)],
                        timeout=300)

        multi_put_gb(1)
        emit("multi_client_put_gigabytes", timeit(multi_put_gb, 8))

        refs_obj = make_10k_refs.remote()
        ray_tpu.wait([refs_obj], timeout=120)

        def get_10k_refs(n):
            for _ in range(n):
                ray_tpu.get(refs_obj, timeout=120)

        emit("single_client_get_object_containing_10k_refs",
             timeit(get_10k_refs, 20))

        def wait_1k_refs(n):
            for _ in range(n):
                not_ready = [nop.remote() for _ in range(1000)]
                while not_ready:
                    _ready, not_ready = ray_tpu.wait(not_ready, timeout=60)

        emit("single_client_wait_1k_refs", timeit(wait_1k_refs, 10))

    def sec_adag():
        # Compiled-graph channel plane: a 3-stage pipeline moving a 64MB
        # activation per execute (4 hops: driver->s1->s2->s3->driver),
        # pickle channels vs the zero-copy tensor channels. Per-hop µs
        # lands in the BENCH_OUT sidecar (acceptance: tensor plane >=5x
        # cheaper per hop); the headline only carries the speedup.
        from ray_tpu.dag import InputNode

        @ray_tpu.remote(num_cpus=0)
        class PipeStage:
            def step(self, x):
                return x

        act = np.zeros(16 << 20, dtype=np.float32)  # 64 MB
        hops = 4
        per_hop_us = {}
        for ctype in ("pickle", "tensor"):
            stages = [PipeStage.remote() for _ in range(3)]
            with InputNode() as inp:
                dag = inp
                for s in stages:
                    dag = s.step.bind(dag)
            compiled = dag.experimental_compile(
                buffer_size_bytes=96 << 20, channel_type=ctype)
            try:
                compiled.execute(act).get(timeout=120)  # warm
                n = 8
                t0 = time.perf_counter()
                for _ in range(n):
                    compiled.execute(act).get(timeout=120)
                dt = time.perf_counter() - t0
            finally:
                compiled.teardown()
            per_hop_us[ctype] = dt / (n * hops) * 1e6
            emit(f"adag_pipeline_{ctype}_per_hop_us", per_hop_us[ctype])
        EXTRAS["adag_pipeline"] = {
            "activation_mb": act.nbytes >> 20, "stages": 3,
            "hops_per_execute": hops,
            "pickle_per_hop_us": round(per_hop_us["pickle"], 1),
            "tensor_per_hop_us": round(per_hop_us["tensor"], 1),
            "tensor_speedup_x": round(
                per_hop_us["pickle"] / per_hop_us["tensor"], 2)}

    def sec_data_pipeline():
        # Data plane (PR 15): (a) the adag-style A/B — a >=64MB Arrow
        # block through one map hop (submit -> worker reads the block ->
        # returns it -> driver reads the result), arrow-native arena
        # blocks vs the pickle path (RAY_TPU_DATA_BLOCK_ARROW=0), each in
        # its own fresh cluster (cold-vs-cold); (b) pipeline throughput:
        # synthetic read -> map_batches -> random_shuffle -> iter_batches
        # rows/s + GB/s on the default (arrow) path.
        code = r"""
import json, time
import numpy as np
import pyarrow as pa
import ray_tpu
from ray_tpu import data as rd

rt = ray_tpu.init(num_cpus=4, object_store_memory=4 << 30)

NROW = 8 << 20  # 8M rows x 8B = 64MB block
t = pa.table({"x": pa.array(np.arange(NROW, dtype=np.int64))})

@ray_tpu.remote
def ident(block):
    return block

ref = ray_tpu.put(t)

def hop():
    got = ray_tpu.get(ident.remote(ref), timeout=120)
    assert got.num_rows == NROW
    del got

# Warm to steady state: the first hops fault fresh reservation-extent
# pages (hundreds of ms of page population BOTH paths pay identically);
# after frees land, owner-affine extents recycle pid-warm ranges and the
# hop settles. The settle sleeps let async frees land so the allocator
# can recycle — they sit OUTSIDE the timed window on both paths.
for _ in range(8):
    hop()
    time.sleep(0.25)
n = 6
hop_s = 0.0
for _ in range(n):
    t0 = time.perf_counter()
    hop()
    hop_s += time.perf_counter() - t0
    time.sleep(0.25)
hop_ms = hop_s / n * 1e3

NR, NB = 4 << 20, 8  # 8 blocks; 16B/row after the map = 64MB total
ds = rd.range(NR, override_num_blocks=NB)
ds = ds.map_batches(lambda b: {"id": b["id"], "v": b["id"] * 2})
t0 = time.perf_counter()
rows = 0
for batch in ds.random_shuffle(seed=5).iter_batches(batch_size=65536):
    rows += len(batch["id"])
wall = time.perf_counter() - t0
assert rows == NR
print("DATA_RES", json.dumps(
    {"hop_ms": round(hop_ms, 2), "rows_s": round(rows / wall, 1),
     "gb_s": round(rows * 16 / wall / 1e9, 3)}))
ray_tpu.shutdown()
"""
        out_a = run_sub(code, timeout=min(200, max(90, _remaining() - 30)),
                        tag="data_arrow")
        arrow = json.loads([ln for ln in out_a.splitlines()
                            if ln.startswith("DATA_RES")][0][9:])
        os.environ["RAY_TPU_DATA_BLOCK_ARROW"] = "0"
        try:
            out_p = run_sub(code,
                            timeout=min(200, max(90, _remaining() - 30)),
                            tag="data_pickle")
        finally:
            os.environ.pop("RAY_TPU_DATA_BLOCK_ARROW", None)
        pickle_r = json.loads([ln for ln in out_p.splitlines()
                               if ln.startswith("DATA_RES")][0][9:])
        emit("data_pipeline_rows_s", arrow["rows_s"])
        emit("data_block_hop_ms", arrow["hop_ms"])
        EXTRAS["data_pipeline"] = {
            "block_mb": 64, "hop": "map task + driver read",
            "arrow_hop_ms": arrow["hop_ms"],
            "pickle_hop_ms": pickle_r["hop_ms"],
            "arrow_speedup_x": round(
                pickle_r["hop_ms"] / max(arrow["hop_ms"], 1e-9), 2),
            "pipeline": "read->map_batches->random_shuffle->iter_batches",
            "arrow_rows_s": arrow["rows_s"], "arrow_gb_s": arrow["gb_s"],
            "pickle_rows_s": pickle_r["rows_s"],
            "pickle_gb_s": pickle_r["gb_s"],
        }

    def sec_pg():
        # Comparability fix (r5 verdict: the single-node PG churn skipped
        # the whole reservation plane and inflated the vs-Ray geomean
        # ~+20% at 48.6x): churn placement groups against a 2-agent
        # Cluster whose agents exclusively hold the bundled resource, so
        # every bundle reserves on a REAL agent node — the same
        # multi-node path the reference's 743.6/s measures. Runs in a
        # subprocess (own process group) like the other cluster sections.
        code = (
            "import time\n"
            "import ray_tpu\n"
            "from ray_tpu.cluster_utils import Cluster\n"
            "from ray_tpu.util.placement_group import (placement_group,\n"
            "                                          remove_placement_group)\n"
            "c = Cluster(initialize_head=True,\n"
            "            head_node_args={'num_cpus': 2,\n"
            "                            'object_store_memory': 64 << 20})\n"
            "c.add_node(num_cpus=1, resources={'custom': 100},\n"
            "           object_store_memory=32 << 20)\n"
            "c.add_node(num_cpus=1, resources={'custom': 100},\n"
            "           object_store_memory=32 << 20)\n"
            "c.wait_for_nodes(3)\n"
            "def churn(n):\n"
            "    pgs = [placement_group([{'custom': 0.001}])\n"
            "           for _ in range(n)]\n"
            "    for pg in pgs:\n"
            "        pg.wait(timeout_seconds=30)\n"
            "    for pg in pgs:\n"
            "        remove_placement_group(pg)\n"
            "churn(20)\n"
            "rates = []\n"
            "for _ in range(2):\n"
            "    t0 = time.perf_counter()\n"
            "    churn(200)\n"
            "    rates.append(200 / (time.perf_counter() - t0))\n"
            "print('RATE', sum(rates) / len(rates))\n"
            "c.shutdown()\n")
        out = run_sub(code, timeout=min(150, max(60, _remaining() - 30)),
                      tag="pg")
        line = [ln for ln in out.splitlines() if ln.startswith("RATE")][0]
        emit("placement_group_create_removal", float(line.split()[1]))

    def sec_task_events():
        # Task-event pipeline overhead: the identical no-op task storm
        # with the pipeline on (default) vs off. Acceptance gate: <5%.
        # Measured as the MEDIAN of counterbalanced ABBA pairs inside ONE
        # cluster (the ring toggles at runtime in head + workers): this
        # box's storm rate drifts +-15% over minutes and whichever mode
        # runs second in a pair inherits the cluster's drift, so naive
        # A-then-B cluster pairs read drift as overhead — ABBA ordering
        # cancels the position bias and the median rejects the outlier
        # pairs a 1-CPU box throws.
        code = (
            "import os, time, statistics\n"
            "os.environ['RAY_TPU_TASK_EVENTS'] = '1'\n"
            "import ray_tpu\n"
            "from ray_tpu.core import task_events\n"
            "ray_tpu.init(num_cpus=4, object_store_memory=256 << 20)\n"
            "@ray_tpu.remote\n"
            "def nop():\n"
            "    pass\n"
            "@ray_tpu.remote\n"
            "def set_tev(on):\n"
            "    import time as _t\n"
            "    from ray_tpu.core import task_events as te\n"
            "    te.ring().enabled = bool(on)\n"
            "    _t.sleep(0.15)\n"
            "    return True\n"
            "def toggle(on):\n"
            "    task_events.ring().enabled = bool(on)\n"
            "    ray_tpu.get([set_tev.remote(on) for _ in range(8)],\n"
            "                timeout=60)\n"
            "def storm(n):\n"
            "    ray_tpu.get([nop.remote() for _ in range(n)],\n"
            "                timeout=120)\n"
            "def rate(n=2000):\n"
            "    t0 = time.perf_counter()\n"
            "    storm(n)\n"
            "    return n / (time.perf_counter() - t0)\n"
            "storm(2000)\n"
            "ratios, rs = [], {'on': [], 'off': []}\n"
            "for i in range(8):\n"
            "    first = i % 2 == 0  # ABBA: alternate which mode leads\n"
            "    toggle(first); storm(300); r1 = rate()\n"
            "    toggle(not first); storm(300); r2 = rate()\n"
            "    r_on, r_off = (r1, r2) if first else (r2, r1)\n"
            "    rs['on'].append(r_on); rs['off'].append(r_off)\n"
            "    ratios.append(r_off / r_on)\n"
            "print('RES', statistics.median(ratios),\n"
            "      statistics.median(rs['on']),\n"
            "      statistics.median(rs['off']))\n")
        out = run_sub(code, timeout=min(240, max(90, _remaining() - 30)),
                      tag="task_events")
        line = [ln for ln in out.splitlines() if ln.startswith("RES")][0]
        _, ratio, r_on, r_off = line.split()
        emit("task_events_storm_on", float(r_on))
        emit("task_events_storm_off", float(r_off))
        overhead_pct = round(100.0 * (float(ratio) - 1.0), 2)
        EXTRAS["task_events"] = {
            "on_tasks_s": round(float(r_on), 1),
            "off_tasks_s": round(float(r_off), 1),
            "overhead_pct": overhead_pct,
            "method": "median of 8 counterbalanced ABBA toggle pairs, "
                      "one cluster",
        }

    def sec_cross_language():
        # Cross-language worker plane: trivial-task round-trip latency +
        # throughput on a C++ worker vs the Python pool in the SAME
        # cluster (an emulated agent node advertises CPP and spawns
        # cpp/raytpu_worker.cc on demand). Full numbers live in BENCH_OUT
        # under "cross_language"; the headline stays under its byte cap.
        from ray_tpu.cluster_utils import Cluster
        cluster = Cluster(initialize_head=False)
        node = cluster.add_node(num_cpus=2)
        try:
            cpp_nop = ray_tpu.cpp_function("rt.noop")
            ray_tpu.get(cpp_nop.remote(), timeout=180)  # build+spawn warm

            def cpp_sync(n):
                for _ in range(n):
                    ray_tpu.get(cpp_nop.remote(), timeout=60)

            cpp_sync_rate = timeit(cpp_sync, 1000)
            emit("cross_language_tasks_sync", cpp_sync_rate)

            def cpp_async(n):
                ray_tpu.get([cpp_nop.remote() for _ in range(n)],
                            timeout=120)

            cpp_async_rate = timeit(cpp_async, 4000, warm=2000)
            emit("cross_language_tasks_async", cpp_async_rate)
            # Python comparators measured earlier in sec_tasks on this
            # same host (nop through the Python worker pool).
            py_sync = RESULTS.get("single_client_tasks_sync", 0.0)
            py_async = RESULTS.get("single_client_tasks_async", 0.0)
            EXTRAS["cross_language"] = {
                "cpp_tasks_sync_s": round(cpp_sync_rate, 1),
                "cpp_tasks_async_s": round(cpp_async_rate, 1),
                "cpp_rtt_ms": round(1e3 / cpp_sync_rate, 3)
                if cpp_sync_rate else None,
                "py_tasks_sync_s": round(py_sync, 1),
                "py_tasks_async_s": round(py_async, 1),
                "cpp_vs_py_async_x": round(cpp_async_rate / py_async, 3)
                if py_async else None,
            }
        finally:
            cluster.remove_node(node)

    def sec_client():
        # Client mode (remote driver over the cluster socket): a
        # subprocess connects via address and hammers get/put (parity:
        # ray_client_microbenchmark.py).
        addr = rt.enable_cluster()
        code = (
            "import os, sys, time\n"
            "import ray_tpu\n"
            "ray_tpu.init(address=%r)\n"
            "n = 2000\n"
            "refs = [ray_tpu.put(i) for i in range(n)]\n"
            "t0 = time.perf_counter()\n"
            "for r in refs: ray_tpu.get(r, timeout=30)\n"  # distinct refs:
            "g = n / (time.perf_counter() - t0)\n"          # every get RPCs
            "t0 = time.perf_counter()\n"
            "for _ in range(n): ray_tpu.put(0)\n"
            "p = n / (time.perf_counter() - t0)\n"
            "print('RATES', g, p)\n" % addr)
        out = run_sub(code, timeout=min(180, max(60, _remaining() - 30)),
                      tag="client")
        line = [ln for ln in out.splitlines() if ln.startswith("RATES")][0]
        _, g, p = line.split()
        emit("client_get_calls", float(g))
        emit("client_put_calls", float(p))

    def sec_many_agents():
        # Many-agent scalability: ONE sized run (r4 ran 16/32/64 at 700s
        # timeout each — 2100s worst case that no driver budget fits; the
        # 16->64 scaling curve is recorded per-round in HEADPROF instead).
        # All agent processes share this machine's cores, so per-agent
        # rates fall with agent count by construction; the head scale-out
        # claim lives in HEADPROF_r05.md, this metric gates regression.
        n_agents = int(os.environ.get("RAY_TPU_BENCH_AGENTS", "16"))
        budget = min(420, max(120, _remaining() - 30))
        code = ("from ray_tpu.util.many_agents import run_many_agents\n"
                f"r = run_many_agents(n_agents={n_agents}, "
                f"n_tasks=1500, spawn_timeout={int(budget - 30)})\n"
                "print('RATE', r['rate'], r['nodes_used'],\n"
                "      r['head_cpu_s'], r['tasks_per_head_cpu_s'],\n"
                "      r['lease_spills'])\n")
        out = run_sub(code, timeout=budget, tag="many_agents")
        line = [ln for ln in out.splitlines() if ln.startswith("RATE")][0]
        _, rate, used, head_cpu, per_cpu, spills = line.split()
        EXTRAS["many_nodes_scaling"] = {
            n_agents: {"tasks_s": round(float(rate), 1),
                       "nodes_used": int(used),
                       # head-cost-per-task: the head is off the per-task
                       # critical path when this holds/grows as agents
                       # scale (the spillback acceptance criterion).
                       "head_cpu_s": float(head_cpu),
                       "tasks_per_head_cpu_s": float(per_cpu),
                       "lease_spills": int(spills)},
            "note": "one sized run; 16/32/64/128 curve in HEADPROF_r05.md",
        }
        emit("many_nodes_tasks_s", float(rate))

        # Native-HEAD A/B (sidecar only): the SAME workload with the C++
        # head core (PR 14) on vs off — native_sched (the agent half)
        # stays ON in both modes, so the delta isolates the head's
        # listener/ledger/grant port (the r07 A/B already isolated the
        # agent half). COUNTERBALANCED on-off-off-on (the PR 4 lesson:
        # naive A-then-B cluster pairs read machine drift as signal —
        # this box swings several-fold run to run under 33 processes),
        # best-of per mode reported alongside every sample.
        try:
            samples = {"on": [{"tasks_s": round(float(rate), 1),
                               "head_cpu_s": float(head_cpu),
                               "tasks_per_head_cpu_s": float(per_cpu)}],
                       "off": []}
            for mode in ("off", "off", "on"):
                ab_budget = min(180, max(90, _remaining() - 60))
                if ab_budget < 90:
                    break
                if mode == "off":
                    os.environ["RAY_TPU_NATIVE_HEAD"] = "0"
                try:
                    out_ab = run_sub(code, timeout=ab_budget,
                                     tag=f"many_agents_nhead_{mode}")
                finally:
                    os.environ.pop("RAY_TPU_NATIVE_HEAD", None)
                line = [ln for ln in out_ab.splitlines()
                        if ln.startswith("RATE")][0]
                _, r_s, _u, hc, pc, _sp = line.split()
                samples[mode].append(
                    {"tasks_s": round(float(r_s), 1),
                     "head_cpu_s": float(hc),
                     "tasks_per_head_cpu_s": float(pc)})
            best = {m: max(s, key=lambda r: r["tasks_s"])
                    for m, s in samples.items() if s}
            EXTRAS["native_head_ab"] = {
                "workload": f"run_many_agents(n_agents={n_agents}, "
                            "n_tasks=1500)",
                "order": "on off off on (counterbalanced)",
                "note": "native_sched ON in both modes; off = "
                        "RAY_TPU_NATIVE_HEAD=0 (pure-Python listener)",
                "best": best,
                "samples": samples,
            }
        except Exception as e:  # noqa: BLE001 — A/B is informational
            EXTRAS["native_head_ab"] = {"error": str(e)[:300],
                                        "samples": samples}

    def sec_cluster_scale():
        # Control-plane scale-out (head shards): the emulated-agent swarm
        # (util/agent_emu.py — protocol-complete agents over one selector,
        # no worker processes) pushes the head to 256 REGISTERED nodes on
        # one box, far past what OS-process agents afford. Sharded
        # (head_shards=2) vs single-head A/B at 64 and 256 agents,
        # COUNTERBALANCED across the two counts (sharded-first at 64,
        # sharded-last at 256 — the PR 4 lesson: naive A-then-B pairs
        # read machine drift as signal). view_spread_* is the cluster-view
        # fan-out latency: first->last agent arrival of one broadcast
        # version across the whole swarm.
        runs = ((64, 1200, (2, 0)), (256, 2000, (0, 2)))
        curve: dict = {}
        for n_agents, n_tasks, order in runs:
            for shards in order:
                budget = min(150, max(90, _remaining() - 30))
                code = (
                    "import json\n"
                    "from ray_tpu.util.many_agents import "
                    "run_emulated_storm\n"
                    f"r = run_emulated_storm(n_agents={n_agents}, "
                    f"n_tasks={n_tasks}, head_shards={shards})\n"
                    "print('CSCALE', json.dumps(r))\n")
                out = run_sub(code, timeout=budget,
                              tag=f"cscale_{n_agents}_{shards}")
                line = [ln for ln in out.splitlines()
                        if ln.startswith("CSCALE ")][0]
                r = json.loads(line[len("CSCALE "):])
                assert r["correct"] and r["exec_errors"] == 0, r
                mode = "sharded" if shards else "single"
                curve.setdefault(n_agents, {})[mode] = {
                    "tasks_s": r["rate"],
                    "agents_used": r["agents_used"],
                    "head_cpu_s": r["head_cpu_s"],
                    "tasks_per_head_cpu_s": r["tasks_per_head_cpu_s"],
                    "fanout_p50_ms": r["view_spread_p50_ms"],
                    "fanout_p95_ms": r["view_spread_p95_ms"],
                    "tev_shard": r["tev_shard"],
                    "tev_head": r["tev_head"],
                }
        EXTRAS["cluster_scale"] = {
            "workload": "run_emulated_storm (emulated protocol-complete "
                        "agents; real head, real tasks, real fan-out)",
            "order": "64: sharded,single; 256: single,sharded",
            "curve": curve,
            # Sublinear head CPU: head seconds per task must not grow
            # linearly with agent count (the scale-out acceptance gate).
            "head_cpu_sublinear": bool(
                curve.get(256, {}).get("sharded", {}).get(
                    "tasks_per_head_cpu_s", 0)
                > 0.25 * curve.get(64, {}).get("sharded", {}).get(
                    "tasks_per_head_cpu_s", 1e9)),
        }
        sh = curve.get(256, {}).get("sharded", {})
        if sh.get("tasks_s"):
            emit("cluster_scale_256_tasks_s", float(sh["tasks_s"]))

    def sec_chaos():
        # Chaos storm (core/chaos.py): the same retryable task storm run
        # under a seeded 1% fault schedule + a mid-storm worker SIGKILL.
        # r08 verdict (PR 15): an ARMED process intentionally drops the
        # native agent/head cores to per-frame Python sends (chaos
        # equivalence by construction, PRs 12/14), so comparing the storm
        # against an UNARMED clean run conflates the native-vs-python gap
        # with the fault tax — that artifact, not a recovery regression,
        # is what dropped chaos_x 1.11 -> 0.397/0.658 in r07/r08.
        # chaos_x now compares like with like: the denominator is a
        # CLEAN-ARMED run (schedule armed with an unreachable nth hit —
        # zero faults, same per-frame execution mode); the unarmed run is
        # kept in the sidecar as native_gap_x.
        armed_noop = "transport.send.delay:1000000000"
        schedule = ("transport.send.delay:0.01,transport.send.drop:0.002,"
                    "worker.exec.kill:150")
        code_tmpl = r"""
import json, os, time
import ray_tpu
sched = {sched!r}
cfg = {{"chaos_schedule": sched, "chaos_seed": 42}} if sched else {{}}
rt = ray_tpu.init(num_cpus=2, _system_config=cfg)

@ray_tpu.remote(num_cpus=1, max_retries=3)
def work(i):
    return i * 2

ray_tpu.get([work.remote(i) for i in range(50)], timeout=60)  # warm
t0 = time.perf_counter()
refs = [work.remote(i) for i in range(400)]
out = ray_tpu.get(refs, timeout=240)
el = time.perf_counter() - t0
assert out == [i * 2 for i in range(400)], "storm refs must resolve"
rec = None
if sched:
    ws = [w for w in rt.head_node.workers.values()
          if getattr(w, "proc", None) is not None]
    if ws:
        try:
            os.kill(ws[0].proc.pid, 9)
        except (ProcessLookupError, AttributeError):
            pass
        t1 = time.perf_counter()
        got = ray_tpu.get([work.remote(i) for i in range(20)],
                          timeout=120)
        assert got == [i * 2 for i in range(20)]
        rec = time.perf_counter() - t1
    rt.store.reclaim_orphans()
    assert rt.store.stats()["rsv_unused"] == 0, "leaked reservations"
print("CHAOS_RES", json.dumps({{"tasks_s": 400 / el, "recovery_s": rec}}))
ray_tpu.shutdown()
"""
        out_clean = run_sub(code_tmpl.format(sched=""), timeout=150,
                            tag="chaos_clean")
        clean = json.loads([ln for ln in out_clean.splitlines()
                            if ln.startswith("CHAOS_RES")][0][10:])
        out_armed = run_sub(code_tmpl.format(sched=armed_noop),
                            timeout=150, tag="chaos_clean_armed")
        armed = json.loads([ln for ln in out_armed.splitlines()
                            if ln.startswith("CHAOS_RES")][0][10:])
        out_chaos = run_sub(code_tmpl.format(sched=schedule), timeout=200,
                            tag="chaos_storm")
        chaotic = json.loads([ln for ln in out_chaos.splitlines()
                              if ln.startswith("CHAOS_RES")][0][10:])
        EXTRAS["chaos_storm"] = {
            "clean_tasks_s": round(clean["tasks_s"], 1),
            "clean_armed_tasks_s": round(armed["tasks_s"], 1),
            "chaos_tasks_s": round(chaotic["tasks_s"], 1),
            # Fault tax at matched execution mode (armed = native cores
            # off by construction in both numerator and denominator).
            "chaos_x": round(chaotic["tasks_s"]
                             / max(armed["tasks_s"], 1e-9), 3),
            # Speed-invariant fault tax: absolute extra wall for the
            # 400-task storm vs the armed-clean run. chaos_x's
            # denominator sped up ~3x over PRs 12-14 while the seeded
            # delays are an absolute floor, so the RATIO falls as the
            # scheduler gets faster even with recovery cost flat — this
            # number is the one comparable across rounds.
            "chaos_overhead_ms": round(
                (400.0 / max(chaotic["tasks_s"], 1e-9)
                 - 400.0 / max(armed["tasks_s"], 1e-9)) * 1e3, 1),
            # The native-core speedup an armed process forgoes — the r08
            # 0.658 artifact, now measured on purpose.
            "native_gap_x": round(armed["tasks_s"]
                                  / max(clean["tasks_s"], 1e-9), 3),
            "chaos_x_vs_unarmed": round(chaotic["tasks_s"]
                                        / max(clean["tasks_s"], 1e-9), 3),
            "recovery_s": (round(chaotic["recovery_s"], 2)
                           if chaotic.get("recovery_s") else None),
            "schedule": schedule, "seed": 42,
            "clean_armed_schedule": armed_noop,
        }

    def sec_elastic_train():
        # Elastic training plane (ROADMAP item 3): the same deterministic
        # 2-worker training run executed clean and with a seeded mid-run
        # worker SIGKILL (chaos train.worker_kill). train_rec_s = wall
        # time from the last pre-death report to the first post-restart
        # report (death detection + gang respawn + committed-manifest
        # resume); train_bit = the resumed loss trajectory is BIT-equal
        # to the clean run's at every step (state is a pure function of
        # step, so any divergence means the resume restored wrong state).
        code = r"""
import json, os, tempfile, time
import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.train.trainer import FailureConfig

def loop(config):
    import os as _os, time as _time
    from ray_tpu.core import chaos as _chaos
    from ray_tpu.train import session
    rank = session.get_world_rank()
    marker = _os.path.join(config["marker_dir"], "armed_%d" % rank)
    if config["kill"] and rank == 1 and not _os.path.exists(marker):
        open(marker, "w").close()
        _chaos.configure("train.worker_kill:%d" % config["kill_at"],
                         seed=7)
    ckpt = session.get_checkpoint()
    state, start = 1.0, 0
    if ckpt:
        d = ckpt.load_shard(rank)
        state, start = d["state"], d["step"] + 1
    for step in range(start, config["steps"]):
        state = (state * 1.000003 + 0.000007) % 1.7
        session.report({"step": step, "loss": abs(state - 0.5),
                        "t": time.time()},
                       checkpoint={"step": step, "state": state})
        _time.sleep(0.03)  # a "step": lets commits land between reports

rt = ray_tpu.init(num_cpus=4)
tmp = tempfile.mkdtemp()
mk = os.path.join(tmp, "markers")
os.makedirs(mk, exist_ok=True)
STEPS = 40

def fit(kill, name):
    t = JaxTrainer(
        loop,
        train_loop_config={"steps": STEPS, "marker_dir": mk,
                           "kill": kill, "kill_at": 12},
        scaling_config=ScalingConfig(num_workers=2, min_workers=1),
        run_config=RunConfig(name=name, storage_path=tmp,
                             failure_config=FailureConfig(max_failures=2)))
    return t.fit()

ref = fit(False, "ref")
assert ref.error is None, ref.error
chaotic = fit(True, "chaos")
assert chaotic.error is None, chaotic.error
assert chaotic.metrics_history[-1]["step"] == STEPS - 1
ts = [m["t"] for m in chaotic.metrics_history]
rec = max(b - a for a, b in zip(ts, ts[1:]))
ref_by_step = {m["step"]: m["loss"] for m in ref.metrics_history}
ch_by_step = {}
for m in chaotic.metrics_history:
    ch_by_step[m["step"]] = m["loss"]  # re-run steps: resumed wins
bit = all(ch_by_step[s] == ref_by_step[s] for s in ch_by_step)
print("ELASTIC_RES", json.dumps(
    {"recovery_s": round(rec, 2), "bit_stable": bool(bit)}))
ray_tpu.shutdown()
"""
        out = run_sub(code, timeout=120, tag="elastic_train")
        res = json.loads([ln for ln in out.splitlines()
                          if ln.startswith("ELASTIC_RES")][0][12:])
        EXTRAS["elastic_train"] = {
            "recovery_s": res["recovery_s"],
            "bit_stable": res["bit_stable"],
            "kill": "train.worker_kill:12 (rank 1, seeded)",
        }

    def sec_multi_tenant():
        # Multi-tenant fair-share A/B (job ledger + weighted-DRF grant
        # order): a victim tenant's closed-loop latency run executed (a)
        # alone, (b) against a seeded hostile task storm (chaos site
        # job.hostile: 1500-task burst + giant puts) with fair_share ON,
        # and (c) the same storm with fair_share OFF. Acceptance: ON
        # holds the victim's p99 + throughput within 20% of alone; OFF
        # shows the collapse fair-share prevents (the storm's key is
        # created first, so submission-order granting starves the
        # victim until the whole burst drains).
        tmpl = r"""
import json, time
import ray_tpu
from ray_tpu.core import chaos
from ray_tpu.core.jobs import hostile_tick

FAIR, STORM = %(fair)s, %(storm)s
rt = ray_tpu.init(num_cpus=4, _system_config={"fair_share": FAIR})
rt.jobs.register("victim")
rt.jobs.register("hostile")

@ray_tpu.remote(num_cpus=1)
def victim_step():
    time.sleep(0.5)
    return 1

@ray_tpu.remote(num_cpus=1)
def hog():
    time.sleep(0.02)
    return 1

# Warm the worker pool first (spawn is on-demand + rate-limited): the
# A/B measures scheduling policy, not cold-start.
ray_tpu.get([hog.remote() for _ in range(8)], timeout=120)

if STORM:
    chaos.configure("job.hostile:1", seed=11)
    fired = hostile_tick(
        lambda: hog.options(_job_id="hostile").remote(),
        put=lambda n: ray_tpu.put(b"x" * n),
        burst=1500, put_bytes=1 << 20)
    assert fired, "job.hostile chaos site did not arm"
    chaos.configure("")

N, W = 12, 2
lat, pending, t0s = [], [], {}
i = 0
t_start = time.time()
while len(lat) < N:
    while i < N and len(pending) < W:
        r = victim_step.options(_job_id="victim").remote()
        t0s[r] = time.time(); pending.append(r); i += 1
    done, pending = ray_tpu.wait(pending, num_returns=1, timeout=120)
    for r in done:
        ray_tpu.get(r)
        lat.append(time.time() - t0s.pop(r))
wall = time.time() - t_start
lat.sort()
snap = {row["job_id"]: row for row in rt.job_state()}
print("MT_RES", json.dumps({
    "p99_ms": round(lat[max(0, int(len(lat) * 0.99) - 1)] * 1000, 1),
    "p50_ms": round(lat[len(lat) // 2] * 1000, 1),
    "tput_s": round(N / wall, 2),
    "victim_finished": snap.get("victim", {}).get("finished", 0),
    "hostile_submitted": snap.get("hostile", {}).get("submitted", 0)}))
ray_tpu.shutdown()
"""

        def run(fair, storm, tag):
            out = run_sub(tmpl % {"fair": fair, "storm": storm},
                          timeout=120, tag=f"multi_tenant_{tag}")
            return json.loads([ln for ln in out.splitlines()
                               if ln.startswith("MT_RES")][0][7:])

        alone = run(True, False, "alone")
        fair_on = run(True, True, "fair_on")
        fair_off = run(False, True, "fair_off")
        emit("multi_tenant_victim_p99_ms", fair_on["p99_ms"])
        p99_x = (fair_on["p99_ms"] / alone["p99_ms"]
                 if alone["p99_ms"] else 0.0)
        tput_x = (fair_on["tput_s"] / alone["tput_s"]
                  if alone["tput_s"] else 0.0)
        EXTRAS["multi_tenant"] = {
            "storm": "job.hostile:1 (seed 11): 1500x 20ms tasks + 1MiB "
                     "put, hostile tenant, 4-CPU head",
            "victim": "12x 500ms tasks, closed loop window 2",
            "alone": alone, "fair_on": fair_on, "fair_off": fair_off,
            "fair_on_p99_x_vs_alone": round(p99_x, 3),
            "fair_on_tput_x_vs_alone": round(tput_x, 3),
            "fair_off_p99_x_vs_alone": round(
                fair_off["p99_ms"] / alone["p99_ms"]
                if alone["p99_ms"] else 0.0, 2),
            "fair_on_within_20pct": bool(p99_x <= 1.2 and tput_x >= 0.8),
        }

    def sec_serve_storm():
        # Disaggregated LLM serving plane (llm/serve.py, ROADMAP item 1):
        # the same open-loop arrival curve (requests fire on a fixed QPS
        # schedule regardless of completions — the million-user shape)
        # driven at (a) the disaggregated prefill/decode app, (b) a dense
        # 2-replica LLMServer comparator, and (c) the disaggregated app
        # with every decode replica armed to SIGKILL itself mid-storm
        # (serve.decode.kill, fixed seed; respawns come back clean).
        # Contract: admitted requests NEVER drop — overflow sheds loudly
        # (OverloadedError) at admission, and mid-storm replica death
        # degrades p99 while every in-flight stream re-resolves
        # exactly-once. p50/p99 land in the headline.
        code = r"""
import json, threading, time
import ray_tpu
from ray_tpu import serve as serve_api
from ray_tpu.core.status import OverloadedError, RayTpuError
from ray_tpu.llm import (DisaggConfig, EngineConfig, LLMConfig,
                         build_disagg_deployment, build_llm_deployment)
from ray_tpu.models import ModelConfig

MODEL = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, dtype="float32")
ENG = EngineConfig(max_slots=4, max_len=96, prompt_buckets=(32,),
                   eos_token=-1, default_max_new_tokens=16, page_size=16)
QPS, N_REQ, MAX_NEW = 4.0, 32, 16
PROMPTS = ["storm tenant %d asks question %d" % (i % 4, i)
           for i in range(N_REQ)]

rt = ray_tpu.init(num_cpus=6)

def storm(handle, tag):
    lat, shed, dropped = [], [], []
    lock = threading.Lock()
    t0 = time.monotonic()
    def fire(i, p):
        t_sched = t0 + i / QPS
        time.sleep(max(0.0, t_sched - time.monotonic()))
        ts = time.monotonic()
        try:
            out = handle.completions.remote(
                p, max_tokens=MAX_NEW, temperature=0.0).result(timeout_s=120)
            ok = out["usage"]["completion_tokens"] > 0
            with lock:
                (lat if ok else dropped).append(
                    (time.monotonic() - ts) * 1e3 if ok else p)
        except OverloadedError:
            with lock:
                shed.append(p)
        except Exception as e:
            if "OverloadedError" in str(e) or "overloaded" in str(e):
                with lock:
                    shed.append(p)
            else:
                with lock:
                    dropped.append("%s: %r" % (p, e))
    ths = [threading.Thread(target=fire, args=(i, p))
           for i, p in enumerate(PROMPTS)]
    for t in ths: t.start()
    for t in ths: t.join(timeout=240)
    lat.sort()
    def pct(q):
        return round(lat[min(int(q * len(lat)), len(lat) - 1)], 1) if lat else None
    return {"tag": tag, "admitted": len(lat), "shed": len(shed),
            "dropped": len(dropped), "drop_detail": dropped[:3],
            "p50_ms": pct(0.50), "p99_ms": pct(0.99),
            "wall_s": round(time.monotonic() - t0, 1)}

# (a) disaggregated: 1 prefill + 2 decode + coordinator, token budgets
# sized so the 4 QPS open-loop curve overflows into sheds at the burst.
cfg = LLMConfig(model_id="storm", model=MODEL, engine=ENG, tokenizer="byte")
dapp = build_disagg_deployment(cfg, DisaggConfig(
    decode_replicas=2, max_decode_inflight_tokens=320,
    max_prefill_queue_tokens=512))
serve_api.run(dapp, name="disagg", route_prefix=None, http_port=18311,
              blocking_timeout_s=300)
h = serve_api.get_deployment_handle("DisaggLLMServer:storm", "disagg")
h.completions.remote(PROMPTS[0], max_tokens=4, temperature=0.0).result(
    timeout_s=240)  # warm the compile caches before the clock starts
r_disagg = storm(h, "disagg")

# (c) the same curve with every decode replica armed to die mid-storm
dec = serve_api.get_deployment_handle("DecodePool:storm", "disagg")
pids = set()
for _ in range(30):
    pids.add(dec.configure_chaos.remote("serve.decode.kill:24", 42
                                        ).result(timeout_s=60))
    if len(pids) >= 2: break
r_kill = storm(h, "disagg_kill")
stats = h.stats.remote().result(timeout_s=30)
serve_api.delete("disagg")

# (b) dense comparator: 2 monolithic engine replicas, no admission plane
cfg2 = LLMConfig(model_id="storm", model=MODEL, engine=ENG,
                 tokenizer="byte", num_replicas=2)
serve_api.run(build_llm_deployment(cfg2), name="dense", route_prefix=None,
              http_port=18312, blocking_timeout_s=300)
hd = serve_api.get_deployment_handle("LLMServer:storm", "dense")
hd.completions.remote(PROMPTS[0], max_tokens=4, temperature=0.0).result(
    timeout_s=240)
r_dense = storm(hd, "dense")
serve_api.delete("dense")

assert r_kill["dropped"] == 0, r_kill   # zero admitted requests dropped
print("STORM_RES", json.dumps({
    "qps": QPS, "n_req": N_REQ, "max_new": MAX_NEW,
    "disagg": r_disagg, "disagg_kill": r_kill, "dense": r_dense,
    "armed_replicas": len(pids),
    "streams_resumed": stats.get("streams_resumed", 0),
    "decode_failures": stats.get("decode_failures", 0)}))
ray_tpu.shutdown()
"""
        out = run_sub(code, timeout=min(420, max(180, _remaining() - 20)),
                      tag="serve_storm")
        res = json.loads([ln for ln in out.splitlines()
                          if ln.startswith("STORM_RES")][0][10:])
        d, k, dn = res["disagg"], res["disagg_kill"], res["dense"]
        emit("serve_storm_p99_ms", d["p99_ms"] or 0.0)
        EXTRAS["serve_storm"] = {
            "open_loop_qps": res["qps"], "n_req": res["n_req"],
            "max_new_tokens": res["max_new"],
            "disagg": d, "disagg_kill": k, "dense": dn,
            "dense_vs_disagg_p99_x": (round(dn["p99_ms"] / d["p99_ms"], 2)
                                      if d["p99_ms"] and dn["p99_ms"]
                                      else None),
            "kill": {"schedule": "serve.decode.kill:24 (both replicas, "
                                 "seed 42)",
                     "streams_resumed": res["streams_resumed"],
                     "decode_failures": res["decode_failures"],
                     "admitted_dropped": k["dropped"]},
        }

    sections = [
        ("tasks", 120, sec_tasks),
        ("actors", 150, sec_actors),
        ("objects", 120, sec_objects),
        ("adag", 90, sec_adag),
        ("data_pipeline", 120, sec_data_pipeline),
        ("task_events", 180, sec_task_events),
        ("cross_language", 90, sec_cross_language),
        ("pg", 90, sec_pg),
        ("client", 90, sec_client),
        ("chaos", 150, sec_chaos),
        ("elastic_train", 60, sec_elastic_train),
        ("multi_tenant", 75, sec_multi_tenant),  # fair-share A/B
        ("many_agents", 280, sec_many_agents),  # main run + native-off A/B
        ("cluster_scale", 320, sec_cluster_scale),  # 64/256 sharded A/B
        ("serve_storm", 180, sec_serve_storm),
    ]
    # Resilience-test hooks: a section that hangs forever and one that
    # throws, injectable so the watchdog/headline contract stays pinned
    # by tests (tests/test_bench_resilience.py) instead of by the next
    # rc=124 postmortem.
    if os.environ.get("RAY_TPU_BENCH_TEST_HANG"):
        def sec_hang():
            while True:
                time.sleep(3600)
        sections.append(("_hang", 5, sec_hang))
    if os.environ.get("RAY_TPU_BENCH_TEST_CRASH"):
        def sec_crash():
            raise ValueError("injected section crash")
        sections.append(("_crash", 5, sec_crash))
    only = os.environ.get("RAY_TPU_BENCH_SECTIONS")
    if only:
        wanted = set(only.split(","))
        sections = [s for s in sections if s[0] in wanted]
    watchdog_env = os.environ.get("RAY_TPU_BENCH_SECTION_TIMEOUT_S")
    for name, est, fn in sections:
        if _remaining() < est:
            SKIPPED.append(name)
            print(json.dumps({"partial": "_skip", "section": name,
                              "remaining_s": round(_remaining(), 1)}),
                  file=sys.stderr, flush=True)
            continue
        # Per-section watchdog (r04: one hung get() rc=124'd the WHOLE
        # run): SIGALRM raises SectionTimeout in this thread, the
        # section is stamped skipped, and the suite moves on. 2x the
        # estimate leaves the section's own internal timeouts room to
        # fire first (they clean up more precisely).
        watchdog = (float(watchdog_env) if watchdog_env
                    else max(est * 2.0, 60.0))
        watchdog = min(watchdog, max(5.0, _remaining() - 10.0))
        try:
            signal.setitimer(signal.ITIMER_REAL, watchdog)
            try:
                fn()
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
        except SectionTimeout:
            SKIPPED.append(f"{name}: watchdog timeout after "
                           f"{watchdog:.0f}s")
            print(json.dumps({"partial": "_watchdog", "section": name,
                              "timeout_s": watchdog}),
                  file=sys.stderr, flush=True)
        except Exception as e:  # keep the suite alive; stamp the failure
            SKIPPED.append(f"{name}: {str(e)[:200]}")
            print(f"section {name} failed: {e}", file=sys.stderr)

    try:
        ray_tpu.shutdown()
    except Exception:
        pass
    final_line("complete" if not SKIPPED else "partial")


def _memcpy_ceiling_gbps() -> float:
    """This box's warm 1GB single-thread copy bandwidth — the hardware
    ceiling for single_client_put_gigabytes (a blocking put IS one big
    copy into shm; the reference's 17.8 GB/s was recorded on hardware
    whose ceiling exceeded that)."""
    import ctypes
    import mmap as mmap_mod
    libc = ctypes.CDLL("libc.so.6")
    n = 1 << 30
    src = np.zeros(n, np.uint8)
    src.sum()  # fault
    dst = mmap_mod.mmap(-1, n)
    dst_addr = ctypes.addressof(ctypes.c_char.from_buffer(dst))
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        libc.memcpy(ctypes.c_void_p(dst_addr),
                    ctypes.c_void_p(src.ctypes.data), n)
        best = max(best, 1.0 / (time.perf_counter() - t0))
    return round(best, 1)


if __name__ == "__main__":
    main()
