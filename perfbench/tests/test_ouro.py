"""`ouro_2_6b`: its cost function against a count made by hand, its file
against the published configuration (nothing cut), its cell end to end at
rehearsal sizes, and tools/checkdist_ouro.py at those sizes. Collected by
tier-1 through tests/test_ouro.py."""

import json
import os
import subprocess
import sys
import types

from perfbench.harness import cells, modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OURO_CELL = "ouro_2_6b-serve-solver"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

MODEL = types.SimpleNamespace(loops=4, n_layers=48, n_heads=16,
                              n_kv_heads=16, head_dim=128, dtype="bfloat16")


def test_looped_attn_cost_by_hand():
    mod = cells.load_module(os.path.join(ROOT, "perfbench", "kernels",
                                         "looped_attn.py"))
    # one cache layer's call, a slot of 300 tokens: 3 whole pages of K and
    # of V at 16 heads x 128 x 2 B a token, q and the output, and q.K^T
    # and p.V over the 300 keys
    flops, nbytes = mod.cost_of_step([300], MODEL, 128)
    assert flops == 4 * 16 * 128 * 300
    assert nbytes == 2 * 3 * 128 * 16 * 128 * 2 + 2 * 16 * 128 * 2
    assert 3 * 128 * 2 * 16 * 128 * 2 == 3 * 2**20      # 1 MiB a page, K + V
    eng = types.SimpleNamespace(page_size=128)
    ctx = {"model": MODEL, "engine": eng,
           "steps": [{"lengths": [300]}, {"lengths": [300, 129, 1]}]}
    f2, b2 = mod.cost_of_step([300, 129, 1], MODEL, 128)
    assert b2 - nbytes == 3 * (2 * 128 * 16 * 128 * 2) + 2 * (2 * 16 * 128 * 2)
    # a step makes loops x n_layers = 192 calls
    assert mod.cost(ctx) == (192 * (flops + f2), 192 * (nbytes + b2))
    assert mod.cost({"model": MODEL, "engine": eng}) is None
    # the parent's program has no passes, and a stack that runs once is
    # `paged_attn`'s: nothing to read, no error
    for model in (types.SimpleNamespace(), types.SimpleNamespace(
            **{**vars(MODEL), "loops": 1})):
        assert mod.cost({**ctx, "model": model}) is None


def test_the_configuration_is_the_published_one_whole():
    """`reduced` is empty: every number of the catalog's config is in the
    file under its own key, the file builds the program's preset, and the
    engine's shape and the traffic are ISSUE 45's."""
    from ray_tpu.models import configs
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == []
    found = cells.load_cell(ROOT, OURO_CELL)
    cfg = found["cfg"]
    assert entry["source"] == cfg["source"]
    assert cfg["source_values"] == {} and cfg["reduced_why"] == {}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == cfg["source"]
        assert all(cfg[k] == v for k, v in row["config"].items())
    assert set(cfg["assumed"]) >= {
        "a_four_norms", "b_closing_norm", "c_exit_gate",
        "d_no_bias_no_qk_norm", "e_cache_a_pass"}
    assert all("other reading" in cfg["assumed"][k] for k in (
        "a_four_norms", "b_closing_norm", "c_exit_gate", "e_cache_a_pass"))
    assert "one v5e chip holds one replica whole" in cfg["deployment"]
    model = modelcfg.model_config(cfg, found["traffic"]["kind"])
    assert model == configs.ouro_2_6b()
    assert (model.loops, model.n_layers, model.cache_layers) == (4, 48, 192)
    assert model.n_heads == model.n_kv_heads == 16      # a group of one
    # the count ISSUE 45 made from the keys: 48 x 51.39 M + 2 x 100.66 M
    d, hd, ff = model.d_model, model.head_dim, model.d_ff
    layer = 4 * d * 16 * hd + 3 * d * ff + 4 * d
    assert layer == 51_388_416
    assert 48 * layer + 2 * model.vocab * d + d + d + 1 == 2_667_974_657
    # a token keeps 1.5 MiB, a page 192 MiB
    assert 2 * model.cache_layers * 16 * 128 * 2 * 128 == 192 * 2**20
    engine = modelcfg.engine_config(cfg, found["cellp"])
    assert (engine.max_slots, engine.max_len, engine.page_size,
            engine.prompt_buckets, engine.num_pages, engine.admit_rows,
            engine.eos_token) == (7, 768, 128, (128, 256, 512), 43, 512, -1)
    # every slot can reach max_len: the cell never preempts
    assert engine.num_pages == engine.max_slots * (768 // 128) + 1
    t, cellp = found["traffic"], found["cellp"]
    assert (cellp["clients"], cellp["client_stagger_s"], cellp["ramp_s"],
            cellp["warm_admit_together"]) == (9, 0.5, 12.0, 2)
    assert t["kind"] == "closed_loop" and t["shared_prefix_tokens"] == 0
    assert t["prompt_tokens"] == {"dist": "uniform_grid", "min": 64,
                                  "max": 384}
    assert t["output_tokens"] == {"dist": "uniform_grid", "min": 128,
                                  "max": 384}
    assert (t["grid_size"], t["max_total_tokens"], t["pairing_seed"]) == (
        16, 768, 0)
    rehearsal = modelcfg.model_config(cfg, t["kind"], rehearsal=True)
    assert rehearsal.loops == 4 and rehearsal.post_norms


def test_every_prompt_fits_one_bucket_and_one_admission():
    """No chunk continuation runs in the cell (no prompt is over the
    largest bucket), all three buckets do, every sequence stays under
    max_len, and warm-up reaches every program a step of the window can:
    each bucket with one and two together (two 512-row prompts go in two
    steps: the row budget), and the decode page buckets up to the longest
    sequence's six pages."""
    from perfbench.harness import serve_cell, traffic
    found = cells.load_cell(ROOT, OURO_CELL)
    engine = modelcfg.engine_config(found["cfg"], found["cellp"])
    pairs = traffic.request_pairs(found["traffic"], 16)
    assert all(p <= max(engine.prompt_buckets)
               and p + o < engine.max_len for p, o in pairs)
    assert max(p + o for p, o in pairs) == 750
    buckets = [min(b for b in engine.prompt_buckets if b >= p)
               for p, _ in pairs]
    assert [buckets.count(b) for b in engine.prompt_buckets] == [3, 7, 6]
    assert sum(p for p, _ in pairs) / 16 == 224
    assert sum(o for _, o in pairs) / 16 == 256
    assert max(engine.prompt_buckets) == engine.admit_rows
    waves = serve_cell.warm_waves(found["traffic"], engine,
                                  found["cellp"]["warm_admit_together"])
    for rep in (128, 256, 384):
        assert sorted(len(w) for w in waves if w[0] == (rep, 2)) == [1, 2]
    pages = {-(-(p + o) // 128) for w in waves for p, o in w}
    assert pages == {1, 2, 3, 4, 5}      # page buckets 1, 2, 4 and 8


def test_the_ouro_cell_rehearses_end_to_end():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", OURO_CELL, "--seed", str(2**31 + 4545), "--seconds",
         "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert out["metrics"] == {}          # never a device metric from a CPU
    assert out["check"]["max_abs_diff"] < 1e-3
    got = out["rehearsal_only_not_device_numbers"]
    assert "decode_step_ms_p50.tput" in got and "prefill_chunk_ms_p50" in got


def test_checkdist_ouro_refuses_its_faults_at_rehearsal_sizes(capsys,
                                                               monkeypatch):
    """The tool's whole flow on the CPU: the honest program passes, every
    fault of its list is refused by the rule that decides `correct`."""
    from perfbench.tools import checkdist, checkdist_ouro
    faults = "none," + ",".join(
        f for f in checkdist_ouro.FAULTS if f != "none_ouro")
    monkeypatch.setattr(sys, "argv", [
        "checkdist_ouro.py", "--workload", OURO_CELL, "--seed", "7",
        "--sequences", "1", "--prompt-tokens", "40", "--new-tokens", "8",
        "--fault", faults + ",last_token", "--rehearsal"])
    before = dict(checkdist.FAULTS)
    try:
        assert checkdist_ouro.main() == 0
    finally:
        checkdist.FAULTS.clear()
        checkdist.FAULTS.update(before)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["fault"] for x in lines] == (faults + ",last_token").split(",")
    assert all(x["verdict"]["ok"] == (x["fault"] == "none") for x in lines)
    assert set(checkdist_ouro.FAULTS) == {
        "none_ouro", "three_passes", "shared_cache", "open_stream",
        "two_norms", "kv_f8"}


def test_closedloop_sim_counts_as_the_harness_does():
    """tools/closedloop_sim.py against a count made by hand: one caller on
    one slot, prompts of 100 and answers of 50, a 10 ms step, no stall: a
    request is 49 delivered tokens (the stream swallows the first) and its
    101 counted with the first of them, 150 in 49 steps; 490 steps fall
    inside 4.905 s."""
    from perfbench.tools import closedloop_sim as sim
    traffic = {"prompt_tokens": {"dist": "fixed", "value": 100,
                                 "min": 100, "max": 100},
               "output_tokens": {"dist": "fixed", "value": 50,
                                 "min": 50, "max": 50},
               "grid_size": 4}
    cellp = {"clients": 1, "ramp_s": 0.0, "requests_upper_bound": 50}
    out = sim.simulate(traffic, cellp, [128], 1, 7, 4.905, 10.0, 0.0,
                       {128: 0.0})
    assert out["steps"] == 490 and out["first_tokens"] == 10
    assert out["tok_per_s"] == 1500 / 4.905
    # a set's spread: the run farthest from the median left out where that
    # narrows it (quartiles 1.5 and 4.5 of 1..5, median 3.5)
    assert sim.set_spread([1, 2, 3, 4, 5, 100]) == 3 / 3.5


def test_closedloop_sim_holds_the_chips_readings_of_the_cell():
    """The simulation on the cell's own files, at the two numbers a traced
    run gave it, against six readings of the chip (PR 45, call 12's seeds;
    tools/closedloop_sim.py's docstring has all fourteen): the same
    requests counted, the rate within 1.2 tokens/s but for a request on
    the other side of an edge."""
    from perfbench.tools import closedloop_sim as sim
    found = cells.load_cell(ROOT, OURO_CELL)
    chip = {4501200002: (291.07, 28), 4501200003: (285.11, 27),
            4501200004: (281.96, 26), 4501200005: (292.38, 28)}
    for seed, (rate, n) in chip.items():
        out = sim.simulate(found["traffic"], found["cellp"],
                           [128, 256, 512], 7, seed, 45.0, 38.0, 2.0,
                           {128: 30.0, 256: 56.0, 512: 105.0})
        assert out["first_tokens"] == n
        assert abs(out["tok_per_s"] - rate) < 1.2
