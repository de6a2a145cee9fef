"""`mimo_v2_5`: its three cost functions against counts made by hand, its
file against the published configuration, its cell end to end at rehearsal
sizes, and tools/checkwindow_mimo_v2.py at those sizes. Collected by tier-1
through tests/test_mimo_v2.py."""

import dataclasses
import json
import os
import subprocess
import sys
import types

from perfbench.harness import cells, modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MIMO_CELL = "mimo_v2_5-serve-longturn"

MIMO = types.SimpleNamespace(
    window=128, n_heads=64, window_heads=64, n_kv_heads=4, window_kv_heads=8,
    head_dim=192, v_head_dim=128, dtype="bfloat16", attn_sink="W",
    attn_pattern="FWWWWFWWWWW")
ENGINE = types.SimpleNamespace(page_size=128)
# a step of two slots at lengths 100 and 5000 (after the step)
STEPS = [{"lengths": [100, 5000]}]


def _kernel(name):
    return cells.load_module(os.path.join(ROOT, "perfbench", "kernels",
                                          name + ".py"))


def test_sinkwin_prefill_cost_by_hand():
    """One `sinkwin_prefill_n2_s2048_t256` call: 2 x 2048 rows over 256
    held keys, so every row's window of 128 is full."""
    mod = _kernel("sinkwin_prefill")
    assert mod.keys_in_window(2048, 256, 128) == 2048 * 128
    # from a sequence's start the first 127 rows see fewer
    assert mod.keys_in_window(300, 0, 128) == 127 * 128 // 2 + 173 * 128
    flops, nbytes = mod.cost_of_call(2, 2048, 256, MIMO)
    # a key, head and query: 2 x 192 for q.K^T, 2 x 128 for p.V
    assert flops == 2 * 2048 * 128 * 64 * 2 * (192 + 128)
    q_and_out = 2 * 2048 * 64 * (192 + 128) * 2
    k_and_v = 2 * (256 + 2048) * 8 * (192 + 128) * 2   # the window kind's 8
    assert nbytes == q_and_out + k_and_v + 64 * 4      # and the 64 sinks
    ctx = {"model": MIMO, "op_pattern": "sinkwin_prefill", "op_count": {
        "sinkwin_prefill_n2_s2048_t256": 9, "gqa_prefill_attention": 2,
        "swa_prefill_n2_s2048_t256": 5, "sinkwin_paged_decode": 50}}
    assert mod.cost(ctx) == (9 * flops, 9 * nbytes)
    assert mod.cost({**ctx, "op_count": {"fusion.3": 4}}) is None
    # a program without the sink (the parent's, Laguna's): nothing to read
    assert mod.cost({**ctx, "model": types.SimpleNamespace()}) is None


def test_sinkwin_decode_cost_by_hand():
    mod = _kernel("sinkwin_decode")
    # 100 tokens, short of the window: all of them, in one page
    flops, nbytes = mod.cost_of_step([100], MIMO, 128)
    assert flops == 2 * (192 + 128) * 64 * 100
    assert nbytes == (128 * 8 * (192 + 128) * 2 + 64 * (192 + 128) * 2
                      + 64 * 4)
    # 5000 tokens: the last 128 (positions 4872..4999, pages 38 and 39),
    # not the 40 pages the sequence would hold if it grew
    f2, b2 = mod.cost_of_step([5000], MIMO, 128)
    assert f2 == 2 * (192 + 128) * 64 * 128
    assert b2 == 2 * 128 * 8 * 320 * 2 + 64 * 320 * 2 + 64 * 4
    # a window that ends with its page lies in that page alone
    assert mod.cost_of_step([5120], MIMO, 128)[1] == b2 - 128 * 8 * 320 * 2
    both = mod.cost_of_step([100, 5000], MIMO, 128)
    assert both == (flops + f2, nbytes + b2 - 64 * 4)   # the sinks once
    ctx = {"model": MIMO, "engine": ENGINE, "steps": STEPS}
    assert mod.cost(ctx) == (9 * both[0], 9 * both[1])  # 9 "W" layers
    assert mod.cost({"model": MIMO, "engine": ENGINE}) is None
    assert mod.cost({**ctx, "model": types.SimpleNamespace()}) is None


def test_splitkv_decode_cost_by_hand():
    mod = _kernel("splitkv_decode")
    flops, nbytes = mod.cost_of_step([100, 5000], MIMO, 128)
    assert flops == 2 * (192 + 128) * 64 * (100 + 5000)
    # whole pages: 1 and 40, of the FULL kind's 4 K/V heads
    assert nbytes == ((1 + 40) * 128 * 4 * (192 + 128) * 2
                      + 2 * 64 * (192 + 128) * 2)
    ctx = {"model": MIMO, "engine": ENGINE, "steps": STEPS}
    assert mod.cost(ctx) == (2 * flops, 2 * nbytes)     # 2 "F" layers
    assert mod.cost({"model": MIMO, "engine": ENGINE}) is None
    # K and V alike (Laguna's full layers): `paged_attn.py`'s, not this
    alike = types.SimpleNamespace(**{**vars(MIMO), "v_head_dim": 0})
    assert mod.cost({**ctx, "model": alike}) is None
    assert mod.cost({**ctx, "model": types.SimpleNamespace(
        head_dim=128)}) is None


def test_the_configuration_is_the_published_one_cut_as_stated():
    """Every number of the catalog's `config` is in the file under its own
    key but the three `reduced` names, which carry the source's values
    beside the cut ones; the file builds the program's preset with the
    chip's share laid over, field by field."""
    from ray_tpu.models import configs
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "mimo_v2_5")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    found = cells.load_cell(ROOT, MIMO_CELL)
    cfg = found["cfg"]
    assert entry["source"] == cfg["source"]
    assert cfg["source_values"] == {"num_hidden_layers": 48,
                                    "n_routed_experts": 256,
                                    "vocab_size": 152576}
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    # every width as published
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "swa_num_attention_heads",
        "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
        "swa_head_dim", "v_head_dim", "swa_v_head_dim", "sliding_window",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "n_routed_experts_source", "attention_value_scale",
        "partial_rotary_factor", "rope_theta", "swa_rope_theta")] == [
        4096, 64, 64, 4, 8, 192, 192, 128, 128, 128, 16384, 2048, 8, 256,
        0.707, 0.334, 10000000, 10000]
    assert cfg["add_swa_attention_sink_bias"] is True
    assert cfg["add_full_attention_sink_bias"] is False
    # the per-layer lists whole; the program reads their first 11 entries
    assert len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"]) == 48
    n = cfg["num_hidden_layers"]
    assert cfg["attn_pattern"] == "".join(
        "FW"[k] for k in cfg["hybrid_layer_pattern"][:n])
    assert cfg["moe_layer_freq"][:n] == [0] + [1] * (n - 1)
    assert set(cfg["assumed"]) >= {
        "a_attention_chunk_size", "b_projection_layout", "c_value_scale",
        "d_qk_norm", "e_sink", "f_rotary", "g_router", "m_left_out"}
    assert all(w in cfg["deployment"] for w in (
        "64 v5e", "four pipeline stages", "STAGE 0", "group 0", "16 x"))
    model = modelcfg.model_config(cfg, found["traffic"]["kind"])
    want = configs.mimo_v2_5(
        n_layers=11, attn_pattern=configs.mimo_v2_pattern(11),
        moe_experts=16, vocab=19072)
    for f in dataclasses.fields(model):
        assert getattr(model, f.name) == getattr(want, f.name), f.name
    assert (model.window_kv_heads, model.attn_sink, model.value_scale,
            model.v_head_dim, model.moe_router_bias) == (8, "W", 0.707, 128,
                                                         True)
    engine = modelcfg.engine_config(cfg, found["cellp"])
    assert (engine.max_slots, engine.max_len, engine.page_size,
            engine.prompt_buckets, engine.admit_rows) == (
        32, 16512, 128, (4096, 8192), 8192)
    assert model.window_span(engine.page_size) == 2
    t, cellp = found["traffic"], found["cellp"]
    assert (cellp["clients"], cellp["warm_admit_together"]) == (34, 2)
    assert t["prompt_tokens"]["dist"] == "lognormal_grid"
    assert (t["prompt_tokens"]["median"], t["prompt_tokens"]["sigma"],
            t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]) == (
        6144, 0.4, 1024, 15360)
    assert (t["output_tokens"]["min"], t["output_tokens"]["max"],
            t["grid_size"], t["max_total_tokens"]) == (384, 1024, 32, 16384)
    # the rehearsal: K and V of different widths, neither a multiple of the
    # other's tile; 2 and 4 K/V heads; a window of exactly the page
    tiny = modelcfg.model_config(cfg, t["kind"], rehearsal=True)
    small = modelcfg.engine_config(cfg, cellp, rehearsal=True)
    assert (tiny.head_dim, tiny.v_head_dim, tiny.n_kv_heads,
            tiny.window_kv_heads) == (24, 16, 2, 4)
    assert tiny.window == small.page_size == 16


def test_every_prompt_is_at_most_one_chunk_and_one_continuation():
    """The admission shapes warm-up covers: each bucket, and a chunk with a
    continuation in each bucket; no prompt needs a third admission."""
    from perfbench.harness import serve_cell, traffic
    found = cells.load_cell(ROOT, MIMO_CELL)
    engine = modelcfg.engine_config(found["cfg"], found["cellp"])
    chunk = max(engine.prompt_buckets)
    pairs = traffic.request_pairs(found["traffic"], 32)
    assert all(p <= 2 * chunk and p + o < engine.max_len for p, o in pairs)
    assert sum(p > chunk for p, _ in pairs) == 8        # the long quarter
    assert sum(p <= 4096 for p, _ in pairs) == 5
    waves = serve_cell.warm_waves(found["traffic"], engine, 2)
    reps = {p for w in waves for p, _ in w}
    assert {4096, 8192, chunk + 4096, 15360} <= reps
    assert max(len(w) for w in waves) == 2


def test_the_mimo_cell_rehearses_end_to_end():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", MIMO_CELL, "--seed", str(2**31 + 4242), "--seconds",
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


def test_checkwindow_refuses_its_faults_at_rehearsal_sizes(capsys):
    """The tool's whole flow on the CPU (window 16 = the page): a prompt in
    one bucket, one of a chunk and a continuation, and one of three chunks;
    every honest comparison passes, every fault is refused, and
    checkwindow_laguna is left as it was."""
    from perfbench.tools import checkwindow_laguna, checkwindow_mimo_v2
    theirs = checkwindow_laguna.faults
    rc = checkwindow_mimo_v2.main(["--rehearsal", "--prompt-tokens",
                                   "50,100,150", "--new-tokens", "20"])
    assert checkwindow_laguna.faults is theirs
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert rc == 0 and lines[-1]["checkwindow"] == "ok"
    assert lines[-1]["window_span_pages"] == 2
    assert [(x["prompt_tokens"], x["fault"]) for x in lines[:-1]] == [
        (n, f) for n in (50, 100, 150)
        for f in ("none", "no_sink", "full_sink", "window_12", "v_unscaled",
                  "rotary_swapped", "weights_f8")]
    assert all(x["ok"] == (x["fault"] == "none") for x in lines[:-1])
    assert all(x["kv"]["window_seq_pages_peak"] <= 2 for x in lines[:-1])
    assert lines[-2]["kv"]["window_pages_released"] > 0
    assert checkwindow_mimo_v2.main(["--sink-share", "--rehearsal"]) == 0
    share = json.loads(capsys.readouterr().out.splitlines()[-1])[
        "sink_share_of_a_full_window_row"]
    assert 0.1 < share["mean"] < 0.6
