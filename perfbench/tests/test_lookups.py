"""The look-ups that stand between a configuration's file and the program:
the field table, the engine's keys, the reference and the flop count found
by name. No compile, no device: seconds on the CPU.

The expected objects are literals written from what the harness built for
the benchmark's first two configurations before the table existed (PR 23's
modelcfg.py): what the code did for them by name it does by default."""

import json
import os
import re

import pytest

from perfbench.harness import cells, modelcfg
from perfbench.harness.record import Record

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

QWEN = dict(vocab=152064, d_model=3584, n_layers=12, n_heads=28,
            n_kv_heads=4, d_ff=18944, rope_theta=1e6, norm_eps=1e-6,
            moe_experts=0, moe_top_k=2, dtype="bfloat16", remat=False,
            attn_impl="auto", tie_embeddings=False, unroll_layers=None)
MIXTRAL = dict(QWEN, vocab=32000, d_model=4096, n_layers=2, n_heads=32,
               n_kv_heads=8, d_ff=14336, norm_eps=1e-5, moe_experts=8)
TINY = dict(vocab=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, dtype="float32")
ENGINE = dict(max_slots=16, max_len=2048, prompt_buckets=(64, 256, 1024),
              eos_token=-1, default_max_new_tokens=128,
              default_temperature=0.0, kv_layout="paged", page_size=128,
              num_pages=None, prefix_cache=True, speculation=None, spec_k=4)
TINY_ENGINE = dict(ENGINE, max_slots=4, max_len=512, prompt_buckets=(64, 256))

EXPECTED = [
    ("qwen2_7b-serve-chat", False, QWEN, ENGINE),
    ("qwen2_7b-serve-chat", True, dict(QWEN, **TINY), TINY_ENGINE),
    ("qwen2_7b-train-fsdp4", False, dict(QWEN, n_layers=20, remat=True),
     None),
    ("qwen2_7b-train-fsdp4", True, dict(QWEN, **TINY, remat=True), None),
    # the cell's own slots (cells/mixtral_8x7b-serve-chat.json, PR 53)
    ("mixtral_8x7b-serve-chat", False, MIXTRAL, dict(ENGINE, max_slots=32)),
    ("mixtral_8x7b-serve-chat", True,
     {**MIXTRAL, **TINY, "n_kv_heads": 4, "moe_experts": 4}, TINY_ENGINE),
]


@pytest.mark.parametrize("cell,rehearsal,model,engine", EXPECTED)
def test_the_two_first_configurations_build_what_they_always_built(
        cell, rehearsal, model, engine):
    from ray_tpu.llm import EngineConfig
    from ray_tpu.models import ModelConfig
    found = cells.load_cell(ROOT, cell, rehearsal)
    got = modelcfg.model_config(found["cfg"], found["traffic"]["kind"],
                                rehearsal)
    assert got == ModelConfig(**model)
    hash(got)                      # a static argument of the jitted programs
    if engine is not None:
        assert modelcfg.engine_config(found["cfg"], found["cellp"],
                                      rehearsal) == EngineConfig(**engine)


def test_the_cells_own_engine_shape_wins():
    found = cells.load_cell(ROOT, "qwen2_7b-serve-longprompt")
    assert modelcfg.engine_config(found["cfg"], found["cellp"]).max_slots == 8


def _cfg(**over):
    cfg = cells.load_cell(ROOT, "qwen2_7b-serve-chat")["cfg"]
    return {**cfg, "_file": "perfbench/configs/other.json", **over}


def test_a_files_own_table_is_laid_over_the_default():
    cfg = _cfg(
        model_fields={
            "attn_impl": {"value": "reference"},            # a literal
            "unroll_layers": {"key": "unroll"},             # any key, no cast
            "n_kv_heads": {"key": "kv_heads", "cast": "int", "default": 7},
            "moe_experts": {"key": "n_routed_experts", "cast": "int"},
            "remat": None},                                 # a line dropped
        unroll=True, n_routed_experts=160.0,
        by_kind={"open_loop": {"n_routed_experts": 40}},
        rehearsal={"model": {"unroll": False}})
    got = modelcfg.model_config(cfg, "open_loop")
    assert (got.attn_impl, got.unroll_layers, got.n_kv_heads,
            got.moe_experts, got.remat) == ("reference", True, 7, 40, False)
    assert got.d_ff == 18944                     # the default lines stay
    assert modelcfg.model_config(cfg, "open_loop", True).unroll_layers is False
    assert modelcfg.model_config(cfg, "train_job").moe_experts == 160


def test_lists_and_objects_arrive_hashable():
    got = modelcfg._hashable({"rope_scaling": {"type": "yarn", "factor": 40,
                                              "betas": [32, 1]}})
    assert got == (("rope_scaling", (("betas", (32, 1)), ("factor", 40),
                                     ("type", "yarn"))),)
    hash(got)
    assert dict(dict(got)["rope_scaling"])["type"] == "yarn"
    eng = modelcfg.engine_config(_cfg(), {"engine": {"prompt_buckets": [32]}})
    assert eng.prompt_buckets == (32,)


@pytest.mark.parametrize("over,exc,names", [
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
def test_a_bad_table_is_an_error_that_names_file_field_and_key(over, exc,
                                                               names):
    with pytest.raises(exc) as e:
        modelcfg.model_config(_cfg(**over), "open_loop")
    assert all(n in str(e.value) for n in names), str(e.value)


def test_the_engine_takes_every_field_it_has_and_refuses_the_rest():
    cfg = _cfg()
    cfg["engine"] = {**cfg["engine"], "num_pages": 300, "kv_layout": "dense"}
    got = modelcfg.engine_config(cfg, {})
    assert (got.num_pages, got.kv_layout) == (300, "dense")
    cfg["engine"]["latent_cache"] = True
    with pytest.raises(ValueError) as e:
        modelcfg.engine_config(cfg, {})
    assert "other.json" in str(e.value) and "latent_cache" in str(e.value)
    with pytest.raises(ValueError):     # the cell's keys are held alike
        modelcfg.engine_config(_cfg(), {"engine": {"slots": 2}})


def _root_with(tmp_path, rel, text):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)
    return str(tmp_path)


def test_the_reference_is_the_file_the_configuration_names(tmp_path):
    mine = cells.load_reference(ROOT, _cfg())
    assert mine.__file__ == os.path.join(ROOT, "perfbench", "reference",
                                         "decoder.py")
    assert callable(mine.logprobs_of) and callable(mine.mean_loss)
    assert cells.load_reference(ROOT, _cfg()) is mine    # imported once
    # the same relative path under another root is another module
    root = _root_with(tmp_path, "perfbench/reference/decoder.py",
                      "def logprobs_of(*a):\n    return 'theirs'\n")
    theirs = cells.load_reference(root, _cfg())
    assert theirs is not mine and theirs.logprobs_of() == "theirs"


def test_a_configuration_without_a_reference_is_refused(tmp_path):
    cfg = _cfg()
    del cfg["reference"]
    with pytest.raises(KeyError) as e:
        cells.load_reference(ROOT, cfg)
    assert "other.json" in str(e.value) and "reference" in str(e.value)
    with pytest.raises(FileNotFoundError):
        cells.load_reference(str(tmp_path), _cfg())


def test_the_flop_count_is_the_named_modules_else_the_decoders(tmp_path):
    assert cells.load_flops(ROOT, _cfg()) is modelcfg.train_flops_per_token
    root = _root_with(tmp_path, "perfbench/kernels/other_flops.py",
                      "def train_flops_per_token(model, seq):\n"
                      "    return 6.0 * model * seq\n")
    count = cells.load_flops(root, _cfg(flops="perfbench/kernels/"
                                        "other_flops.py"))
    assert count(2, 3) == 36.0


def test_a_cost_function_is_found_under_the_cells_root_and_sees_the_file(
        tmp_path):
    """readers._roofline: kernels/<kernel>.py of the benchmark root the
    cell came from, handed the configuration's file as read."""
    from perfbench.harness import readers
    root = _root_with(
        tmp_path, "perfbench/kernels/latent.py",
        "def cost(ctx):\n"
        "    held = ctx['cfg']['n_routed_experts']\n"
        "    return 1e9 * held * ctx['n_events'], 1.0\n")
    rec = Record(tracing=True)
    rec.trace = {"op_self_s": {"latent_kernel": 0.5}, "op_count":
                 {"latent_kernel": 4}}
    rec.context.update(cfg={"n_routed_experts": 40},
                       peaks={"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e12})
    _root_with(tmp_path, "perfbench/metrics/latent_roofline_pct.json",
               json.dumps({"reducer": "roofline", "op_pattern": "^latent",
                           "kernel": "latent"}))
    got = readers.read_metric("latent_roofline_pct", rec,
                              os.path.join(root, "perfbench", "metrics"))
    assert got == pytest.approx(100.0 * (1e9 * 40 * 4 / 1e12) / 0.5)


def _cells_of(*kinds):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return [n for n in names
            if cells.load_cell(ROOT, n)["traffic"]["kind"] in kinds]


@pytest.mark.parametrize("cell", _cells_of("open_loop"))
def test_an_open_loop_cells_why_prints_the_rate_its_file_offers(cell):
    """BENCHMARK.json's `why` is what a reader and the driver's reviewer
    see; cells/<cell>.json's `rate_rps` is what runs. One number."""
    found = cells.load_cell(ROOT, cell)
    said = re.findall(r"(\d+(?:\.\d+)?) req/s", found["cell"]["why"])
    assert said == [format(found["cellp"]["rate_rps"], "g")], found["cell"]


@pytest.mark.parametrize("cell", _cells_of("open_loop", "closed_loop"))
def test_a_why_that_names_its_slots_names_the_engines(cell):
    found = cells.load_cell(ROOT, cell)
    said = re.findall(r"(\d+) slots", found["cell"]["why"])
    slots = modelcfg.engine_config(found["cfg"], found["cellp"]).max_slots
    assert said in ([], [str(slots)]), found["cell"]


def test_every_configuration_of_the_benchmark_passes_the_look_ups():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        found = cells.load_cell(ROOT, w["name"])
        kind = found["traffic"]["kind"]
        modelcfg.model_config(found["cfg"], kind)
        if kind != "train_job":
            modelcfg.engine_config(found["cfg"], found["cellp"])
        assert os.path.isfile(os.path.join(ROOT, found["cfg"]["reference"]))


def test_a_named_module_may_define_a_dataclass_and_a_broken_one_is_not_kept(
        tmp_path):
    root = _root_with(
        tmp_path, "perfbench/reference/shapes.py",
        "from __future__ import annotations\nimport dataclasses\n\n\n"
        "@dataclasses.dataclass(frozen=True)\nclass Shape:\n    n: int = 3\n")
    path = os.path.join(root, "perfbench", "reference", "shapes.py")
    assert cells.load_module(path).Shape().n == 3
    _root_with(tmp_path, "perfbench/reference/broken.py", "1 / 0\n")
    for _ in range(2):                 # the second try runs the file again
        with pytest.raises(ZeroDivisionError):
            cells.load_module(os.path.join(root, "perfbench", "reference",
                                           "broken.py"))
