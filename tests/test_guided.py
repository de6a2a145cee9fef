"""Guided decoding: regex engine, JSON-schema regex, token guides, and
end-to-end constrained generation through the engine.

Parity: the guided-decoding request surface the reference inherits from
vLLM (`python/ray/llm/_internal/serve/deployments/llm/vllm/` —
guided_regex / guided_json)."""

import json

import jax
import numpy as np
import pytest

from ray_tpu.llm.guided import (compile_byte_dfa, compile_json_guide,
                                compile_token_guide, json_schema_to_regex)
from ray_tpu.llm.tokenizer import ByteTokenizer


@pytest.mark.parametrize("pattern,good,bad", [
    ("abc", ["abc"], ["ab", "abcd", "abd"]),
    ("a*b", ["b", "ab", "aaab"], ["a", "ba"]),
    ("a+", ["a", "aa"], ["", "b"]),
    ("(ab|cd)+", ["ab", "cdab"], ["a", "abc"]),
    ("[a-c]x?", ["a", "bx"], ["d", "axx"]),
    ("[^0-9]", ["a", "!"], ["3", ""]),
    ("a{2,3}", ["aa", "aaa"], ["a", "aaaa"]),
    ("a{2,}", ["aa", "aaaa"], ["a"]),
    (r"\d+\.\d+", ["3.14"], ["3.", ".5"]),
    (r"-?(0|[1-9][0-9]*)", ["0", "-42", "100"], ["007", "-"]),
    (r'"[^"]*"', ['""', '"hi"'], ['"', 'hi']),
])
def test_regex_dfa(pattern, good, bad):
    dfa = compile_byte_dfa(pattern)
    for s in good:
        assert dfa.matches(s.encode()), (pattern, s)
    for s in bad:
        assert not dfa.matches(s.encode()), (pattern, s)


def test_dfa_prunes_dead_ends():
    # After 'a' the only completion is 'b'; 'x' must be disallowed even
    # though a naive NFA walk would briefly permit exploring it.
    dfa = compile_byte_dfa("ab")
    s = int(dfa.delta[0, ord("a")])
    assert s >= 0
    assert int(dfa.delta[s, ord("x")]) == -1


def test_token_guide_masks_and_advances():
    tok = ByteTokenizer()
    g = compile_token_guide("[ab]c", tok, vocab=258, eos_id=tok.eos_id)
    row0 = g.table[0]
    allowed0 = {i for i in range(258) if row0[i] >= 0}
    assert allowed0 == {ord("a"), ord("b")}
    s1 = row0[ord("a")]
    row1 = g.table[s1]
    assert {i for i in range(258) if row1[i] >= 0} == {ord("c")}
    s2 = row1[ord("c")]
    # accepting: EOS becomes legal (and nothing else in this pattern)
    assert g.table[s2, tok.eos_id] >= 0


def test_json_schema_regex_shapes():
    rx = json_schema_to_regex({
        "type": "object",
        "properties": {"name": {"type": "string"},
                       "age": {"type": "integer"},
                       "ok": {"type": "boolean"}}})
    dfa = compile_byte_dfa(rx)
    assert dfa.matches(b'{"name":"bo","age":3,"ok":true}')
    assert not dfa.matches(b'{"name":"bo"}')
    assert not dfa.matches(b'{"age":3,"name":"bo","ok":true}')


def test_json_schema_enum_array():
    rx = json_schema_to_regex({
        "type": "array", "items": {"enum": ["x", "y"]},
        "minItems": 1, "maxItems": 2})
    dfa = compile_byte_dfa(rx)
    assert dfa.matches(b'["x"]')
    assert dfa.matches(b'["x","y"]')
    assert not dfa.matches(b"[]")
    assert not dfa.matches(b'["x","y","x"]')


def test_json_guide_compiles_for_byte_tokenizer():
    tok = ByteTokenizer()
    g = compile_json_guide({"type": "object",
                            "properties": {"n": {"type": "integer"}}},
                           tok, vocab=300, eos_id=tok.eos_id)
    # initial state allows exactly '{'
    assert {i for i in range(300) if g.table[0, i] >= 0} == {ord("{")}


TINY_G = None


def _tiny():
    global TINY_G
    if TINY_G is None:
        from ray_tpu.models import ModelConfig, init_params
        cfg = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, d_ff=128, dtype="float32")
        TINY_G = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return TINY_G


def test_engine_guided_regex():
    """Constrained generation emits a string matching the pattern and
    stops at an accepting state via EOS."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    cfg, params = _tiny()
    tok = ByteTokenizer()
    g = compile_token_guide("[ab]{3}c", tok, vocab=300,
                            eos_id=tok.eos_id)
    eng = InferenceEngine(
        cfg, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                          eos_token=tok.eos_id), params=params)
    rid = eng.add_request([5, 6, 7], max_new_tokens=16, temperature=0.0,
                          guide=g)
    while eng.has_work():
        eng.step()
    out = eng.finished.pop(rid).generated
    if out and out[-1] == tok.eos_id:
        out = out[:-1]
    text = tok.decode(out)
    import re
    assert re.fullmatch(r"[ab]{3}c", text), text


def test_engine_guided_json_schema():
    """guided_json yields parseable, schema-shaped JSON from an untrained
    model — the constraint does all the work."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    cfg, params = _tiny()
    tok = ByteTokenizer()
    schema = {"type": "object",
              "properties": {"name": {"type": "string", "maxLength": 8},
                             "n": {"type": "integer"}}}
    g = compile_json_guide(schema, tok, vocab=300, eos_id=tok.eos_id)
    eng = InferenceEngine(
        cfg, EngineConfig(max_slots=2, max_len=96, prompt_buckets=(16,),
                          eos_token=tok.eos_id), params=params)
    rid = eng.add_request([10, 11, 12], max_new_tokens=64,
                          temperature=0.8)
    rid_g = eng.add_request([10, 11, 12], max_new_tokens=64,
                            temperature=0.8, guide=g)
    while eng.has_work():
        eng.step()
    out = eng.finished.pop(rid_g).generated
    if out and out[-1] == tok.eos_id:
        out = out[:-1]
    obj = json.loads(tok.decode(out))
    assert set(obj) == {"name", "n"}
    assert isinstance(obj["name"], str) and isinstance(obj["n"], int)
    # the unguided request ran concurrently and was NOT constrained
    assert eng.finished.pop(rid).generated


def test_engine_guided_survives_preemption():
    """Pool exhaustion preempts a guided slot; on re-admission the DFA
    state resumes and the final output still matches."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    cfg, params = _tiny()
    tok = ByteTokenizer()
    g = compile_token_guide("[ab]{20}c", tok, vocab=300,
                            eos_id=tok.eos_id)
    # (a victim is re-prefilled in one piece: its 3 + 22 tokens fit the
    # largest bucket)
    eng = InferenceEngine(
        cfg, EngineConfig(max_slots=4, max_len=64, prompt_buckets=(16, 32),
                          eos_token=tok.eos_id, page_size=8,
                          num_pages=10), params=params)
    rids = [eng.add_request([3 + i, 4, 5], max_new_tokens=40,
                            temperature=0.0, guide=g) for i in range(4)]
    while eng.has_work():
        eng.step()
    import re
    for rid in rids:
        out = eng.finished.pop(rid).generated
        if out and out[-1] == tok.eos_id:
            out = out[:-1]
        assert re.fullmatch("[ab]{20}c", tok.decode(out))
    assert eng.preemptions > 0


def test_openai_guided_json_http(ray_start_regular):
    """response_format json_schema over the OpenAI HTTP surface returns
    schema-valid JSON (parity: vLLM guided_json through the reference's
    serve router)."""
    import urllib.request

    from ray_tpu import serve as serve_api
    from ray_tpu.llm import EngineConfig, LLMConfig, build_openai_app
    from ray_tpu.models import ModelConfig
    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    cfg = LLMConfig(
        model_id="tiny", model=ModelConfig(
            vocab=300, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, dtype="float32"),
        engine=EngineConfig(max_slots=2, max_len=96, prompt_buckets=(32,),
                            default_max_new_tokens=48),
        tokenizer="byte")
    app = build_openai_app(cfg)
    serve_api.run(app, name="llm-guided", route_prefix="/lg")
    base = f"http://127.0.0.1:{DEFAULT_HTTP_PORT}/lg"
    try:
        schema = {"type": "object",
                  "properties": {"x": {"type": "integer", "minimum": 0,
                                       "maximum": 99},
                                 "t": {"enum": ["a", "b"]}}}
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({
                "prompt": "extract", "max_tokens": 40,
                "response_format": {
                    "type": "json_schema",
                    "json_schema": {"schema": schema}}}).encode(),
            headers={"content-type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.load(r)
        obj = json.loads(out["choices"][0]["text"])
        assert set(obj) == {"x", "t"}
        assert isinstance(obj["x"], int) and obj["t"] in ("a", "b")
    finally:
        serve_api.delete("llm-guided")


def test_integer_interval_exact_boundaries():
    """The bounded-integer automaton is EXACT: the old digit-count
    approximation admitted any value sharing the bound's digit count
    (maximum=500 accepted 999)."""
    from ray_tpu.llm.guided import json_schema_to_regex

    dfa = compile_byte_dfa(json_schema_to_regex(
        {"type": "integer", "maximum": 500}))
    assert dfa.matches(b"500")
    assert not dfa.matches(b"501")
    assert not dfa.matches(b"999")
    assert dfa.matches(b"0") and dfa.matches(b"499")
    assert dfa.matches(b"-999")  # no minimum: unbounded below

    dfa = compile_byte_dfa(json_schema_to_regex(
        {"type": "integer", "minimum": 0, "maximum": 500}))
    assert not dfa.matches(b"-1") and not dfa.matches(b"501")
    assert dfa.matches(b"0") and dfa.matches(b"500")
    assert not dfa.matches(b"007")  # canonical decimals only

    # negative-straddling interval, exhaustive over the decision range
    dfa = compile_byte_dfa(json_schema_to_regex(
        {"type": "integer", "minimum": -12, "maximum": 34}))
    for v in range(-60, 61):
        assert dfa.matches(str(v).encode()) == (-12 <= v <= 34), v

    # minimum alone is exact too (and still unbounded above)
    dfa = compile_byte_dfa(json_schema_to_regex(
        {"type": "integer", "minimum": 7}))
    assert not dfa.matches(b"6") and dfa.matches(b"7")
    assert dfa.matches(b"70") and dfa.matches(b"123456789")
    assert not dfa.matches(b"-7")


def test_integer_interval_inside_object_schema():
    """Bounded integers compose into object schemas (the serve-surface
    path that hits json_schema_to_regex end to end)."""
    from ray_tpu.llm.guided import json_schema_to_regex

    rx = json_schema_to_regex({
        "type": "object",
        "properties": {"score": {"type": "integer", "minimum": 1,
                                 "maximum": 10}}})
    dfa = compile_byte_dfa(rx)
    assert dfa.matches(b'{"score":10}')
    assert dfa.matches(b'{"score":1}')
    assert not dfa.matches(b'{"score":0}')
    assert not dfa.matches(b'{"score":11}')
    assert not dfa.matches(b'{"score":99}')
