"""LLM stack tests: engine numerics, continuous batching, TP sharding,
LoRA, serving (OpenAI surface), batch processor.

Parity: reference llm tests (`python/ray/llm/tests/`) — engine behavior,
router contract, multiplexing."""

import json
from functools import partial

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, InferenceEngine, LLMConfig
from ray_tpu.llm.engine import (decode_paged, insert_pages_batch,
                                prefill_batch, prefill_with_prefix_batch,
                                sample)
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import ModelConfig, forward, init_params

# Engine tests jit-compile prefill/decode graphs per config — the
# compile-heavy tier. `-m "not heavy"` skips them to contain full-suite
# wall time; nothing here is excluded from the full run.
pytestmark = pytest.mark.heavy

TINY = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128, dtype="float32")


@pytest.fixture(scope="module")
def tiny_params(tiny_llm_params):
    # Session-shared params (conftest.py): identical TINY config across
    # the LLM test files, initialized once per test run.
    cfg, params = tiny_llm_params
    assert cfg == TINY
    return params


# One JITTED reference forward per model config: the bare `forward` runs
# EAGERLY (hundreds of per-op dispatches, ~0.45s/call on this box), which
# made the naive-greedy verifications the single biggest cost in this
# file (~80 calls = ~36s in the pool-exhaustion test alone).
_FWD_JIT: dict = {}


def _jit_forward(config):
    fn = _FWD_JIT.get(id(config))
    if fn is None:
        fn = _FWD_JIT[id(config)] = jax.jit(
            lambda p, t: forward(p, t, config))
    return fn


def _naive_greedy(params, prompt, n, config=TINY):
    """Reference greedy decode via the full forward. Fixed-length right
    padding (attention is causal, so the pad tail is inert) + the jitted
    forward above: every step and every caller shares ONE compiled
    executable instead of paying eager dispatch per token."""
    fwd = _jit_forward(config)
    seq = list(prompt)
    out = []
    pad_to = 64
    while len(prompt) + n > pad_to:
        pad_to += 32
    for _ in range(n):
        padded = seq + [0] * (pad_to - len(seq))
        logits = fwd(params, jnp.asarray([padded]))
        nxt = int(jnp.argmax(logits[0, len(seq) - 1]))
        out.append(nxt)
        seq.append(nxt)
    return out


def test_engine_matches_naive_greedy(tiny_params):
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=4, max_len=64, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13], [3, 1, 4, 1, 5, 9, 2, 6]]
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    for p, got in zip(prompts, outs):
        assert got == _naive_greedy(tiny_params, p, 6)


def test_engine_streams_more_prompts_than_slots(tiny_params):
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=48, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    outs = eng.generate([[i + 1, i + 2] for i in range(7)],
                        max_new_tokens=3)
    assert len(outs) == 7 and all(len(o) == 3 for o in outs)


def test_engine_tp_mesh_matches_single_device(tiny_params):
    """TP=2 over the CPU mesh must produce the single-device tokens."""
    from ray_tpu.parallel import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(tp=2, fsdp=1, dp=1),
                     devices=jax.devices()[:2], axis_names=("dp", "fsdp",
                                                            "pp", "sp",
                                                            "tp", "ep"))
    single = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=48, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    sharded = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=48, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params, mesh=mesh)
    prompts = [[7, 8, 9], [20, 21]]
    a = single.generate(prompts, max_new_tokens=5, temperature=0.0)
    b = sharded.generate(prompts, max_new_tokens=5, temperature=0.0)
    assert a == b


def test_engine_moe_model_matches_naive_greedy():
    """The MoE model family decodes through the same engine (top-k routing
    runs inside the jitted prefill/decode steps)."""
    moe = ModelConfig(vocab=200, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, d_ff=96, moe_experts=4, moe_top_k=2,
                      dtype="float32")
    params = init_params(moe, jax.random.PRNGKey(3))
    eng = InferenceEngine(
        moe, EngineConfig(max_slots=2, max_len=48, prompt_buckets=(16,),
                          eos_token=-1), params=params)
    prompts = [[4, 5, 6], [11, 12]]
    outs = eng.generate(prompts, max_new_tokens=5, temperature=0.0)
    for p, got in zip(prompts, outs):
        assert got == _naive_greedy(params, p, 5, config=moe)


def test_sampling_temperature_zero_is_greedy():
    logits = jnp.asarray([[1.0, 5.0, 2.0], [0.1, 0.2, 9.0]])
    t = sample(logits, jnp.asarray([0.0, 0.0]), jax.random.PRNGKey(0))
    assert t.tolist() == [1, 2]


def test_eos_stops_generation(tiny_params):
    """Force eos = the greedy first token of a prompt: generation stops."""
    first = _naive_greedy(tiny_params, [5, 6, 7], 1)[0]
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                           eos_token=first), params=tiny_params)
    (out,) = eng.generate([[5, 6, 7]], max_new_tokens=10)
    assert out == []  # eos produced immediately and stripped


def test_lora_merge_changes_outputs(tiny_params):
    from ray_tpu.llm.lora import init_lora, merge_lora
    lora = init_lora(TINY, rank=4, key=jax.random.PRNGKey(1))
    merged = merge_lora(tiny_params, lora, alpha=16.0)
    # B=0 -> identity
    for t in ("wq", "wk", "wv", "wo"):
        np.testing.assert_allclose(merged["layers"][t],
                                   tiny_params["layers"][t])
    lora["wq"]["B"] = jax.random.normal(
        jax.random.PRNGKey(2), lora["wq"]["B"].shape) * 0.1
    merged = merge_lora(tiny_params, lora, alpha=16.0)
    assert not np.allclose(merged["layers"]["wq"],
                           tiny_params["layers"]["wq"])


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello TPU")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello TPU"


def _llm_config():
    return LLMConfig(
        model_id="tiny", model=TINY,
        engine=EngineConfig(max_slots=2, max_len=64, prompt_buckets=(32,),
                            eos_token=-1, default_max_new_tokens=4),
        tokenizer="byte")


@pytest.fixture(scope="module")
def openai_llm_app(ray_start_regular):
    """ONE OpenAI app over the shared tiny config for every read-only
    HTTP surface test in this module — each private serve.run/delete
    cycle paid a ~4s replica boot for an identical app. Yields the
    route prefix."""
    from ray_tpu import serve as serve_api
    from ray_tpu.llm import build_openai_app

    serve_api.run(build_openai_app(_llm_config()), name="llm-shared",
                  route_prefix="/llmshared")
    yield "/llmshared"
    serve_api.delete("llm-shared")


def test_openai_serve_app(openai_llm_app):
    """serve.run(build_openai_app(...)) then speak OpenAI over HTTP."""
    import urllib.request

    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    base = f"http://127.0.0.1:{DEFAULT_HTTP_PORT}{openai_llm_app}"
    with urllib.request.urlopen(base + "/v1/models", timeout=30) as r:
        models = json.load(r)
    assert models["data"][0]["id"] == "tiny"

    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 3}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.load(r)
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] == 3

    req = urllib.request.Request(
        base + "/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 2}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.load(r)
    assert out["choices"][0]["message"]["role"] == "assistant"


def test_serve_lora_adapters(ray_start_regular):
    """Registered adapters serve on any replica; unknown ids 400."""
    import urllib.error
    import urllib.request

    from ray_tpu import serve as serve_api
    from ray_tpu.llm import LoraConfig, build_openai_app
    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    cfg = _llm_config()
    cfg.lora = LoraConfig(rank=2)
    app = build_openai_app(cfg)
    serve_api.run(app, name="llm-lora", route_prefix="/lora")
    base = f"http://127.0.0.1:{DEFAULT_HTTP_PORT}/lora"
    try:
        handle = serve_api.get_deployment_handle("LLMServer:tiny",
                                                 "llm-lora")
        handle.load_adapter.remote("tiny-ft").result(timeout_s=60)

        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"prompt": "x", "max_tokens": 2,
                             "model": "tiny-ft"}).encode(),
            headers={"content-type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.load(r)
        assert out["model"] == "tiny-ft"

        bad = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"prompt": "x", "model": "no-such"}).encode(),
            headers={"content-type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(bad, timeout=60)
        assert e.value.code == 400
    finally:
        serve_api.delete("llm-lora")


def test_batch_processor(ray_start_regular):
    import ray_tpu.data as rd
    from ray_tpu.llm import build_llm_processor

    ds = rd.from_items([{"prompt": f"p{i}"} for i in range(6)])
    processor = build_llm_processor(_llm_config(), max_new_tokens=2,
                                    batch_size=3)
    rows = processor(ds).take_all()
    assert len(rows) == 6
    assert all("generated" in r for r in rows)


def test_top_k_top_p_sampling_masks():
    """top_k=1 must reduce to greedy even at high temperature; top_p ~0
    likewise (the nucleus keeps only the argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import sample

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, -1))
    key = jax.random.PRNGKey(0)
    hot = jnp.full((4,), 5.0)  # temperature 5: near-uniform without masks
    out_k1 = np.asarray(sample(logits, hot, key,
                               jnp.ones(4), jnp.full((4,), 1)))
    assert (out_k1 == greedy).all()
    out_p0 = np.asarray(sample(logits, hot, key,
                               jnp.full((4,), 1e-6), jnp.zeros(4, jnp.int32)))
    assert (out_p0 == greedy).all()
    # unconstrained hot sampling really does deviate (sanity)
    outs = set()
    for i in range(8):
        k = jax.random.PRNGKey(i)
        outs.add(tuple(np.asarray(sample(
            logits, hot, k, jnp.ones(4), jnp.zeros(4, jnp.int32)))))
    assert len(outs) > 1


def test_engine_top_k_request(tiny_params):
    """Engine threads per-request top_k through prefill + decode."""
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    rid = eng.add_request([1, 2, 3], max_new_tokens=4, temperature=2.0,
                          top_k=1)
    while eng.has_work():
        eng.step()
    req = eng.finished.pop(rid)
    assert len(req.generated) >= 1


def test_openai_stream_sse(openai_llm_app):
    """stream=true serves SSE chunks; first delta arrives before [DONE]
    (end-to-end token streaming: engine pump -> streaming actor method ->
    router __stream__ -> proxy chunked response)."""
    import http.client

    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    body = json.dumps({"prompt": "hi", "max_tokens": 4,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", DEFAULT_HTTP_PORT,
                                      timeout=120)
    conn.request("POST", f"{openai_llm_app}/v1/completions", body=body,
                 headers={"content-type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.headers.get("content-type", "").startswith(
        "text/event-stream")
    raw = resp.read().decode()
    conn.close()
    events = [ln for ln in raw.splitlines() if ln.startswith("data: ")]
    assert events[-1] == "data: [DONE]"
    chunks = [json.loads(e[6:]) for e in events[:-1]]
    assert chunks, raw
    assert chunks[0]["object"] == "text_completion"
    assert all(c["choices"][0]["finish_reason"] is None for c in chunks)
    # Non-stream requests on the same app still return plain JSON.
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{DEFAULT_HTTP_PORT}{openai_llm_app}"
        "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 2}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.load(r)
    assert out["object"] == "text_completion"


def test_paged_kv_growth_beyond_initial_pages(tiny_params):
    """A sequence grows past its prompt's page allocation: new pages are
    appended from the pool mid-decode and greedy output stays exact
    (parity: vLLM block-table growth, vllm_models.py:123-137)."""
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                           eos_token=-1, page_size=8), params=tiny_params)
    prompt = [5, 6, 7, 8, 9]
    out = eng.generate([prompt], max_new_tokens=30, temperature=0.0)[0]
    assert out == _naive_greedy(tiny_params, prompt, 30)
    # 5 + 30 tokens at page_size 8 -> at least 5 pages were chained.
    stats = eng.kv_stats()
    assert stats["layout"] == "paged"
    # Finished: owned unregistered pages freed, full prompt/decode pages
    # may stay cached; nothing is still "in use".
    assert stats["pages_in_use"] == 0


def test_paged_prefix_cache_reuses_pages(tiny_params):
    """Two prompts sharing a long prefix: the second admission borrows the
    cached prefix pages (prefill runs only on the suffix) and produces
    exactly the same tokens as the uncached path."""
    cfg = EngineConfig(max_slots=2, max_len=96, prompt_buckets=(16, 32),
                       eos_token=-1, page_size=8)
    shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]  # 2 pages
    p1 = shared + [2, 3]
    p2 = shared + [11, 12, 13]
    eng = InferenceEngine(TINY, cfg, params=tiny_params)
    out1 = eng.generate([p1], max_new_tokens=8, temperature=0.0)[0]
    assert eng.kv_stats()["prefix_hits"] == 0
    out2 = eng.generate([p2], max_new_tokens=8, temperature=0.0)[0]
    assert eng.kv_stats()["prefix_hits"] == 1
    assert out1 == _naive_greedy(tiny_params, p1, 8)
    assert out2 == _naive_greedy(tiny_params, p2, 8)


def test_paged_pool_exhaustion_preempts_and_completes(tiny_params):
    """A pool far smaller than slots x max_len: concurrent sequences
    preempt (vLLM recompute semantics) yet every request finishes with
    exact greedy output."""
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=4, max_len=64, prompt_buckets=(16,),
                           eos_token=-1, page_size=8, num_pages=10),
        params=tiny_params)
    prompts = [[5, 6, 7], [9, 10, 11], [3, 1, 4, 1, 5], [2, 7, 1, 8]]
    outs = eng.generate(prompts, max_new_tokens=20, temperature=0.0)
    for p, got in zip(prompts, outs):
        assert got == _naive_greedy(tiny_params, p, 20)
    assert eng.kv_stats()["preemptions"] > 0


def test_chunked_prefill_long_prompt_exact(tiny_params):
    """A prompt longer than every prompt bucket admits chunk by chunk
    (one page-aligned chunk per engine step, interleaved with decode of
    other slots) and still produces exact greedy tokens. Parity: vLLM
    chunked prefill."""
    cfg = EngineConfig(max_slots=2, max_len=128, prompt_buckets=(16,),
                       eos_token=-1, page_size=16)
    eng = InferenceEngine(TINY, cfg, params=tiny_params)
    rng = np.random.default_rng(3)
    long_prompt = [int(t) for t in rng.integers(1, 250, 60)]  # 60 > 16
    short = [5, 6, 7]
    outs = eng.generate([long_prompt, short], max_new_tokens=6,
                        temperature=0.0)
    assert outs[0] == _naive_greedy(tiny_params, long_prompt, 6)
    assert outs[1] == _naive_greedy(tiny_params, short, 6)
    # chunk continuations resume through the prefix cache
    assert eng.kv_stats()["prefix_hits"] >= 3


def test_chunked_prefill_interleaves_with_decode(tiny_params):
    """While a long prompt admits chunk-by-chunk, an already-running slot
    keeps emitting tokens between chunks."""
    cfg = EngineConfig(max_slots=2, max_len=128, prompt_buckets=(16,),
                       eos_token=-1, page_size=16)
    eng = InferenceEngine(TINY, cfg, params=tiny_params)
    rng = np.random.default_rng(4)
    long_prompt = [int(t) for t in rng.integers(1, 250, 60)]
    r_long = eng.add_request(long_prompt, max_new_tokens=4,
                             temperature=0.0)
    r_short = eng.add_request([5, 6, 7], max_new_tokens=30,
                              temperature=0.0)

    def short_progress():
        for i in range(cfg.max_slots):
            r = eng.slot_req[i]
            if r is not None and r.request_id == r_short:
                return len(r.generated)
        r = eng.finished.get(r_short)
        return len(r.generated) if r else 0

    progressed_during_admission = False
    prev = 0
    while eng.has_work():
        eng.step()
        cur = short_progress()
        if eng.queue and cur > prev:
            # the long prompt is still chunk-admitting, yet the short
            # slot emitted tokens this step
            progressed_during_admission = True
        prev = cur
    assert progressed_during_admission
    assert (eng.finished[r_long].generated
            == _naive_greedy(tiny_params, long_prompt, 4))


def test_openai_stop_sequences(openai_llm_app):
    """OpenAI `stop` truncates at the earliest stop string and reports
    finish_reason=stop (parity: the reference's OpenAI surface)."""
    import urllib.request

    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    base = f"http://127.0.0.1:{DEFAULT_HTTP_PORT}{openai_llm_app}"
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 8}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        full = json.load(r)["choices"][0]["text"]
    assert len(full) >= 2
    stop_at = full[1]  # use the 2nd generated char as the stop seq
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 8,
                         "stop": [stop_at]}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.load(r)
    cut = out["choices"][0]["text"]
    assert stop_at not in cut and full.startswith(cut)
    assert out["choices"][0]["finish_reason"] == "stop"


def test_engine_logprobs_match_forward(tiny_params):
    """logprobs=True collects log p(token) per generated token; greedy
    values must match a naive full-forward log_softmax (parity: the
    OpenAI logprobs surface the reference serves through vLLM)."""
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    prompt = [5, 6, 7]
    rid = eng.add_request(prompt, max_new_tokens=5, temperature=0.0,
                          logprobs=True)
    while eng.has_work():
        eng.step()
    req = eng.finished.pop(rid)
    assert len(req.token_logprobs) == len(req.generated) == 5
    # naive reference (jitted fixed-length forward — see _naive_greedy)
    fwd = _jit_forward(TINY)
    seq = list(prompt)
    for tok, lp in zip(req.generated, req.token_logprobs):
        padded = seq + [0] * (64 - len(seq))
        logits = fwd(tiny_params, jnp.asarray([padded]))[0, len(seq) - 1]
        want = float(jax.nn.log_softmax(logits)[tok])
        assert abs(lp - want) < 1e-3, (lp, want)
        seq.append(tok)
    assert all(lp <= 0.0 for lp in req.token_logprobs)


def test_openai_logprobs_surface(openai_llm_app):
    import urllib.request

    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    req = urllib.request.Request(
        f"http://127.0.0.1:{DEFAULT_HTTP_PORT}{openai_llm_app}"
        "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 3,
                         "logprobs": True}).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.load(r)
    lp = out["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 3
    assert len(lp["tokens"]) == 3
    # ids alongside the (lossy, byte-tokenizer) strings
    assert len(lp["token_ids"]) == 3
    assert all(isinstance(t, int) for t in lp["token_ids"])
    assert all(x <= 0.0 for x in lp["token_logprobs"])


def test_openai_stream_stop_sequences(openai_llm_app):
    """stream=true with stop: the SSE stream ends at the stop string and
    never emits it (including stop strings straddling token
    boundaries)."""
    import http.client

    from ray_tpu.serve.config import DEFAULT_HTTP_PORT

    def run(body_extra):
        body = json.dumps({"prompt": "hi", "max_tokens": 8,
                           "stream": True, **body_extra}).encode()
        conn = http.client.HTTPConnection(
            "127.0.0.1", DEFAULT_HTTP_PORT, timeout=120)
        conn.request("POST", f"{openai_llm_app}/v1/completions",
                     body=body,
                     headers={"content-type": "application/json"})
        raw = conn.getresponse().read().decode()
        conn.close()
        chunks = [json.loads(e[6:]) for e in raw.splitlines()
                  if e.startswith("data: ") and e != "data: [DONE]"]
        return "".join(c["choices"][0]["text"] for c in chunks)

    full = run({})
    assert len(full) >= 2
    stop_at = full[1]
    cut = run({"stop": [stop_at]})
    assert stop_at not in cut and full.startswith(cut)


def test_engine_cancel_frees_slot_and_finishes(tiny_params):
    """cancel() drops a queued request and aborts an active slot with
    its generated-so-far; pages release (no leak)."""
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=1, max_len=64, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    r_active = eng.add_request([5, 6, 7], max_new_tokens=50,
                               temperature=0.0)
    r_queued = eng.add_request([8, 9], max_new_tokens=50, temperature=0.0)
    for _ in range(3):
        eng.step()
    assert eng.active.any()
    eng.cancel(r_active)
    eng.cancel(r_queued)
    eng.step()
    assert r_active in eng.finished and r_queued in eng.finished
    assert len(eng.finished[r_active].generated) >= 1
    assert eng.finished[r_queued].generated == []
    assert not eng.active.any()
    assert eng.kv_stats()["pages_in_use"] == 0


def test_stream_utf8_boundary_holdback():
    """A multi-byte char whose bytes straddle stream chunks must NOT emit
    replacement chars mid-stream: the incomplete tail is held back until
    its continuation bytes arrive (ROADMAP leftover — the token plane was
    exact, the text plane emitted U+FFFD). Driven through the real
    completions_stream generator with a controlled token feed."""
    import threading
    import time as time_mod

    from ray_tpu.llm.serve import _LLMServerImpl

    impl = _LLMServerImpl.__new__(_LLMServerImpl)
    impl.tokenizer = ByteTokenizer()
    impl._lock = threading.Lock()
    impl._token_subs = {}
    impl._discard = set()

    class _Eng:
        params = None
        finished = {}

        def add_request(self, ids, *a, **k):
            return 1

        def cancel(self, rid):
            raise AssertionError("clean end must not cancel")

    impl.engine = _Eng()
    impl._params_for = lambda model: None

    payload = "a😀é!"  # 4-byte and 2-byte chars straddling byte-tokens

    def feed():
        deadline = time_mod.monotonic() + 10
        while 1 not in impl._token_subs:
            if time_mod.monotonic() > deadline:
                return
            time_mod.sleep(0.005)
        q = impl._token_subs[1]
        for b in payload.encode("utf-8"):
            q.put(b)
        q.put(None)

    threading.Thread(target=feed, daemon=True).start()
    deltas = list(impl.completions_stream("hi", max_tokens=16))
    assert "".join(deltas) == payload
    assert all("�" not in d for d in deltas), deltas


def test_stream_early_stop_no_leak():
    """A stream cut by a stop sequence cancels the engine request: the
    decode slot frees, no finished record strands on the replica, and
    the pump discards the cancelled request's record."""
    import time as time_mod

    from ray_tpu.llm.serve import _LLMServerImpl

    impl = _LLMServerImpl(_llm_config())
    try:
        # discover a stop character from an unconstrained stream
        full = "".join(impl.completions_stream("hi", 6, 0.0))
        assert len(full) >= 2
        stop_at = full[1]
        out = "".join(impl.completions_stream("hi", 6, 0.0,
                                              stop=[stop_at]))
        assert stop_at not in out and full.startswith(out)
        deadline = time_mod.monotonic() + 30
        while time_mod.monotonic() < deadline:
            if (not impl.engine.finished and not impl._discard
                    and not impl.engine.active.any()):
                break
            time_mod.sleep(0.2)
        assert impl.engine.finished == {}
        assert not impl._discard
        assert not impl.engine.active.any()
        assert impl.engine.kv_stats()["pages_in_use"] == 0
    finally:
        impl._stop = True


def test_serve_tp2_decode_identical_to_tp1(monkeypatch):
    """The SERVING path's tensor-parallelism wiring (serve.py builds the
    tp mesh from LLMConfig.tensor_parallelism): greedy decode through the
    OpenAI surface under tp=2 must be bit-identical to tp=1. Runs the XLA
    fallback attention formulation — the same path the multichip dryrun
    gates on (`llm tp=2 ok`)."""
    import asyncio

    from ray_tpu.llm.serve import _LLMServerImpl

    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", "xla")

    def run(tp):
        cfg = LLMConfig(
            model_id="tiny", model=TINY,
            engine=EngineConfig(max_slots=2, max_len=48,
                                prompt_buckets=(16,), eos_token=-1),
            tokenizer="byte", tensor_parallelism=tp, seed=0)
        srv = _LLMServerImpl(cfg)
        try:
            out = asyncio.run(srv.completions("hello tp", max_tokens=5,
                                              temperature=0.0))
        finally:
            srv._stop = True
        return out["choices"][0]["text"]

    assert run(2) == run(1)


def test_decode_steady_state_no_recompiles(tiny_params):
    """The dynamic half of graphcheck finding class 3: after warmup, 8
    decode steps in one page bucket must not touch the compiler — any
    increment of the process-global jit-miss counter is a recompile
    hazard (weak-type fork, unstable static, shape wobble) that static
    analysis can only flag as a maybe."""
    from ray_tpu import diagnostics
    eng = InferenceEngine(
        TINY, EngineConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                           eos_token=-1), params=tiny_params)
    eng.add_request([5, 6, 7], max_new_tokens=16)
    eng.add_request([9, 10, 11, 12], max_new_tokens=16)
    for _ in range(3):   # admission + prefill + first decode variants
        eng.step()
    base = diagnostics.jit_misses()
    for _ in range(8):
        eng.step()
    assert diagnostics.jit_misses() == base, \
        "steady-state decode recompiled"


# ---- the paged decode program, as a pure function ----

_PAGED_CONFIGS = {
    "dense": TINY,
    "moe": ModelConfig(vocab=200, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, d_ff=96, moe_experts=4, moe_top_k=2,
                       dtype="float32"),
}
_PAGE, _SLOTS, _TABLE = 16, 2, 2


def _paged_args(c, page=_PAGE):
    """(params, pool_k, pool_v, page_tables) for `_SLOTS` slots of
    `_TABLE` pages each; page 0 is the engine's scratch page."""
    params = init_params(c, jax.random.PRNGKey(7))
    pool = jnp.zeros((c.n_layers, c.n_kv_heads, 1 + _SLOTS * _TABLE,
                      c.head_dim, page), jnp.float32)
    tables = 1 + jnp.arange(_SLOTS * _TABLE, dtype=jnp.int32).reshape(
        _SLOTS, _TABLE)
    return params, pool, pool, tables


# The prefill programs run the head at each request's last token only, so
# a row of "the logits prefill would give" is one call at that length:
# ragged inside a batch, and over the cases every row the decode steps
# below produce (positions 0..7) for both slots.
_LAST = [(1, 8), (2, 7), (3, 6), (4, 5), (5, 4), (6, 3), (7, 2), (8, 1)]


@pytest.mark.parametrize("lengths", _LAST, ids=lambda l: "len%d_%d" % l)
@pytest.mark.parametrize("family", sorted(_PAGED_CONFIGS))
def test_paged_decode_reproduces_prefill_logits(family, lengths):
    """Token-by-token `decode_paged` against `prefill_batch` read at the
    same positions: the two programs call the one block with another
    attention each and must stay one function of the same
    `params["layers"]` leaves."""
    c = _PAGED_CONFIGS[family]
    params, pool_k, pool_v, tables = _paged_args(c)
    n_decode = 8
    tokens = jax.random.randint(jax.random.PRNGKey(11),
                                (_SLOTS, n_decode), 1, c.vocab)
    lengths = jnp.asarray(lengths, jnp.int32)
    want, _, _ = jax.jit(partial(prefill_batch, config=c))(
        params, tokens, lengths)
    assert want.shape == (_SLOTS, c.vocab)
    decode = jax.jit(partial(decode_paged, config=c))
    active = jnp.ones((_SLOTS,), jnp.bool_)
    rows = []                                       # [position][slot, vocab]
    for t in range(n_decode):
        got, pool_k, pool_v = decode(
            params, pool_k, pool_v, tokens[:, t],
            jnp.full((_SLOTS,), t, jnp.int32), active, tables)
        rows.append(got)
    got = jnp.stack(rows, 1)                        # [slot, position, vocab]
    np.testing.assert_allclose(
        jnp.take_along_axis(got, (lengths - 1)[:, None, None], 1)[:, 0],
        want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("lengths", _LAST, ids=lambda l: "len%d_%d" % l)
@pytest.mark.parametrize("family", sorted(_PAGED_CONFIGS))
def test_prefix_prefill_reproduces_prefill_logits(family, lengths):
    """A prompt whose page-aligned prefix lies in the pools
    (`insert_pages_batch`) and whose suffix goes through
    `prefill_with_prefix_batch`, against `prefill_batch` over the whole
    prompt: the logits at the suffix's last token, and the same suffix K
    and V. The two requests cache one page and two."""
    c = _PAGED_CONFIGS[family]
    params, pool_k, pool_v, tables = _paged_args(c)
    n_suffix = 8
    cached = jnp.asarray([_PAGE, _TABLE * _PAGE], jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    tokens = jax.random.randint(
        jax.random.PRNGKey(13), (_SLOTS, _TABLE * _PAGE + n_suffix), 1,
        c.vocab)
    want, ks, vs = jax.jit(partial(prefill_batch, config=c))(
        params, tokens, cached + lengths)
    pool_k, pool_v = jax.jit(insert_pages_batch)(
        pool_k, pool_v, ks[:, :, :_TABLE * _PAGE], vs[:, :, :_TABLE * _PAGE],
        tables, cached)
    rows = cached[:, None] + jnp.arange(n_suffix)[None]       # [n, S]
    got, got_k, got_v = jax.jit(
        partial(prefill_with_prefix_batch, config=c))(
        params, jnp.take_along_axis(tokens, rows, 1), lengths, pool_k,
        pool_v, tables, cached)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    for new, whole in ((got_k, ks), (got_v, vs)):
        np.testing.assert_allclose(
            new, jnp.take_along_axis(whole, rows[None, :, :, None, None], 2),
            atol=1e-5, rtol=0)


def _offences(jaxpr, tainted, is_view, offends):
    """Equations of `jaxpr` that read a tainted var and that
    `offends(primitive, output shapes)` names: [(primitive, shapes)].
    `tainted` is a set of jaxpr vars; the outputs of an equation that
    `is_view(primitive, positions of its tainted inputs)` accepts join
    it, as do the outputs a kernel aliases to its tainted inputs (the
    pools a fused insert writes where they lie), and a call's inner jaxpr
    is walked with the taint carried in and out."""
    found = []
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if isinstance(v, jex_core.Var) and v in tainted]
        if not hit:
            continue
        name = eqn.primitive.name
        shapes = [v.aval.shape for v in eqn.outvars]
        inner = [p for p in eqn.params.values()
                 if isinstance(p, (jex_core.Jaxpr, jex_core.ClosedJaxpr))]
        if is_view(name, hit):
            tainted.update(eqn.outvars)
        elif name == "pallas_call":
            tainted.update(eqn.outvars[o] for i, o in
                           eqn.params["input_output_aliases"] if i in hit)
        elif len(inner) == 1 and name in ("pjit", "jit", "closed_call",
                                          "custom_jvp_call"):
            sub = getattr(inner[0], "jaxpr", inner[0])
            inside = tainted | {sub.invars[i] for i in hit}
            found += _offences(sub, inside, is_view, offends)
            tainted.update(out for out, var in zip(eqn.outvars, sub.outvars)
                           if var in inside)
        elif offends(name, shapes):
            found.append((name, shapes))
    return found


# What may touch a layer's weight matrix besides the matmul that reads it
# (`dot_general`): picking the layer out of the stack, a view of the leaf.
_WEIGHT_VIEWS = {"slice", "squeeze", "dynamic_slice", "reshape"}


def _weight_copies(jaxpr, weights):
    """Equations that build an array out of a weight matrix rather than
    read it. `weights`: the jaxpr vars that are `params["layers"]`
    matrices."""
    return _offences(jaxpr, weights, lambda name, _: name in _WEIGHT_VIEWS,
                     lambda name, _: name != "dot_general")


@pytest.mark.parametrize("program", ["decode"])   # the ids the records name
@pytest.mark.parametrize("family", sorted(_PAGED_CONFIGS))
def test_paged_programs_read_weights_in_place(family, program):
    """The program builds no weight-sized array: every matrix of
    `params["layers"]` goes from its per-layer view straight into a
    matmul. A `concatenate` of `wq|wk|wv` or `wg|wu` inside the jit is a
    read and a write of those weights on EVERY token — the replica's pump
    runs one `decode_paged` per token, so nothing hoists it (it was 10.8
    of qwen2_7b's 30.2 ms decode step on the chip, ledger PR 23)."""
    c = _PAGED_CONFIGS[family]
    params, pool_k, pool_v, tables = _paged_args(c)
    lengths = jnp.zeros((_SLOTS,), jnp.int32)
    active = jnp.ones((_SLOTS,), jnp.bool_)
    tokens = jnp.ones((_SLOTS,), jnp.int32)
    args = (params, pool_k, pool_v, tokens, lengths, active, tables)
    closed = jax.make_jaxpr(partial(decode_paged, config=c))(*args)
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    weights = {
        var for (path, leaf), var in zip(leaves, closed.jaxpr.invars)
        if jax.tree_util.keystr(path).startswith("[0]['layers']")
        and leaf.ndim >= 3}                    # [L, in, out]: a matrix
    assert len(weights) == (8 if c.moe_experts else 7)
    assert _weight_copies(closed.jaxpr, weights) == []


def _pool_touches(jaxpr, pools, per_layer):
    """Equations that scatter into a KV pool, update a slice of one or
    build a `per_layer`-shaped array out of one (`pool[li]`, with or
    without its unit axis). `pools`: the jaxpr vars that are a pool; what a
    kernel aliases to a pool is a pool (`_offences`)."""
    size = int(np.prod(per_layer))
    return _offences(
        jaxpr, pools, lambda name, hit: False,
        lambda name, shapes: name.startswith("scatter") or (
            name == "dynamic_update_slice") or any(
            s[-len(per_layer):] == per_layer and int(np.prod(s)) == size
            for s in shapes))


@pytest.mark.parametrize("family", sorted(_PAGED_CONFIGS))
def test_decode_paged_touches_its_pools_in_place(family, monkeypatch):
    """`decode_paged` hands the kernel the stacked pools and takes them
    back from it, the new token's K and V written by the kernel where the
    pools lie (a call a layer, both pools aliased): no `scatter` over a
    pool, no `dynamic_update_slice` into one and no per-layer view
    `pool[li]`. The scatter indexed the pool's minor (page) axis, so on
    the chip XLA re-laid the whole pool out and back and re-tiled a
    `pool[li]` slice for every layer's kernel call, on every token (ledger
    PR 28: `copy.*` of `bf16[12,4,257,128,128]`, 23 % of qwen2_7b's busy
    time); the 384 column updates a step that followed were 0.86 ms of its
    10.5 (PERF.md section 5, PR 46). The program traced is the CHIP's
    (`make_jaxpr` only traces): off it the call falls back to a scatter,
    which no cell runs."""
    # the dispatcher asks the backend whether to interpret the kernel, and
    # Mosaic tiles pages of 128 tokens alone
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = _PAGED_CONFIGS[family]
    params, pool_k, pool_v, tables = _paged_args(c, page=128)
    args = (params, pool_k, pool_v, jnp.ones((_SLOTS,), jnp.int32),
            jnp.zeros((_SLOTS,), jnp.int32), jnp.ones((_SLOTS,), jnp.bool_),
            tables)
    closed = jax.make_jaxpr(partial(decode_paged, config=c))(*args)
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    pools = {var for (path, _), var in zip(leaves, closed.jaxpr.invars)
             if jax.tree_util.keystr(path) in ("[1]", "[2]")}
    assert len(pools) == 2
    assert _pool_touches(closed.jaxpr, pools, pool_k.shape[1:]) == []
    calls = [e for e in closed.jaxpr.eqns
             if e.params.get("name") == "_decode_insert_dma"]
    assert len(calls) == c.n_layers
    for call in calls:
        (kernel,) = [e for e in call.params["jaxpr"].jaxpr.eqns
                     if e.primitive.name == "pallas_call"]
        assert len(kernel.params["input_output_aliases"]) == 2
    assert closed.jaxpr.outvars[1] in pools and (
        closed.jaxpr.outvars[2] in pools)     # the taint reached the end
    # the guard sees what it guards against: the three expressions the
    # program had before (PR 29's scatter and view, PR 46's column update)
    old = jax.make_jaxpr(lambda p: (
        p.at[0, jnp.arange(2)[:, None], jnp.array([[1, 2]]), :,
             jnp.array([[0, 3]])].set(1.0), p[1],
        jax.lax.dynamic_update_slice(
            p, jnp.ones((1, p.shape[1], 1, p.shape[3], 1), p.dtype),
            (0, 0, 1, 0, 3))))(pool_k)
    assert sorted(n for n, _ in _pool_touches(
        old.jaxpr, set(old.jaxpr.invars), pool_k.shape[1:])) == [
            "dynamic_update_slice", "scatter", "slice"]


def _decode_paged_scatter(params, pool_k, pool_v, tokens, lengths, active,
                          page_tables, c):
    """`decode_paged` as it stood before PR 29, kept as the reference of
    the write path: one scatter over (head, page, offset) per pool and
    layer, and the kernel over the per-layer view `pool[li]`; an inactive
    slot's column goes to the scratch page. A looped stack's passes are
    spelled out (pass t's layer l in cache layer t * n_layers + l, each
    pass closed by the final norm, the exit rule choosing what the head
    reads)."""
    from ray_tpu.llm.engine import _mlp_block, _qkv
    from ray_tpu.models.transformer import exit_step, exit_zero
    from ray_tpu.ops.layers import apply_rope, rmsnorm, rope
    from ray_tpu.ops.paged_attention import paged_decode_attention
    B, P = page_tables.shape
    page = pool_k.shape[4]
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]
    sin, cos = rope(lengths[:, None], c.head_dim, c.rope_theta)
    w_idx = jnp.clip(lengths // page, 0, P - 1)
    w_page = jnp.take_along_axis(page_tables, w_idx[:, None], 1)[:, 0]
    w_page = jnp.where(lengths // page >= P, 0, w_page)
    w_page = jnp.where(active, w_page, 0)
    w_off = lengths % page
    hkv_idx = jnp.arange(c.n_kv_heads)[:, None]
    state = exit_zero(x[:, 0])
    for t in range(c.loops):
        for li in range(c.n_layers):
            at = t * c.n_layers + li
            lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
            q, k, v = _qkv(rmsnorm(x, lp["attn_norm"], c.norm_eps), lp, c,
                           fence=True)
            q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
            pool_k = pool_k.at[at, hkv_idx, w_page[None], :,
                               w_off[None]].set(
                k[:, 0].transpose(1, 0, 2).astype(pool_k.dtype))
            pool_v = pool_v.at[at, hkv_idx, w_page[None], :,
                               w_off[None]].set(
                v[:, 0].transpose(1, 0, 2).astype(pool_v.dtype))
            attn = paged_decode_attention(
                q[:, 0], pool_k[at][None], pool_v[at][None], lengths + 1,
                page_tables, layer=0)
            attn = attn.reshape(B, 1, c.n_heads * c.head_dim).astype(x.dtype)
            out = jnp.einsum("bsq,qd->bsd", attn, lp["wo"])
            if c.post_norms:
                out = rmsnorm(out, lp["attn_post_norm"], c.norm_eps)
            x = _mlp_block(x + out, lp, c)
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        if c.loops > 1:
            state = exit_step(params, c, t, x[:, 0], state)
    x = state[0] if c.loops > 1 else x[:, 0]
    head = params["embed"].T if c.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x.astype(jnp.float32),
                        head.astype(jnp.float32))
    neg = jnp.full_like(logits, -1e30).at[:, 0].set(0.0)
    return jnp.where(active[:, None], logits, neg), pool_k, pool_v


# lengths, active, page tables of three slots over two-page tables
# (`_PAGE` 16): where each slot's new column lands.
_WRITE_CASES = {
    "offset_0": ([16, 0, 5], [1, 1, 1], [[1, 2], [3, 4], [5, 6]]),
    "offset_last": ([15, 31, 5], [1, 1, 1], [[1, 2], [3, 4], [5, 6]]),
    "inactive_slot": ([7, 20, 5], [1, 0, 1], [[1, 2], [3, 4], [5, 6]]),
    "past_table_bucket": ([32, 3, 5], [1, 1, 1], [[1, 2], [3, 4], [5, 6]]),
    "shared_prefix_pages": ([20, 18, 5], [1, 1, 1],
                            [[1, 2], [1, 3], [5, 6]]),
}


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_decode_paged_writes_what_the_scatter_wrote(case):
    """The in-place column writes leave the pools BITWISE as the parent's
    scatter left them, in the pool's own bf16 (greedy outputs must not
    move), and the logits with them. Scratch page 0 alone may differ, and
    only where a slot was sent there (inactive, or past its table
    bucket); every column no slot owns keeps its bytes — prefix pages
    that two slots share among them."""
    c = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, dtype="bfloat16")
    lengths, active, tables = _WRITE_CASES[case]
    params = init_params(c, jax.random.PRNGKey(7))
    shape = (c.n_layers, c.n_kv_heads, 7, c.head_dim, _PAGE)
    pool_k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.bfloat16)
    args = (params, pool_k, pool_v, jnp.asarray([11, 12, 13], jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active, jnp.bool_),
            jnp.asarray(tables, jnp.int32))
    got = jax.jit(partial(decode_paged, config=c))(*args)
    want = jax.jit(partial(_decode_paged_scatter, c=c))(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    to_scratch = case in ("inactive_slot", "past_table_bucket")
    written = np.zeros(shape, bool)
    for n, a, tab in zip(lengths, active, tables):
        if a and n // _PAGE < len(tab):
            written[:, :, tab[n // _PAGE], :, n % _PAGE] = True
    assert written.sum() == (3 - to_scratch) * shape[0] * shape[1] * shape[3]
    for new, ref, before in zip(got[1:], want[1:], (pool_k, pool_v)):
        new, ref, before = (np.asarray(a.astype(jnp.float32))[
            :, :, int(to_scratch):] for a in (new, ref, before))
        np.testing.assert_array_equal(new, ref)
        own = written[:, :, int(to_scratch):]
        np.testing.assert_array_equal(new[~own], before[~own])
        assert (new[own] != before[own]).mean() > 0.9


@pytest.mark.parametrize("loops", [1, 4])
def test_decode_paged_moves_nothing_for_an_inactive_slot(loops):
    """Five slots, three of them inactive (one that never held a request:
    length 0, a table of zeros; two whose requests ended, tables and
    lengths still as they were, one of them at a page's last column):
    every real page of both pools keeps its bytes for them, in every cache
    layer of every pass, and the two active slots' columns and logits are
    what the scatter's reference writes and reads, bitwise (a looped
    stack's passes a loop here, spelled out there). The kernel is handed a length of 0 for an
    inactive slot, so on the chip it neither reads, merges nor writes back
    a page for it; off the chip the fallback's insert sends its column to
    the scratch page."""
    from ray_tpu.models import configs
    c = (configs.tiny_ouro(dtype="bfloat16") if loops > 1 else
         ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=128, dtype="bfloat16"))
    assert c.loops == loops
    lengths = [20, 0, 7, 31, 16]
    active = [1, 0, 0, 0, 1]
    tables = [[1, 2], [0, 0], [3, 4], [5, 6], [7, 8]]
    params = init_params(c, jax.random.PRNGKey(7))
    shape = (c.cache_layers, c.n_kv_heads, 9, c.head_dim, _PAGE)
    pool_k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    pool_v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.bfloat16)
    args = (params, pool_k, pool_v,
            jnp.asarray([11, 12, 13, 14, 15], jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active, jnp.bool_),
            jnp.asarray(tables, jnp.int32))
    got = jax.jit(partial(decode_paged, config=c))(*args)
    want = jax.jit(partial(_decode_paged_scatter, c=c))(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert (np.asarray(got[0])[1:4, 1:] == -1e30).all()
    written = np.zeros(shape, bool)
    for n, a, tab in zip(lengths, active, tables):
        if a:
            written[:, :, tab[n // _PAGE], :, n % _PAGE] = True
    assert written.sum() == 2 * shape[0] * shape[1] * shape[3]
    for new, ref, before in zip(got[1:], want[1:], (pool_k, pool_v)):
        new, ref, before = (np.asarray(a.astype(jnp.float32))[:, :, 1:]
                            for a in (new, ref, before))
        own = written[:, :, 1:]
        np.testing.assert_array_equal(new[~own], before[~own])
        np.testing.assert_array_equal(new[own], ref[own])
        assert (new[own] != before[own]).mean() > 0.9

