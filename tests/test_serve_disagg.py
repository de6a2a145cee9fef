"""Disaggregated prefill/decode serving plane (llm/serve.py).

The robustness contract under test, in order of escalation: the KV
handoff is bit-exact (prefill-pool export == in-engine prefill), the
admission controller sheds overflow fast and loud while admitted
requests complete, injected handoff loss / router drops degrade to
re-prefill / paced redrive, and — the headline — a decode replica
SIGKILLed mid-storm has every in-flight stream re-resolved exactly-once
on a surviving replica (no dropped positions, no duplicates)."""

import threading
import time

import pytest

from ray_tpu.core import chaos
from ray_tpu.core.status import OverloadedError
from ray_tpu.llm import (DisaggConfig, EngineConfig, InferenceEngine,
                         LLMConfig, PrefillEngine, build_disagg_deployment,
                         build_disagg_openai_app, build_llm_deployment,
                         build_openai_app)
from ray_tpu.llm.tokenizer import get_tokenizer
from ray_tpu.models import ModelConfig

# Same compile-heavy tier as the other LLM-engine files.
pytestmark = pytest.mark.heavy

HTTP_PORT = 8127  # distinct from test_serve (8123) / test_llm (8000)

TINY = ModelConfig(vocab=300, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128, dtype="float32")
ENG = EngineConfig(max_slots=4, max_len=64, prompt_buckets=(32,),
                   eos_token=-1, default_max_new_tokens=8, page_size=8)


def _cfg(max_new=8):
    import dataclasses
    eng = dataclasses.replace(ENG, default_max_new_tokens=max_new)
    return LLMConfig(model_id="tiny", model=TINY, engine=eng,
                     tokenizer="byte")


def _reference_texts(params, prompts, max_new):
    """Greedy reference through a plain single engine."""
    tok = get_tokenizer("byte")
    eng = InferenceEngine(TINY, ENG, params=params)
    return {p: tok.decode(eng.generate([tok.encode(p)], max_new, 0.0)[0])
            for p in prompts}


def _reference_logprobs(params, prompts, max_new):
    """Greedy per-token logprobs through a plain single engine (the
    monolithic twin of prefill-export + decode-import)."""
    tok = get_tokenizer("byte")
    eng = InferenceEngine(TINY, ENG, params=params)
    out = {}
    for p in prompts:
        rid = eng.add_request(tok.encode(p), max_new, 0.0, logprobs=True)
        while eng.has_work():
            eng.step()
        req = eng.finished.pop(rid)
        out[p] = (req.generated, list(req.token_logprobs))
    return out


def test_prefill_export_import_matches_engine(tiny_llm_params):
    """The handoff seam itself: a PrefillEngine export spliced into a
    fresh decode engine (import_kv + resume_token) continues bit-exactly
    where a monolithic engine would, with the imported pages prefix-hit
    rather than re-prefilled."""
    cfg, params = tiny_llm_params
    assert cfg == TINY
    prompt = list(range(3, 23))  # 20 tokens = 2 full pages + tail
    ref = InferenceEngine(TINY, ENG, params=params)
    want = ref.generate([prompt], max_new_tokens=6, temperature=0.0)[0]

    pe = PrefillEngine(TINY, ENG, params=params)
    first, ks, vs = pe.prefill_export(prompt, temperature=0.0)
    assert first == want[0]
    assert ks.shape[1] == 16  # full pages only ever leave the worker

    dec = InferenceEngine(TINY, ENG, params=params)
    rid = dec.add_request(prompt, 6, 0.0, resume_token=first,
                          kv_handoff=(ks, vs))
    while dec.has_work():
        dec.step()
    assert dec.finished.pop(rid).generated == want
    assert dec.prefix_hits >= 1, "handoff pages must be prefix-hit"

    # Mid-stream resume: 3 tokens already delivered; a fresh replica
    # continues from the cursor without re-emitting a position.
    dec2 = InferenceEngine(TINY, ENG, params=params)
    gen = want[:3]
    rid2 = dec2.add_request(prompt + gen[:-1], 6 - len(gen) + 1, 0.0,
                            resume_token=gen[-1], kv_handoff=(ks, vs))
    while dec2.has_work():
        dec2.step()
    assert dec2.finished.pop(rid2).generated == gen[-1:] + want[3:]


def test_disagg_local_mode_matches_dense(tiny_llm_params):
    """Full pipeline in serve local-testing mode: the disaggregated
    plane's completions are byte-identical to the dense deployment's."""
    import json

    from ray_tpu import serve as serve_api

    class Req:
        path = "/v1/completions"
        method = "POST"
        body = json.dumps({"prompt": "hello disagg world!",
                           "max_tokens": 6, "temperature": 0.0}).encode()

    h_d = serve_api.run(build_disagg_openai_app(_cfg(6)),
                        local_testing_mode=True)
    h_ref = serve_api.run(build_openai_app(_cfg(6)),
                          local_testing_mode=True)
    out = h_d.remote(Req()).result(timeout_s=120)
    ref = h_ref.remote(Req()).result(timeout_s=120)
    assert out["choices"][0]["text"] == ref["choices"][0]["text"]
    assert out["usage"] == ref["usage"]


def test_disagg_logprobs_match_dense_path(tiny_llm_params):
    """ROADMAP item 1 (today they 400'd): logprobs thread through
    prefill-export (first token's logp rides the handoff dict) →
    decode-import ((token, logprob) pair chunks) and come out identical
    to the dense replica's — same tokens, same values, same
    stop-truncation alignment via the shared _logprob_fields helper."""
    from ray_tpu import serve as serve_api
    _cfg_obj, params = tiny_llm_params
    refs = _reference_logprobs(params, ["logprob parity probe!"], 6)

    h_d = serve_api.run(build_disagg_deployment(_cfg(6)),
                        local_testing_mode=True)
    h_ref = serve_api.run(build_llm_deployment(_cfg(6)),
                          local_testing_mode=True)
    out = h_d.completions.remote("logprob parity probe!", max_tokens=6,
                                 temperature=0.0,
                                 logprobs=1).result(timeout_s=240)
    ref = h_ref.completions.remote("logprob parity probe!", max_tokens=6,
                                   temperature=0.0,
                                   logprobs=1).result(timeout_s=240)
    assert out["choices"][0]["text"] == ref["choices"][0]["text"]
    lp_d = out["choices"][0]["logprobs"]
    lp_r = ref["choices"][0]["logprobs"]
    assert lp_d["tokens"] == lp_r["tokens"]
    assert lp_d["token_logprobs"] == pytest.approx(
        lp_r["token_logprobs"], abs=1e-4)
    # ...and against the from-scratch single-engine reference.
    _toks, ref_lps = refs["logprob parity probe!"]
    assert lp_d["token_logprobs"] == pytest.approx(ref_lps, abs=1e-4)
    # Guided decoding stays rejected (the 400 that REMAINS by design).
    with pytest.raises(Exception, match="guided"):
        h_d.completions.remote("x", guided_regex="a+").result(timeout_s=60)


def test_overload_sheds_fast_while_admitted_complete(tiny_llm_params):
    """The open-loop overload contract: past the decode token budget,
    requests shed IMMEDIATELY with OverloadedError (no queue collapse —
    the shed must not wait behind admitted work), and every admitted
    request still completes exactly."""
    from ray_tpu import serve as serve_api
    _cfg_obj, params = tiny_llm_params
    max_new = 8
    prompts = [f"overload probe {i}" for i in range(8)]
    refs = _reference_texts(params, prompts, max_new)
    # Budget fits ~2 requests: cost = prompt(~16) + max_new(8).
    disagg = DisaggConfig(max_decode_inflight_tokens=52,
                          max_prefill_queue_tokens=64)
    h = serve_api.run(build_disagg_deployment(_cfg(max_new), disagg),
                      local_testing_mode=True)

    done, shed, slow_sheds = {}, [], []

    def one(p):
        t0 = time.monotonic()
        try:
            done[p] = h.completions.remote(p, max_tokens=max_new,
                                           temperature=0.0
                                           ).result(timeout_s=120)
        except OverloadedError as e:
            dt = time.monotonic() - t0
            shed.append(p)
            assert "shed" in str(e)
            if dt > 2.0:  # loud AND fast: never queued behind decode
                slow_sheds.append((p, dt))

    ts = [threading.Thread(target=one, args=(p,)) for p in prompts]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert shed, "the storm must overflow the token budget"
    assert done, "backpressure must not starve everything"
    assert not slow_sheds, f"sheds queued behind decode: {slow_sheds}"
    for p, out in done.items():
        assert out["choices"][0]["text"] == refs[p]
    # Budget fully released: the plane serves again after the storm.
    again = h.completions.remote(prompts[0], max_tokens=max_new,
                                 temperature=0.0).result(timeout_s=120)
    assert again["choices"][0]["text"] == refs[prompts[0]]


def test_kv_handoff_loss_falls_back_to_reprefill(tiny_llm_params):
    """serve.kv_handoff.lose: the decode pool must re-prefill and still
    produce the identical completion."""
    from ray_tpu import serve as serve_api
    _cfg_obj, params = tiny_llm_params
    refs = _reference_texts(params, ["handoff loss probe"], 6)
    h = serve_api.run(build_disagg_deployment(_cfg(6)),
                      local_testing_mode=True)
    chaos.configure("serve.kv_handoff.lose:1", seed=5)
    try:
        out = h.completions.remote("handoff loss probe", max_tokens=6,
                                   temperature=0.0).result(timeout_s=120)
        _hits, fires = chaos.snapshot()["serve.kv_handoff.lose"]
        assert fires == 1, "loss never injected — test proves nothing"
        assert out["choices"][0]["text"] == refs["handoff loss probe"]
    finally:
        chaos.configure("")


def test_router_drop_redriven_through_backoff(tiny_llm_params):
    """serve.router.drop: a dropped dispatch is redriven through the
    shared Backoff policy (paced, not hot-looped) and the request still
    completes."""
    from ray_tpu import serve as serve_api
    _cfg_obj, params = tiny_llm_params
    refs = _reference_texts(params, ["router drop probe"], 6)
    h = serve_api.run(build_disagg_deployment(_cfg(6)),
                      local_testing_mode=True)
    chaos.configure("serve.router.drop:1", seed=5)
    try:
        out = h.completions.remote("router drop probe", max_tokens=6,
                                   temperature=0.0).result(timeout_s=120)
        assert ("serve.router.drop", 1) in chaos.fire_log()
        assert out["choices"][0]["text"] == refs["router drop probe"]
    finally:
        chaos.configure("")


def test_decode_sigkill_mid_storm_resumes_exactly_once(ray_start_regular,
                                                       tiny_llm_params):
    """THE acceptance scenario: every decode replica armed to SIGKILL
    itself mid-stream (per-replica arming — controller respawns come
    back clean, so the kills are bounded); a storm of concurrent greedy
    requests must all complete bit-identically to the single-engine
    reference — every in-flight stream re-resolves exactly-once on a
    surviving (or respawned) replica, no dropped or duplicated
    positions — and the coordinator's stats must show real recoveries."""
    from ray_tpu import serve as serve_api

    cfg = _cfg(10)
    prompts = [f"shared prefix req {i}" for i in range(6)]
    _tiny_cfg, params = tiny_llm_params  # == the replicas' seed-0 init
    refs = _reference_texts(params, prompts, 10)
    ref_lps = _reference_logprobs(params, prompts[:2], 10)

    app = build_disagg_deployment(cfg, DisaggConfig(decode_replicas=2))
    serve_api.run(app, name="disagg-kill", route_prefix=None,
                  http_port=HTTP_PORT, blocking_timeout_s=240)
    try:
        h = serve_api.get_deployment_handle("DisaggLLMServer:tiny",
                                            "disagg-kill")
        dec = serve_api.get_deployment_handle("DecodePool:tiny",
                                              "disagg-kill")
        pids = set()
        for _ in range(30):  # pow-2 hides identity; arm until both seen
            pids.add(dec.configure_chaos.remote(
                "serve.decode.kill:4", 11).result(timeout_s=60))
            if len(pids) >= 2:
                break
        assert len(pids) == 2, "both decode replicas must be armed"

        results, errs = {}, {}

        def one(p):
            try:
                # The first two prompts also carry logprobs through the
                # storm: a mid-stream kill must resume the logprob
                # stream exactly-once too (delivered positions keep
                # their original values; only new positions append).
                results[p] = h.completions.remote(
                    p, max_tokens=10, temperature=0.0,
                    logprobs=1 if p in ref_lps else None).result(
                    timeout_s=240)
            except Exception as e:  # noqa: BLE001 — recorded + asserted
                errs[p] = repr(e)

        ts = [threading.Thread(target=one, args=(p,)) for p in prompts]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=240)
        stats = serve_api.get_deployment_handle(
            "DisaggLLMServer:tiny", "disagg-kill").stats.remote().result(
            timeout_s=30)
        assert not errs, f"admitted requests dropped: {errs}"
        assert stats.get("streams_resumed", 0) >= 1, stats
        for p in prompts:
            assert results[p]["choices"][0]["text"] == refs[p], p
            assert results[p]["usage"]["completion_tokens"] == 10
        for p, (_toks, lps) in ref_lps.items():
            got = results[p]["choices"][0]["logprobs"]
            assert got is not None, p
            assert got["token_logprobs"] == pytest.approx(lps,
                                                          abs=1e-4), p
        assert stats["completed"] == len(prompts)
    finally:
        serve_api.delete("disagg-kill")


def test_shed_rate_autoscales_decode_pool(ray_start_regular,
                                          tiny_llm_params):
    """ROADMAP item 1's missing wire: a sustained admission-shed rate
    (the `ray_tpu_serve_shed_total{pool=...}` signal, forwarded by the
    coordinator as record_shed_metrics) makes the serve controller grow
    the DecodePool, and — because the coordinator's decode token budget
    is per LIVE replica — the shed rate then drops: a wave that shed
    before the scale-up admits fully after it."""
    from ray_tpu import serve as serve_api

    max_new = 8
    prompts = [f"autoscale probe {i}" for i in range(6)]
    # Budget fits ~2 requests per replica: cost = prompt(~17) + 8.
    disagg = DisaggConfig(
        decode_replicas=1,
        max_decode_inflight_tokens=60,
        decode_autoscale=dict(min_replicas=1, max_replicas=2,
                              upscale_shed_rate=0.2, shed_window_s=8.0,
                              upscale_delay_s=0.2))
    app = build_disagg_deployment(_cfg(max_new), disagg)
    serve_api.run(app, name="disagg-auto", route_prefix=None,
                  http_port=8129, blocking_timeout_s=240)
    try:
        h = serve_api.get_deployment_handle("DisaggLLMServer:tiny",
                                            "disagg-auto")

        def wave(ps):
            done, shed = [], []

            def one(p):
                try:
                    done.append(h.completions.remote(
                        p, max_tokens=max_new,
                        temperature=0.0).result(timeout_s=240))
                except OverloadedError:
                    shed.append(p)

            ts = [threading.Thread(target=one, args=(p,)) for p in ps]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=240)
            return done, shed

        done1, shed1 = wave(prompts)
        assert shed1, "the storm must overflow the 1-replica budget"
        assert done1, "backpressure must not starve everything"

        # The controller acts on the reported rate: DecodePool -> 2.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            st = serve_api.status()["disagg-auto"]["deployments"]
            dp = st["DecodePool:tiny"]
            if dp["running_replicas"] >= 2:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(f"decode pool never scaled up: {st}")

        # One probe dispatch refreshes the coordinator's live count...
        h.completions.remote(prompts[0], max_tokens=max_new,
                             temperature=0.0).result(timeout_s=240)
        stats = h.stats.remote().result(timeout_s=30)
        assert stats["n_decode_live"] >= 2, stats
        # ...and the doubled budget admits the 4-wide wave that WOULD
        # have shed at one replica (2x60 >= 4 x ~25 tokens): the shed
        # rate dropped to zero with the extra replica.
        done2, shed2 = wave(prompts[:4])
        assert not shed2, f"post-scale-up wave still shed: {shed2}"
        assert len(done2) == 4
    finally:
        serve_api.delete("disagg-auto")


def test_shed_metric_per_pool_and_prometheus_escaping():
    """ROADMAP item 1's autoscaler signal: every admission shed exports
    `ray_tpu_serve_shed_total{pool=...}` tagged with the budget that
    tripped, and the exposition lines escape label values per the
    Prometheus format (a hostile value cannot corrupt the scrape)."""
    import collections
    import types

    from ray_tpu.llm import serve as serve_mod
    from ray_tpu.util import metrics as umetrics

    def mk_coord(**cfg):
        coord = types.SimpleNamespace(
            d=DisaggConfig(**cfg), _lock=threading.Lock(),
            _prefill_queue_tokens=0, _decode_inflight_tokens=0,
            _ongoing=0, _tok_rate_ema=0.0,
            _n_decode_live=1, _shed_pending=0, _shed_reporting=False,
            _local_decode=object(),  # short-circuits the shed reporter
            counters=collections.Counter())
        coord._admit = types.MethodType(
            serve_mod._DisaggServerImpl._admit, coord)
        coord._maybe_report_sheds = types.MethodType(
            serve_mod._DisaggServerImpl._maybe_report_sheds, coord)
        return coord

    def shed_counts():
        m = serve_mod._shed_metric
        return dict(m._values) if m is not None else {}

    before = shed_counts()
    c = mk_coord(max_prefill_queue_tokens=4, max_decode_inflight_tokens=6,
                 max_ongoing_requests=1)
    with pytest.raises(OverloadedError, match="pool=decode"):
        c._admit(2, 8)       # 2+8 > decode budget 6
    with pytest.raises(OverloadedError, match="pool=prefill"):
        c._admit(5, 1)       # prompt 5 > prefill budget 4
    c._admit(1, 1)
    with pytest.raises(OverloadedError, match="pool=requests"):
        c._admit(1, 1)       # ongoing cap 1
    slo = mk_coord(max_prefill_queue_tokens=1 << 20,
                   max_decode_inflight_tokens=1 << 20,
                   max_ongoing_requests=64, admission_slo_ms=1.0)
    slo._tok_rate_ema = 10.0
    slo._decode_inflight_tokens = 1000  # est wait 100s >> 1ms SLO
    with pytest.raises(OverloadedError, match="pool=slo"):
        slo._admit(1, 1)
    after = shed_counts()
    for pool in ("decode", "prefill", "requests", "slo"):
        assert after.get((pool,), 0) == before.get((pool,), 0) + 1, pool
    assert c.counters["shed"] == 3 and c.counters["shed_decode"] == 1

    text = umetrics.prometheus_text()
    assert "# TYPE ray_tpu_serve_shed_total counter" in text
    for pool in ("decode", "prefill", "requests", "slo"):
        assert f'ray_tpu_serve_shed_total{{pool="{pool}"}}' in text, pool

    # Escaping: a hostile label value through the same family renders
    # backslash -> \\, quote -> \", newline -> \n (exposition spec).
    serve_mod._record_shed('bad"pool\nwith\\slash')
    text = umetrics.prometheus_text()
    assert ('ray_tpu_serve_shed_total{pool="bad\\"pool\\nwith\\\\slash"}'
            in text)
