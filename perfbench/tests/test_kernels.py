"""Each cost function against a count made by hand."""

import types

from perfbench.kernels import flash_attn, paged_attn

QWEN = types.SimpleNamespace(n_heads=28, n_kv_heads=4, head_dim=128,
                             n_layers=12, remat=True)


def test_paged_attention_one_slot_by_hand():
    # one slot of 300 tokens, pages of 128: 3 pages = 384 tokens moved
    flops, nbytes = paged_attn.cost_of_step([300], QWEN, 128)
    assert flops == 4 * 28 * 128 * 300                  # q.K^T and p.V
    kv = 2 * 384 * 4 * 128 * 2                          # K and V, bf16
    q_and_out = 2 * 28 * 128 * 2
    assert nbytes == kv + q_and_out


def test_paged_attention_window_sums_steps_and_layers():
    eng = types.SimpleNamespace(page_size=128)
    ctx = {"model": QWEN, "engine": eng,
           "steps": [{"lengths": [300]}, {"lengths": [300, 128]}]}
    f1, b1 = paged_attn.cost_of_step([300], QWEN, 128)
    f2, b2 = paged_attn.cost_of_step([300, 128], QWEN, 128)
    assert paged_attn.cost(ctx) == (12 * (f1 + f2), 12 * (b1 + b2))
    assert paged_attn.cost({"model": QWEN, "engine": eng}) is None


def test_flash_kernels_by_hand():
    b, s, h, hkv, hd = 1, 2048, 28, 4, 128
    half = s * s // 2
    f, nb = flash_attn.cost_of_call("_fwd_kernel", b, s, QWEN)
    assert f == 2 * 2 * b * h * half * hd               # Q.K^T, P.V
    # q, o over 28 heads; k, v over 4; bf16; plus fp32 row statistics
    assert nb == 2 * b * s * hd * (2 * h + 2 * hkv) + 8 * b * s * h
    assert flash_attn.cost_of_call("_bwd_dq_kernel", b, s, QWEN)[0] == \
        3 * 2 * b * h * half * hd
    assert flash_attn.cost_of_call("_bwd_dkv_kernel", b, s, QWEN)[0] == \
        4 * 2 * b * h * half * hd


def test_flash_window_counts_events_per_device():
    ctx = {"model": QWEN, "chips": 4, "batch": 4, "seq": 2048,
           "op_count": {"_fwd_kernel.3": 10, "_bwd_dq_kernel": 5,
                        "fusion.7": 99}}
    f_fwd, b_fwd = flash_attn.cost_of_call("_fwd_kernel", 1, 2048, QWEN)
    f_dq, b_dq = flash_attn.cost_of_call("_bwd_dq_kernel", 1, 2048, QWEN)
    assert flash_attn.cost(ctx) == (10 * f_fwd + 5 * f_dq,
                                    10 * b_fwd + 5 * b_dq)
    assert flash_attn.cost({**ctx, "op_count": {"fusion": 3}}) is None


def test_flash_events_named_after_shard_map_are_whole_layer_passes():
    # with remat a layer's step is fwd, fwd, dq, dkv: four events
    ctx = {"model": QWEN, "chips": 4, "batch": 4, "seq": 2048,
           "op_pattern": "^shard_map\\.", "op_count": {
               "shard_map.403": 60, "shard_map.404": 60, "shard_map.405": 60,
               "shard_map.406": 60, "fusion.1": 7}}
    one = [flash_attn.cost_of_call(k, 1, 2048, QWEN) for k in
           ("_fwd_kernel", "_fwd_kernel", "_bwd_dq_kernel",
            "_bwd_dkv_kernel")]
    f, b = flash_attn.cost(ctx)
    assert f == 60 * sum(c[0] for c in one)
    assert b == 60 * sum(c[1] for c in one)
