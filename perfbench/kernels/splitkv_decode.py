"""Operations and bytes of the full layers' paged decode attention over
pools whose K and V differ in width (ray_tpu/ops/paged_attention.py
`_dma_kernel`, named `splitkv_paged_decode` in the trace where
ModelConfig.v_head_dim differs from the head size), from shapes.

One call serves one full-attention layer of one decode step. A slot whose
sequence holds n tokens after the step attended over all n: 2 * head_dim
operations a key and query head for q.K^T (192 wide) and 2 * v_head_dim
for p.V (128 wide), H = n_heads. It needs q ([H, hd]) and the output ([H,
dv]) and the K and V pages that hold the n tokens (whole pages are moved:
ceil(n / page) * page tokens, [hkv, hd] and [hkv, dv] each, hkv the FULL
kind's K/V heads, in the pool's type). The token's own K and V are written
by the program before the call (`models/windowed._write_columns`): not
this kernel's. The traced window's decode steps come from the benchmark's
spans (lengths of the active slots after each step); the kernel runs once
a full layer (the "F" letters of ModelConfig.attn_pattern) a step. The same
work whatever implements it.
"""

from __future__ import annotations


def cost_of_step(lengths: list, model, page: int, itemsize: int = 2) -> tuple:
    h, hkv = model.n_heads, model.n_kv_heads
    hd, dv = model.head_dim, model.v_head_dim or model.head_dim
    flops = nbytes = 0
    for n in lengths:
        pages = -(-int(n) // page)
        flops += 2 * (hd + dv) * h * int(n)
        nbytes += pages * page * hkv * (hd + dv) * itemsize   # K, V pages
        nbytes += h * (hd + dv) * itemsize                    # q in, out out
    return flops, nbytes


def cost(ctx: dict):
    steps, model = ctx.get("steps"), ctx["model"]
    hd, dv = model.head_dim, getattr(model, "v_head_dim", 0)
    layers = getattr(model, "attn_pattern", "").count("F")
    if not steps or not layers or not dv or dv == hd:
        return None     # K and V alike: `paged_attn.py`'s kernel, not this
    flops = nbytes = 0
    for s in steps:
        f, b = cost_of_step(s["lengths"], model, ctx["engine"].page_size)
        flops += f * layers
        nbytes += b * layers
    return flops, nbytes
