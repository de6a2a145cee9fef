"""Operations and bytes of the sink-window layers' paged decode attention
(ray_tpu/ops/paged_attention.py `_dma_kernel` with `lows` and a sink over
pools whose K and V differ in width, named `sinkwin_paged_decode` in the
trace), from shapes.

One call serves one window layer of one decode step. A slot whose sequence
holds n tokens after the step attended over the last min(n, window) of
them: only those keys count, 2 * head_dim operations a key and query head
for q.K^T (192 wide) and 2 * v_head_dim for p.V (128 wide), H =
window_heads. It needs q ([H, hd]) and the output ([H, dv]) and the K and V
pages those keys lie in (whole pages are moved, [hkv, hd] and [hkv, dv] a
token, hkv the WINDOW kind's K/V heads, in the pool's type): the pages
INSIDE the window (one where the slot's last position ends a page, else
two, at a window of exactly a page), never the sequence's earlier ones,
which the engine has released; and the layer's sinks, H float32, once a
call. The traced window's decode steps come from the benchmark's spans
(lengths of the active slots after each step); the kernel runs once a
window layer (the "W" letters of ModelConfig.attn_pattern) a step. The
same work whatever implements it.
"""

from __future__ import annotations


def cost_of_step(lengths: list, model, page: int, itemsize: int = 2) -> tuple:
    h = model.window_heads
    hkv = model.window_kv_heads or model.n_kv_heads
    hd, dv = model.head_dim, model.v_head_dim or model.head_dim
    flops, nbytes = 0, 4 * h                    # the sinks
    for n in lengths:
        n = int(n)
        keys = min(n, model.window)
        pages = (n - 1) // page - (n - keys) // page + 1 if keys else 0
        flops += 2 * (hd + dv) * h * keys
        nbytes += pages * page * hkv * (hd + dv) * itemsize   # K, V pages
        nbytes += h * (hd + dv) * itemsize                    # q in, out out
    return flops, nbytes


def cost(ctx: dict):
    steps, model = ctx.get("steps"), ctx["model"]
    if "W" not in getattr(model, "attn_sink", ""):
        return None     # a program without the sink: nothing to read
    layers = model.attn_pattern.count("W")
    if not steps or not layers:
        return None
    flops = nbytes = 0
    for s in steps:
        f, b = cost_of_step(s["lengths"], model, ctx["engine"].page_size)
        flops += f * layers
        nbytes += b * layers
    return flops, nbytes
