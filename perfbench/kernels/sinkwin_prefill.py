"""Operations and bytes of the sink-window layers' prefill attention kernel
(ray_tpu/ops/attention.py `_prefill_kernel` with a window and a sink, keys
and values of different widths), from shapes.

One call serves one window layer of one prefill program: n sequences of S
query rows over `pre_t` cached keys (the window's tail before the chunk)
and the chunk's own S. A trace keeps an op's name and no shapes, so the
call carries them in its name: `sinkwin_prefill_n<n>_s<S>_t<pre_t>`
(ray_tpu/models/windowed._prefill_name). S is the BUCKET and n the batch
padded to a power of two: every row is counted, padding included, as
`swa_prefill.py` counts its rows (a trace does not say which rows were
real; the kernel skips whole query blocks past a request's end, so the
share reads high by the bucket's padding and never by more).

Only the keys INSIDE the window count: row r sees min(window, pre_t + r + 1)
keys; a key costs a head and query 2 * head_dim operations for q.K^T (192
wide) and 2 * v_head_dim for p.V (128 wide): 2 * (192 + 128), not 4 * hd.
The sink adds one exponential a row and head and no product: not counted.
It moves q ([n, H, S, hd]) and the output ([n, H, S, dv]), K ([n, hkv,
pre_t + S, hd]) and V ([.., dv]) once, hkv the WINDOW kind's K/V heads, and
the layer's sinks, H float32. The same work whatever implements it.
"""

from __future__ import annotations

import re

NAME = re.compile(r"sinkwin_prefill_n(\d+)_s(\d+)_t(\d+)")
ACT_BYTES = {"bfloat16": 2, "float32": 4}


def widths(model) -> tuple:
    """(q and k's width, v's) of a head."""
    return model.head_dim, model.v_head_dim or model.head_dim


def keys_in_window(s: int, pre_t: int, window: int) -> int:
    """Sum over the S rows of the keys each one sees."""
    ramp = max(min(window - pre_t - 1, s), 0)    # rows that see fewer
    return ramp * pre_t + ramp * (ramp + 1) // 2 + (s - ramp) * window


def cost_of_call(n: int, s: int, pre_t: int, model) -> tuple:
    h = model.window_heads
    hkv = model.window_kv_heads or model.n_kv_heads
    hd, dv = widths(model)
    act = ACT_BYTES[model.dtype]
    flops = 2 * (hd + dv) * h * n * keys_in_window(s, pre_t, model.window)
    nbytes = (n * s * h * (hd + dv) + n * (pre_t + s) * hkv * (hd + dv)
              ) * act + 4 * h
    return flops, nbytes


def cost(ctx: dict):
    model = ctx["model"]
    if "W" not in getattr(model, "attn_sink", ""):
        return None     # a program without the sink: nothing to read
    rx = re.compile(ctx["op_pattern"]) if ctx.get("op_pattern") else None
    flops = nbytes = 0
    found = False
    for name, count in ctx["op_count"].items():
        m = NAME.search(name)
        if m is None or (rx is not None and not rx.search(name)):
            continue
        f, b = cost_of_call(int(m.group(1)), int(m.group(2)),
                            int(m.group(3)), model)
        flops += f * count
        nbytes += b * count
        found = True
    return (flops, nbytes) if found else None
