"""Operations and bytes of the flash attention kernels (ops/attention.py
`_fwd_kernel`, `_bwd_dq_kernel`, `_bwd_dkv_kernel`), from shapes.

Causal attention over s positions does half the s x s score matrix. Per
call, for b sequences and h query heads of size hd (K and V have hkv):
  forward   2 score-sized matmuls (Q.K^T, P.V)
  bwd dq    3 (recompute Q.K^T, dO.V^T, dS.K)
  bwd dkv   4 (recompute Q.K^T, P^T.dO, dO.V^T, dS^T.Q)
each 2 * b * h * (s * s / 2) * hd operations. Bytes: every operand and
result once (q, k, v, o, do and the gradients the call writes) in bf16,
plus the fp32 row statistics. Each kernel's cost is multiplied by the
number of its events in the traced window (a recomputed forward is an
event like any other: this is a kernel's roofline share, not an MFU).
The calls run under shard_map, so one event is one device's share: the
global batch over the fsdp axis.

Names: the kernels' pallas_calls carry no `name`, so the device trace
calls them after what encloses them: `_flash_fwd` / `_flash_bwd` under a
plain jit, `shard_map.<n>` on a mesh (PR 23's four-chip trace:
shard_map.403-406). An event whose name tells the kernel is costed as
that kernel; the others are costed as whole layer passes: with per-layer
remat a layer's step is two forwards, one dq and one dkv (four events),
without it three.
"""

from __future__ import annotations

import re

MATMULS = {"_fwd_kernel": 2, "_bwd_dq_kernel": 3, "_bwd_dkv_kernel": 4}
# operands moved per call, in units of one [b, s, heads, hd] bf16 array:
# (count over h query heads, count over hkv kv heads)
ARRAYS = {"_fwd_kernel": (2, 2), "_bwd_dq_kernel": (4, 2),
          "_bwd_dkv_kernel": (3, 4)}


def cost_of_call(kernel: str, b: int, s: int, model) -> tuple:
    h, hkv, hd = model.n_heads, model.n_kv_heads, model.head_dim
    flops = MATMULS[kernel] * 2 * b * h * (s * s // 2) * hd
    qa, ka = ARRAYS[kernel]
    nbytes = 2 * b * s * hd * (qa * h + ka * hkv) + 4 * 2 * b * s * h
    return flops, nbytes


GENERIC = re.compile(r"^shard_map\.|^_flash_fwd|^_flash_bwd")


def cost_of_layer_pass(b: int, s: int, model) -> tuple:
    """(operations, bytes, events) of one layer's flash calls in a step."""
    kernels = (["_fwd_kernel"] * (2 if model.remat else 1)
               + ["_bwd_dq_kernel", "_bwd_dkv_kernel"])
    costs = [cost_of_call(k, b, s, model) for k in kernels]
    return (sum(c[0] for c in costs), sum(c[1] for c in costs),
            len(kernels))


def cost(ctx: dict):
    model, chips = ctx["model"], ctx["chips"]
    b_local = max(1, ctx["batch"] // chips)
    flops = nbytes = 0.0
    found = False
    rx = re.compile(ctx["op_pattern"]) if ctx.get("op_pattern") else None
    for name, count in ctx["op_count"].items():
        if rx is not None and not rx.search(name):
            continue
        kernel = next((k for k in MATMULS if re.search(k, name)), None)
        if kernel:
            f, b = cost_of_call(kernel, b_local, ctx["seq"], model)
        elif GENERIC.search(name):
            f, b, n = cost_of_layer_pass(b_local, ctx["seq"], model)
            f, b = f / n, b / n
        else:
            continue
        flops += f * count
        nbytes += b * count
        found = True
    return (flops, nbytes) if found else None
