"""Operations and bytes of the paged decode attention kernel, from shapes
(ray_tpu/ops/paged_attention.py `paged_decode_insert_attention`: since PR
46 every per-head decode step calls `_fused_kernel` at one query a slot,
which also WRITES the step's token's K and V into the page that holds
them; named `_paged_decode_insert` in the trace where the stack runs once.
Until PR 46 it was the read-only `_dma_kernel` (`_paged_decode_dma`),
which the window and hybrid models' decode programs still call and the
metrics' pattern still matches).

One call serves one layer of one decode step. For a slot of length n the
algorithm needs: q and the output ([h, hd] each), and the K and V pages
that hold its n tokens (whole pages are moved: ceil(n / page) * page
tokens, [hkv, hd] each, in the pool's type); 2 * n * hd multiply-adds per
head for q.K^T and the same for p.V. The same work whatever implements
it. The WRITE-BACK is left out on purpose, as kernels/looped_attn.py
leaves it out: the attention needs one new column of K and of V a slot,
and `_fused_kernel` writes the two whole pages that hold it back; those
bytes are the implementation's, so the share reads them as inefficiency
and can only read lower for them. The traced window's decode steps come
from the benchmark's spans (lengths of the active slots after each step);
the kernel runs n_layers times a step.
"""

from __future__ import annotations


def cost_of_step(lengths: list, model, page: int, itemsize: int = 2) -> tuple:
    h, hkv, hd = model.n_heads, model.n_kv_heads, model.head_dim
    flops = nbytes = 0
    for n in lengths:
        pages = -(-int(n) // page)
        flops += 4 * h * hd * int(n)
        nbytes += 2 * pages * page * hkv * hd * itemsize   # K and V pages
        nbytes += 2 * h * hd * itemsize                    # q in, out out
    return flops, nbytes


def cost(ctx: dict):
    steps = ctx.get("steps")
    if not steps:
        return None
    model, page = ctx["model"], ctx["engine"].page_size
    flops = nbytes = 0
    for s in steps:
        f, b = cost_of_step(s["lengths"], model, page)
        flops += f * model.n_layers
        nbytes += b * model.n_layers
    return flops, nbytes
