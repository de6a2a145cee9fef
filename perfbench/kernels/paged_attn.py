"""Operations and bytes of the paged decode attention kernel
(ops/paged_attention.py `_dma_kernel`), from shapes.

One call serves one layer of one decode step. For a slot of length n the
algorithm needs: q and the output ([h, hd] each), and the K and V pages
that hold its n tokens (whole pages are moved: ceil(n / page) * page
tokens, [hkv, hd] each, in the pool's type); 2 * n * hd multiply-adds per
head for q.K^T and the same for p.V. The traced window's decode steps come
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
