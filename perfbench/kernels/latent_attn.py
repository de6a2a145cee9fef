"""Operations and bytes of the latent paged decode attention kernel
(ray_tpu/ops/latent_attention.py `_latent_decode_kernel`, named
`paged_latent_decode` in the trace), from shapes.

One call serves one layer of one decode step, every head at once. For a
slot that attends over n cached tokens the algorithm needs: the pages that
hold them (whole pages are moved: ceil(n / page) * page tokens of
kv_lora_rank + rope numbers each, in the pool's type, ONE copy for all
heads), q~|q_pe in ([h, rank + rope]) and the latent-space output out
([h, rank]); 2 * n * (rank + rope) operations a head for the scores and
2 * n * rank for p . c_kv. The traced window's decode steps come from the
benchmark's spans (lengths of the active slots after each step: the count
the kernel attended over); the kernel runs n_layers times a step.
"""

from __future__ import annotations


def cost_of_step(lengths: list, model, page: int, itemsize: int = 2) -> tuple:
    h, rank = model.n_heads, model.kv_lora_rank
    width = rank + model.qk_rope_head_dim
    flops = nbytes = 0
    for n in lengths:
        pages = -(-int(n) // page)
        flops += 2 * h * int(n) * (width + rank)
        nbytes += pages * page * width * itemsize          # latent pages
        nbytes += h * (width + rank) * itemsize            # q in, out out
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
