"""Operations and bytes of a looped stack's paged decode attention
(ray_tpu/ops/paged_attention.py `paged_decode_insert_attention`: on the
chip `_fused_kernel`, the speculative verify's kernel at one query a slot
with its cache layer prefetched, which also WRITES the step's token's K
and V into the page that holds them; named `looped_paged_decode` in the
trace: the call a pass and layer of `llm/engine.decode_paged` where
`ModelConfig.loops` > 1), from shapes.

One call serves one CACHE layer of one decode step, and a step makes
loops x n_layers of them: pass t's layer l attends its own keys, cache
layer t * n_layers + l. For a slot of length n a call needs q and the
output ([h, hd] each) and the K and V pages that hold its n tokens (whole
pages are moved: ceil(n / page) * page tokens, [hkv, hd] each, in the
pool's type); 2 * n * hd multiply-adds a head for q.K^T and the same for
p.V. The same work whatever implements it: a later kernel (one call a
pass, a group of one blocked another way) is read against this count.
The WRITE-BACK is left out on purpose: the attention needs one new column
of K and of V a slot (2 * hkv * hd * itemsize bytes, nothing beside the
pages), and `_fused_kernel` writes the whole K page and the whole V page
that hold it back (2 pages a slot and call). Those bytes are the
implementation's, not the work's, so the share reads them as inefficiency
(47 % as shipped, where the read-only `_dma_kernel` of this PR's first
form read 75 %: PERF.md section 5). The traced window's decode steps come
from the benchmark's spans (lengths of the active slots after each step).
"""

from __future__ import annotations


def cost_of_step(lengths: list, model, page: int, itemsize: int = 2) -> tuple:
    """One cache layer's call of one step."""
    h, hkv, hd = model.n_heads, model.n_kv_heads, model.head_dim
    flops = nbytes = 0
    for n in lengths:
        pages = -(-int(n) // page)
        flops += 4 * h * hd * int(n)
        nbytes += 2 * pages * page * hkv * hd * itemsize   # K and V pages
        nbytes += 2 * h * hd * itemsize                    # q in, out out
    return flops, nbytes


def cost(ctx: dict):
    steps, model = ctx.get("steps"), ctx["model"]
    loops = getattr(model, "loops", 1)      # the parent's program has none
    if not steps or loops <= 1:
        return None
    calls = loops * model.n_layers
    flops = nbytes = 0
    for s in steps:
        f, b = cost_of_step(s["lengths"], model, ctx["engine"].page_size)
        flops += f * calls
        nbytes += b * calls
    return flops, nbytes
