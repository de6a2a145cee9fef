"""Operations and bytes of the Mamba-2 decode state update kernel
(ray_tpu/ops/ssm.py `_update_kernel`, named `ssm_state_update` in the
trace), from shapes.

One call serves one Mamba layer of one decode step, for the ACTIVE slots
only (rows of inactive slots are neither read nor written). For an active
slot the algorithm needs: its row of the state pool in and out ([heads, P,
N] each way, in the pool's type: float32 as the configuration states it),
the decay and dt * x tiles ([P, heads] float32 each), B and C ([groups, N]
float32 each) in, and y ([P, heads] float32) out; a head's [P, N] state
takes a multiply by the decay, a multiply-add of x (x) B, and a multiply
and a sum against C: 6 operations a state element. The traced window's
decode steps come from the benchmark's spans (the active slots after each
step); the kernel runs once a Mamba layer a step.
"""

from __future__ import annotations

STATE_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def cost_of_step(active: int, model) -> tuple:
    heads, p, n = model.ssm_heads, model.ssm_head_dim, model.ssm_state
    state = heads * p * n
    flops = 6 * state
    nbytes = 2 * state * STATE_ITEMSIZE[model.ssm_state_dtype]   # in, out
    nbytes += 3 * p * heads * 4                  # decay, dt * x in; y out
    nbytes += 2 * model.ssm_groups * n * 4       # B, C
    return active * flops, active * nbytes


def cost(ctx: dict):
    steps = ctx.get("steps")
    model = ctx["model"]
    layers = getattr(model, "layer_pattern", "").count("M")
    if not steps or not layers:
        return None
    flops = nbytes = 0
    for s in steps:
        f, b = cost_of_step(len(s["lengths"]), model)
        flops += f * layers
        nbytes += b * layers
    return flops, nbytes
