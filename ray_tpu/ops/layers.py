"""Transformer layer ops (jnp; XLA-fused on TPU).

Kept as plain jnp on purpose: RMSNorm/RoPE/SwiGLU are bandwidth-bound
elementwise chains that XLA fuses into neighboring matmuls; a Pallas kernel
here would only pin the schedule. fp32 accumulation where it matters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def last_rows(x, lengths):
    """x [n, S, d] right-padded, lengths [n] -> [n, d]: each request's last
    token, the one position a prefill program's head is read at."""
    return jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(head_dim: int, theta: float, scaling: dict):
    """YaRN (arXiv:2309.00071) as DeepSeek-V2's config spells it: per
    frequency a blend of 1/theta_i (kept: fast dims) and 1/(factor *
    theta_i) (interpolated: slow dims) by a linear ramp between the
    correction dims of beta_fast and beta_slow rotations over the original
    context. Returns (frequencies [head_dim/2] as numpy float32, the factor
    on cos and sin: mscale / mscale_all_dim)."""
    half = head_dim // 2
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    base = theta ** (np.arange(half, dtype=np.float64) / half)

    def correction_dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    freqs = (1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    amp = (yarn_mscale(factor, float(scaling.get("mscale", 1.0)))
           / yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0.0))))
    return freqs.astype(np.float32), amp


def rope(positions, head_dim: int, theta: float = 10000.0, scaling=None):
    """Rotary embedding tables. positions: [..., seq] -> (sin, cos) each
    [..., seq, head_dim/2]. `scaling`: a `rope_scaling` of type "yarn" (a
    dict, or ModelConfig's tuple of pairs); None = plain frequencies."""
    half = head_dim // 2
    amp = 1.0
    if scaling:
        scaling = dict(scaling)
        if scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {scaling.get('type')!r}: "
                             f"only \"yarn\" is implemented")
        freqs, amp = yarn_frequencies(head_dim, theta, scaling)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if amp != 1.0:
        return jnp.sin(angles) * amp, jnp.cos(angles) * amp
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x, sin, cos):
    """x: [batch, seq, heads, head_dim]; sin/cos: [batch?, seq, head_dim/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # [seq, half] -> broadcast over batch
        sin = sin[None]
        cos = cos[None]
    sin = sin[:, :, None, :]  # [batch, seq, 1, half]
    cos = cos[:, :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: silu(x@Wg) * (x@Wu) @ Wd. Outputs stay in x.dtype — the
    MXU accumulates in fp32 regardless, and fp32 outputs double HBM traffic
    and the AD-saved residual footprint."""
    g = jnp.einsum("bse,ef->bsf", x, w_gate)
    u = jnp.einsum("bse,ef->bsf", x, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fe->bse", h, w_down)
