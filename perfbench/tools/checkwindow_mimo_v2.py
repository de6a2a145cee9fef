#!/usr/bin/env python3
"""tools/checkwindow_laguna.py for `mimo_v2_5`: the same flow (one replica at
the cell's engine sizes, prompts in one bucket, of a chunk with its held
window page and a continuation, and at the longest; the program's
log-probabilities against the plain reference's under the configuration's
`check` rule), with this model's faults in place of Laguna's:

    python3 perfbench/tools/checkwindow_mimo_v2.py --seed <n> \
        --prompt-tokens 1500,9000,15000 --new-tokens 64

  no_sink         the window layers' softmax without its learned sink
  full_sink       a sink on the full layers too, of ln(prompt tokens) - 0.85
                  a head: what holds of a full layer's row the share the
                  seeded sinks hold of a window layer's (a sink of 4.0 is
                  1 / 300 of a 9000-key row and reads as the honest run)
  window_96       a window of 96 (a page released too early, a narrower mask)
  v_unscaled      v without attention_value_scale
  rotary_swapped  each kind of layer rotated with the other kind's base
  weights_f8      every weight matrix through float8_e4m3fn: the precision
                  below the bfloat16 the configuration states, and the
                  control the cell's median limit refuses

and, only when named (readings that decide nothing: `CONTROLS`), K and V
alone through float8_e4m3fn (`kv_f8`: it reads four to five times the
honest median and still under the harness's 0.1),
the router's logits, the attention scores and the sinks in bfloat16.

`--sink-share` instead prints what share of a full window row's softmax
mass the seeded sinks of layer 1 hold (mean over heads and rows, and the
least and largest head), over the normed embeddings of 512 seeded tokens:
the configuration's `assumed.e_sink` states it.

Importing this module changes nothing of `checkwindow_laguna`; `main()` lays
its faults over that module's while it runs and puts them back.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.tools import checkwindow_laguna  # noqa: E402

CELL = "mimo_v2_5-serve-longturn"
CONTROLS = ("kv_f8", "router_bf16", "scores_bf16", "sink_bf16")


def faults(reference, window: int) -> dict:
    """name -> fault(model_cfg, ids) -> (model_cfg, ids) for the reference;
    each sets the reference's switches as it needs them."""
    def make(swap: bool = False, full_sink: bool = False, **kw):
        flags = {k: kw.pop(k, None) for k in (
            "KV_DTYPE", "ROUTER_DTYPE", "SCORES_DTYPE", "SINK_DTYPE",
            "WEIGHTS_DTYPE")}

        def fault(c, ids):
            for k, v in flags.items():
                setattr(reference, k, v)
            reference.SINK_WHERE_ABSENT = (
                math.log(len(ids)) - 0.85 if full_sink and ids else None)
            swapped = dict(rope_theta=c.window_rope_theta,
                           window_rope_theta=c.rope_theta) if swap else {}
            return dataclasses.replace(c, **kw, **swapped), ids
        return fault

    return {"none": make(), "no_sink": make(attn_sink=""),
            "full_sink": make(attn_sink="FW", full_sink=True),
            f"window_{window * 3 // 4}": make(window=window * 3 // 4),
            "v_unscaled": make(value_scale=1.0),
            "rotary_swapped": make(swap=True),
            "weights_f8": make(WEIGHTS_DTYPE="float8_e4m3fn"),
            "kv_f8": make(KV_DTYPE="float8_e4m3fn"),
            "router_bf16": make(ROUTER_DTYPE="bfloat16"),
            "scores_bf16": make(SCORES_DTYPE="bfloat16"),
            "sink_bf16": make(SINK_DTYPE="bfloat16")}


def sink_share(argv) -> int:
    """The share of a full window row's mass the seeded sinks hold."""
    from perfbench.harness import cells, modelcfg
    cells.prepare_env(ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import windowed
    rehearsal = "--rehearsal" in argv
    found = cells.load_cell(ROOT, CELL, rehearsal)
    c = modelcfg.model_config(found["cfg"], found["traffic"]["kind"],
                              rehearsal)
    ref = cells.load_reference(ROOT, found["cfg"])
    li = c.attn_pattern.index("W")
    lp = windowed._init_layer(jax.random.PRNGKey(1), c, li)
    s, w, h = 4 * c.window, c.window, c.window_heads
    hkv = c.window_kv_heads or c.n_kv_heads
    with jax.default_matmul_precision("highest"):
        u = jax.random.normal(jax.random.PRNGKey(2), (s, c.d_model))
        u = ref._rmsnorm(u, 1.0, c.norm_eps)
        cos, sin = ref._rotary(c, "W", s)
        q = ref._rotate((u @ ref._f32(lp["wq"])).reshape(s, h, -1), cos, sin)
        k = ref._rotate((u @ ref._f32(lp["wk"])).reshape(s, hkv, -1), cos,
                        sin)
        k = jnp.repeat(k, h // hkv, axis=1)
        sc = jnp.einsum("qhe,khe->hqk", q, k) * c.head_dim ** -0.5
        rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None]
        mask = (cols <= rows) & (cols > rows - w)
        keys = jnp.sum(jnp.where(mask[None], jnp.exp(sc), 0.0), -1)  # [h, s]
        mine = jnp.exp(ref._f32(lp["sink"]))[:, None]
        share = np.asarray((mine / (mine + keys))[:, w - 1:].mean(1))
    print(json.dumps({"sink_share_of_a_full_window_row": {
        "mean": float(share.mean()), "least_head": float(share.min()),
        "largest_head": float(share.max()), "heads": int(h), "window": w,
        "sink_mean": float(np.mean(lp["sink"])),
        "sink_spread": float(np.std(lp["sink"]))}}), flush=True)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--sink-share" in argv:
        return sink_share(argv)
    theirs = checkwindow_laguna.faults, checkwindow_laguna.CONTROLS
    checkwindow_laguna.faults, checkwindow_laguna.CONTROLS = faults, CONTROLS
    try:
        return checkwindow_laguna.main([
            "--workload", CELL, "--prompt-tokens", "1500,9000,15000", *argv])
    finally:
        checkwindow_laguna.faults, checkwindow_laguna.CONTROLS = theirs


if __name__ == "__main__":
    sys.exit(main())
