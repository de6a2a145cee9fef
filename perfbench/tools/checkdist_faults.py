#!/usr/bin/env python3
"""tools/checkdist.py with the faults of a hybrid state-space model added
(`nemotron3_nano_30b`): what a wrong or lower-precision program would read
under the configuration's `check` rule. Same options; `--fault` may also
name

  state_bf16      the recurrent state kept in bfloat16 between positions
                  (the precision below the one the configuration states)
  state_f8        the same in float8_e4m3fn
  conv_tap        the convolution's oldest tap dropped (3 taps of 4)
  no_scale        the routed weights without routed_scaling_factor
  wrong_snapshot  the Mamba layers resume the prompt's last
                  `--prompt-tokens mod 1024` tokens from the state and
                  window of the boundary one page (128 tokens) before the
                  1024-token chunk's end: a snapshot of the wrong boundary
                  (needs --prompt-tokens over 1024)
  no_snapshot     the same from the sequence's start (boundary 0)

    python3 perfbench/tools/checkdist_faults.py --workload <cell> --seed <n> \
        --sequences 6 --prompt-tokens 1500 --new-tokens 48 \
        --fault none,state_bf16,conv_tap,no_scale,wrong_snapshot
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import cells  # noqa: E402
from perfbench.tools import checkdist  # noqa: E402

REFERENCE = os.path.join(ROOT, "perfbench", "reference", "nemotron_h.py")
CHUNK, PAGE = 1024, 128


def _blind(span):
    def fault(c, ids):
        cells.load_module(REFERENCE).MAMBA_BLIND = span
        return c, ids
    return fault


def _with(**kw):
    def fault(c, ids):
        cells.load_module(REFERENCE).MAMBA_BLIND = None
        return dataclasses.replace(c, **kw), ids
    return fault


checkdist.FAULTS.update({
    "state_bf16": _with(ssm_state_dtype="bfloat16"),
    "state_f8": _with(ssm_state_dtype="float8_e4m3fn"),
    "conv_tap": lambda c, ids: _with(
        ssm_conv_width=c.ssm_conv_width - 1)(c, ids),
    "no_scale": _with(moe_routed_scale=1.0),
    "wrong_snapshot": _blind((CHUNK - PAGE, CHUNK)),
    "no_snapshot": _blind((0, CHUNK)),
})

if __name__ == "__main__":
    sys.exit(checkdist.main())
