#!/usr/bin/env python3
"""tools/checkdist.py with the faults of `ouro_2_6b` added: what a wrong or
lower-precision looped program would read under the rule that decides
`correct`. Same options; `--fault` may also name

  three_passes  three passes of four (ModelConfig.loops 3)
  shared_cache  every pass attends pass 0's keys and values (a cache
                indexed by layer alone: `assumed` (e)'s other reading)
  open_stream   the pass's closing norm left out of the stream, read by
                the gate and the head alone (`assumed` (b)'s other reading)
  two_norms     two norms a layer, as Qwen2 (`assumed` (a)'s other reading)
  kv_f8         keys and values through float8_e4m3fn (the precision below
                the bfloat16 pages)

(`last_token` and `mid_token`, one wrong token, are checkdist's own.)
`--post-norm-gain <g>` sets the two output norms' seeded gains to g in the
program AND the reference (they share the engine's parameters): how far the
honest program and the controls lie apart at another conditioning of the
seeded function than `assumed.seeded_weights`' (PERF.md section 2). The
derivation of the configuration's rule in one command:

    python3 perfbench/tools/checkdist_ouro.py \
        --workload ouro_2_6b-serve-solver --seed <n> --sequences 6 \
        --prompt-tokens 200 --new-tokens 16 --fault \
        none,three_passes,shared_cache,open_stream,two_norms,kv_f8,last_token
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

REFERENCE = os.path.join(ROOT, "perfbench", "reference", "ouro.py")


def _switch(*switches):
    cells.load_module(REFERENCE).FAULTS = frozenset(switches)


def _fault(*switches, **fields):
    """The reference's switches set for THIS fault (and cleared of the
    last one's), the model's fields replaced."""
    def fault(c, ids):
        _switch(*switches)
        return dataclasses.replace(c, **fields), ids
    return fault


FAULTS = {
    "none_ouro": _fault(),      # clears the switches between faults
    "three_passes": lambda c, ids: _fault(loops=c.loops - 1)(c, ids),
    "shared_cache": _fault("shared_cache"),
    "open_stream": _fault("open_stream"),
    "two_norms": _fault(post_norms=False),
    "kv_f8": _fault("kv_f8"),
}


def _gained(gain: float):
    """serve_cell.Replica whose two output norms are seeded at `gain`."""
    import jax.numpy as jnp

    from perfbench.harness import serve_cell

    class Gained(serve_cell.Replica):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            base = self.server._base_params
            layers = dict(base["layers"])
            for k in ("attn_post_norm", "mlp_post_norm"):
                layers[k] = jnp.full_like(layers[k], gain)
            self.server._base_params = {**base, "layers": layers}
            self.engine.params = self.server._base_params

    return Gained


def main() -> int:
    """checkdist.main() with these faults beside its own, for this process
    only: importing this module changes nothing of `checkdist`. Its own
    faults run with the reference's switches cleared; `none` (no fault
    function at all) reads what the fault before it left, so it goes
    first, or `none_ouro` in its place."""
    own = {name: (lambda c, ids, f=f: f(*FAULTS["none_ouro"](c, ids)))
           for name, f in checkdist.FAULTS.items()}
    checkdist.FAULTS.update({**own, **FAULTS})
    if "--post-norm-gain" in sys.argv:
        at = sys.argv.index("--post-norm-gain")
        gain = float(sys.argv[at + 1])
        del sys.argv[at:at + 2]
        from perfbench.harness import serve_cell
        serve_cell.Replica = _gained(gain)
    try:
        return checkdist.main()
    finally:
        _switch()


if __name__ == "__main__":
    sys.exit(main())
