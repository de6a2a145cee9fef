"""CLI: `python -m tools.graphcheck [--update-baseline] [--graphs PAT]`.

Exit codes: 0 clean (all findings covered by the baseline and every
fingerprint matches), 1 new violations/drift, 2 usage/internal error.
`--update-baseline` rewrites BOTH tools/graphcheck/baseline.json (the
findings debt ledger — kept empty for ray_tpu/) and fingerprints.json
(the per-graph contract).
"""

from __future__ import annotations

import argparse
import fnmatch
import os
import sys


def main(argv=None) -> int:
    # Simulated-mesh environment must be pinned before jax touches a
    # backend (jax may already be imported by the caller; backends
    # initialize lazily, so the env + config update still land).
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms",
                      os.environ["JAX_PLATFORMS"].split(",")[0])

    from tools import checklib
    from tools import graphcheck
    from tools.graphcheck import fingerprint, lowering

    p = argparse.ArgumentParser(
        prog="python -m tools.graphcheck",
        description="XLA-graph static analysis: donation, host-sync, "
                    "recompile, collective/sharding drift, memory gates "
                    "over every registered TPU hot graph")
    p.add_argument("--graphs", default=None,
                   help="fnmatch pattern over registered graph names "
                        "(fingerprint cover checks are skipped when "
                        "filtered)")
    p.add_argument("--root", default=checklib.repo_root())
    p.add_argument("--baseline", default=None)
    p.add_argument("--fingerprints", default=None)
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignore the baseline")
    p.add_argument("--update-baseline", action="store_true",
                   help="accept current findings + fingerprints")
    p.add_argument("--list", action="store_true",
                   help="list registered graphs and exit")
    args = p.parse_args(argv)

    registry = graphcheck.load_corpus()
    if args.graphs:
        registry = {k: v for k, v in registry.items()
                    if fnmatch.fnmatch(k, args.graphs)}
        if not registry:
            print(f"no registered graph matches {args.graphs!r}",
                  file=sys.stderr)
            return 2
    if args.list:
        for name, reg in sorted(registry.items()):
            meshes = ", ".join(graphcheck.mesh_key(m) for m in reg.meshes)
            print(f"{name}  [{meshes}]  ({reg.source[0]}:{reg.source[1]})")
        return 0

    fpath = args.fingerprints or os.path.join(
        args.root, graphcheck.FINGERPRINTS_REL)
    bpath = args.baseline or os.path.join(args.root,
                                          graphcheck.BASELINE_REL)
    corpus = lowering.lower_all(registry)
    for rec in corpus:
        print(f"lowered {rec.graph_id}", file=sys.stderr)

    if args.update_baseline:
        fps = graphcheck.current_fingerprints(corpus)
        if args.graphs:
            merged = fingerprint.load(fpath)
            merged.update(fps)
            fps = merged
        fingerprint.save(fpath, fps)
        print(f"fingerprints updated: {len(fps)} graphs -> {fpath}")
        findings = graphcheck.run(args.root, corpus=corpus,
                                  fingerprints_path=fpath)
        checklib.save_baseline(bpath, findings)
        print(f"baseline updated: {len(findings)} entries -> {bpath}")
        return 0

    findings = graphcheck.run(args.root, corpus=corpus,
                              fingerprints_path=fpath)
    if args.graphs:
        # A filtered run cannot see the whole corpus; cover checks would
        # misfire as stale.
        findings = [f for f in findings if f.rule != "fingerprint-stale"]
    return checklib.report(findings, bpath,
                           use_baseline=not args.no_baseline)


if __name__ == "__main__":
    sys.exit(main())
