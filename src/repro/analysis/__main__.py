"""`python -m repro.analysis` — run the full static-analysis pass.

Sections (each prints PASS or its violation list; exit 1 if any fail):

  kernel-contracts   AST lint of every kernels/<family>/ package
  purity             unseeded np.random + wall-clock-in-core lint
  engine-dispatch    pallas_call budgets per method x fused x impl,
                     banned primitives, no-f64 (traced jaxprs)
  segment-scan       the fused inner-step scan's pallas_call budget per
                     attn_impl (none on the reference path)
  serve              decode/prefill budgets per attn_impl
  donation           declared donations appear in the lowering

`--smoke` is the CI entrypoint (the pass is already smoke-sized — identical
checks, kept as a flag so every CI job reads uniformly). `--section NAME`
runs one section (repeatable).
"""
from __future__ import annotations

import argparse
import sys
from typing import Callable, Dict, List


def _sections() -> Dict[str, Callable[[], List[str]]]:
    # imported lazily so `--help` stays instant and import errors surface
    # per-section instead of killing the whole CLI
    from repro.analysis import jaxpr_audit, kernel_lint
    return {
        "kernel-contracts": kernel_lint.run_kernel_lint,
        "purity": kernel_lint.lint_purity,
        "engine-dispatch": jaxpr_audit.audit_engine,
        "segment-scan": jaxpr_audit.audit_segment,
        "serve": jaxpr_audit.audit_serve,
        "donation": jaxpr_audit.audit_donation,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static analysis: trace auditor + kernel-contract linter")
    ap.add_argument("--smoke", action="store_true",
                    help="CI entrypoint (same checks; the pass is smoke-sized)")
    ap.add_argument("--section", action="append", default=None,
                    metavar="NAME", help="run only the named section(s)")
    args = ap.parse_args(argv)

    sections = _sections()
    names = args.section or list(sections)
    unknown = [n for n in names if n not in sections]
    if unknown:
        ap.error(f"unknown section(s) {unknown}; options: {list(sections)}")

    n_violations = 0
    for name in names:
        try:
            violations = sections[name]()
        except Exception as e:                     # a crashed checker FAILS
            violations = [f"{name}: checker crashed: {type(e).__name__}: {e}"]
        status = "PASS" if not violations else f"FAIL ({len(violations)})"
        print(f"[{name:16s}] {status}")
        for v in violations:
            print(f"  - {v}")
        n_violations += len(violations)
    if n_violations:
        print(f"\nstatic analysis: {n_violations} violation(s)")
        return 1
    print("\nstatic analysis: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
