"""``python -m repro.analysis`` — the repo's static-analysis gate.

Default run = AST lint rules over the given paths + the Pallas VMEM
candidate-space audit (both pure stdlib, no jax import). ``--jaxpr`` adds
the traced-program audits (f64 / host callbacks / retrace) which import
jax and take a few seconds. Exit status is 0 iff no *new* findings — i.e.
nothing unsuppressed and unbaselined, and every auditor invariant holds.

Typical invocations::

    python -m repro.analysis src/ --baseline analysis_baseline.json
    python -m repro.analysis src/ --jaxpr --baseline analysis_baseline.json
    python -m repro.analysis src/ --write-baseline analysis_baseline.json
    python -m repro.analysis path/to/file.py --format json
"""
from __future__ import annotations

import argparse
import json
import sys

from .runner import (analyze_paths, filter_baseline, format_report,
                     load_baseline, write_baseline)
from .vmem import audit_candidate_space, best_fitting_blocks


def _run_vmem_audit(out) -> int:
    """Audit the {64, 128, 256} block candidates against the VMEM budget.

    The raw grid is *expected* to contain oversized combinations at large
    (n, m) — the invariant we enforce is that the VMEM-filtered chooser
    never returns one of them: every shape bucket either has a fitting
    best pair or is marked as taking the XLA fallback of
    ``lk_mvm_fused``. A violation here means the byte model and the
    chooser in vmem.py have drifted apart.
    """
    from .vmem import fused_vmem_breakdown

    rows = audit_candidate_space()
    buckets = [2 ** k for k in range(3, 14)]
    failures = 0
    no_fit = 0
    for n in buckets:
        for m in buckets:
            for prec in ("f32", "bf16"):
                pair = best_fitting_blocks(n, m, precision=prec)
                if pair is None:
                    no_fit += 1   # fine: the XLA fallback handles it
                elif not fused_vmem_breakdown(n, m, *pair, prec).fits():
                    failures += 1
                    print(f"vmem: FILTER BUG — chooser returned oversized "
                          f"{pair} for (n={n}, m={m}, {prec})", file=out)
    print(f"vmem: {len(rows)} oversized (shape, candidate) combinations in "
          f"the raw {{64,128,256}} sweep; {no_fit} (shape, precision) "
          "bucket(s) take the XLA fallback; filtered chooser "
          f"emitted {'no' if not failures else failures} oversized pair(s).",
          file=out)
    return failures


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="JAX/Pallas-aware static analysis (AST lints, VMEM "
                    "budget audit, optional jaxpr audits).")
    parser.add_argument("paths", nargs="*", default=["src"],
                        help="files or directories to lint (default: src)")
    parser.add_argument("--baseline", metavar="FILE",
                        help="JSON baseline of grandfathered fingerprints")
    parser.add_argument("--write-baseline", metavar="FILE",
                        help="write current findings as the new baseline "
                             "and exit 0")
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument("--jaxpr", action="store_true",
                        help="also run the jaxpr auditors (imports jax)")
    parser.add_argument("--no-vmem", action="store_true",
                        help="skip the Pallas VMEM candidate-space audit")
    args = parser.parse_args(argv)

    paths = args.paths or ["src"]
    findings = analyze_paths(paths)

    if args.write_baseline:
        write_baseline(findings, args.write_baseline)
        print(f"wrote {len(findings)} finding(s) to {args.write_baseline}")
        return 0

    baseline: set[str] = set()
    if args.baseline:
        try:
            baseline = load_baseline(args.baseline)
        except FileNotFoundError:
            print(f"warning: baseline {args.baseline} not found; "
                  "treating all findings as new", file=sys.stderr)
    new, baselined = filter_baseline(findings, baseline)

    failed = bool(new)
    if args.format == "json":
        print(json.dumps({"findings": [f.to_json() for f in new],
                          "baselined": baselined}, indent=2))
    else:
        print(format_report(new, baselined))

    if not args.no_vmem:
        failed |= bool(_run_vmem_audit(sys.stdout))

    if args.jaxpr:
        # Imported lazily: jax is heavy and the lint layer must work
        # without it (e.g. in a minimal CI container).
        from .jaxpr_audit import run_all_audits
        failures = run_all_audits(verbose=True)
        failed |= bool(failures)

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
