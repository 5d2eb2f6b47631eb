"""Benchmark harness: one module per paper table/figure + roofline summary.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV lines per benchmark (harness
contract) and the per-benchmark tables used in EXPERIMENTS.md.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer seeds")
    ap.add_argument("--only", default="",
                    help="comma list: scaling,prediction,automl,roofline")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    t_all = time.time()

    if only is None or "scaling" in only:
        from . import bench_scaling
        t0 = time.time()
        rows = bench_scaling.main(sizes=(16, 32) if args.quick
                                  else (16, 32, 64))
        it_time = [r[2] for r in rows if r[0] == "iterative"][-1]
        print(f"bench_scaling,{(time.time()-t0)*1e6:.0f},"
              f"iterative_fit_s_at_max={it_time:.2f}")

    if only is None or "prediction" in only:
        from . import bench_prediction
        t0 = time.time()
        res = bench_prediction.main(
            n_seeds=2 if args.quick else 5,
            budgets=(60, 120) if args.quick else (60, 120, 240))
        budget = 120
        print(f"bench_prediction,{(time.time()-t0)*1e6:.0f},"
              f"lkgp_mse_b{budget}={res[('LKGP', budget)][0]:.5f}")

    if only is None or "automl" in only:
        from . import bench_automl
        t0 = time.time()
        payload = bench_automl.main(
            quick=args.quick,
            out_path="BENCH_automl.quick.json" if args.quick
            else "BENCH_automl.json")
        acc = payload["acceptance"]
        print(f"bench_automl,{(time.time()-t0)*1e6:.0f},"
              f"sh_lkgp_beats_rank={acc['sh_lkgp_beats_rank']},"
              f"precond_reduces_cg_iters={acc['precond_reduces_cg_iters']}")

    if (only is None and not args.quick) or (only and "ablation" in only):
        from .bench_prediction import ablate_t_kernel
        t0 = time.time()
        res = ablate_t_kernel()
        best = min(res, key=lambda k: res[k][0])
        print(f"bench_ablation,{(time.time()-t0)*1e6:.0f},"
              f"best_t_kernel={best}")

    if only is None or "roofline" in only:
        # summarise dry-run artifacts if present (no compile here)
        import glob
        import json
        import os
        d = "artifacts/dryrun_opt" if os.path.isdir("artifacts/dryrun_opt") \
            else "artifacts/dryrun"
        arts = sorted(glob.glob(f"{d}/*.json"))
        if arts:
            from repro.launch.roofline import summarize_artifacts
            t0 = time.time()
            table = summarize_artifacts(arts)
            worst = min(table, key=lambda r: r["roofline_fraction"])
            best = max(table, key=lambda r: r["roofline_fraction"])
            print(f"bench_roofline,{(time.time()-t0)*1e6:.0f},"
                  f"cells={len(table)},best_fraction="
                  f"{best['roofline_fraction']:.3f}"
                  f"({best['arch']}/{best['shape']}),worst_fraction="
                  f"{worst['roofline_fraction']:.3f}")
        else:
            print("bench_roofline,0,no_artifacts (run repro.launch.dryrun)")

    print(f"# total benchmark wall time: {time.time()-t_all:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
