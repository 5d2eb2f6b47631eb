"""Readings of the comparison's numbers, program and control, over many seeds.

    python3 perfbench/readings.py --workload lcbench.stream_fixed \
        --seeds 1 2 3 --seconds 30 [--faults mean_altered ...]

One process runs, for each seed, what a benchmark run does (that seed's
tasks, the warm-up cycles, a window of ``--seconds``) without the timing,
and prints, as one JSON line, the numbers of ``perfbench/check.py`` on the
window's rounds for the program and for the control (the reference at the
next precision below the configuration's, in the program's place), or for
the program with each planted fault. The limits in the configuration files
are set from these readings; the benchmark's own runs never run them.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from perfbench import check, faults, harness  # noqa: E402
from perfbench.run import devices, load_spec, set_up, use_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults", choices=faults.FAULTS, nargs="*", default=[],
                    help="plant each fault in turn and read the program alone")
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    devices(spec.cell["chips"])

    use_cache()
    gp, limits = spec.config["gp"], spec.config["limits"]
    first = spec.traffic["warmup_cycles"]
    for seed in args.seeds:
        for fault in args.faults or [None]:
            try:
                with (faults.planted(fault) if fault
                      else contextlib.nullcontext()):
                    cell = set_up(spec, seed)
                    start, end, _ = harness.run_window(cell, args.seconds,
                                                       first_cycle=first)
            except Exception as e:
                # a run that crashes gives no number: recorded as such
                print(json.dumps({"seed": seed, "fault": fault,
                                  "crashed": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)
                continue
            row = {"seed": seed, "fault": fault, "rounds": len(cell.rounds),
                   "round_s": (end - start) / len(cell.rounds)}
            for who, control in (("program", False), ("control", True)):
                if control and fault:
                    continue
                ok, checks = check.compare(cell.rounds, cell.tasks, gp,
                                           limits, seed, control=control)
                row[who] = {k: v for k, (v, _) in checks.items()}
                row[who + "_correct"] = ok
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
