"""One benchmark cell of the latent-Kronecker GP on the chip.

    python3 perfbench/run.py --workload lcbench.stream_fixed --seed 7 --seconds 30

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file (``configs[].file``), the traffic mix
(``perfbench/traffic/<traffic>.json``, whose ``kind`` names
``perfbench/kinds/<kind>.py``) and, for each per-layer metric, a
reader (``perfbench/metrics/<name>.py`` with ``read(ctx)``).

A run makes its tasks from ``--seed``, warms up every program the cell's
traffic uses (set-up, ``setup_s``), measures whole cycles of rounds for
``--seconds`` seconds, reads the device's peak memory, and then compares a
seeded sample of the window's rounds with the plain reference
(``perfbench/check.py``). With ``--trace 1`` the first cycle of the window
is traced and the per-layer metrics are reported instead of the
end-to-end ones. The last line of stdout is one JSON object; the numbers
compared, each with its limit, are the last lines of stderr and the last
key of that object. Without a TPU, or with fewer chips than the cell
asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from perfbench import check, harness  # noqa: E402


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def _for_cell(metrics: list, workload: str) -> list:
    return [x for x in metrics if workload in x.get("workloads", [workload])]


def spec_of(config_file: Path, traffic: str, cell: dict | None = None,
            end_to_end=(), per_layer=()) -> SimpleNamespace:
    """A cell's configuration and traffic mix, read from their files."""
    return SimpleNamespace(
        cell=cell, config=json.loads(Path(config_file).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=list(end_to_end), per_layer=list(per_layer))


def load_spec(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell's entry, configuration, traffic mix and metrics, by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return spec_of(root / entry["file"], cell["traffic"], cell,
                   _for_cell(bench["end_to_end"], workload),
                   _for_cell(bench["per_layer"], workload))


def reader(name: str):
    """``read(ctx)`` of the per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def _compiles(counter, before, what: str) -> str:
    n, hits, secs = (a - b for a, b in zip(counter.snapshot(), before))
    return (f"{what}: {n} executables built, {hits} loaded from the compile "
            f"cache, {n - hits} compiled, {secs:.3f} s in backend compile")


def use_cache() -> str:
    """The checkout's persistent compilation cache, with every executable
    written to it: those that compile in under a second (JAX's default
    threshold) would otherwise be compiled again by every run."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def set_up(spec, seed: int) -> harness.Cell:
    """The cell's tasks from the seed, and one warm-up of every program its
    traffic uses (nothing kept for the comparison)."""
    cell = harness.Cell(spec.config, spec.traffic, seed)
    cell.make_tasks()
    cell.record = False
    cycle = harness.cycle_of(spec.traffic["kind"])
    for k in range(spec.traffic["warmup_cycles"]):
        cycle(cell, k)
    cell.record = True
    return cell


def run(spec, seed: int, seconds: float, trace: bool, devs, *,
        device_kind: str | None = None) -> dict:
    """One run of the cell; returns the result object (not printed)."""
    import jax

    cache_dir = use_cache()
    counter = harness.CompileCounter()
    start_counts = counter.snapshot()
    cell = set_up(spec, seed)
    setup_s = time.perf_counter() - T_START
    print(_compiles(counter, start_counts, "set-up"), f"(cache {cache_dir})",
          file=sys.stderr, flush=True)

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    traced = {}

    def first_cycle_done(done: int):
        if trace and done == 1:
            traced["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            cell.spans.annotate = False

    if trace:
        jax.profiler.start_trace(trace_dir)
        cell.spans.annotate = True
        traced["ann"] = jax.profiler.TraceAnnotation("pb.window")
        traced["ann"].__enter__()
    before = counter.snapshot()
    start, end, cycles = harness.run_window(
        cell, seconds, first_cycle=spec.traffic["warmup_cycles"],
        on_cycle=first_cycle_done)
    print(_compiles(counter, before, "window"), "(none expected)",
          file=sys.stderr, flush=True)
    counter.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    kind = device_kind or devs[0].device_kind
    tr = None
    if trace:
        from perfbench import trace as trace_mod
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        tr = trace_mod.load(str(files[-1])) if files else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    n_rounds = len(cell.rounds)
    cell.carry = None
    gc.collect()
    correct, checks = check.compare(cell.rounds, cell.tasks,
                                    spec.config["gp"], spec.config["limits"],
                                    seed)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if not trace:
        values = {"setup_s": setup_s, "round_s": (end - start) / n_rounds,
                  "peak_hbm_mib": peak / 2**20}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
        out = {"correct": correct, "attempted": n_rounds, "failed": 0,
               "metrics": metrics, "device": device}
    else:
        ctx = SimpleNamespace(spans=cell.spans, window=(start, end),
                              window_rounds=cell.rounds, trace=tr,
                              config=spec.config, device_kind=kind)
        metrics = {}
        for m in spec.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        from perfbench import trace as trace_mod
        if tr is not None and tr.window is not None:
            device["busy_s"] = trace_mod.busy_s(tr)
            device["window_s"] = tr.window_s
        out = {"correct": correct, "attempted": n_rounds, "failed": 0,
               "metrics": metrics, "device": device}
        if tr is not None:
            out["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                                "idle_gaps": trace_mod.idle_gaps(tr)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    try:
        devs = devices(spec.cell["chips"])
    except NoChip as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 3
    out = run(spec, args.seed, args.seconds, bool(args.trace), devs)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
