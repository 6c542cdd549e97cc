#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``); the mix names the driver
(``drivers/<driver>.py``) that runs its unit of work; each per-layer metric
is read by ``metrics/<metric>.py``.

A run: set-up (the driver's warm-up of every shape the window uses), then
units of work back to back until ``--seconds`` have passed, the window
ending with the last unit; then device memory is read, the program's state
dropped, and the driver's readings against the plain reference, each held
to its limit in the traffic mix (``judge``), decide ``correct``. With ``--trace 1`` the window is traced and the per-layer
metrics are reported in place of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. The last line of standard output is the result, one JSON
object; the numbers compared are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from modules import load_module  # noqa: E402

NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclass
class Ctx:
    """What a driver and a metric reader see of one run."""

    root: Path
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    workdir: Path
    peaks: Dict = field(default_factory=dict)
    #: counts the driver keeps of the window's work
    counters: Dict[str, float] = field(default_factory=dict)
    window_s: float = 0.0
    spans: Any = None
    xtrace: Any = None
    device: Dict = field(default_factory=dict)


def setup_environment(root: Path) -> None:
    """Before JAX is imported: the persistent compilation cache at a fixed
    place in the checkout, every program cached however short its compile,
    and the TPU runtime's logs kept out of fixed system paths."""
    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(root / "src"))


def describe_device(chips: int, require_tpu: bool = True) -> Dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def bytes_written() -> int:
    """Bytes this process has passed to ``write`` so far (files, pipes and
    all; the storage counter reads 0 on a machine whose disk is memory)."""
    try:
        for line in Path("/proc/self/io").read_text().splitlines():
            if line.startswith("wchar:"):
                return int(line.split()[1])
    except OSError:
        pass
    return -1


class CompileCounter:
    """Counts compile requests and persistent-cache hits (JAX's own
    monitoring events) while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.requests = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if self.active and event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event, **_):
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def load_cell(root: Path, workload: str, manifest: Optional[Dict] = None,
              traffic_overrides: Optional[Dict] = None):
    """The cell's entry, its configuration, its traffic mix (with any
    overrides) and the driver the mix names, all found by name."""
    manifest = manifest or json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    traffic.update(traffic_overrides or {})
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    return cell, config, traffic, driver


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> List[Dict]:
    """Each number beside its limit: the traffic's ``limits`` for the
    measured ones, 0 for the exact ones; a number passes at or under it."""
    return [{"name": k, "value": v, "limit": limits.get(k, 0), "ok": v <= limits.get(k, 0)}
            for k, v in readings.items()]


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, manifest: Optional[Dict] = None,
             out_dir: Optional[Path] = None,
             traffic_overrides: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result object. ``require_tpu``,
    ``manifest`` and ``traffic_overrides`` exist for the harness's own
    tests on the CPU."""
    manifest = manifest or json.loads((root / "BENCHMARK.json").read_text())
    cell, config, traffic, driver = load_cell(root, workload, manifest, traffic_overrides)
    e2e = [m for m in manifest["end_to_end"] if applies(m, workload)]
    layers = [m for m in manifest["per_layer"] if applies(m, workload)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py",
                                      "bench_metric_" + m["name"].replace(".", "_"))
               for m in layers} if trace else {}

    device = describe_device(int(cell["chips"]), require_tpu)
    peaks_table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if require_tpu and device["kind"] not in peaks_table:
        raise KeyError(f"device kind {device['kind']!r} is not in peaks.json")
    workdir = root / "benchmarks" / "results" / "work" / workload
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    ctx = Ctx(root=root, cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, workdir=workdir,
              peaks=peaks_table.get(device["kind"], {}), device=device)
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", file=sys.stderr)

    import jax

    compiles = CompileCounter()
    try:
        driver.setup(ctx)
        print(f"[setup] seconds={time.time() - T_PROCESS} "
              f"process_bytes_written={bytes_written()}", file=sys.stderr)
        if trace:
            from spans import Spans

            ctx.spans = Spans()
            for name, target in getattr(driver, "SPANS", {}).items():
                ctx.spans.wrap(name, target)
            trace_dir = workdir / "trace"
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no per-call Python events: they slow the host
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        setup_s = time.time() - T_PROCESS
        results: List[Dict] = []
        compiles.active = True
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                t_unit = time.perf_counter()
                results.append(driver.run_unit(ctx, len(results)))
                print(f"[unit] {len(results) - 1} seconds={time.perf_counter() - t_unit}",
                      file=sys.stderr)
                if time.perf_counter() - t0 >= seconds:
                    break
        ctx.window_s = time.perf_counter() - t0
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
            ctx.spans.remove()
        print(f"[window] seconds={ctx.window_s} units={len(results)} "
              f"compile_requests={compiles.requests} cache_hits={compiles.cache_hits} "
              f"compiles={compiles.requests - compiles.cache_hits}", file=sys.stderr)
        device["memory_peak_bytes"] = memory_peak(int(cell["chips"]))
        counts = driver.account(ctx, results)
        ctx.counters.update(counts)

        metrics: Dict[str, Dict] = {}
        breakdown = None
        if trace:
            import xplane

            ctx.xtrace = xplane.load(str(trace_dir)) if require_tpu else \
                xplane.load(str(trace_dir), device_prefix=None)
            if out_dir is not None:
                out_dir.mkdir(parents=True, exist_ok=True)
                (out_dir / f"{workload}.trace_lines.txt").write_text(
                    "\n".join(xplane.describe(str(trace_dir))) + "\n")
            device["busy_s"] = ctx.xtrace.busy_s()
            device["window_s"] = ctx.xtrace.window_s
            breakdown = {"device_ops": ctx.xtrace.top_ops(10),
                         "idle_gaps": ctx.xtrace.idle_gaps(10, set(driver.SPANS))}
            for m in layers:
                value = readers[m["name"]].read(ctx)
                if value is None:
                    print(f"[metrics] {m['name']}: nothing to read; left out", file=sys.stderr)
                    continue
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            shutil.rmtree(trace_dir, ignore_errors=True)
        else:
            values = driver.end_to_end(ctx, results)
            values["setup_s"] = setup_s
            for m in e2e:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

        gc.collect()
        compared = judge(driver.readings(ctx, results), traffic["limits"])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(f"[io] process_bytes_written={bytes_written()}", file=sys.stderr)
    correct = all(c["ok"] for c in compared)
    result = {
        "correct": correct,
        "attempted": int(ctx.counters.get("attempted", 0)),
        "failed": int(ctx.counters.get("failed", 0)),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                          for c in compared}
    for c in compared:
        print(f"[compared] {c['name']} value={c['value']} limit={c['limit']} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for a description of the trace's planes")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}: nothing to measure", file=sys.stderr)
        return 2
    setup_environment(ROOT)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                          out_dir=Path(args.out) if args.out else None)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return NO_CHIP
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
