#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... --control-seeds 11,12,13

One process: the cell's set-up once, then for each seed one unit of work
through the timed path at the cell's own size, and the numbers the harness
compares (the program's readings); for each control seed also the same
numbers for the control, the plain reference in the next precision down
put in the program's place, judged by the harness's own comparison
(``run.judge``) against the traffic's limits: ``control_correct`` has to be
false on every control seed. Prints one ``[reading]`` line per seed and a
last JSON line: per number, the largest program reading (the lower end of
the limit's room) and the smallest control reading (the upper end).

Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def calibrate(root: Path, workload: str, seeds, control_seeds, require_tpu: bool = True,
              manifest=None, traffic_overrides=None):
    cell, config, traffic, driver = run.load_cell(root, workload, manifest, traffic_overrides)
    device = run.describe_device(int(cell["chips"]), require_tpu)
    workdir = root / "benchmarks" / "results" / "work" / f"calibrate.{workload}"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    ctx = run.Ctx(root=root, cell=cell, config=config, traffic=traffic,
                  seed=int(seeds[0]), seconds=0.0, trace=False, workdir=workdir,
                  device=device)
    program, control, control_correct = {}, {}, {}
    program_max, control_min = {}, {}
    t0 = time.time()
    try:
        driver.setup(ctx)
        print(f"[setup] seconds={time.time() - t0}", file=sys.stderr, flush=True)
        for seed in dict.fromkeys(list(seeds) + list(control_seeds)):
            ctx.seed = int(seed)
            results = [driver.run_unit(ctx, 0)]
            line = {"seed": seed, "unit_s": results[0]["seconds"]}
            if seed in seeds:
                line["program"] = program[seed] = driver.readings(ctx, results)
                for k, v in line["program"].items():
                    program_max[k] = max(program_max.get(k, v), v)
            if seed in control_seeds:
                line["control"] = control[seed] = driver.control_readings(ctx, results)
                for k, v in line["control"].items():
                    control_min[k] = min(control_min.get(k, v), v)
                compared = run.judge(line["control"], traffic["limits"])
                line["control_correct"] = control_correct[seed] = all(c["ok"] for c in compared)
            print("[reading] " + json.dumps(line), file=sys.stderr, flush=True)
            shutil.rmtree(results[0]["workdir"], ignore_errors=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {"workload": workload, "device": device, "limits": traffic["limits"],
            "program": program, "control": control, "control_correct": control_correct,
            "program_max": program_max, "control_min": control_min}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    run.setup_environment(run.ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    try:
        out = calibrate(run.ROOT, args.workload, seeds, control_seeds)
    except run.NoChip as e:
        print(str(e), file=sys.stderr)
        return run.NO_CHIP
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
