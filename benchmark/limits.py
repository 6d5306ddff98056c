"""The readings a cell's limits are set from, on the chip at the cell's own
size, in one process (set-up is long):

    python3 benchmark/limits.py --workload <cell> --seeds 12 --controls 3

For each seed the program's first steps, through the runner's own set-up,
against the plain reference: the lower readings. For the first ``--controls``
seeds the control (the reference in the program's place, computed in the
precision below the one the configuration states) and each planted fault the
runner names (``FAULTS``), against the same reference: the upper readings.
The benchmark's own runs never run this; nothing here decides ``correct``."""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as bench  # noqa: E402

BELOW = {"float32": "bf16", "bfloat16": "fp8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    args = ap.parse_args(argv)
    cell = bench.load_cell(bench.ROOT, args.workload)
    sys.path.insert(0, bench.ROOT)
    bench.configure_cache(bench.ROOT)
    stamp = bench.device_stamp(cell.chips, bench.load_json(
        os.path.join(cell.bench_dir, "peaks.json")))
    if isinstance(stamp, str):
        print(f"no readings - {stamp}", file=sys.stderr)
        return bench.NO_CHIP
    runner = importlib.import_module("runners." + cell.config["runner"])
    compare = importlib.import_module("compare")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]

    def ctx_for(seed):
        return types.SimpleNamespace(
            config=cell.config, traffic=cell.traffic, seed=seed,
            chips=cell.chips, span=bench.no_span)

    def show(kind, seed, got, ref, t0):
        row = {name: value for name, (value, _) in
               compare.gaps(got, ref).items()}
        print(json.dumps({"kind": kind, "seed": seed, **row,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        return row

    below = BELOW[cell.config["compute_dtype"]]
    planted = {"control_" + below: {"compute": below}}
    planted.update({"fault_" + k: v for k, v in runner.FAULTS.items()})
    program = {}
    for seed in seeds:  # the program first, alone on the chip
        t0 = time.perf_counter()
        run = runner.Run(ctx_for(seed))
        program[seed] = run.readings
        print(f"program seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        run.close()
        del run
        gc.collect()
    rows = {"program": []}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ref = runner.reference(ctx_for(seed))
        rows["program"].append(show("program", seed, program[seed], ref, t0))
        if i >= args.controls:
            continue
        for kind, kw in planted.items():
            t0 = time.perf_counter()
            rows.setdefault(kind, []).append(
                show(kind, seed, runner.reference(ctx_for(seed), **kw), ref, t0))
    for name in compare.NUMBERS:
        summary = {"number": name,
                   "lower": max(r[name] for r in rows["program"])}
        summary.update({kind: min(r[name] for r in rs)
                        for kind, rs in rows.items() if kind != "program"})
        print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
