#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, many seeds in one
process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        [--side program|control|both] [--fault <name>]

For each seed it makes the cell's corpus and then, on the ``program`` side,
runs one job through the cell's own entry (the timed path, at the timed
sizes); on the ``control`` side it puts the reference in the program's
place, one precision step down (float32 matrix products as three bfloat16
passes, the TPU's ``BF16_BF16_F32_X3``, where the configuration states
``highest``). ``--fault`` plants one of the faults that the cell's entry
lists (``entries/<entry>.py``) under the program first. Each job's answers
go through the harness's own check, with the cell's limits, and one JSON
line per seed and side gives its numbers, diagnostics and verdict. A limit lies above every program reading and below
the control's. The benchmark's own runs never run the control or a fault.
``--rehearse`` runs tiny on the CPU.
"""

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def readings(cell, seeds, sides, rehearse: bool):
    import jax

    import reference

    for seed in seeds:
        key = harness.base_key(seed)
        setup = harness.Setup(cell, jax.random.fold_in(key, 0), rehearse)
        keys = harness.JobKeys(key, 1)
        for side in sides:
            t0 = time.perf_counter()
            if side == "control":
                out = jax.block_until_ready(reference.run_reference(
                    setup.kind, setup.data(), keys(0), cell.params(), lower=True
                ))
            else:
                out = setup.run(keys(0))
            job_s = time.perf_counter() - t0
            checks, failed, diags = harness.check(cell, setup, keys, [(0, out)])
            yield {"workload": cell.name, "seed": seed, "side": side, "job_s": job_s,
                   "correct": failed == 0,
                   "numbers": {n: c["value"] for n, c in checks.items()},
                   "diagnostics": {n: v[0] for n, v in diags.items()}}
        del setup
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--side", choices=("program", "control", "both"), default="both")
    ap.add_argument("--fault", help="a fault of the cell's entry, planted under the program")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell(spec, args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("no TPU", file=sys.stderr)
            return 2
        harness.use_compile_cache()
    sides = ("program", "control") if args.side == "both" else (args.side,)
    if args.fault:
        import faults

        faults.plant(cell.traffic["entry"], args.fault)
        sides = (f"fault:{args.fault}",)
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in readings(cell, seeds, sides, args.rehearse):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
