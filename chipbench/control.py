#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 ...

For every seed, in this one process: the cell's data, a warm-up pass
and one timed pass of the program at the cell's own size, the check's
numbers for that pass (the program's readings), and the same numbers
for the control -- the reference itself computed in bfloat16, the next
precision below the float32 that the configuration states, put in the
program's place.  The last line is a JSON object with every reading,
the largest program reading and the smallest control reading of each
number.  The benchmark's own runs never run this; it needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seeds, info) -> dict:
    """Program and control numbers of ``cell`` for every seed."""
    from chipbench import run
    out = {"program": {}, "control": {}}
    for seed in seeds:
        t0 = time.perf_counter()
        root = os.path.join(run.WORK, cell.name)
        tracks, store_dir, _, _ = run.build_data(cell, seed, root)
        drv = cell.driver.Driver(cell.config, cell.traffic, root, store_dir)
        drv.run_pass()
        last = drv.run_pass()
        check = cell.driver.Check(tracks, cell.config, cell.limits)
        out["program"][seed] = check.program(last)
        out["control"][seed] = check.control()
        print(f"seed {seed}: program {out['program'][seed]}; control "
              f"{out['control'][seed]} ({time.perf_counter() - t0:.1f}s)",
              flush=True)
    keys = next(iter(out["program"].values())).keys()
    out["lower"] = {k: max(r[k] for r in out["program"].values())
                    for k in keys}
    out["upper"] = {k: min(r[k] for r in out["control"].values())
                    for k in keys}
    out["device"] = info
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from chipbench import run
    run.use_cache_dir()
    cell = run.Cell.load(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    info = run.require_tpu(cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import device
    device.enable_compile_cache()
    print(json.dumps(readings(cell, args.seeds, info)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
