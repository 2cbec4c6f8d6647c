"""Read the check's two limits on the chip: the program's ``max_gap`` and
the float8 control's, for several seeds of one cell in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Each seed is a whole run of the cell, as ``bench/run.py`` makes it, whose
check also runs the control: the reference again with every matrix
product's operands rounded to float8, reading at each served position the
gap of the token the control puts first, judged by the same limit as
the program's: ``control_correct`` has to come out false. The benchmark's own runs do not run it. One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    run.use_cache()
    devices = run.chips(cell)[:cell.chips]
    from bench import compilelog, flops

    peak = flops.peaks(devices[0].device_kind)
    compiles = compilelog.CompileLog()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        res = run.execute(cell, seed, args.seconds, False, devices, peak,
                          compiles=compiles, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "max_gap": res["check"]["max_gap"]["value"],
                          "control_max_gap": res["control"]["max_gap"],
                          "control_correct": res["control"]["correct"],
                          "limit": res["check"]["max_gap"]["limit"],
                          "tokens": res["check"]["max_gap"]["tokens"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
