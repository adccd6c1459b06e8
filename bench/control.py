"""The control of `correct`: the reference computed in float32, the
precision below the configuration's float64, put in the program's place
and compared with the float64 reference by the cell's own comparison and
limits.  A sound comparison must call it not correct.

    python3 bench/control.py --workload <cell> --periods <n> --seeds s1 s2 ...

Runs on the host alone (no chip), at the cell's own fleet size, over the
number of periods a run of the cell checks.  Prints one line per seed
with each number compared, its limit, and whether the control failed.
"""
import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import fleetgen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--periods", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = manifest.cell(args.workload)
    config, traffic, cp = spec["config"], spec["traffic"], spec["params"]
    limits = cp["limits"]
    failed_all = True
    with ThreadPoolExecutor(max(1, min(8, (os.cpu_count() or 2) - 1))) as pool:
        for seed in args.seeds:
            t0 = time.perf_counter()
            fleet = fleetgen.make_fleet(config, traffic,
                                        int(cp["trace_periods"]), seed)
            ref = reference.run(fleet, config, args.periods, pool=pool)
            low = reference.run(fleet, config, args.periods, np.float32,
                                pool=pool)
            got = compare.compare(compare.reference_as_program(low), ref)
            failed = any(got[k] > limits[k] for k in limits)
            failed_all &= failed
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "periods": args.periods, "control": got,
                              "limits": limits, "control_failed": failed,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
