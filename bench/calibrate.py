"""Readings that set a cell's correctness limits, on the chip.

    python3 -m bench.calibrate --workload <cell> --seeds s1,s2,... --seconds <s>

One process sets the cell up once and, for each seed, makes new weights
and traffic, runs a window of ``--seconds`` through the same compiled
programs, and prints one JSON line: every number the run compares, read
for the program, and the same numbers read for the control (the plain
reference computed in fp8 in the program's place) and, for a training
cell, for the reference given half of each batch (a planted fault).  A
limit lies above the program's largest reading and below the smallest
reading of the control and of the faults.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

from bench import run as R
from bench import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import enable_compile_cache

    R.tpu_devices(1)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = R.find_cell(R.load_manifest(), args.workload)
    conf = R.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    sc = None
    for seed in (int(s) for s in args.seeds.split(",")):
        if sc is None:
            sc = R.cell_class(conf["kind"])(conf, mix, R.load_reference(conf), seed, args.seconds)
            sc.setup()
        else:
            sc.restart(seed)
        w = sc.window()
        checks = sc.check(w, control=True)
        print(json.dumps({"seed": seed, "readings": sc.readings,
                          "correct": all(c["value"] <= c["limit"] for c in checks.values())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
