"""Look at one cell's profiler trace by hand.

    python3 -m bench.trace_tool --workload <cell> --seed <n> --seconds <s> --out <dir>

Runs the cell once with ``--trace 1`` as ``bench.run`` does, keeps the
profiler's output under ``<dir>/raw``, and writes two files beside it:
``describe.json``, every trace line's most frequent events with their
statistics (how to find a kernel's name before writing a reader), and
``excerpt.json``, the reduced trace of the window's first ``--excerpt-s``
seconds with the engine steps tagged (the recorded trace the host tests
read).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from bench import run as R
from bench import trace as tr
from bench import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--excerpt-s", type=float, default=1.5)
    args = ap.parse_args(argv)
    out = Path(args.out)
    raw = out / "raw"
    raw.mkdir(parents=True, exist_ok=True)
    manifest = R.load_manifest()
    cell = R.find_cell(manifest, args.workload)
    conf = R.load_config(cell["config"])

    def keep_excerpt(run) -> None:
        if run.trace_window:
            lo = run.trace_window[0]
            tr.dump(tr.excerpt(run.trace, lo, lo + args.excerpt_s * 1e9), out / "excerpt.json")

    R.run_cell(cell, conf, traffic.load_mix(cell["traffic"]),
               R.metrics_for(manifest, cell["name"], True), args.seed, args.seconds, True,
               trace_dir=str(raw), on_record=keep_excerpt)
    (out / "describe.json").write_text(json.dumps(tr.describe(tr.find_xplane(str(raw))),
                                                  indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
