"""Find the knee of a serving cell's traffic mix on the chip.

    python3 -m bench.knee --workload <cell> --seed <n> --seconds <s> --rates r1,r2,...

One process sets the cell up once and runs one window per mean rate, in
order, on the same engine.  For each it prints the requests due, how many
finished within the window and the drain, the queue at the start of each
burst period, and the end-to-end readings.  The knee is the highest rate
at which the queue is empty at the start of every period, so that no
burst's backlog carries into the next; a cell's mix then sets
``rate_per_s`` at about four fifths of it.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

from bench import run as R
from bench import traffic
from bench.peaks import peaks_for
from bench.record import RunRecord
from bench.serve import ServeCell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import enable_compile_cache

    device = R.tpu_devices(1)[0]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    manifest = R.load_manifest()
    cell = R.find_cell(manifest, args.workload)
    conf = R.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    family = R.load_reference(conf)
    sc = ServeCell(conf, mix, family, args.seed, args.seconds)
    sc.setup()
    period = mix["burst"]["period_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        sc.arrivals = traffic.schedule(m, args.seed, args.seconds)
        sc.prompts = traffic.prompt_tokens(args.seed, sc.arrivals, conf["model"]["vocab_size"])
        w = sc.window()
        run = RunRecord(cell=cell, config=conf, mix=m, seconds=args.seconds, setup_s=0.0,
                        peaks=peaks_for(device.device_kind), family=family, t0=w.t0,
                        t_end=w.t_end, requests=w.requests, steps=w.steps)
        at_periods = []
        for k in range(int(args.seconds // period) + 1):
            t = w.t0 + k * period
            before = [s.queued for s in w.steps if s.end <= t]
            at_periods.append(before[-1] if before else 0)
        done_in_window = sum(1 for c in w.requests
                             if len(c.token_times) == c.max_new_tokens and c.token_times[-1] <= w.t_end)
        out = {"rate_per_s": rate, "due": len(w.requests), "done_in_window": done_in_window,
               "drain_s": w.drained_at - w.t_end, "queue_at_period_starts": at_periods,
               "max_queue": max((s.queued for s in w.steps), default=0)}
        for name in ("ttft_p95_ms", "itl_p95_ms", "output_tokens_per_s",
                     "queue_wait_p95_ms", "mfu_pct.serve"):
            out[name] = R.read_metric(name, run)
        print(json.dumps(out), flush=True)
        sc.eng.drain_retired()
    return 0


if __name__ == "__main__":
    sys.exit(main())
