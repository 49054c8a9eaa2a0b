"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  One process, which holds the chip and
starts no children.  It reads the cell from ``BENCHMARK.json``, loads its
configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<mix>.json``) and the reader of each of its metrics
(``bench/metrics/<metric>.py``), sets up, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output.  With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer metrics
instead of the end-to-end ones.

A device that is not a TPU, or fewer chips than the cell asks for, is an
error before any model work: the exit code is 2 and nothing is printed on
standard output.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(ROOT / "src"))

from bench import traffic  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from bench.record import RunRecord  # noqa: E402


class NoChip(RuntimeError):
    pass


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(manifest: Dict, name: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> Dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_reference(conf: Dict):
    """The configuration's family module, ``reference/<family>.py``: the
    plain reference and the one place that reads the family's keys."""
    return load_module(BENCH_DIR / "reference" / f"{conf['reference']}.py")


def metrics_for(manifest: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_metric(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def read_metric(name: str, run: RunRecord) -> Optional[float]:
    return load_metric(name).read(run)


def check_family(family, kind: str, metrics: List[Dict]) -> None:
    """Raise, naming the module and each function, where the family module
    lacks a function that a cell of ``kind`` or one of ``metrics`` calls."""
    needs = [(f, f"{kind} cells") for f in cell_class(kind).NEEDS]
    for m in metrics:
        needs += [(f, m["name"]) for f in getattr(load_metric(m["name"]), "NEEDS", ())]
    missing = [f"{f} (for {who})" for f, who in needs if not callable(getattr(family, f, None))]
    if missing:
        where = getattr(family, "__file__", None) or family.__name__
        raise AttributeError(f"family module {where} lacks " + ", ".join(missing))


def cell_class(kind: str):
    """The cell class for a configuration's kind: serving or training."""
    if kind == "serve":
        from bench.serve import ServeCell
        return ServeCell
    if kind == "train":
        from bench.train import TrainCell
        return TrainCell
    raise ValueError(f"unknown configuration kind {kind!r}")


def tpu_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


class Collections:
    """The garbage collector's pauses, on the host clock, while it is on."""

    def __init__(self) -> None:
        self.pauses: List[tuple] = []  # (start, seconds, generation)
        self._start = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((self._start, time.perf_counter() - self._start,
                                info["generation"]))

    def __enter__(self) -> "Collections":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def summary(self, lo: float, hi: float) -> str:
        inside = [(s, g) for t, s, g in self.pauses if lo <= t <= hi]
        full = [s for s, g in inside if g == 2]
        return (f"{len(inside)} garbage collections inside the window and drain, "
                f"{len(full)} of them full; longest {max((s for s, _ in inside), default=0) * 1e3:.1f}"
                f" ms, {sum(s for s, _ in inside) * 1e3:.1f} ms in all")


def run_cell(cell: Dict, conf: Dict, mix: Dict, metrics: List[Dict], seed: int,
             seconds: float, trace: bool, *, require_chip: bool = True,
             peaks=None, trace_dir: Optional[str] = None,
             on_record: Optional[Callable[[RunRecord], None]] = None,
             compile_cache: bool = True) -> Dict:
    """Set up, measure, check and reduce one run; returns the result object.
    With ``trace`` the profiler writes to ``trace_dir``, which is kept, or
    else to a temporary directory, which is removed.  ``on_record`` sees the
    run's record once the metrics are read.  A family module that lacks a
    function the cell or a metric calls is an error before anything else."""
    family = load_reference(conf)
    check_family(family, conf["kind"], metrics)
    import jax

    devices = tpu_devices(cell["chips"]) if require_chip else jax.devices()
    device = devices[0]
    peaks = peaks or peaks_for(device.device_kind)

    from repro.compile_cache import enable_compile_cache

    if compile_cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: List[tuple] = []

    def on_event(name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            compiles.append((time.perf_counter(), name, secs))

    jax.monitoring.register_event_duration_secs_listener(on_event)

    sc = cell_class(conf["kind"])(conf, mix, family, seed, seconds)
    sc.setup()
    keep_trace = trace_dir is not None
    if trace and not keep_trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    t_setup = {}

    def start() -> None:
        t_setup["end"] = time.perf_counter()
        if trace:
            jax.profiler.start_trace(trace_dir)

    with Collections() as collections:
        w = sc.window(on_window_start=start)
    if trace:
        jax.profiler.stop_trace()
    stats = device.memory_stats() or {}
    before = [(n, s) for t, n, s in compiles if t < w.t0]
    backend = sum(s for n, s in before if n.endswith("backend_compile_duration"))
    print(f"bench: set-up: {len(before)} compilation events, {backend:.2f} s in the backend "
          f"compiler", file=sys.stderr)
    in_window = sum(1 for t, _, _ in compiles if w.t0 <= t <= w.drained_at)
    print(f"bench: {in_window} compilation events inside the window and drain", file=sys.stderr)
    print(f"bench: {collections.summary(w.t0, w.drained_at)}", file=sys.stderr)
    sc.log_window(w)

    run = RunRecord(cell=cell, config=conf, mix=mix, seconds=seconds,
                    setup_s=t_setup["end"] - PROCESS_START, peaks=peaks, family=family,
                    t0=w.t0, t_end=w.t_end, requests=getattr(w, "requests", []), steps=w.steps)
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    device_info.update(sc.compiled_memory())
    breakdown = None
    if trace:
        from bench import trace as tr

        run.trace = tr.extract(tr.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        sc.label_spans(run.trace, w)
        run.trace_window = tr.span(run.trace, "window")
        if run.trace_window:
            lo, hi = run.trace_window
            device_info["busy_s"] = tr.busy_ns(run.trace, lo, hi) / 1e9
            device_info["window_s"] = (hi - lo) / 1e9
            breakdown = {"device_ops": tr.top_ops(run.trace, lo, hi),
                         "idle_gaps": tr.idle_by_span(run.trace, lo, hi)}

    values = {}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if on_record is not None:
        on_record(run)

    checks = sc.check(w)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    attempted, failed = sc.outcomes(w)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": values, "device": device_info}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    manifest = load_manifest()
    cell = find_cell(manifest, args.workload)
    conf = load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    try:
        result = run_cell(cell, conf, mix, metrics_for(manifest, cell["name"], bool(args.trace)),
                          args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
