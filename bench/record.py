"""What one run leaves for the metric readers in ``bench/metrics/``.

A reader is a module with ``read(run: RunRecord) -> float | None``; it
returns ``None`` where the run holds nothing for it to read, and the
harness then leaves that metric out of the result line.  A reader that
calls the configuration's family module (``run.family``) lists the
functions it calls in ``NEEDS``; a family that lacks one is an error
before the run.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass
class RunRecord:
    cell: Dict
    config: Dict
    mix: Dict
    seconds: float
    setup_s: float
    peaks: object  # bench.peaks.Peaks
    #: the configuration's family module, ``bench/reference/<family>.py``:
    #: the readers count FLOPs and bytes with its functions
    family: object = None
    #: the window on the host clock (``time.perf_counter`` seconds)
    t0: float = 0.0
    t_end: float = 0.0
    #: serving: ``bench.serve.ClientRequest`` and ``StepSpan`` of the window
    requests: List = field(default_factory=list)
    steps: List = field(default_factory=list)
    #: the reduced profiler trace (``bench.trace.Trace``) and the window on
    #: its clock, in nanoseconds, where the run was traced
    trace: Optional[object] = None
    trace_window: Optional[Tuple[float, float]] = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0
