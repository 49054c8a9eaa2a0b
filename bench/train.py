"""Training cells: a model configuration trained by ``repro.train.Trainer``.

Set-up makes the weights on the device from the seed and builds the one
``Trainer`` the window drives.  It then takes the first three steps
through the window's own call and feed, which compiles the step, and
keeps what the check needs: the weights before step 1, the optimizer's
first moment after it (whence the gradient the optimizer got), the
weights after step 3, and the three losses.  The window then runs steps
back to back until ``--seconds`` have passed; it ends with the step that
crosses that mark, so the rate takes all the work and all the time.

The feed is a plain iterator of rows of uniform random tokens, batch
``i`` drawn from the seed and ``i`` alone, so every row differs.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Callable

import numpy as np

from . import traffic
from .serve import log


@dataclass
class TrainStep:
    start: float
    end: float
    tokens: int
    loss: float


@dataclass
class TrainWindow:
    t0: float
    t_end: float
    steps: List[TrainStep]
    drained_at: float


def train_config(conf: Dict):
    from repro.optim import AdamWConfig, ScheduleConfig
    from repro.train.trainer import TrainConfig

    t = conf["train"]
    return TrainConfig(
        adamw=AdamWConfig(b1=t["b1"], b2=t["b2"], eps=t["adam_eps"],
                          weight_decay=t["weight_decay"], grad_clip=t["grad_clip"]),
        schedule=ScheduleConfig(peak_lr=t["peak_lr"], warmup_steps=t["warmup_steps"],
                                decay_steps=t["decay_steps"], min_lr_ratio=t["min_lr_ratio"],
                                kind=t["schedule"]),
        microbatches=t["microbatches"], z_loss=t["z_loss"])


def batch_at(seed: int, index: int, batch: int, seq: int, vocab: int) -> Dict[str, np.ndarray]:
    toks = traffic.rng_for(seed, 1000 + index).integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class Feed:
    """The plain iterator the Trainer reads: batch ``i`` from the seed."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int) -> None:
        self.seed, self.batch, self.seq, self.vocab, self.index = seed, batch, seq, vocab, 0

    def __iter__(self) -> Iterator:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        b = batch_at(self.seed, self.index, self.batch, self.seq, self.vocab)
        self.index += 1
        return b


def leaf_norms(tree) -> List[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64))) for x in jax.tree_util.tree_leaves(tree)]


def leaf_gaps(got: List[float], ref: List[float], keep=None) -> List[float]:
    """Each leaf's gap between the program's norm and the reference's, over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    med = float(np.median(ref))
    idx = range(len(ref)) if keep is None else keep
    return [abs(got[i] - ref[i]) / max(ref[i], med) for i in idx]


class TrainCell:
    #: what a training cell calls in the configuration's family module
    NEEDS = ("program_config", "make_weights", "train")

    def __init__(self, conf: Dict, mix: Dict, family, seed: int, seconds: float) -> None:
        self.conf, self.mix, self.ref, self.seed, self.seconds = conf, mix, family, seed, seconds
        self.batch = conf["train"]["global_batch"]
        self.seq = mix["seq_len"]
        self.vocab = conf["model"]["vocab_size"]
        self.trainer = None

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.train.trainer import Trainer

        t = time.perf_counter()
        self.feed = Feed(self.seed, self.batch, self.seq, self.vocab)
        self.trainer = Trainer(self.ref.program_config(self.conf), train_config(self.conf),
                               self.feed)
        self.first_steps()
        log(f"set-up: weights and the first three steps {time.perf_counter() - t:.2f} s")

    def first_steps(self) -> None:
        """New weights from the seed, then three steps through the window's
        own call and feed; keeps what the check compares, on the host."""
        import jax
        from jax.profiler import TraceAnnotation

        from repro.optim import adamw_init

        self.params = self.opt = None
        gc.collect()
        key = jax.random.key(traffic.weights_seed(self.seed))
        params = self.ref.make_weights(self.conf["model"], key)
        self.params0 = jax.device_get(params)
        opt = adamw_init(params)
        self.feed.seed, self.feed.index = self.seed, 0
        losses, times = [], []
        with TraceAnnotation("bench.warmup"):
            for k in range(3):
                t = time.perf_counter()
                params, opt, hist = self.trainer.run(params, opt, 1)
                times.append(time.perf_counter() - t)
                losses.append(hist[0]["loss"])
                if k == 0:
                    self.m1 = jax.device_get(opt["m"])
        log("set-up: first three steps " + ", ".join(f"{t:.2f} s" for t in times))
        self.params3 = jax.device_get(params)
        self.losses = losses
        self.params, self.opt = params, opt

    # ------------------------------------------------------------- window
    def window(self, on_window_start: Optional[Callable[[], None]] = None) -> TrainWindow:
        from jax.profiler import TraceAnnotation

        steps: List[TrainStep] = []
        params, opt = self.params, self.opt
        self.params = self.opt = None
        if on_window_start is not None:
            on_window_start()
        t0 = time.perf_counter()
        t_end = t0 + self.seconds
        with TraceAnnotation("bench.window"):
            while True:
                start = time.perf_counter()
                with TraceAnnotation("bench.train_step"):
                    params, opt, hist = self.trainer.run(params, opt, 1)
                end = time.perf_counter()
                steps.append(TrainStep(start, end, int(hist[0]["tokens"]), hist[0]["loss"]))
                if end >= t_end:
                    break
        self.params, self.opt = params, opt
        return TrainWindow(t0, end, steps, end)

    # ------------------------------------------------------------- checks
    def check(self, w: TrainWindow, control: bool = False) -> Dict[str, Dict[str, float]]:
        """The first gradient as the optimizer got it and the weights'
        change over the first three steps, against the reference from the
        same weights and batches.  The losses are read but not compared:
        neither the fp8 control nor a planted fault moves them past what
        sound runs read."""
        import jax

        t = self.conf["train"]
        self.params = self.opt = None
        gc.collect()
        t0 = time.perf_counter()
        batches = [batch_at(self.seed, i, self.batch, self.seq, self.vocab) for i in range(3)]
        ref = self.ref.train(self.conf["model"], t, self.params0, batches)
        first_grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - t["b1"]), self.m1)
        readings = self.compare(ref, (self.losses, first_grad, self.params3))
        log(f"reference: three steps {time.perf_counter() - t0:.2f} s")
        if control:
            for name, precision, rows in (("control", "fp8", self.batch),
                                          ("fault.half_batch", "float32", self.batch // 2)):
                part = [{k: v[:rows] for k, v in b.items()} for b in batches]
                got = self.ref.train(self.conf["model"], t, self.params0, part, precision=precision)
                readings.update({f"{name}.{k}": v for k, v in self.compare(ref, got).items()})
            readings["leaf_names"] = [jax.tree_util.keystr(k) for k, _ in
                                      jax.tree_util.tree_flatten_with_path(self.params0)[0]]
        self.readings = readings
        limits = self.conf["limits"]
        return {k: {"value": readings[k], "limit": limits[k]}
                for k in ("grad_gap", "grad_gap_median", "change_gap")}

    def compare(self, ref, got) -> Dict:
        """The numbers read, for ``got`` = (losses of the first three steps,
        first gradient as the optimizer got it, weights after step 3)
        against the reference's.  The worst leaf of the gradient is nearly
        always ``D``, a small leaf whose gap is noise, so the median leaf's
        gap is read beside it.  Leaves whose reference gradient is under a
        thousandth of the median leaf's move by round-off alone and are left
        out of the change."""
        import jax

        (ref_losses, ref_g, ref_p3), (losses, grad, p3) = ref, got
        delta = lambda p: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: np.asarray(a) - b, p, self.params0))
        g_ref = leaf_norms(ref_g)
        med = float(np.median(g_ref))
        moved = [i for i, g in enumerate(g_ref) if g >= 1e-3 * med]
        grad = leaf_gaps(leaf_norms(grad), g_ref)
        change = leaf_gaps(delta(p3), delta(ref_p3), moved)
        loss = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
        return {
            "loss_gap": max(loss),
            "loss_steps": loss,
            "grad_gap": max(grad),
            "grad_gap_median": float(np.median(grad)),
            "change_gap": max(change),
            "grad_leaves": grad,
            "change_leaves": change,
            "moved": moved,
        }

    def compiled_memory(self) -> Dict[str, int]:
        """What the compiled step needs on the device, as its compiler
        reckons it: the arguments, the outputs and the temporaries, less
        what the outputs alias of the arguments.  JAX's ``peak_bytes_in_use``
        counts the arrays that outlive a step, not its temporaries."""
        mem = self.trainer._compiled_step.memory_analysis()
        parts = {k: int(getattr(mem, f"{k}_size_in_bytes"))
                 for k in ("argument", "output", "temp", "alias")}
        log("compiled step: " + ", ".join(f"{k} {v} bytes" for k, v in parts.items()))
        return {"compiled_step_bytes": parts["argument"] + parts["output"] + parts["temp"] - parts["alias"]}

    def log_window(self, w: TrainWindow) -> None:
        """The longest step beside the median, so that a stall shows."""
        d = [s.end - s.start for s in w.steps]
        log(f"window: {len(d)} steps, median {float(np.median(d)) * 1e3:.1f} ms, "
            f"the longest {max(d) * 1e3:.1f} ms")

    def label_spans(self, trace, w: TrainWindow) -> None:
        """Training's spans need no tags."""

    def outcomes(self, w: TrainWindow):
        """(steps taken in the window, those whose loss was not finite)."""
        return len(w.steps), sum(1 for s in w.steps if not np.isfinite(s.loss))

    def restart(self, seed: int) -> None:
        """Calibration only: new weights and batches from ``seed``, through
        the same compiled step."""
        self.seed = seed
        self.first_steps()
