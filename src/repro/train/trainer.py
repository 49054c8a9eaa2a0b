"""Training step + loop: grad-accumulation scan, remat, AdamW, per-stream
telemetry, checkpoint/resume.

``make_train_step`` builds the jittable pure step; ``Trainer`` owns the live
loop (data, checkpoints, per-stream instrumentation via ``repro.core``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import ReportSink, StepCost, StreamStats
from repro.core.instrument import SPANS
from repro.models import forward, init_params, model_defs
from repro.optim import (
    AdamWConfig,
    ScheduleConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    ef_compress,
    ef_state_init,
    learning_rate,
)
from repro.perf.hlo import summarize_compiled

__all__ = ["TrainConfig", "make_train_step", "make_loss_fn", "Trainer", "cross_entropy"]


@dataclass(frozen=True)
class TrainConfig:
    adamw: AdamWConfig = AdamWConfig()
    schedule: ScheduleConfig = ScheduleConfig()
    microbatches: int = 1  # gradient-accumulation chunks per step
    compress_grads: bool = False  # int8 + error feedback on the accum path
    accum_dtype: str = "float32"  # grad accumulator (bf16 halves its HBM)
    aux_weight: float = 0.01  # MoE load-balance loss weight
    z_loss: float = 1e-4  # logit-norm regulariser (stability at scale)
    seed: int = 0


def cross_entropy(logits: jax.Array, labels: jax.Array, z_loss: float = 0.0):
    """Token-mean CE over valid (label >= 0) positions, fp32, with z-loss."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0] - lse
    nll = -jnp.where(valid, ll, 0.0)
    denom = jnp.maximum(valid.sum(), 1)
    loss = nll.sum() / denom
    if z_loss > 0:
        loss = loss + z_loss * (jnp.where(valid, lse, 0.0) ** 2).sum() / denom
    return loss, denom


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    def loss_fn(params, batch):
        # The Pallas attention and SSD kernels have no backward pass yet, so
        # the differentiated forward takes the blocked-jnp paths.
        logits, aux = forward(cfg, params, batch, attn_impl="xla", ssd_impl="xla")
        loss, n_tok = cross_entropy(logits, batch["labels"], tcfg.z_loss)
        total = loss + tcfg.aux_weight * aux
        return total, {"loss": loss, "aux": aux, "tokens": n_tok}

    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig) -> Callable:
    """Builds ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)``.  ``batch`` arrays are (global_batch, ...) and are split into
    ``tcfg.microbatches`` accumulation chunks along axis 0 with ``lax.scan``
    (activation memory ∝ one microbatch; the paper-independent standard for
    fitting train_4k on 16 GB chips)."""
    loss_fn = make_loss_fn(cfg, tcfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    n_micro = tcfg.microbatches

    def train_step(params, opt_state, batch):
        if n_micro == 1:
            (_, metrics), grads = grad_fn(params, batch)
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            if tcfg.compress_grads:
                grads, ef = ef_compress(grads, opt_state["ef"])
                opt_state = {**opt_state, "ef": ef}
        else:
            def reshape(x):
                return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

            micro = jax.tree_util.tree_map(reshape, batch)
            acc_dt = jnp.dtype(tcfg.accum_dtype)
            acc0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, acc_dt), params)
            ef0 = opt_state.get("ef") if tcfg.compress_grads else None
            met0 = {"loss": jnp.zeros((), jnp.float32), "aux": jnp.zeros((), jnp.float32),
                    "tokens": jnp.zeros((), jnp.int32)}

            def body(carry, mb):
                acc, ef, met = carry
                (_, metrics), grads = grad_fn(params, mb)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
                if tcfg.compress_grads:
                    grads, ef = ef_compress(grads, ef)
                acc = jax.tree_util.tree_map(
                    lambda a, g: (a.astype(jnp.float32) + g).astype(a.dtype), acc, grads
                )
                met = {
                    "loss": met["loss"] + metrics["loss"],
                    "aux": met["aux"] + metrics["aux"],
                    "tokens": met["tokens"] + metrics["tokens"].astype(jnp.int32),
                }
                return (acc, ef, met), None

            (grads, ef, met), _ = jax.lax.scan(body, (acc0, ef0, met0), micro)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            metrics = {"loss": met["loss"] / n_micro, "aux": met["aux"] / n_micro,
                       "tokens": met["tokens"]}
            if tcfg.compress_grads:
                opt_state = {**opt_state, "ef": ef}

        grads, gnorm = clip_by_global_norm(grads, tcfg.adamw.grad_clip)
        lr = learning_rate(opt_state["step"], tcfg.schedule)
        inner = {k: opt_state[k] for k in ("m", "v", "step")}
        new_params, new_inner = adamw_update(grads, inner, params, lr, tcfg.adamw)
        new_state = {**opt_state, **new_inner}
        metrics = {**metrics, "grad_norm": gnorm, "lr": lr}
        return new_params, new_state, metrics

    return train_step


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, key=None):
    """(params, opt_state) — real allocation (small models / smoke tests)."""
    key = key if key is not None else jax.random.PRNGKey(tcfg.seed)
    params = init_params(model_defs(cfg), key, cfg.param_jdtype())
    opt_state = adamw_init(params, jnp.dtype(cfg.opt_state_dtype))
    if tcfg.compress_grads:
        opt_state["ef"] = ef_state_init(params)
    return params, opt_state


class Trainer:
    """Live training loop with per-stream stats + checkpoint/restart.

    The train lane and the (optional) eval lane are distinct *streams* in
    the paper's sense: their step records and byte/FLOP attribution never
    mix (``stats.summary(train_stream)`` vs ``stats.summary(eval_stream)``).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        data_iter,
        *,
        eval_iter=None,
        ckpt_manager=None,
        ckpt_every: int = 0,
        eval_every: int = 0,
        sinks: Optional[Tuple[ReportSink, ...]] = None,
    ) -> None:
        self.cfg = cfg
        self.tcfg = tcfg
        self.data_iter = data_iter
        self.eval_iter = eval_iter
        self.ckpt = ckpt_manager
        self.ckpt_every = ckpt_every
        self.eval_every = eval_every
        self.sinks = list(sinks) if sinks else []
        self.stats = StreamStats()
        from repro.core import StreamManager

        self.streams = StreamManager()
        self.train_stream = self.streams.create_stream("train").stream_id
        self.eval_stream = self.streams.create_stream("eval").stream_id
        self.step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        self.eval_fn = jax.jit(lambda p, b: make_loss_fn(cfg, tcfg)(p, b)[1])
        self.step = 0
        self._step_cost: Optional[StepCost] = None
        #: the train step compiled once, on the first batch's shapes
        self._compiled_step = None

    def restore_or_init(self):
        if self.ckpt is not None:
            restored = self.ckpt.restore_latest()
            if restored is not None:
                params, opt_state, meta = restored
                self.step = int(meta.get("step", 0))
                return params, opt_state
        return init_train_state(self.cfg, self.tcfg)

    def run(self, params, opt_state, num_steps: int):
        """``num_steps`` steps, then the reports.  Program spans:
        ``train.batch`` (the next batch), ``train.step`` (the step and its
        sync, the profiler's step annotation) and ``train.report``."""
        history = []
        for _ in range(num_steps):
            with SPANS.span("train.batch", self.train_stream):
                batch = next(self.data_iter)
            if self._compiled_step is None:
                # compile once; the stream's per-step cost comes from the
                # same executable that runs
                self._compiled_step = self.step_fn.lower(params, opt_state, batch).compile()
                s = summarize_compiled(self._compiled_step)
                self._step_cost = StepCost(
                    s.flops_per_device, s.hbm_bytes_per_device, s.collective_wire_bytes_per_device
                )
            with SPANS.span("train.step", self.train_stream, step_num=self.step):
                uid = self.stats.step_begin("train_step", self.train_stream)
                params, opt_state, metrics = self._compiled_step(params, opt_state, batch)
                metrics = jax.tree_util.tree_map(lambda x: x.block_until_ready(), metrics)
                self.stats.step_end(
                    uid,
                    tokens=int(metrics["tokens"]),
                    cost=self._step_cost,
                    loss=float(metrics["loss"]),
                )
            self.step += 1
            history.append({k: float(v) for k, v in metrics.items()})
            if self.ckpt is not None and self.ckpt_every and self.step % self.ckpt_every == 0:
                self.ckpt.save(params, opt_state, {"step": self.step}, step=self.step)
            if self.eval_iter is not None and self.eval_every and self.step % self.eval_every == 0:
                ebatch = next(self.eval_iter)
                with self.stats.step("eval_step", self.eval_stream):
                    self.eval_fn(params, ebatch)
        with SPANS.span("train.report"):
            self.emit_reports()
        return params, opt_state, history

    def frame(self):
        """The trainer's per-stream telemetry as a
        :class:`~repro.core.query.StatsFrame` — the train and eval lanes
        resolve by name (``trainer.frame().filter(stream="train",
        access_type="GLOBAL_ACC_R").sum()`` is the train lane's HBM bytes)."""
        from repro.core.query import StatsFrame

        return StatsFrame(
            self.stats.table,
            timeline=self.stats.timeline,
            names={"train": self.train_stream, "eval": self.eval_stream},
        )

    def emit_reports(self) -> int:
        """Per-stream summary reports (train/eval lanes) through the plugged
        sinks — the same reporting path the simulator and serving engine use."""
        if not self.sinks:
            return 0
        return self.stats.emit(self.sinks, source="train")
