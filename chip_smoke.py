"""Bring-up smoke run on one TPU through the entry points a user calls.

    python chip_smoke.py

Run from the root of a checkout.  Three phases, in one process that starts
no children (a chip belongs to one process at a time):

1. Serving: deepseek-7b at its published widths, cut only in depth, with
   seeded random weights, through ``serve.Engine``: a few requests over two
   tenants, every one retired ``done`` with its tokens, and the per-stream
   KV bytes adding up to the aggregate.
2. Kernel numerics on the chip: the compiled Pallas attention and SSD
   kernels against the blocked-jnp paths, prefill logits through the model
   with either attention, and one cached decode step against the full
   forward pass over the extended sequence.
3. Training: three ``Trainer`` steps of mamba2-130m at its published shape,
   with finite losses.

JAX must find a TPU; on anything else the script exits non-zero before any
model work.  A failed check raises, so the script exits non-zero and prints
no result.  The last line of stdout is one JSON object naming the device.
Every time printed is set-up (weight init, compilation), never a speed.
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import replace
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
#: 16 of deepseek-7b's 30 layers.  At 30 layers the bf16 weights alone are
#: 12.87 GiB of the chip's 16, and a 4 x 1024 KV cache brings the decode
#: step's arguments to 14.75 GiB, leaving no room for admission's extra
#: cache copy.  16 layers hold 7.59 GiB of weights and a 1 GiB cache.
SERVE_LAYERS = 16
N_SLOTS, MAX_LEN, MAX_NEW = 4, 1024, 16
#: (prompt length, tenant): lengths off the 128 grid, over two tenants
REQUESTS = [(7, "interactive"), (300, "batch"), (1000, "batch"),
            (7, "batch"), (300, "interactive")]
#: mamba2-130m training: its published 2048-token context, a batch of 8
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 3

#: Relative L2 error of one kernel call against the blocked-jnp path.  The
#: outputs are bf16, one rounding of at most 2^-8 relative, and the jnp
#: path's float32 matmuls run at the TPU's default precision, which rounds
#: their operands to bf16 once more.  A wrong mask, head mapping or state
#: carry is off by order 1.
KERNEL_RTOL = 1e-2
#: Relative L2 error of last-position logits after SERVE_LAYERS bf16
#: layers.  Every layer rounds its activations to bf16, and a random-weight
#: stack amplifies a one-ulp difference in one attention output about ten
#: times: a host run at d_model 1024 with 16 layers gave 3.8e-2 between
#: the two attention paths and 1.3e-2 between decode and forward.  A wrong
#: mask, cache position or kernel is off by order 1.
MODEL_RTOL = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(1)
    return devices


def serve(cfg, params):
    """Phase 1: requests through ``serve.Engine``; returns the engine."""
    from repro.serve import Engine, Request, ServeConfig

    eng = Engine(cfg, params, ServeConfig(n_slots=N_SLOTS, max_len=MAX_LEN))
    rng = np.random.default_rng(SEED)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=MAX_NEW, name=f"req{i}", tenant=tenant)
        for i, (n, tenant) in enumerate(REQUESTS)
    ]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    retired = eng.run_until_idle()
    print(f"serve: {len(retired)} requests retired in {time.perf_counter() - t0:.1f} s "
          f"of set-up and serving, compiles included (not a speed)")

    check(len(retired) == len(reqs), f"{len(retired)} of {len(reqs)} requests retired")
    # KV bytes one token writes: K and V of every layer, bf16
    kv_per_token = (2 * cfg.n_kv_heads * cfg.resolved_head_dim * cfg.n_layers
                    * np.dtype(cfg.compute_dtype).itemsize)
    report = eng.per_stream_report()
    for r in reqs:
        kv = int(report[r.stream_id]["kv_bytes"])
        print(f"  stream {r.stream_id} {r.name} tenant={r.tenant} prompt={len(r.prompt)} "
              f"status={r.status} tokens_out={len(r.generated)} kv_bytes={kv}")
        check(r.status == "done", f"{r.name} retired {r.status!r}")
        check(len(r.generated) == MAX_NEW, f"{r.name} produced {len(r.generated)} tokens")
        # the prompt's K/V at prefill, then one token per decode step
        want = (len(r.prompt) + MAX_NEW - 1) * kv_per_token
        check(kv == want, f"{r.name} kv_bytes {kv} != {want}")
    total = int(eng.frame.filter(access_type="KV_ACC_W").sum())
    per_stream = sum(int(v["kv_bytes"]) for v in report.values())
    print(f"  sum of per-stream kv_bytes {per_stream} == aggregate {total}: {per_stream == total}")
    check(per_stream == total, "per-stream KV bytes do not add up to the aggregate")
    for tenant, sub in sorted(eng.frame.groupby("tenant").frames().items()):
        n = len(sub.streams())
        toks = int(sub.filter(access_type="SLO", outcome="TOKENS_OUT").sum())
        print(f"  tenant {tenant}: requests={n} "
              f"kv_bytes={int(sub.filter(access_type='KV_ACC_W').sum())} tokens_out={toks}")
        check(toks == n * MAX_NEW, f"tenant {tenant} tokens_out {toks}")
    return eng, reqs


def kernel_numerics(cfg, params, eng, prompt):
    """Phase 2: compiled kernels and the model paths that use them."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import decode_step, forward, init_cache, prefill
    from repro.serve.cache_utils import transplant

    # attention kernel alone, at deepseek-7b widths
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    qkv = [jax.random.normal(k, (1, len(prompt), cfg.n_heads, cfg.resolved_head_dim),
                             jnp.bfloat16) for k in ks]
    err = rel_l2(ops.flash_attention(*qkv, impl="pallas"), ops.flash_attention(*qkv, impl="xla"))
    print(f"kernels: flash_attention pallas vs xla, S={len(prompt)}: rel L2 {err:.3e} "
          f"(tolerance {KERNEL_RTOL})")
    check(err <= KERNEL_RTOL, "flash_attention kernel disagrees with the jnp path")

    # SSD kernel alone, at mamba2-130m widths, on a ragged length
    m = get_config("mamba2-130m")
    H, P, N, S = m.ssm.n_heads(m.d_model), m.ssm.head_dim, m.ssm.d_state, len(prompt)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 5)
    x = jax.random.normal(ks[0], (1, S, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, S, H)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    Bm, Cm = ((jax.random.normal(k, (1, S, 1, N)) * N ** -0.5).astype(jnp.bfloat16)
              for k in ks[3:])
    D = jnp.ones((H,), jnp.float32)
    yp, hp = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=m.ssm.chunk, impl="pallas")
    yx, hx = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=m.ssm.chunk, impl="xla")
    ey, eh = rel_l2(yp, yx), rel_l2(hp, hx)
    print(f"kernels: ssd_scan pallas vs xla, S={S}: rel L2 y {ey:.3e} state {eh:.3e} "
          f"(tolerance {KERNEL_RTOL})")
    check(ey <= KERNEL_RTOL and eh <= KERNEL_RTOL, "ssd_scan kernel disagrees with the jnp path")

    # the engine's own prefill must hold the kernel, not the jnp fallback
    batch = {"tokens": jnp.asarray(prompt)[None]}
    hlo = eng._prefill.lower(params, batch).compile().as_text()
    check("tpu_custom_call" in hlo, "the served prefill holds no Pallas kernel")
    print("kernels: served prefill HLO holds tpu_custom_call: True")

    logits_p, cache_p = eng._prefill(params, batch)
    logits_x, _ = jax.jit(partial(prefill, cfg, attn_impl="xla"))(params, batch)
    err = rel_l2(logits_p, logits_x)
    print(f"model: prefill logits, Pallas vs xla attention, S={len(prompt)}: rel L2 {err:.3e} "
          f"(tolerance {MODEL_RTOL})")
    check(err <= MODEL_RTOL, "prefill logits differ between attention paths")

    # one decode step through the cache == the full forward, extended
    nxt = int(jnp.argmax(logits_p[0]))
    one = transplant(init_cache(cfg, 1, MAX_LEN, dtype=cfg.compute_jdtype()), cache_p)
    pos = jnp.asarray([len(prompt)], jnp.int32)
    logits_d, _ = jax.jit(partial(decode_step, cfg))(params, one, jnp.asarray([nxt], jnp.int32), pos)
    ext = {"tokens": jnp.asarray(np.append(prompt, nxt).astype(np.int32))[None]}
    logits_f, _ = jax.jit(partial(forward, cfg))(params, ext)
    err = rel_l2(logits_d[0], logits_f[0, -1])
    print(f"model: decode step vs forward at position {len(prompt)}: rel L2 {err:.3e} "
          f"(tolerance {MODEL_RTOL})")
    check(err <= MODEL_RTOL, "cached decode disagrees with the full forward")


def deepseek_phases(device) -> None:
    import jax

    from repro.configs import get_config
    from repro.models import init_params, model_defs

    full = get_config("deepseek-7b")
    cfg = replace(full, n_layers=SERVE_LAYERS)
    print(f"deepseek-7b: depth cut to {cfg.n_layers} of {full.n_layers} layers; widths as "
          f"published: d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.resolved_head_dim} "
          f"kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"dtype={cfg.param_dtype}; seeded random weights (seed {SEED})")
    t0 = time.perf_counter()
    params = jax.jit(lambda key: init_params(model_defs(cfg), key, cfg.param_jdtype()))(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    print(f"set-up: weight init {time.perf_counter() - t0:.1f} s")
    eng, reqs = serve(cfg, params)
    kernel_numerics(cfg, params, eng, reqs[1].prompt)
    print(f"set-up: peak device bytes in use so far {device.memory_stats()['peak_bytes_in_use']}")


def train_phase() -> None:
    """Phase 3: three Trainer steps of mamba2-130m at its published shape."""
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_train_iter
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_config("mamba2-130m")
    data = make_train_iter(DataConfig(global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                                      vocab_size=cfg.vocab_size, seed=SEED))
    try:
        trainer = Trainer(cfg, TrainConfig(seed=SEED), data)
        params, opt = trainer.restore_or_init()
        t0 = time.perf_counter()
        _, _, hist = trainer.run(params, opt, TRAIN_STEPS)
        print(f"train: mamba2-130m {cfg.n_layers} layers d_model={cfg.d_model}, batch "
              f"{TRAIN_BATCH} x {TRAIN_SEQ}: {TRAIN_STEPS} steps in "
              f"{time.perf_counter() - t0:.1f} s, compile included (not a speed)")
    finally:
        data.close()
    losses = [h["loss"] for h in hist]
    print(f"  losses {losses}")
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)), "non-finite training loss")


def main() -> None:
    devices = tpu_devices()
    device = devices[0]
    import jax

    from repro.compile_cache import enable_compile_cache

    compile_s = {}

    def on_event(name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            compile_s[name] = compile_s.get(name, 0.0) + secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    print(f"device: {device.platform} {device.device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}")
    deepseek_phases(device)
    train_phase()
    print(f"set-up: compile seconds (trace, lowering, backend) "
          f"{sum(compile_s.values()):.1f}")
    print(f"set-up: peak device bytes in use {device.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {"platform": device.platform,
                                             "kind": device.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
