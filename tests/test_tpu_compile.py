"""Compile-only checks of the main path for a described TPU v5e.

Nothing here runs on a chip: each test compiles a kernel or a jitted step at
real widths for one device of a described ``v5e:2x2`` topology, so the TPU
compiler refuses here what it would refuse on the chip (illegal block
shapes, VMEM overflow, programs that do not fit the device's memory).  Where
a Pallas kernel belongs in the program, the test asserts that the compiled
HLO holds a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
Code that asks ``jax.default_backend()`` still sees the host here, so tests
that compile a model step steer :func:`repro.kernels.ops._on_tpu` to the
chip's answer themselves.
"""

import os
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models import decode_step, init_cache, model_defs, prefill
from repro.models.params import abstract_params
from repro.optim import adamw_init
from repro.train.trainer import TrainConfig, make_loss_fn, make_train_step

#: one v5e chip's HBM
HBM_BYTES = 16 * 2**30
#: mamba2-130m train step: its published context, a batch one chip holds
MAMBA_TRAIN_BATCH, MAMBA_TRAIN_SEQ = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_dispatch(monkeypatch):
    """``impl="auto"`` resolves as it does on the chip: compiled Pallas."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _fits_one_chip(compiled) -> int:
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, f"{peak / 2**30:.2f} GiB does not fit one v5e chip"
    return peak


def _deepseek(n_layers: int):
    """deepseek-7b at its published widths, cut only in depth."""
    return replace(get_config("deepseek-7b"), n_layers=n_layers)


@pytest.mark.parametrize("seq", [7, 300, 2048])
def test_flash_attention_deepseek_7b_widths(one_chip, seq):
    cfg = _deepseek(1)
    q = jax.ShapeDtypeStruct(
        (1, cfg.n_heads, seq, cfg.resolved_head_dim), jnp.bfloat16, sharding=one_chip
    )
    fn = partial(flash_attention_pallas, causal=True, q_block=256, kv_block=1024)
    compiled = jax.jit(fn).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_mamba2_130m_widths(one_chip):
    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    H, S = s.n_heads(cfg.d_model), 2048
    shapes = dict(
        x=((1, H, S, s.head_dim), jnp.bfloat16),
        dt=((1, H, S), jnp.float32),
        A=((H,), jnp.float32),
        Bm=((1, s.n_groups, S, s.d_state), jnp.bfloat16),
        Cm=((1, s.n_groups, S, s.d_state), jnp.bfloat16),
        D=((H,), jnp.float32),
    )
    args = [jax.ShapeDtypeStruct(shp, dt, sharding=one_chip) for shp, dt in shapes.values()]
    fn = partial(ssd_scan_pallas, chunk=s.chunk)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_deepseek_7b_layer_prefill(one_chip, chip_dispatch):
    cfg = _deepseek(1)
    params = _on(one_chip, abstract_params(model_defs(cfg), cfg.param_jdtype()))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 300), jnp.int32, sharding=one_chip)}
    compiled = jax.jit(partial(prefill, cfg)).lower(params, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_deepseek_7b_layer_decode_step(one_chip):
    """Decode attention is plain jnp (bandwidth-bound), so no kernel here.

    Two layers, so the in-place layer loop really indexes its stacked
    cache: no op of the compiled step outputs one layer's whole K or V slab,
    which would be a copy of it out of the stack and back."""
    cfg = _deepseek(2)
    n_slots, max_len = 4, 1024
    params = _on(one_chip, abstract_params(model_defs(cfg), cfg.param_jdtype()))
    cache = _on(one_chip, jax.eval_shape(
        lambda: init_cache(cfg, n_slots, max_len, dtype=cfg.compute_jdtype())
    ))
    vec = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=one_chip)
    step = jax.jit(partial(decode_step, cfg), donate_argnums=(1,))
    compiled = step.lower(params, cache, vec, vec).compile()
    _fits_one_chip(compiled)
    slab = f"bf16[{n_slots},{max_len},{cfg.n_kv_heads},{cfg.resolved_head_dim}]"
    copies = [line.strip() for line in compiled.as_text().splitlines() if f"= {slab}" in line]
    assert not copies, copies[:2]


def test_mamba2_130m_train_step(one_chip, chip_dispatch):
    """The train step at the published shape compiles on the chip even where
    ``impl="auto"`` would pick Pallas: training asks for the jnp paths."""
    cfg = get_config("mamba2-130m")
    tcfg = TrainConfig()
    params = abstract_params(model_defs(cfg), cfg.param_jdtype())
    opt = jax.eval_shape(partial(adamw_init, moment_dtype=jnp.dtype(cfg.opt_state_dtype)), params)
    tok = jax.ShapeDtypeStruct((MAMBA_TRAIN_BATCH, MAMBA_TRAIN_SEQ), jnp.int32)
    args = _on(one_chip, (params, opt, {"tokens": tok, "labels": tok}))
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    _fits_one_chip(step.lower(*args).compile())


def test_deepseek_7b_layer_loss_grad(one_chip, chip_dispatch):
    """``jax.grad`` of the loss at full width, batch 1 x 512, no optimizer
    state (Adam's moments at this vocabulary do not fit one chip)."""
    cfg = _deepseek(1)
    params = abstract_params(model_defs(cfg), cfg.param_jdtype())
    tok = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    args = _on(one_chip, (params, {"tokens": tok, "labels": tok}))
    grad = jax.jit(jax.grad(make_loss_fn(cfg, TrainConfig()), has_aux=True))
    _fits_one_chip(grad.lower(*args).compile())
