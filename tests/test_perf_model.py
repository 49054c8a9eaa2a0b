"""Loop-aware HLO cost model validation (perf/hlo_cost_model)."""

import jax
import jax.numpy as jnp
import pytest

from repro.perf.hlo_cost_model import analyze_compiled, analyze_hlo_text


class TestLoopAwareCosts:
    def test_scan_equals_unrolled_equals_closed_form(self):
        N, L = 128, 8

        def body(c, _):
            return c @ c, None

        def f_scan(x):
            return jax.lax.scan(body, x, None, length=L)[0]

        def f_unroll(x):
            for _ in range(L):
                x = x @ x
            return x

        x = jax.ShapeDtypeStruct((N, N), jnp.float32)
        cs = analyze_compiled(jax.jit(f_scan).lower(x).compile())
        cu = analyze_compiled(jax.jit(f_unroll).lower(x).compile())
        exact = L * 2 * N**3
        assert cs.flops == pytest.approx(exact, rel=0.01)
        assert cu.flops == pytest.approx(exact, rel=0.01)
        assert cs.n_while_loops == 1

    def test_nested_scan_multiplies(self):
        N, inner, outer = 64, 4, 6

        def f(x):
            def ob(c, _):
                def ib(c2, _):
                    return c2 @ c2, None

                return jax.lax.scan(ib, c, None, length=inner)[0], None

            return jax.lax.scan(ob, x, None, length=outer)[0]

        x = jax.ShapeDtypeStruct((N, N), jnp.float32)
        r = analyze_compiled(jax.jit(f).lower(x).compile())
        assert r.flops == pytest.approx(outer * inner * 2 * N**3, rel=0.01)

    def test_matches_cost_analysis_without_loops(self):
        """Loop-free module: our count must bracket XLA's own cost analysis."""

        def f(a, b):
            return jax.nn.relu(a @ b)

        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        compiled = jax.jit(f).lower(a, b).compile()
        mine = analyze_compiled(compiled)
        xla = compiled.cost_analysis()
        assert mine.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)
        # XLA counts the relu's elementwise flops too; dot dominates
        assert mine.flops <= xla["flops"] <= mine.flops * 1.1

    def test_dot_general_batched(self):
        def f(a, b):
            return jnp.einsum("bij,bjk->bik", a, b)

        a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
        r = analyze_compiled(jax.jit(f).lower(a, b).compile())
        assert r.flops == pytest.approx(2 * 4 * 32 * 16 * 8, rel=0.01)

    def test_bytes_scale_with_trip_count(self):
        def body(c, _):
            return jnp.sin(c) * 2.0, None

        def f4(x):
            return jax.lax.scan(body, x, None, length=4)[0]

        def f16(x):
            return jax.lax.scan(body, x, None, length=16)[0]

        x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        # elementwise-only body: traffic shows in the pessimistic all-ops count
        b4 = analyze_compiled(jax.jit(f4).lower(x).compile()).hbm_bytes_allops
        b16 = analyze_compiled(jax.jit(f16).lower(x).compile()).hbm_bytes_allops
        assert 3.0 < b16 / b4 < 4.5  # ~4x work, same fixed overhead

    def test_synthetic_while_and_collective_text(self):
        text = """
HloModule test

%cond.1 (p: (s32[], f32[128,128])) -> pred[] {
  %p = (s32[], f32[128,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.2 (p: (s32[], f32[128,128])) -> (s32[], f32[128,128]) {
  %p = (s32[], f32[128,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128,128] get-tuple-element(%p), index=1
  %d = f32[128,128] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[128,128] all-reduce(%d), channel_id=1, replica_groups=[4,2]<=[8], to_apply=%add.3
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[128,128]) tuple(%ip, %ar)
}

%add.3 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[128,128]) -> f32[128,128] {
  %x = f32[128,128] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[128,128]) tuple(%zero, %x)
  %w = (s32[], f32[128,128]) while(%init), condition=%cond.1, body=%body.2
  ROOT %out = f32[128,128] get-tuple-element(%w), index=1
}
"""
        r = analyze_hlo_text(text)
        assert r.flops == pytest.approx(12 * 2 * 128**3)
        # all-reduce wire: 2·r·(g-1)/g per trip, g=2
        per = 2 * (128 * 128 * 4) * (2 - 1) / 2
        assert r.collective_wire_bytes == pytest.approx(12 * per)
        assert r.collective_count == 12
        assert r.n_while_loops == 1
