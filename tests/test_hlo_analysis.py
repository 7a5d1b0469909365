"""HLO analyzer: while-loop trip multipliers, dot flops, collective model —
validated on (a) synthetic HLO text and (b) a real compiled jax program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo_text
from repro.launch.roofline import hw_for

SYNTH = """
%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%it, %c), direction=LT
}

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %it = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %y = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add.1
  %one = s32[] constant(1)
  %nit = s32[] add(%it, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%nit, %ar)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


class TestSyntheticHLO:
    def test_trip_count_multiplies_flops(self):
        stats = analyze_hlo_text(SYNTH, num_partitions=4)
        # dot: 2*8*8*8 = 1024 flops, x10 trips (+ the s32 add x10 = 10)
        assert stats.while_trip_counts == [10]
        assert abs(stats.flops - (1024 * 10 + 10)) < 1e-6

    def test_all_reduce_ring_model(self):
        stats = analyze_hlo_text(SYNTH, num_partitions=4)
        # AR of 8*8*4B=256B over groups of 4: 2*(3/4)*256 = 384 B x10 trips
        assert abs(stats.collective_bytes - 384 * 10) < 1e-6
        assert stats.collective_by_kind["all-reduce"] == stats.collective_bytes

    def test_traffic_counts_loop_body(self):
        stats = analyze_hlo_text(SYNTH, num_partitions=4, bf16_native=False)
        # dot (in+in+out = 3*256) + AR (256+256) appear x10
        assert stats.hbm_bytes >= 10 * (3 * 256)


class TestRealProgram:
    def test_scan_flops_counted(self):
        """A jitted lax.scan of matmuls must report ~trips x body flops."""
        n, trips = 64, 12

        def step(x, _):
            return jnp.tanh(x @ x), None

        def fn(x):
            y, _ = jax.lax.scan(step, x, None, length=trips)
            return y

        compiled = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
        stats = analyze_hlo_text(compiled.as_text(), num_partitions=1)
        want = 2 * n * n * n * trips
        assert want <= stats.flops <= want * 1.5, \
            (stats.flops, want, stats.while_trip_counts)

    def test_no_loop_program(self):
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((32, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 8), jnp.float32)).compile()
        stats = analyze_hlo_text(compiled.as_text())
        want = 2 * 32 * 16 * 8
        assert want <= stats.flops <= want * 1.2


class TestPeaks:
    def test_known_kind_has_published_peaks(self):
        hw = hw_for("TPU v5 lite")
        assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)

    def test_unknown_kind_is_an_error(self):
        """No default peaks: a roofline against the wrong chip is wrong."""
        with pytest.raises(KeyError, match="cpu"):
            hw_for("cpu")
