"""Numerics for the fused dequantize+aggregate+norm kernel.

The fused kernel must match the unfused composition it replaces
(``vmap(dequantize_op)`` then ``grad_aggregate_op``) to f32 tolerance in
interpret mode, across ragged row tiles, ragged N chunks, and the
streaming (multi-chunk) path; on the gathered wire rows ``[P, R, 1024]``
it must give bitwise what the ``[N, D]`` entry gives on the same payload.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.dequant_aggregate import dequant_aggregate
from repro.kernels.ops import (dequant_aggregate_op, dequantize_op,
                               grad_aggregate_op, quantize_op)

pytestmark = pytest.mark.pallas_interpret

TOL = dict(rtol=1e-5, atol=1e-5)


def _quantized_stack(n, d, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)) * rng.uniform(0.1, 3.0),
                    jnp.float32)
    qs, ss = zip(*(quantize_op(x[i]) for i in range(n)))
    return jnp.stack(qs), jnp.stack(ss), x


class TestFusedMatchesUnfused:
    @pytest.mark.parametrize("n,d,row_tile,chunk_n", [
        (8, 4096, 2048, 8),      # N=8 pods, whole wire rows, single chunk
        (8, 5000, 2048, 3),      # ragged last row AND ragged N chunk
        (1, 300, 128, 8),        # single update (the PS wire round-trip)
        (5, 1000, 512, 2),       # streaming: 3 N-chunks revisit the tile
        (16, 2048, 256, 4),      # wide fan-in, four chunks
        (3, 256, 2048, 8),       # one quantization block: a part row
    ])
    def test_matches_unfused_composition(self, n, d, row_tile, chunk_n):
        q, s, _ = _quantized_stack(n, d)
        w = jnp.asarray(np.random.default_rng(1).uniform(0.5, 1.5, n),
                        jnp.float32)
        agg, ssq = dequant_aggregate_op(q, s, w, row_tile=row_tile,
                                        chunk_n=chunk_n, orig_len=d)
        deq = jax.vmap(lambda qq, sc: dequantize_op(qq, sc, orig_len=d))(q, s)
        agg_ref, ssq_ref = grad_aggregate_op(deq, w)
        assert agg.shape == (d,) and agg.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(agg), np.asarray(agg_ref),
                                   **TOL)
        np.testing.assert_allclose(float(ssq), float(ssq_ref), rtol=1e-5)

    def test_matches_pure_jnp_ref(self):
        q, s, _ = _quantized_stack(4, 777, seed=2)
        w = jnp.ones((4,), jnp.float32)
        agg, ssq = dequant_aggregate_op(q, s, w, orig_len=777)
        agg_ref, ssq_ref = ref.dequant_aggregate_ref(q, s, w, orig_len=777)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(agg_ref),
                                   **TOL)
        np.testing.assert_allclose(float(ssq), float(ssq_ref), rtol=1e-5)

    def test_weighted_sum_semantics(self):
        """weights scale each update before summation (paper §4)."""
        d = 512
        x = jnp.ones((2, d), jnp.float32)
        q0, s0 = quantize_op(x[0])
        q = jnp.stack([q0, q0])
        s = jnp.stack([s0, s0])
        agg, ssq = dequant_aggregate(q, s, jnp.asarray([1.0, 3.0]),
                                     orig_len=d, interpret=True)
        np.testing.assert_allclose(np.asarray(agg), 4.0, rtol=1e-5)
        np.testing.assert_allclose(float(ssq), 16.0 * d, rtol=1e-5)

    def test_ragged_tail_excluded_from_norm(self):
        """orig_len trims quantization padding; the pad lanes must not
        leak into agg or the norm."""
        d = 200                       # quantize pads to 256
        q, s, x = _quantized_stack(2, d, seed=3)
        assert q.shape[1] == 256
        w = jnp.ones((2,), jnp.float32)
        agg, ssq = dequant_aggregate_op(q, s, w, orig_len=d)
        assert agg.shape == (d,)
        expect = np.asarray(
            dequantize_op(q[0], s[0], orig_len=d)
            + dequantize_op(q[1], s[1], orig_len=d))
        np.testing.assert_allclose(np.asarray(agg), expect, **TOL)
        np.testing.assert_allclose(float(ssq), float(np.sum(expect ** 2)),
                                   rtol=1e-5)

    def test_wire_roundtrip_isolates_leaf_scales(self):
        """A tiny-magnitude leaf packed after a large-magnitude one must
        keep its own quantization scale: without block-aligned leaf
        packing in flat_compress_roundtrip, the shared scale block would
        round the small leaf to all-zero int8 and it would never train."""
        from repro.dist.flatbuf import flat_compress_roundtrip
        tree = {"big": jnp.full((300,), 5.0, jnp.float32),       # not a
                "tiny": jnp.full((7,), 1e-4, jnp.float32)}       # block mult
        out, norm = flat_compress_roundtrip(tree, block=256)
        np.testing.assert_allclose(np.asarray(out["tiny"]), 1e-4,
                                   rtol=1e-2)
        np.testing.assert_allclose(np.asarray(out["big"]), 5.0, rtol=1e-2)
        expect = float(jnp.sqrt(sum(jnp.sum(jnp.square(v))
                                    for v in out.values())))
        assert abs(norm - expect) < 1e-6 * max(expect, 1.0)

    def test_roundtrip_error_bounded_through_fusion(self):
        """End-to-end: fused decode of a quantized gradient stays within
        the int8 quantization error bound of the raw f32 sum."""
        q, s, x = _quantized_stack(8, 4096, seed=4)
        w = jnp.ones((8,), jnp.float32)
        agg, _ = dequant_aggregate_op(q, s, w, orig_len=4096)
        raw = np.asarray(jnp.sum(x, axis=0))
        step = np.abs(np.asarray(x)).max() / 127.0
        assert np.abs(np.asarray(agg) - raw).max() <= 8 * (step * 0.5 + 1e-6)


def _wire_stack(p, rows, seed):
    """``p`` senders' quantized buckets of ``rows`` wire rows, stacked as
    the inter-pod all-gather delivers them: [P, R, 1024] int8 and
    [P, R*1024/256] scales."""
    q, s, _ = _quantized_stack(p, rows * 1024, seed=seed)
    return q.reshape(p, rows, 1024), s


class TestWireRows:
    """The operand the inter-pod stage hands the kernel: the all-gather's
    output, unreshaped."""

    @pytest.mark.parametrize("p,rows,row_tile,chunk_n,tail", [
        (1, 64, 32, 8, 0),       # one pod: two whole row tiles
        (2, 64, 32, 8, 0),       # two pods, the four-chip cell's fan-in
        (1, 70, 32, 8, 0),       # rows ragged against the row tile
        (2, 45, 32, 8, 300),     # ragged rows; orig_len ends inside a row
        (2, 5, 512, 8, 1000),    # fewer rows than one tile
        (8, 33, 32, 8, 17),      # eight pods, one chunk, ragged rows
        (8, 40, 32, 3, 0),       # P split into three chunks, the last ragged
        (8, 37, 64, 2, 513),     # four chunks; orig_len inside the last row
    ])
    def test_matches_ref_and_flat_entry(self, p, rows, row_tile, chunk_n,
                                        tail):
        q, s = _wire_stack(p, rows, seed=10 + p + rows)
        w = jnp.asarray(np.random.default_rng(p).uniform(0.5, 1.5, p),
                        jnp.float32)
        d = rows * 1024 - tail
        agg, ssq = dequant_aggregate(q, s, w, row_tile=row_tile,
                                     chunk_n=chunk_n, orig_len=d,
                                     interpret=True)
        assert agg.shape == (d,) and agg.dtype == jnp.float32
        agg_ref, ssq_ref = ref.dequant_aggregate_ref(
            q.reshape(p, -1), s, w, orig_len=d)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(agg_ref),
                                   **TOL)
        np.testing.assert_allclose(float(ssq), float(ssq_ref), rtol=1e-5)
        flat, flat_ssq = dequant_aggregate(q.reshape(p, -1), s, w,
                                           row_tile=row_tile,
                                           chunk_n=chunk_n, orig_len=d,
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(agg), np.asarray(flat))
        assert float(ssq) == float(flat_ssq)

    @pytest.mark.parametrize("p", [1, 2])
    def test_bitwise_q_s_w_sum_in_pod_order(self, p):
        """For the cells' fan-ins the aggregate is, bit for bit, each pod's
        (q * s) * w added in pod order."""
        rows = 40
        q, s = _wire_stack(p, rows, seed=20 + p)
        w = jnp.asarray([1.0, 0.75][:p], jnp.float32)
        agg, _ = dequant_aggregate_op(q, s, w, row_tile=32,
                                      orig_len=rows * 1024)
        qf = np.asarray(q, np.float32).reshape(p, -1, 256)
        sf = np.asarray(s, np.float32)[:, :, None]
        wf = np.asarray(w, np.float32)
        expect = (qf[0] * sf[0]) * wf[0]
        for k in range(1, p):
            expect = expect + (qf[k] * sf[k]) * wf[k]
        np.testing.assert_array_equal(np.asarray(agg), expect.reshape(-1))

    def test_unit_weights_are_the_plain_sum(self):
        """The hot path's weights are ones: the aggregate is the dequantized
        payloads' sum, and rows past orig_len stay out of the norm."""
        q, s = _wire_stack(2, 3, seed=30)
        w = jnp.ones((2,), jnp.float32)
        d = 3 * 1024 - 100
        agg, ssq = dequant_aggregate_op(q, s, w, orig_len=d)
        deq = jax.vmap(lambda qq, sc: dequantize_op(qq, sc, orig_len=d))(
            q.reshape(2, -1), s)
        expect = np.asarray(deq[0] + deq[1])
        np.testing.assert_array_equal(np.asarray(agg), expect)
        np.testing.assert_allclose(float(ssq), float(np.sum(expect ** 2)),
                                   rtol=1e-5)
