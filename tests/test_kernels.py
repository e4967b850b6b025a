"""Per-kernel tests: shape/dtype sweeps, allclose vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body itself executes), per
the assignment contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grad_aggregate import grad_aggregate
from repro.kernels.quantize import dequantize, quantize
from repro.kernels.ops import (dequantize_op, flash_attention_op,
                               grad_aggregate_op, quantize_op)

pytestmark = pytest.mark.pallas_interpret

TOL = dict(rtol=2e-2, atol=2e-2)


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kvh,sq,skv,d", [
        (1, 2, 2, 64, 64, 32),       # MHA square
        (2, 4, 2, 64, 64, 32),       # GQA 2:1
        (1, 8, 2, 32, 128, 64),      # GQA 4:1, rectangular (prefix cache)
        (1, 2, 1, 128, 128, 64),     # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, h, kvh, sq, skv, d, dtype):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
        k = jax.random.normal(ks[1], (b, kvh, skv, d), dtype)
        v = jax.random.normal(ks[2], (b, kvh, skv, d), dtype)
        causal = sq == skv
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32), **TOL)

    @pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 64), (64, 32)])
    def test_block_shape_sweep(self, block_q, block_k):
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=False, block_q=block_q,
                              block_k=block_k, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), **TOL)

    def test_causal_mask_exact(self):
        """First query token attends only to the first kv token."""
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (1, 1, 32, 16), jnp.float32)
        k = jax.random.normal(ks[1], (1, 1, 32, 16), jnp.float32)
        v = jax.random.normal(ks[2], (1, 1, 32, 16), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out[0, 0, 0]),
                                   np.asarray(v[0, 0, 0]), rtol=1e-5)

    def test_jit_wrapper(self):
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 32), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.bfloat16)
        out = flash_attention_op(q, k, v, causal=True)
        assert out.shape == q.shape and out.dtype == q.dtype


class TestGradAggregate:
    @pytest.mark.parametrize("n,d", [(2, 256), (5, 1024), (8, 4096), (1, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, d, dtype):
        ks = jax.random.split(jax.random.key(4), 2)
        u = jax.random.normal(ks[0], (n, d), dtype)
        w = jax.random.uniform(ks[1], (n,), jnp.float32, 0.5, 1.5)
        agg, ssq = grad_aggregate(u, w, block_d=256, interpret=True)
        agg_ref, ssq_ref = ref.grad_aggregate_ref(u, w)
        np.testing.assert_allclose(np.asarray(agg, np.float32),
                                   np.asarray(agg_ref, np.float32), **TOL)
        np.testing.assert_allclose(float(ssq), float(ssq_ref), rtol=5e-2)

    def test_uniform_weights_is_sum(self):
        u = jnp.ones((4, 512), jnp.float32)
        agg, ssq = grad_aggregate(u, jnp.ones((4,)), block_d=512,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(agg), 4.0)
        np.testing.assert_allclose(float(ssq), 16.0 * 512)

    def test_ragged_d_through_wrapper(self):
        """Ragged D runs masked in-kernel — no pad+slice copy in the
        wrapper anymore."""
        u = jax.random.normal(jax.random.key(5), (3, 1000), jnp.float32)
        w = jnp.ones((3,))
        agg, _ = grad_aggregate_op(u, w, block_d=256)
        agg_ref, _ = ref.grad_aggregate_ref(u, w)
        assert agg.shape == (1000,)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(agg_ref),
                                   **TOL)

    @pytest.mark.parametrize("n,d,block_d", [
        (3, 1000, 256),    # ragged last tile (1000 = 3*256 + 232)
        (2, 100, 2048),    # single tile smaller than block_d
        (4, 2049, 1024),   # one full tile + 1-lane ragged tail
    ])
    def test_ragged_last_block_norm_exact(self, n, d, block_d):
        """The masked ragged tail must not leak OOB lanes into the norm."""
        u = jax.random.normal(jax.random.key(9), (n, d), jnp.float32)
        w = jax.random.uniform(jax.random.key(10), (n,), jnp.float32,
                               0.5, 1.5)
        agg, ssq = grad_aggregate(u, w, block_d=block_d, interpret=True)
        agg_ref, ssq_ref = ref.grad_aggregate_ref(u, w)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(agg_ref),
                                   **TOL)
        np.testing.assert_allclose(float(ssq), float(ssq_ref), rtol=1e-5)


class TestQuantize:
    @pytest.mark.parametrize("d,block", [(512, 128), (2048, 256), (256, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_roundtrip_error_bounded(self, d, block, dtype):
        x = jax.random.normal(jax.random.key(6), (d,), dtype)
        q, s = quantize(x.astype(jnp.float32), block=block, interpret=True)
        x_hat = dequantize(q, s, block=block, interpret=True)
        xf = np.asarray(x, np.float32).reshape(-1, block)
        err = np.abs(np.asarray(x_hat).reshape(-1, block) - xf)
        # error bounded by half a quantization step per block
        step = np.abs(xf).max(axis=1, keepdims=True) / 127.0
        assert (err <= step * 0.5 + 1e-6).all()

    @pytest.mark.parametrize("d,block", [(512, 128), (1024, 256)])
    def test_matches_ref(self, d, block):
        x = jax.random.normal(jax.random.key(7), (d,), jnp.float32) * 3.0
        q, s = quantize(x, block=block, interpret=True)
        q_ref, s_ref = ref.quantize_ref(x, block=block)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6)
        # round-to-nearest ties may differ by 1 ulp; allow tiny mismatch
        diff = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
        assert (diff <= 1).all()
        assert diff.mean() < 0.01

    @pytest.mark.parametrize("d,block,row_tile", [
        (300 * 1024, 256, 256),        # two row tiles, the last ragged
        (37 * 1024 + 512, 256, 512),   # not whole wire rows: padded, cut
        (200 * 1024, 128, 128),        # eight scales a row, ragged tiles
        (3 * 1024, 256, 512),          # fewer rows than one int8 tile
    ])
    def test_wire_rows_match_block_formula(self, d, block, row_tile):
        """Bit for bit the per-block formula, blocks in flat order: scale =
        max(max|x| / 127, 1e-30), q = clip(round(x / scale), -127, 127)."""
        x = jax.random.normal(jax.random.key(9), (d,), jnp.float32) * 2.0
        q, s = quantize(x, block=block, row_tile=row_tile, interpret=True)

        @jax.jit
        def formula(x):
            xb = x.reshape(-1, block)
            scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=1) / 127.0, 1e-30)
            q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127)
            return q.astype(jnp.int8).reshape(-1), scale

        q_expect, s_expect = formula(x)
        assert q.shape == (d,) and s.shape == (d // block,)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_expect))
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_expect))

    def test_compression_ratio(self):
        from repro.kernels.ops import compress_update
        x = jax.random.normal(jax.random.key(8), (8192,), jnp.float32)
        (_, _), ratio = compress_update(x, block=256)
        assert ratio > 3.5  # ~4x for f32 -> int8 (+scales overhead)

    def test_zero_block_safe(self):
        x = jnp.zeros((256,), jnp.float32)
        q, s = quantize(x, block=256, interpret=True)
        x_hat = dequantize(q, s, block=256, interpret=True)
        np.testing.assert_allclose(np.asarray(x_hat), 0.0)
