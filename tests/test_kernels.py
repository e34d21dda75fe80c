"""Pallas kernel sweeps vs the ref.py oracles (interpret mode on CPU).

Every kernel: shape x dtype sweep with assert_allclose against the pure-jnp
oracle, as required for each Pallas kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import build_spgemm_schedule
from repro.kernels import ops, ref
from repro.kernels.bsr_spmm import bsr_spmm, plan_bsr
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gustavson_spgemm import pad_schedule_arrays, spgemm_scheduled
from repro.kernels.moe_gmm import moe_gmm
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.random import random_block_sparse


class TestGustavsonSpGEMM:
    @pytest.mark.parametrize("shape,blocks,group", [
        ((128, 128, 128), (32, 32, 32), 1),
        ((256, 128, 192), (64, 64, 64), 2),
        ((256, 384, 256), (64, 64, 128), 4),
    ])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_vs_oracle_and_dense(self, shape, blocks, group, dtype):
        m, k, n = shape
        bm, bk, bn = blocks
        ad = random_block_sparse(m, k, (bm, bk), 0.35, seed=1).astype(dtype)
        bd = random_block_sparse(k, n, (bk, bn), 0.4, seed=2).astype(dtype)
        a = to_bcsv(np.asarray(ad, np.float32), (bm, bk), group=group)
        b = to_bcsr(np.asarray(bd, np.float32), (bk, bn))
        a.blocks = a.blocks.astype(dtype)
        b.blocks = b.blocks.astype(dtype)
        sch = build_spgemm_schedule(a, b)
        a_slot, b_slot, panel, sub_row, start, _ = pad_schedule_arrays(
            sch.a_slot, sch.b_slot, sch.panel, sch.sub_row, sch.start,
            sch.n_panels)
        panels = spgemm_scheduled(
            jnp.asarray(a.blocks), jnp.asarray(b.blocks),
            ((jnp.asarray(a_slot), jnp.asarray(b_slot), jnp.asarray(panel),
              jnp.asarray(sub_row), jnp.asarray(start)),),
            n_panels=sch.n_panels, group=group, interpret=True)
        oracle = ref.spgemm_scheduled_ref(
            jnp.asarray(a.blocks), jnp.asarray(b.blocks),
            sch.a_slot, sch.b_slot, sch.panel, sch.sub_row,
            sch.n_panels, group)
        tol = 1e-5 if dtype == np.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(panels), np.asarray(oracle),
                                   rtol=tol, atol=tol)

    def test_end_to_end_spgemm_vs_dense(self):
        ad = random_block_sparse(192, 256, (64, 64), 0.3, seed=3)
        bd = random_block_sparse(256, 192, (64, 64), 0.35, seed=4)
        c = ops.spgemm(to_bcsv(ad, (64, 64), 2), to_bcsr(bd, (64, 64)),
                       backend="pallas_interpret")
        np.testing.assert_allclose(
            c.todense(), ad.astype(np.float64) @ bd.astype(np.float64),
            rtol=1e-4, atol=1e-4)

    def test_jnp_backend_equals_pallas(self):
        ad = random_block_sparse(128, 128, (32, 32), 0.4, seed=5)
        bd = random_block_sparse(128, 128, (32, 64), 0.4, seed=6)
        a, b = to_bcsv(ad, (32, 32), 2), to_bcsr(bd, (32, 64))
        c1 = ops.spgemm(a, b, backend="pallas_interpret").todense()
        c2 = ops.spgemm(a, b, backend="jnp").todense()
        np.testing.assert_allclose(c1, c2, rtol=1e-5, atol=1e-5)


class TestBsrSpMM:
    @pytest.mark.parametrize("m,k,n,bk,bn", [
        (64, 256, 256, 128, 128),
        (200, 384, 512, 128, 128),
        (128, 256, 384, 128, 128),
    ])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_vs_dense(self, m, k, n, bk, bn, dtype):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((m, k)).astype(np.float32)
        wd = random_block_sparse(k, n, (bk, bn), 0.5, seed=7)
        w = to_bcsv(wd, (bk, bn), group=1)
        w.blocks = w.blocks.astype(dtype)
        y = ops.sparse_dense_matmul(
            jnp.asarray(x.astype(dtype)), w, backend="pallas_interpret")
        yref = x @ np.asarray(wd, np.float32)
        tol = 1e-3 if dtype == np.float32 else 0.15
        np.testing.assert_allclose(np.asarray(y, np.float32), yref,
                                   rtol=tol, atol=tol)

    def test_empty_column_panels_are_zero(self):
        wd = random_block_sparse(256, 512, (128, 128), 0.5, seed=8)
        wd[:, 128:256] = 0.0  # kill one column panel entirely
        w = to_bcsv(wd, (128, 128), group=1)
        x = np.random.default_rng(1).standard_normal((64, 256)).astype(np.float32)
        y = ops.sparse_dense_matmul(jnp.asarray(x), w,
                                    backend="pallas_interpret")
        assert np.abs(np.asarray(y)[:, 128:256]).max() == 0.0


class TestMoEGMM:
    @pytest.mark.parametrize("t,d,f,e,tm", [
        (256, 128, 256, 2, 128),
        (512, 256, 128, 4, 128),
        (1024, 128, 384, 8, 128),
    ])
    def test_vs_oracle(self, t, d, f, e, tm):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((t, d)).astype(np.float32)
        w = rng.standard_normal((e, d, f)).astype(np.float32)
        te = np.sort(rng.integers(0, e, t // tm)).astype(np.int32)
        y = moe_gmm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(te),
                    tm=tm, bd=128, bf=128, interpret=True)
        yref = ref.moe_gmm_ref(jnp.asarray(x), jnp.asarray(w), te, tm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                                   rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("bh,s,d", [(2, 256, 64), (4, 512, 128),
                                        (1, 1024, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_oracle(self, bh, s, d, causal):
        rng = np.random.default_rng(3)
        q, k, v = (rng.standard_normal((bh, s, d)).astype(np.float32)
                   for _ in range(3))
        o = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal, bq=128, bk=128, interpret=True)
        oref = ref.flash_attention_ref(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [64, 128, 1024])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(4)
        q, k, v = (rng.standard_normal((2, 512, 64)).astype(np.float32)
                   for _ in range(3))
        o = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=True, window=window, bq=128, bk=128,
                            interpret=True)
        oref = ref.flash_attention_ref(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(oref),
                                   rtol=2e-4, atol=2e-4)

    def test_q_offset_chunked_prefill(self):
        """Chunked prefill: second q chunk against the full kv must equal
        the corresponding rows of one-shot attention."""
        rng = np.random.default_rng(5)
        q, k, v = (rng.standard_normal((1, 512, 64)).astype(np.float32)
                   for _ in range(3))
        full = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), causal=True)
        part = flash_attention(
            jnp.asarray(q[:, 256:]), jnp.asarray(k), jnp.asarray(v),
            causal=True, q_offset=256, bq=128, bk=128, interpret=True)
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(full)[:, 256:],
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        rng = np.random.default_rng(6)
        q, k, v = (jnp.asarray(rng.standard_normal((2, 256, 64)),
                               jnp.bfloat16) for _ in range(3))
        o = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                            interpret=True)
        oref = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(oref, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_attention_custom_vjp_grads(self):
        rng = np.random.default_rng(7)
        q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 32)),
                               jnp.float32) for _ in range(3))

        def loss_kernel(q, k, v):
            return ops.attention(q, k, v, True, None, 0,
                                 "pallas_interpret").sum()

        def loss_ref(q, k, v):
            return ref.flash_attention_ref(q, k, v, causal=True).sum()

        g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
