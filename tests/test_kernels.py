"""Per-kernel allclose sweeps: interpret-mode Pallas vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_tick import fused_tick
from repro.kernels.grouped_matmul import (grouped_matmul,
                                          sort_tokens_for_experts)
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,sq,hq,hkv,d", [
        (2, 256, 4, 2, 64),      # GQA
        (1, 128, 8, 8, 128),     # MHA
        (2, 256, 4, 1, 64),      # MQA
        (1, 384, 2, 2, 256),     # gemma head_dim
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, b, sq, hq, hkv, d, causal):
        q = jnp.asarray(RNG.normal(size=(b, sq, hq, d)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(b, sq, hkv, d)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(b, sq, hkv, d)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        q = jnp.asarray(RNG.normal(size=(1, 128, 4, 64)), dtype)
        k = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), dtype)
        v = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.float32(out), np.float32(want),
                                   **_tol(dtype))
        assert out.dtype == dtype

    def test_block_shape_independent(self):
        q = jnp.asarray(RNG.normal(size=(1, 512, 2, 64)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 512, 2, 64)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 512, 2, 64)), jnp.float32)
        a = flash_attention(q, k, v, blk_q=128, blk_kv=128, interpret=True)
        b = flash_attention(q, k, v, blk_q=256, blk_kv=64, interpret=True)
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("b,smax,hq,hkv,d", [
        (2, 512, 8, 2, 64), (4, 256, 4, 4, 128), (1, 1024, 16, 1, 128),
    ])
    def test_ragged_lengths(self, b, smax, hq, hkv, d):
        q = jnp.asarray(RNG.normal(size=(b, 1, hq, d)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(b, smax, hkv, d)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(b, smax, hkv, d)), jnp.float32)
        lengths = jnp.asarray(RNG.integers(1, smax, b), jnp.int32)
        out = decode_attention(q, k, v, lengths, interpret=True)
        want = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)

    def test_length_one_attends_first_position_only(self):
        b, smax, h, d = 1, 256, 2, 64
        q = jnp.asarray(RNG.normal(size=(b, 1, h, d)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(b, smax, h, d)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(b, smax, h, d)), jnp.float32)
        out = decode_attention(q, k, v, jnp.asarray([1]), interpret=True)
        np.testing.assert_allclose(out[0, 0], v[0, 0], atol=1e-5)


class TestSSDScan:
    @pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
        (2, 512, 4, 64, 1, 128, 128),
        (1, 256, 8, 64, 2, 128, 256),
        (2, 256, 4, 64, 4, 128, 128),
    ])
    def test_matches_reference(self, b, s, h, p, g, n, chunk):
        x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
        a_log = jnp.asarray(RNG.uniform(0, 1.5, (h,)), jnp.float32)
        bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
        cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
        y, st = ssd_scan(x, dt, a_log, bm, cm, chunk=chunk, interpret=True)
        yr, sr = ref.ssd_scan_ref(x, dt, a_log, bm, cm, chunk=chunk)
        np.testing.assert_allclose(y, yr, atol=5e-5, rtol=5e-5)
        np.testing.assert_allclose(st, sr, atol=5e-5, rtol=5e-5)

    def test_state_continuity_chunks(self):
        """Final state equals the sequential recurrence's final state."""
        from repro.models.mamba2 import ssd_decode_step
        b, s, h, p, n = 1, 128, 2, 64, 128
        x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
        a_log = jnp.asarray(RNG.uniform(0, 1.0, (h,)), jnp.float32)
        bm = jnp.asarray(RNG.normal(size=(b, s, 1, n)), jnp.float32)
        cm = jnp.asarray(RNG.normal(size=(b, s, 1, n)), jnp.float32)
        _, st = ssd_scan(x, dt, a_log, bm, cm, chunk=64, interpret=True)
        state = jnp.zeros((b, h, p, n))
        for t in range(s):
            _, state = ssd_decode_step(state, x[:, t], dt[:, t], a_log,
                                       bm[:, t], cm[:, t])
        np.testing.assert_allclose(st, state, atol=1e-4, rtol=1e-4)


class TestGroupedMatmul:
    @pytest.mark.parametrize("n_tok,e,k,n", [
        (300, 4, 128, 256), (1000, 8, 256, 128), (64, 2, 128, 128),
    ])
    def test_matches_reference(self, n_tok, e, k, n):
        x = RNG.normal(size=(n_tok, k)).astype(np.float32)
        eids = RNG.integers(0, e, n_tok)
        lhs, tiles, inv, mask = sort_tokens_for_experts(x, eids, e, 128)
        rhs = jnp.asarray(RNG.normal(size=(e, k, n)), jnp.float32)
        out = grouped_matmul(jnp.asarray(lhs), rhs, jnp.asarray(tiles),
                             interpret=True)
        want = ref.grouped_matmul_ref(lhs, rhs, tiles, 128)
        np.testing.assert_allclose(out, want, atol=1e-3, rtol=1e-3)

    def test_per_token_expert_routing(self):
        """Gather-back equals per-token x @ W[expert]."""
        x = RNG.normal(size=(100, 128)).astype(np.float32)
        eids = RNG.integers(0, 4, 100)
        lhs, tiles, inv, mask = sort_tokens_for_experts(x, eids, 4, 128)
        rhs = RNG.normal(size=(4, 128, 64)).astype(np.float32)
        out = np.asarray(grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                        jnp.asarray(tiles), interpret=True))
        for row, src in zip(out[mask], inv[mask]):
            want = x[src] @ rhs[eids[src]]
            np.testing.assert_allclose(row, want, atol=1e-3, rtol=1e-3)


class TestFusedTick:
    """Fused sweep tick (lag update + detector observe + rank-1 RLS) vs the
    pure-jnp oracle the CPU path of the fused sweep engine runs. float64:
    the DSP engines execute under enable_x64 to mirror the NumPy oracles."""

    def _operands(self, n, seed=0):
        rng = np.random.default_rng(seed)
        return dict(
            lag=jnp.asarray(rng.uniform(0.0, 1e5, n)),
            lag_add=jnp.asarray(rng.uniform(0.0, 1e4, n)),
            rates=jnp.asarray(rng.uniform(1e4, 9e4, n)),
            cap=jnp.asarray(rng.uniform(1e4, 8e4, n)),
            down_pre=jnp.asarray(rng.random(n) < 0.3),
            w=jnp.asarray(rng.normal(size=(n, 2)) * 0.1),
            P=jnp.asarray(np.broadcast_to(10.0 * np.eye(2),
                                          (n, 2, 2)).copy()),
            y_prev=jnp.asarray(rng.uniform(0.0, 12.0, n)),
        )

    @pytest.mark.parametrize("n", [3, 8, 37])   # sub-block, exact, ragged
    def test_matches_reference(self, n):
        with jax.enable_x64():
            ops = self._operands(n, seed=n)
            got = fused_tick(**ops, lam=0.995, thresh=3.0, dt=5.0,
                             interpret=True)
            want = ref.fused_tick_ref(
                ops["lag"], ops["lag_add"], ops["rates"], ops["cap"],
                ops["down_pre"], ops["w"], ops["P"], ops["y_prev"],
                0.995, 3.0, 5.0)
        names = ("new_lag", "w'", "P'", "err", "flag")
        for g, r, name in zip(got[:4], want[:4], names):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-12, atol=1e-12,
                                       err_msg=name)
        np.testing.assert_array_equal(np.asarray(got[4]),
                                      np.asarray(want[4]), err_msg="flag")

    def test_lag_update_matches_step_batch_arrays(self):
        # The kernel's lag arithmetic must be the simulator's, op for op —
        # the fused engine takes its carry from the tick while the metrics
        # come from step_batch_arrays, so any drift would desync them.
        # 1e-12, not bit-for-bit: these are two separately compiled
        # dispatches, and XLA contracts multiply-adds into FMAs
        # differently per module (inside the engine's single compiled scan
        # the two expressions do agree exactly).
        from repro.dsp import ClusterModel
        from repro.dsp.simulator import step_batch_arrays
        n = 16
        with jax.enable_x64():
            ops = self._operands(n, seed=1)
            rows = jnp.ones(n)
            new_lag, _ = step_batch_arrays(
                ClusterModel(), ops["lag"], ops["lag_add"], ops["rates"],
                rows * 4.0, rows, rows * 4096.0, rows, ops["cap"],
                ops["down_pre"], ops["down_pre"],
                jnp.zeros(n), jnp.zeros(n), 5.0)
            tick_lag = fused_tick(**ops, lam=0.995, thresh=3.0, dt=5.0,
                                  interpret=True)[0]
        np.testing.assert_allclose(np.asarray(tick_lag),
                                   np.asarray(new_lag),
                                   rtol=1e-12, atol=1e-12)


class TestFusedRMSNorm:
    @pytest.mark.parametrize("shape", [(4, 37, 512), (2, 256, 128), (7, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference(self, shape, dtype):
        x = jnp.asarray(RNG.normal(size=shape), dtype)
        res = jnp.asarray(RNG.normal(size=shape), dtype)
        sc = jnp.asarray(RNG.normal(size=shape[-1:]) * 0.1, dtype)
        y, s = fused_rmsnorm(x, res, sc, interpret=True)
        yr, sr = ref.fused_rmsnorm_ref(x, res, sc)
        np.testing.assert_allclose(np.float32(y), np.float32(yr),
                                   **_tol(dtype))
        np.testing.assert_allclose(np.float32(s), np.float32(sr),
                                   **_tol(dtype))
