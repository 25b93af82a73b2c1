"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,nq,nkv,hd,causal,window,softcap",
        [
            (1, 128, 4, 4, 64, True, None, None),   # MHA causal
            (2, 256, 8, 2, 64, True, None, None),   # GQA 4:1
            (2, 128, 4, 1, 128, True, None, None),  # MQA
            (1, 256, 4, 2, 64, True, 64, None),     # sliding window
            (1, 128, 2, 2, 64, True, None, 30.0),   # softcap (gemma2)
            (2, 128, 4, 4, 64, False, None, None),  # bidirectional
            (1, 256, 8, 2, 64, True, 32, 50.0),     # window + cap + GQA
        ],
    )
    def test_matches_reference(self, dtype, B, S, nq, nkv, hd, causal,
                               window, softcap):
        q = jnp.asarray(RNG.standard_normal((B, S, nq, hd)), dtype)
        k = jnp.asarray(RNG.standard_normal((B, S, nkv, hd)), dtype)
        v = jnp.asarray(RNG.standard_normal((B, S, nkv, hd)), dtype)
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, block_q=64, block_kv=64)
        want = ref.ref_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            **_tol(dtype))

    @given(
        bq=st.sampled_from([32, 64, 128]),
        bkv=st.sampled_from([32, 64, 128]),
        seed=st.integers(0, 10**6),
    )
    @settings(max_examples=6, deadline=None)
    def test_block_shape_invariance(self, bq, bkv, seed):
        """Output must not depend on the BlockSpec tiling."""
        r = np.random.default_rng(seed)
        q = jnp.asarray(r.standard_normal((1, 128, 2, 64)), jnp.float32)
        k = jnp.asarray(r.standard_normal((1, 128, 2, 64)), jnp.float32)
        v = jnp.asarray(r.standard_normal((1, 128, 2, 64)), jnp.float32)
        a = ops.flash_attention(q, k, v, block_q=bq, block_kv=bkv)
        b = ops.flash_attention(q, k, v, block_q=128, block_kv=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def test_cross_attention_q_offset(self):
        """Decode-style: 1 query at position pos against a longer KV."""
        r = np.random.default_rng(7)
        q = jnp.asarray(r.standard_normal((2, 64, 4, 64)), jnp.float32)
        k = jnp.asarray(r.standard_normal((2, 256, 4, 64)), jnp.float32)
        v = jnp.asarray(r.standard_normal((2, 256, 4, 64)), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, q_offset=192,
                                  block_q=64, block_kv=64)
        want = ref.ref_attention(q, k, v, causal=True, q_offset=192)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_bad_shapes(self):
        q = jnp.zeros((1, 64, 3, 64))
        k = jnp.zeros((1, 64, 2, 64))
        with pytest.raises(ValueError):
            ops.flash_attention(q, k, k)


# --------------------------------------------------------------------- #
# jacobi stencil
# --------------------------------------------------------------------- #
class TestJacobiStencil:
    @pytest.mark.parametrize("g", [8, 16, 32, 100,
                                   384,   # float64: 2 row tiles, last padded
                                   600])  # both dtypes: >= 2 tiles, last padded
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                           (jnp.float64, 1e-14)])
    def test_matches_reference(self, g, dtype, tol):
        jax.config.update("jax_enable_x64", True)
        x = jnp.asarray(RNG.standard_normal(g * g), dtype)
        b = jnp.asarray(RNG.standard_normal(g * g), dtype)
        out = ops.jacobi_sweep(x, b, g)
        want = ref.ref_jacobi_sweep(x, b, g)
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_float64(self):
        jax.config.update("jax_enable_x64", True)
        g = 16
        x = jnp.asarray(RNG.standard_normal(g * g), jnp.float64)
        b = jnp.asarray(RNG.standard_normal(g * g), jnp.float64)
        out = ops.jacobi_sweep(x, b, g)
        want = ref.ref_jacobi_sweep(x, b, g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-14, atol=1e-14)

    def test_fixed_point_of_solution(self):
        """At A x = b the sweep is a no-op (kernel respects the boundary)."""
        from repro.problems import JacobiProblem

        p = JacobiProblem(grid=16)
        xs = p.exact_solution()
        out = ops.jacobi_sweep(jnp.asarray(xs), jnp.asarray(p._b), 16)
        np.testing.assert_allclose(np.asarray(out), xs, atol=1e-10)


# --------------------------------------------------------------------- #
# bellman
# --------------------------------------------------------------------- #
class TestBellmanKernel:
    @given(
        S=st.sampled_from([32, 96, 200]),
        A=st.sampled_from([2, 4, 10]),
        b=st.sampled_from([3, 5]),
        gamma=st.sampled_from([0.9, 0.95, 0.99]),
        seed=st.integers(0, 10**6),
    )
    @settings(max_examples=10, deadline=None)
    def test_matches_reference(self, S, A, b, gamma, seed):
        r = np.random.default_rng(seed)
        idx = jnp.asarray(r.integers(0, S, (S, A, b)), jnp.int32)
        probs = jnp.asarray(r.dirichlet(np.ones(b), (S, A)), jnp.float32)
        R = jnp.asarray(r.uniform(size=(S, A)), jnp.float32)
        V = jnp.asarray(r.standard_normal(S), jnp.float32)
        out = ops.bellman(idx, probs, R, V, gamma=gamma, block_s=32)
        want = ref.ref_bellman(idx, probs, R, V, gamma=gamma)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_contraction_through_kernel(self):
        r = np.random.default_rng(3)
        S, A, b = 64, 3, 4
        idx = jnp.asarray(r.integers(0, S, (S, A, b)), jnp.int32)
        probs = jnp.asarray(r.dirichlet(np.ones(b), (S, A)), jnp.float32)
        R = jnp.asarray(r.uniform(size=(S, A)), jnp.float32)
        u = jnp.asarray(r.standard_normal(S), jnp.float32)
        w = jnp.asarray(r.standard_normal(S), jnp.float32)
        tu = ops.bellman(idx, probs, R, u, gamma=0.9)
        tw = ops.bellman(idx, probs, R, w, gamma=0.9)
        assert float(jnp.max(jnp.abs(tu - tw))) <= \
            0.9 * float(jnp.max(jnp.abs(u - w))) + 1e-5


# --------------------------------------------------------------------- #
# anderson mix
# --------------------------------------------------------------------- #
class TestAndersonMixKernel:
    @given(
        h=st.integers(2, 8),
        N=st.sampled_from([512, 4096, 10000]),
        beta=st.sampled_from([0.0, 0.5, 1.0]),
        seed=st.integers(0, 10**6),
    )
    @settings(max_examples=10, deadline=None)
    def test_matches_reference(self, h, N, beta, seed):
        r = np.random.default_rng(seed)
        X = jnp.asarray(r.standard_normal((h, N)), jnp.float32)
        G = jnp.asarray(r.standard_normal((h, N)), jnp.float32)
        a = r.standard_normal(h)
        a = jnp.asarray(a / a.sum(), jnp.float32)
        out = ops.anderson_mix(X, G, a, beta=beta, block_n=1024)
        want = ref.ref_anderson_mix(X, G, a, beta=beta)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_simplex_identity(self):
        """alpha = e_j, beta = 0 reproduces X_j exactly."""
        X = jnp.asarray(RNG.standard_normal((4, 256)), jnp.float32)
        G = jnp.asarray(RNG.standard_normal((4, 256)), jnp.float32)
        a = jnp.zeros(4).at[2].set(1.0)
        out = ops.anderson_mix(X, G, a, beta=0.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(X[2]),
                                   rtol=1e-6, atol=1e-6)

    def test_matches_coordinator_solver(self):
        """Kernel x_acc == AndersonState.propose() on the same window."""
        from repro.core.anderson import AndersonConfig, AndersonState

        r = np.random.default_rng(5)
        h, N = 5, 400
        xs = r.standard_normal((h, N))
        gs = xs + 0.1 * r.standard_normal((h, N))
        stt = AndersonState(AndersonConfig(m=h - 1, beta=1.0, reg=1e-12))
        for x, g in zip(xs, gs):
            stt.push(x, g)
        want = stt.propose()
        alpha = stt.last_alpha
        out = ops.anderson_mix(jnp.asarray(xs), jnp.asarray(gs),
                               jnp.asarray(alpha), beta=1.0)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-8,
                                   atol=1e-8)

    @pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                            (jnp.float64, 1e-13)])
    @pytest.mark.parametrize("N,block_n", [
        (1000, 256),   # N % block_n != 0: the last block is padded
        (4096, 4096),  # single block
        (513, 128),    # prime-ish N: worst-case divisor search
    ])
    def test_dtypes_and_nondivisible_blocks(self, dtype, rtol, N, block_n):
        """Pallas vs ref_anderson_mix across dtypes and N % block_n != 0."""
        jax.config.update("jax_enable_x64", True)
        r = np.random.default_rng(11)
        h = 4
        X = jnp.asarray(r.standard_normal((h, N)), dtype)
        G = jnp.asarray(r.standard_normal((h, N)), dtype)
        a = r.standard_normal(h)
        a = jnp.asarray(a / a.sum(), dtype)
        out = ops.anderson_mix(X, G, a, beta=0.7, block_n=block_n)
        want = ref.ref_anderson_mix(X, G, a, beta=0.7)
        assert out.dtype == dtype and out.shape == (N,)
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   np.asarray(want, np.float64),
                                   rtol=rtol, atol=rtol)

    def test_state_dispatches_through_kernel(self):
        """AndersonState with mix_kernel_n set routes the combine through
        the Pallas kernel and stays within float tolerance of the
        numpy-path proposal."""
        from repro.core.anderson import AndersonConfig, AndersonState

        r = np.random.default_rng(6)
        n = 300
        kern = AndersonState(AndersonConfig(m=3, beta=0.6, mix_kernel_n=n))
        ref_st = AndersonState(AndersonConfig(m=3, beta=0.6))
        for _ in range(5):
            x, g = r.standard_normal(n), r.standard_normal(n)
            kern.push(x, g)
            ref_st.push(x, g)
        out, want = kern.propose(), ref_st.propose()
        assert out is not None
        np.testing.assert_allclose(out, want, rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(kern.last_alpha, ref_st.last_alpha)


# --------------------------------------------------------------------- #
# fused frozen-halo jacobi block sweeps (device plane)
# --------------------------------------------------------------------- #
class TestJacobiHaloKernel:
    @pytest.mark.parametrize("rows,g,sweeps", [
        (4, 8, 1),     # minimal
        (5, 33, 3),    # odd grid size, odd block
        (7, 16, 4),    # rows not a divisor of g
        (1, 64, 2),    # single-row block
        (16, 128, 10), # paper-scale sweeps
    ])
    def test_matches_numpy_reference(self, rows, g, sweeps):
        """Fused kernel values bitwise-match the numpy oracle; the norm is
        a reduction so it only has to agree to the last few ULPs."""
        jax.config.update("jax_enable_x64", True)
        blk = RNG.standard_normal((rows, g))
        top = RNG.standard_normal(g)
        bot = RNG.standard_normal(g)
        bg = RNG.standard_normal((rows, g))
        out, norm = ops.jacobi_halo_sweeps(
            jnp.asarray(blk), jnp.asarray(top), jnp.asarray(bot),
            jnp.asarray(bg), sweeps=sweeps, interpret=True)
        want, wnorm = ref.ref_jacobi_halo_sweeps(blk, top, bot, bg,
                                                 sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(out), want)
        np.testing.assert_allclose(float(norm), wnorm, rtol=1e-12)

    @pytest.mark.parametrize("rows,g,sweeps", [
        (256, 1024, 10),  # 2 tiles, 16-row ghost bands
        (300, 1024, 3),   # 3 tiles, the last padded
        (200, 2048, 8),   # 4 tiles, ghost band exactly as deep as the sweeps
        (1365, 128, 10),  # 2 tiles, the last padded (3 workers' block rows)
        (129, 8192, 1),   # 9 tiles, the last holding one row
        (40, 16384, 2),   # 5 tiles of one ghost band each
    ])
    def test_row_tiles_match_numpy_reference(self, rows, g, sweeps):
        """Gridded over row tiles of float64 blocks above the tile budget,
        the ghost bands keep every tile exact, and the padding past the
        block (NaN in interpret mode) stays out of the values and the norm:
        values bitwise equal to the whole-block oracle."""
        from repro.kernels.jacobi_stencil import row_tiling

        jax.config.update("jax_enable_x64", True)
        tile, halo = row_tiling(rows, g, 8, sweeps)
        assert -(-rows // tile) > 1 and halo >= sweeps
        blk, bg = RNG.standard_normal((2, rows, g))
        top, bot = RNG.standard_normal((2, g))
        out, norm = ops.jacobi_halo_sweeps(
            jnp.asarray(blk), jnp.asarray(top), jnp.asarray(bot),
            jnp.asarray(bg), sweeps=sweeps, interpret=True)
        want, wnorm = ref.ref_jacobi_halo_sweeps(blk, top, bot, bg,
                                                 sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(out), want)
        np.testing.assert_allclose(float(norm), wnorm, rtol=1e-12)

    @pytest.mark.parametrize("edge", ["top", "bot", "both"])
    def test_dirichlet_boundary_rows(self, edge):
        """Blocks touching the grid edge freeze zeros (r0=0 / r1=g)."""
        jax.config.update("jax_enable_x64", True)
        rows, g, sweeps = 6, 17, 3
        blk = RNG.standard_normal((rows, g))
        bg = RNG.standard_normal((rows, g))
        z = np.zeros(g)
        top = z if edge in ("top", "both") else RNG.standard_normal(g)
        bot = z if edge in ("bot", "both") else RNG.standard_normal(g)
        out, norm = ops.jacobi_halo_sweeps(
            jnp.asarray(blk), jnp.asarray(top), jnp.asarray(bot),
            jnp.asarray(bg), sweeps=sweeps, interpret=True)
        want, wnorm = ref.ref_jacobi_halo_sweeps(blk, top, bot, bg,
                                                 sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(out), want)
        np.testing.assert_allclose(float(norm), wnorm, rtol=1e-12)

    def test_matches_host_block_update(self):
        """One fused dispatch == the host-path _block_sweeps slice for the
        same whole-rows block (the device plane's bit-compat contract)."""
        import repro.problems  # noqa: F401  (enables jax x64)
        from repro.problems.jacobi import JacobiProblem

        p = JacobiProblem(grid=24, sweeps=4)
        r0, r1 = 5, 12
        x = RNG.standard_normal(p.n)
        idx = np.arange(r0 * p.g, r1 * p.g)
        want = p.block_update(x, idx)
        xg = x.reshape(p.g, p.g)
        out, _ = ops.jacobi_halo_sweeps(
            jnp.asarray(xg[r0:r1]), jnp.asarray(xg[r0 - 1]),
            jnp.asarray(xg[r1]), jnp.asarray(p._b.reshape(p.g, p.g)[r0:r1]),
            sweeps=p.sweeps, interpret=True)
        np.testing.assert_array_equal(np.asarray(out).ravel(), want)

    def test_rejects_bad_shapes(self):
        blk = jnp.zeros((4, 8))
        with pytest.raises(ValueError):
            ops.jacobi_halo_sweeps(blk, jnp.zeros(7), jnp.zeros(8),
                                   jnp.zeros((4, 8)), sweeps=1)
        with pytest.raises(ValueError):
            ops.jacobi_halo_sweeps(blk, jnp.zeros(8), jnp.zeros(8),
                                   jnp.zeros((3, 8)), sweeps=1)
        with pytest.raises(ValueError):
            ops.jacobi_halo_sweeps(blk, jnp.zeros(8), jnp.zeros(8),
                                   jnp.zeros((4, 8)), sweeps=0)


# --------------------------------------------------------------------- #
# fused bellman state-block backup (device plane)
# --------------------------------------------------------------------- #
class TestBellmanBlockKernel:
    def _mdp_block(self, rows, A, b, D, seed):
        r = np.random.default_rng(seed)
        idx = r.integers(0, D, size=(rows, A, b)).astype(np.int32)
        probs = r.random((rows, A, b))
        probs /= probs.sum(axis=-1, keepdims=True)
        rewards = r.standard_normal((rows, A))
        v = r.standard_normal(D)
        v_old = r.standard_normal(rows)
        return idx, probs, rewards, v, v_old

    @pytest.mark.parametrize("rows,A,b,D", [
        (8, 4, 3, 64),
        (13, 5, 2, 100),  # odd block size
        (1, 2, 4, 16),    # single state
        (50, 8, 5, 50),   # D == rows (dense closure)
    ])
    def test_matches_numpy_reference(self, rows, A, b, D):
        jax.config.update("jax_enable_x64", True)
        idx, probs, rewards, v, v_old = self._mdp_block(rows, A, b, D, rows)
        tv, norm = ops.bellman_block(
            jnp.asarray(idx), jnp.asarray(probs), jnp.asarray(rewards),
            jnp.asarray(v), jnp.asarray(v_old), gamma=0.95, interpret=True)
        want, wnorm = ref.ref_bellman_block(idx, probs, rewards, v, v_old,
                                            gamma=0.95)
        np.testing.assert_allclose(np.asarray(tv), want, rtol=1e-14,
                                   atol=1e-14)
        np.testing.assert_allclose(float(norm), wnorm, rtol=1e-12)

    def test_remapped_dependency_closure(self):
        """Gathering from a dependency-closure slice of v (remapped idx)
        gives the same backup as gathering from the full vector."""
        jax.config.update("jax_enable_x64", True)
        idx, probs, rewards, v, v_old = self._mdp_block(6, 3, 4, 200, 7)
        closure = np.unique(idx)
        remap = np.searchsorted(closure, idx).astype(np.int32)
        full, _ = ops.bellman_block(
            jnp.asarray(idx), jnp.asarray(probs), jnp.asarray(rewards),
            jnp.asarray(v), jnp.asarray(v_old), gamma=0.9, interpret=True)
        sliced, _ = ops.bellman_block(
            jnp.asarray(remap), jnp.asarray(probs), jnp.asarray(rewards),
            jnp.asarray(v[closure]), jnp.asarray(v_old), gamma=0.9,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(sliced))

    def test_rejects_bad_shapes(self):
        idx = jnp.zeros((4, 2, 3), jnp.int32)
        with pytest.raises(ValueError):
            ops.bellman_block(idx, jnp.zeros((4, 2, 2)), jnp.zeros((4, 2)),
                              jnp.zeros(10), jnp.zeros(4), gamma=0.9)
        with pytest.raises(ValueError):
            ops.bellman_block(idx, jnp.zeros((4, 2, 3)), jnp.zeros((4, 2)),
                              jnp.zeros(10), jnp.zeros(5), gamma=0.9)
