"""Pallas kernel validation: interpret-mode vs pure-jnp oracle vs numpy gold.

Sweeps shapes, dtypes, lane widths, table sharing and escape pressure for
each kernel, per the kernel-validation contract (assert_allclose against
ref.py oracles).
"""

import numpy as np
import pytest

from repro.core.csr_dtans import encode_matrix, spmv_gold
from repro.kernels import ops
from repro.kernels.pack import pack_matrix
from repro.kernels.ref import decode_ref, spmv_ref
from repro.kernels.sell_spmv import pack_sell, sell_spmv_ref
from repro.sparse.formats import CSR
from repro.sparse.random_graphs import banded, erdos_renyi, stencil_2d


def _rng(seed=0):
    return np.random.default_rng(seed)


def _random_csr(m, n, density, dtype, seed, quantized=False):
    rng = _rng(seed)
    d = rng.standard_normal((m, n)).astype(dtype)
    if quantized:  # low-entropy values (compressible, no escapes)
        d = np.round(d * 2) / 2
    d[rng.random((m, n)) >= density] = 0
    return CSR.from_dense(d)


_CASES = [
    # (name, matrix factory, lane_width, shared_table)
    ("stencil-f64", lambda: stencil_2d(16), 32, True),
    ("stencil-f64-2tab", lambda: stencil_2d(16), 32, False),
    ("er-f64", lambda: erdos_renyi(200, 6, _rng(1)), 128, True),
    ("banded-f32",
     lambda: (lambda b: CSR(b.indptr, b.indices,
                            b.values.astype(np.float32), b.shape))(
         banded(150, 4)), 64, True),
    ("random-f64-escapes", lambda: _random_csr(90, 70, 0.3, np.float64, 2),
     16, True),
    ("random-f32-escapes", lambda: _random_csr(90, 70, 0.3, np.float32, 3),
     16, True),
    ("quantized-f32", lambda: _random_csr(120, 80, 0.2, np.float32, 4,
                                          quantized=True), 32, True),
    ("tall-skinny", lambda: _random_csr(400, 9, 0.5, np.float64, 5), 128,
     True),
    ("wide", lambda: _random_csr(9, 400, 0.4, np.float64, 6), 8, True),
    ("empty-rows", lambda: CSR.from_dense(
        np.diag(np.r_[np.zeros(10), np.arange(1.0, 11.0)])), 16, True),
]


@pytest.fixture(scope="module", params=_CASES, ids=[c[0] for c in _CASES])
def case(request):
    name, factory, lw, shared = request.param
    a = factory()
    mat = encode_matrix(a, lane_width=lw, shared_table=shared)
    return name, a, mat, pack_matrix(mat)


class TestDtansSpmvKernel:
    def test_kernel_vs_gold(self, case):
        _, a, mat, pm = case
        rng = _rng(10)
        x = rng.standard_normal(a.shape[1]).astype(a.values.dtype)
        y_k = np.asarray(ops.spmv(pm, x))
        y_g = spmv_gold(mat, x)
        rtol = 1e-12 if a.values.dtype == np.float64 else 1e-4
        np.testing.assert_allclose(y_k, y_g, rtol=rtol, atol=1e-6)

    def test_kernel_vs_ref_oracle(self, case):
        _, a, _, pm = case
        rng = _rng(11)
        x = rng.standard_normal(a.shape[1]).astype(a.values.dtype)
        np.testing.assert_allclose(np.asarray(ops.spmv(pm, x)),
                                   np.asarray(spmv_ref(pm, x)),
                                   rtol=1e-12, atol=1e-30)

    def test_accumulate_y(self, case):
        _, a, _, pm = case
        rng = _rng(12)
        x = rng.standard_normal(a.shape[1]).astype(a.values.dtype)
        y0 = rng.standard_normal(a.shape[0]).astype(a.values.dtype)
        got = np.asarray(ops.spmv(pm, x, y0))
        rtol = 1e-12 if a.values.dtype == np.float64 else 1e-4
        np.testing.assert_allclose(got, a.to_dense() @ x + y0, rtol=rtol,
                                   atol=1e-6)


class TestDtansDecodeKernel:
    def test_kernel_vs_ref_oracle(self, case):
        _, _, _, pm = case
        ck, vk = ops.decode(pm)
        cr, vr = decode_ref(pm)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), rtol=0)

    def test_reconstructs_matrix(self, case):
        _, a, mat, pm = case
        cols, vals = ops.decode(pm)
        cols, vals = np.asarray(cols), np.asarray(vals)
        dense = np.zeros(a.shape, dtype=a.values.dtype)
        m = a.shape[0]
        L = pm.lane_width
        for i in range(m):
            s, lane = divmod(i, L)
            sel = cols[s, lane] >= 0
            dense[i, cols[s, lane][sel]] = vals[s, lane][sel]
        np.testing.assert_array_equal(dense, a.to_dense())


class TestSellKernel:
    @pytest.mark.parametrize("dtype", [np.float64, np.float32])
    @pytest.mark.parametrize("lw", [8, 128])
    def test_vs_dense_and_ref(self, dtype, lw):
        rng = _rng(20)
        a = _random_csr(130, 75, 0.15, dtype, 21)
        ps = pack_sell(a, lane_width=lw)
        x = rng.standard_normal(75).astype(dtype)
        y_k = np.asarray(ops.sell_spmv(ps, x))
        y_r = np.asarray(sell_spmv_ref(ps.indices, ps.values, x)
                         ).reshape(-1)[:130]
        rtol = 1e-12 if dtype == np.float64 else 1e-5
        np.testing.assert_allclose(y_k, y_r, rtol=rtol)
        np.testing.assert_allclose(y_k, a.to_dense() @ x, rtol=rtol,
                                   atol=1e-5 if dtype == np.float32 else 0)


@pytest.mark.parametrize("n", [576, 4096])
@pytest.mark.parametrize("bt", [1, 4, 16, 32])
def test_gather_mul_is_one_f32_product(bt, n):
    """`gather_mul` gives ``val * xt[:, col]`` bit for bit, 0 on padding
    lanes (column -1), at block edges and on a short last block."""
    import jax.numpy as jnp

    from repro.kernels.common import LANES, gather_mul

    rng = _rng(bt * n)
    xt = rng.standard_normal((bt, n)).astype(np.float32)
    edges = [0, n - 1, 127, 128, 255, 256, n - 128, n - 129, -1, -1]
    col = np.r_[edges, rng.integers(0, n, LANES - len(edges))]
    col = col.astype(np.int32)[None, :]
    val = rng.standard_normal((1, LANES)).astype(np.float32)
    got = np.asarray(gather_mul(jnp.asarray(xt), jnp.asarray(col),
                                jnp.asarray(val)))
    want = np.where(col >= 0, val * xt[:, np.clip(col[0], 0, n - 1)],
                    np.float32(0))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
