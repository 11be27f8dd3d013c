"""SELL-style SpMM Pallas baseline kernel (uncompressed comparator).

The pack stores the padded ELL layout transposed, rows on lanes:
``(Wg, Mp)`` column indices and values, ``Wg`` the matrix-wide max row
nnz and ``Mp`` the row count rounded up to 128.  One program per 128
rows walks the ``Wg`` positions, gathers ``x`` per position (one-hot on
the MXU, `repro.kernels.common.gather_mul`) and accumulates in position
order.  This is the "fastest cuSPARSE format" stand-in the benchmark
harness compares with the fused dtANS kernel under the same roofline
model (both kernels are memory-bound; the ratio of bytes moved predicts
the speedup, Section V-B of the paper).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import LANES, fori, gather_mul
from repro.kernels.tiling import blocked_spmm
from repro.sparse.formats import CSR


@dataclasses.dataclass
class PackedSELL:
    indices: np.ndarray   # (Wg, Mp) int32, -1 = padding
    values: np.ndarray    # (Wg, Mp)
    shape: tuple
    lane_width: int


def padded_rows(m: int) -> int:
    """Rows of an uncoded pack: ``m`` rounded up to whole programs."""
    return max(LANES, -(-int(m) // LANES) * LANES)


def ell_positions(indptr: np.ndarray):
    """(row, position-in-row) of every stored entry, CSR order."""
    rnnz = np.diff(indptr)
    rows = np.repeat(np.arange(rnnz.size), rnnz)
    return rows, np.arange(indptr[-1]) - indptr[rows]


def pack_sell(a: CSR, lane_width: int = 128) -> PackedSELL:
    m, _ = a.shape
    Wg = max(int(np.diff(a.indptr).max()) if m else 0, 1)
    idx = np.full((Wg, padded_rows(m)), -1, dtype=np.int32)
    val = np.zeros((Wg, padded_rows(m)), dtype=a.values.dtype)
    rows, pos = ell_positions(a.indptr)
    idx[pos, rows] = a.indices
    val[pos, rows] = a.values
    return PackedSELL(indices=idx, values=val, shape=a.shape,
                      lane_width=lane_width)


def _sell_kernel(idx_ref, val_ref, x_ref, y_ref):
    xt = x_ref[...]                                   # (Bt, n)

    def body(w, acc):
        c = idx_ref[pl.ds(w, 1), :]                   # (1, 128)
        v = val_ref[pl.ds(w, 1), :]
        return acc + gather_mul(xt, c, v)          # c == -1: pad

    y_ref[...] = fori(idx_ref.shape[0], body,
                      jnp.zeros(y_ref.shape, y_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "bn",
                                             "tile_mode"))
def sell_spmm_pallas(idx, val, x, interpret=None, bn=None,
                     tile_mode="auto"):
    """Multi-RHS SELL kernel: x is (n, B); returns (Mp, B) rows.
    ``bn`` column-tiles the B axis (`repro.kernels.tiling`); blocked
    output is bitwise equal to the untiled kernel."""
    Wg, Mp = idx.shape
    spec = ((Wg, LANES), lambda g: (0, g))
    return blocked_spmm(_sell_kernel, (idx, val), [spec, spec],
                        x.astype(val.dtype), lanes=LANES,
                        grid_s=Mp // LANES, bn=bn, tile_mode=tile_mode,
                        interpret=interpret)


def sell_spmv_pallas(idx, val, x, interpret=None):
    return sell_spmm_pallas(idx, val, x[:, None], interpret=interpret)[:, 0]


def sell_spmv_ref(idx: np.ndarray, val: np.ndarray, x: np.ndarray):
    """Pure-jnp oracle for the SELL kernel ((Mp,) rows)."""
    mask = idx >= 0
    xg = jnp.take(jnp.asarray(x), jnp.clip(idx, 0, x.shape[0] - 1), axis=0)
    return jnp.sum(jnp.where(mask, val * xg, 0), axis=0)
