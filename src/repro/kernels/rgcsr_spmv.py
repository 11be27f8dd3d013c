"""RGCSR SpMM Pallas kernel.

The pack stores each row's delta stream transposed, rows on lanes:
``(Wg, Mp)`` deltas and values (``Wg`` = matrix-wide max row nnz —
address padding only, not counted in `RGCSR.nbytes`, exactly like
`pack.py`'s stream padding) plus each row's entry count.  One program per
128 rows walks the positions, reconstructs absolute columns with a
running sum of the deltas, gathers ``x`` and accumulates.  Compared to
the SELL kernel, the in-kernel extra work is one add per stored element
(the delta prefix sum) — the `spmv_ops_per_elem` the cost model charges
— while the *stored* bytes carry no per-slice padding.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import LANES, fori, gather_mul
from repro.kernels.sell_spmv import ell_positions, padded_rows
from repro.kernels.tiling import blocked_spmm
from repro.sparse.rgcsr import RGCSR


@dataclasses.dataclass
class PackedRGCSR:
    deltas: np.ndarray    # (Wg, Mp) int32 delta streams, 0 = padding
    values: np.ndarray    # (Wg, Mp)
    nnz: np.ndarray       # (1, Mp) int32 — real entries per row
    shape: tuple
    group_size: int


def pack_rgcsr(r: RGCSR) -> PackedRGCSR:
    m, _ = r.shape
    G = r.group_size
    rnnz = r.row_nnz()
    Wg = max(int(rnnz.max()) if m else 0, 1)
    Mp = padded_rows(m)
    indptr = np.zeros(m + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(rnnz)
    deltas = np.zeros((Wg, Mp), dtype=np.int32)
    values = np.zeros((Wg, Mp), dtype=r.values.dtype)
    rows, pos = ell_positions(indptr)
    deltas[pos, rows] = r.delta_indices
    values[pos, rows] = r.values
    nnz = np.zeros((1, Mp), dtype=np.int32)
    nnz[0, :m] = rnnz
    return PackedRGCSR(deltas=deltas, values=values, nnz=nnz,
                       shape=r.shape, group_size=G)


def _rgcsr_kernel(delta_ref, val_ref, nnz_ref, x_ref, y_ref):
    xt = x_ref[...]                                   # (Bt, n)
    nnz = nnz_ref[...]                                # (1, 128)

    def body(w, carry):
        col, acc = carry
        col = col + delta_ref[pl.ds(w, 1), :]         # running column
        v = val_ref[pl.ds(w, 1), :]
        return col, acc + gather_mul(xt, col, jnp.where(w < nnz, v, 0))

    init = (jnp.zeros(nnz.shape, jnp.int32),
            jnp.zeros(y_ref.shape, y_ref.dtype))
    _, y_ref[...] = fori(delta_ref.shape[0], body, init)


@functools.partial(jax.jit, static_argnames=("interpret", "bn",
                                             "tile_mode"))
def rgcsr_spmm_pallas(deltas, val, nnz, x, interpret=None, bn=None,
                      tile_mode="auto"):
    """Multi-RHS RGCSR kernel: x is (n, B); returns (Mp, B) rows. The
    delta prefix sum runs once per row and feeds all B columns.
    ``bn`` column-tiles the B axis (`repro.kernels.tiling`); blocked
    output is bitwise equal to the untiled kernel."""
    Wg, Mp = deltas.shape
    spec = ((Wg, LANES), lambda g: (0, g))
    return blocked_spmm(_rgcsr_kernel, (deltas, val, nnz),
                        [spec, spec, ((1, LANES), lambda g: (0, g))],
                        x.astype(val.dtype), lanes=LANES,
                        grid_s=Mp // LANES, bn=bn, tile_mode=tile_mode,
                        interpret=interpret)


def rgcsr_spmv_pallas(deltas, val, nnz, x, interpret=None):
    return rgcsr_spmm_pallas(deltas, val, nnz, x[:, None],
                             interpret=interpret)[:, 0]


def rgcsr_spmv_ref(deltas: np.ndarray, val: np.ndarray, nnz: np.ndarray,
                   x: np.ndarray):
    """Pure-jnp oracle for the RGCSR kernel ((Mp,) rows)."""
    x = jnp.asarray(x)
    cols = jnp.cumsum(deltas, axis=0)
    mask = (jax.lax.broadcasted_iota(jnp.int32, deltas.shape, 0)
            < nnz)
    xg = jnp.take(x, jnp.clip(cols, 0, x.shape[0] - 1), axis=0)
    return jnp.sum(jnp.where(mask, val * xg, 0), axis=0)
