"""BCSR SpMM Pallas kernel.

The pack stores the blocks rows-on-lanes: for each of the ``W`` block
slots of a block row (``W`` = matrix-wide max blocks per block row —
address padding only, like `pack.py`'s stream padding; padded slots carry
block column -1 and zero values), every row of the block row holds the
block's column and its ``c`` values — ``(W, Mp)`` block columns and
``(W, c, Mp)`` values.  One program per 128 rows walks the block slots,
expands each block column into its ``c`` absolute columns, gathers ``x``
(`repro.kernels.common.gather_mul`) and contracts the dense r x c tiles
— no per-element index storage, which is the format's whole bargain: the
cost model
charges BCSR plain lock-step work over the *filled* cells
(`Fingerprint.block_fill_elems`), with no row-sequential penalty and no
decode term.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import LANES, fori, gather_mul
from repro.kernels.sell_spmv import padded_rows
from repro.kernels.tiling import blocked_spmm
from repro.sparse.bcsr import BCSR


@dataclasses.dataclass
class PackedBCSR:
    block_cols: np.ndarray  # (W, Mp) int32 block-column per row, -1 = pad
    values: np.ndarray      # (W, c, Mp)
    shape: tuple
    block_shape: tuple


def pack_bcsr(b: BCSR) -> PackedBCSR:
    r, c = b.block_shape
    S = b.n_block_rows
    per_row = np.diff(b.block_ptr)
    W = max(int(per_row.max()) if S else 0, 1)
    Mp = padded_rows(S * r)
    cols = np.full((W, Mp), -1, dtype=np.int32)
    vals = np.zeros((W, c, Mp), dtype=b.values.dtype)
    if b.n_blocks:
        # Each block lands at (its slot in its block row, its r rows).
        brow = np.repeat(np.arange(S, dtype=np.int64), per_row)
        pos = np.arange(b.n_blocks, dtype=np.int64) - b.block_ptr[brow]
        rows = brow[:, None] * r + np.arange(r)            # (nb, r)
        cols[pos[:, None], rows] = np.asarray(b.block_cols)[:, None]
        vals[pos[:, None, None], np.arange(c)[None, :, None],
             rows[:, None, :]] = np.asarray(b.values).transpose(0, 2, 1)
    return PackedBCSR(block_cols=cols, values=vals, shape=b.shape,
                      block_shape=b.block_shape)


def _bcsr_kernel(col_ref, val_ref, x_ref, y_ref):
    xt = x_ref[...]                                   # (Bt, n)
    c = val_ref.shape[1]

    def body(w, acc):
        bc = col_ref[pl.ds(w, 1), :]                  # (1, 128)
        vt = val_ref[w]                               # (c, 128)
        s = None
        for cc in range(c):
            t = gather_mul(xt, bc * c + cc, vt[cc:cc + 1])  # bc == -1: pad
            s = t if s is None else s + t
        return acc + s

    y_ref[...] = fori(col_ref.shape[0], body,
                      jnp.zeros(y_ref.shape, y_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "bn",
                                             "tile_mode"))
def bcsr_spmm_pallas(block_cols, val, x, interpret=None, bn=None,
                     tile_mode="auto"):
    """Multi-RHS BCSR kernel: x is (n, B); returns (Mp, B) rows — each
    block's x columns are gathered once and contracted against all B
    right-hand sides.  ``bn`` column-tiles the B axis
    (`repro.kernels.tiling`); blocked output is bitwise equal to the
    untiled kernel."""
    W, c, Mp = val.shape
    return blocked_spmm(_bcsr_kernel, (block_cols, val),
                        [((W, LANES), lambda g: (0, g)),
                         ((W, c, LANES), lambda g: (0, 0, g))],
                        x.astype(val.dtype), lanes=LANES,
                        grid_s=Mp // LANES, bn=bn, tile_mode=tile_mode,
                        interpret=interpret)


def bcsr_spmv_pallas(block_cols, val, x, interpret=None):
    return bcsr_spmm_pallas(block_cols, val, x[:, None],
                            interpret=interpret)[:, 0]


def bcsr_spmv_ref(block_cols: np.ndarray, val: np.ndarray, x: np.ndarray):
    """Pure-jnp oracle for the BCSR kernel ((Mp,) rows)."""
    x = jnp.asarray(x)
    W, c, Mp = val.shape
    n = x.shape[0]
    colidx = jnp.maximum(block_cols, 0)[:, None, :] * c + \
        jax.lax.broadcasted_iota(jnp.int32, (W, c, Mp), 1)
    xg = jnp.take(x, jnp.clip(colidx, 0, n - 1), axis=0)   # (W, c, Mp)
    contrib = jnp.where((block_cols >= 0)[:, None, :], val * xg, 0)
    return jnp.sum(contrib, axis=(0, 1))
