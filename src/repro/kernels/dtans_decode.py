"""Decode-only dtANS Pallas kernel (the library's "decompression kernel").

Same lock-step machinery as the fused SpMM kernel but materializes
(columns, values) per row instead of contracting against x.  The kernel
writes one ``(max_nnz, N)`` block per slice group, one row per decoded
nonzero position; the wrapper returns the transposed group-major
``(G, N, max_nnz)`` layout (`repro.kernels.pack.slice_major` turns it into
``(S, L, max_nnz)``).  cols == -1 marks tail padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import interpret_mode
from repro.core.params import DtansParams
from repro.kernels.common import (bits_to_value, fori, init_state,
                                  kernel_x64, segment_step)
from repro.kernels.dtans_spmv import load_arrays, mat_specs


def _decode_kernel(stream_ref, sbase_ref, esc_ref, ebase_ref, nsegs_ref,
                   nnz_ref, sym_ref, meta_ref, cols_ref, vals_ref, *,
                   params: DtansParams, pattern: tuple, max_nseg: int,
                   lane_width: int):
    arr = load_arrays(stream_ref, sbase_ref, esc_ref, ebase_ref, nsegs_ref,
                      nnz_ref, sym_ref, meta_ref, lane_width=lane_width)
    h = params.l // 2

    def body(j, state):
        state, cols, vbits, valid = segment_step(j, state, arr, params,
                                                 pattern)
        for i in range(h):
            row = pl.ds(j * h + i, 1)
            cols_ref[row, :] = jnp.where(valid[i], cols[i], jnp.int32(-1))
            vals_ref[row, :] = jnp.where(
                valid[i], bits_to_value(vbits[i], vals_ref.dtype), 0)
        return state

    fori(max_nseg, body, init_state(arr, params))


@functools.partial(jax.jit, static_argnames=(
    "params", "pattern", "max_nseg", "lane_width", "out_dtype", "interpret"))
def dtans_decode_pallas(mats, *, params, pattern, max_nseg, lane_width,
                        out_dtype, interpret=None):
    """Returns (cols, vals), each (G, N, max_nnz)."""
    if interpret is None:
        interpret = interpret_mode()
    G = mats[0].shape[0]
    N = mats[4].shape[2]
    max_nnz = max_nseg * (params.l // 2)
    kernel = functools.partial(_decode_kernel, params=params,
                               pattern=pattern, max_nseg=max_nseg,
                               lane_width=lane_width)
    out_spec = pl.BlockSpec((None, max_nnz, N), lambda g: (g, 0, 0))
    with kernel_x64(interpret):
        cols, vals = pl.pallas_call(
            kernel,
            grid=(G,),
            in_specs=[pl.BlockSpec(s, f) for s, f in mat_specs(mats)],
            out_specs=[out_spec, out_spec],
            out_shape=[jax.ShapeDtypeStruct((G, max_nnz, N), jnp.int32),
                       jax.ShapeDtypeStruct((G, max_nnz, N), out_dtype)],
            interpret=interpret,
        )(*mats)
    return cols.transpose(0, 2, 1), vals.transpose(0, 2, 1)
