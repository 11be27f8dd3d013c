"""Fused dtANS-decode + SpMM Pallas TPU kernel (the paper's Fig. 1 right).

Grid: one program per group of ``P`` slices of ``lane_width`` rows — 128
lanes in all when the lane width divides 128 (`repro.kernels.pack`).  Per
program, the kernel holds in VMEM:

  stream block   (Rw, 128) x 4 B    — the group's word streams
  escape block   (T, Re, 128)       — the group's escape streams
  lane vectors   (1, N) x 4 B each  — slice offsets, segment / nnz counts
  coding tables  (T, Rk, 128) x 8 B — symbol and digit/base/escape words,
                                      shared by every program
  x^T tile       (Bt, n)            — the dense right-hand sides
  y^T block      (Bt, N)            — output rows for this group

The decode loop is `lax.fori_loop` over the matrix-wide max segment count;
lanes past their row's end are masked (same lock-step schedule as
`repro.core.dtans_vec.decode_lanes`).  Stream claims and table lookups are
in-tile gathers (`repro.kernels.common.lookup`); each ``value * x[col]``
is a value-carrying one-hot product on the MXU
(`repro.kernels.common.gather_mul`), and the products are summed in the
same order on every path, so all schedules below are bitwise identical to
the plain kernel.

Three static knobs (docs/kernels.md has the full contract):

* ``shared_cols`` — the fused BCSR-dtANS contraction.  A block-filled
  encode (BCSR-dtANS at lane_width == r) gives every in-bounds lane of a
  slice the SAME column sequence, so every lane contracts against its
  slice's first lane's columns.
* ``pipeline`` — decode/contract overlap: the loop body decodes segment
  ``j+1`` BEFORE contracting segment ``j``, so the next segment's claims
  and lookups have no data dependence on the in-flight contraction.  The
  prologue decodes segment 0; the final body iteration decodes one
  segment past the end, which is masked to a no-op.
* ``bn`` — column tiling of the right-hand sides via
  `repro.kernels.tiling.blocked_spmm`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import DtansParams
from repro.kernels.common import (DecodeArrays, bits_to_value, fori,
                                  gather_mul, init_state, lane_matrices,
                                  segment_step)
from repro.kernels.tiling import blocked_spmm


def load_arrays(stream_ref, sbase_ref, esc_ref, ebase_ref, nsegs_ref,
                nnz_ref, sym_ref, meta_ref, *, lane_width: int):
    """One program's `DecodeArrays` from its (squeezed) blocks."""
    nsegs = nsegs_ref[...]
    tri, grp = lane_matrices(nsegs.shape[1], lane_width)
    return DecodeArrays(stream=stream_ref[...], stream_base=sbase_ref[...],
                        esc=esc_ref[...], esc_base=ebase_ref[...],
                        tab_symbol=sym_ref[...], tab_meta=meta_ref[...],
                        nsegs=nsegs, nnz=nnz_ref[...], tri=tri, grp=grp)


def _decode_contract(arr, params, pattern, max_nseg, acc0, contract,
                     pipeline: bool):
    """The shared decode loop: serial (decode j, contract j) or
    software-pipelined (decode j+1, then contract j — the decode of the
    next segment issues with no data dependence on the contraction in
    flight).  Contraction order is identical either way."""
    state = init_state(arr, params)
    if not pipeline:
        def body(j, carry):
            state, acc = carry
            state, cols, vbits, valid = segment_step(j, state, arr,
                                                     params, pattern)
            return state, contract(cols, vbits, valid, acc)

        _, acc = fori(max_nseg, body, (state, acc0))
        return acc

    def step(j, state):
        state, cols, vbits, valid = segment_step(j, state, arr, params,
                                                 pattern)
        # Masks cross the loop boundary as int32 (no i1 loop carries).
        return state, (cols, vbits, [v.astype(jnp.int32) for v in valid])

    def body(j, carry):
        state, seg, acc = carry
        nstate, nseg = step(j + 1, state)
        cols, vbits, valid = seg
        acc = contract(cols, vbits, [v > 0 for v in valid], acc)
        return nstate, nseg, acc

    state, seg = step(0, state)
    _, _, acc = fori(max_nseg, body, (state, seg, acc0))
    return acc


def _first_lane_matrix(n_lanes: int, lane_width: int):
    """``F[i, j] = 1`` where lane ``i`` is the first lane of lane ``j``'s
    slice: ``cols @ F`` hands every lane its slice's first column."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 1)
    if n_lanes > lane_width:   # several slices: lane_width is a power of 2
        sh = int(lane_width).bit_length() - 1
        first = (j >> sh) << sh
    else:
        first = 0
    return (i == first).astype(jnp.float32)


def _spmm_kernel(stream_ref, sbase_ref, esc_ref, ebase_ref, nsegs_ref,
                 nnz_ref, sym_ref, meta_ref, x_ref, y_ref, *,
                 params: DtansParams, pattern: tuple, max_nseg: int,
                 lane_width: int, pipeline: bool, shared_cols: bool):
    """Fused decode + multi-RHS contraction: decode each segment ONCE,
    contract it against all B columns of x before the next segment —
    the amortization the batched cost model prices (decode work is per
    matrix, contraction work per right-hand side)."""
    arr = load_arrays(stream_ref, sbase_ref, esc_ref, ebase_ref, nsegs_ref,
                      nnz_ref, sym_ref, meta_ref, lane_width=lane_width)
    xt = x_ref[...]                                    # (Bt, n)
    acc0 = jnp.zeros((xt.shape[0], arr.nsegs.shape[1]), xt.dtype)
    first = (_first_lane_matrix(arr.nsegs.shape[1], lane_width)
             if shared_cols else None)

    def contract(cols, vbits, valid, acc):
        s = None
        for c, vb, ok in zip(cols, vbits, valid):
            if shared_cols:
                c = jnp.dot(c.astype(jnp.float32), first,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32
                            ).astype(jnp.int32)
            v = jnp.where(ok, bits_to_value(vb, xt.dtype), 0)
            contrib = gather_mul(xt, c, v)             # (Bt, N)
            s = contrib if s is None else s + contrib
        return acc + s

    y_ref[...] = _decode_contract(arr, params, pattern, max_nseg, acc0,
                                  contract, pipeline)


def mat_specs(mats):
    """``(block_shape, index_map)`` per packed operand (`PackedMatrix`
    field order): squeezed group dimension, full trailing dimensions."""
    stream, sbase, esc, ebase, nsegs, nnz, sym, meta = mats
    G, Rw, W = stream.shape
    T, _, Re, _ = esc.shape
    N = nsegs.shape[2]
    lanes = (None, 1, N)
    return [
        ((None, Rw, W), lambda g: (g, 0, 0)),          # stream rows
        (lanes, lambda g: (g, 0, 0)),                   # stream_base
        ((T, None, Re, W), lambda g: (0, g, 0, 0)),     # escape rows
        ((T, None, 1, N), lambda g: (0, g, 0, 0)),      # esc_base
        (lanes, lambda g: (g, 0, 0)),                   # nsegs
        (lanes, lambda g: (g, 0, 0)),                   # nnz
        (sym.shape, lambda g: (0, 0, 0)),               # tab symbol
        (meta.shape, lambda g: (0, 0, 0)),              # tab meta
    ]


@functools.partial(jax.jit, static_argnames=(
    "params", "pattern", "max_nseg", "lane_width", "interpret", "bn",
    "tile_mode", "pipeline", "shared_cols"))
def dtans_spmm_pallas(mats, x, *, params, pattern, max_nseg, lane_width,
                      interpret=None, bn=None, tile_mode="auto",
                      pipeline=False, shared_cols=False):
    """Multi-RHS pallas_call wrapper.  ``mats`` holds the `PackedMatrix`
    arrays in field order (stream .. tab_meta); x is (n, B) in the
    output dtype; returns (G * N, B) rows.

    ``bn`` tiles the B axis into column blocks (None = one tile);
    ``pipeline`` overlaps decode with contraction; ``shared_cols`` runs
    the fused block-decode contraction.  All three are
    bit-identity-preserving.  ``interpret`` defaults to the backend's
    (`repro.backend.interpret_mode`)."""
    kernel = functools.partial(_spmm_kernel, params=params, pattern=pattern,
                               max_nseg=max_nseg, lane_width=lane_width,
                               pipeline=pipeline, shared_cols=shared_cols)
    return blocked_spmm(kernel, mats, mat_specs(mats), x,
                        lanes=mats[4].shape[2], grid_s=mats[0].shape[0],
                        bn=bn, tile_mode=tile_mode, interpret=interpret)


def dtans_spmv_pallas(mats, x, *, params, pattern, max_nseg, lane_width,
                      interpret=None, pipeline=False, shared_cols=False):
    """Single-vector form of `dtans_spmm_pallas` (x is (n,)); returns
    (G * N,) rows — the same kernel at B = 1."""
    return dtans_spmm_pallas(
        mats, x[:, None], params=params, pattern=pattern,
        max_nseg=max_nseg, lane_width=lane_width, interpret=interpret,
        pipeline=pipeline, shared_cols=shared_cols)[:, 0]
