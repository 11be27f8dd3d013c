"""Pure-jnp oracles for the dtANS kernels (no Pallas).

`spmv_ref` / `decode_ref` vmap the shared lock-step segment decoder over
the kernel's slice groups; the x gather and the contraction are plain
``jnp.take`` / ``jnp.sum``, independent of the kernel's.  They are
themselves validated against the numpy gold path
(`repro.core.csr_dtans.spmv_gold`), which in turn is validated against
the scalar big-int codec — a three-deep oracle chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import (DecodeArrays, bits_to_value, fori,
                                  init_state, lane_matrices, segment_step)
from repro.kernels.ops import out_dtype, packed_arrays
from repro.kernels.pack import PackedMatrix, slice_major


def _group_decode(stream, sbase, esc, ebase, nsegs, nnz, sym, meta, *,
                  params, pattern, max_nseg, lane_width, out_dtype, emit,
                  init):
    """Decode one slice group, folding ``emit(j, cols, vals, valid, acc)``
    over its segments."""
    tri, grp = lane_matrices(nsegs.shape[1], lane_width)
    arr = DecodeArrays(stream=stream, stream_base=sbase, esc=esc,
                       esc_base=ebase, tab_symbol=sym, tab_meta=meta,
                       nsegs=nsegs, nnz=nnz, tri=tri, grp=grp)

    def body(j, carry):
        state, acc = carry
        state, cols, vbits, valid = segment_step(j, state, arr, params,
                                                 pattern)
        vals = [bits_to_value(v, out_dtype) for v in vbits]
        return state, emit(j, jnp.concatenate(cols), jnp.concatenate(vals),
                           jnp.concatenate(valid), acc)

    _, acc = fori(max_nseg, body, (init_state(arr, params), init))
    return acc


def _vmap_groups(f, mats):
    return jax.vmap(f, in_axes=(0, 0, 1, 1, 0, 0, None, None))(*mats)


@functools.partial(jax.jit, static_argnames=("params", "pattern",
                                             "max_nseg", "lane_width"))
def _spmv_ref_jit(mats, x, y, *, params, pattern, max_nseg, lane_width):
    n = x.shape[0]
    N = mats[4].shape[2]

    def emit(j, cols, vals, valid, acc):          # (h, N) each
        xg = jnp.take(x, jnp.clip(cols, 0, n - 1), axis=0)
        # The barrier rounds each product before the sum, as the kernel
        # does: XLA would otherwise fuse product and sum into FMAs.
        prods = jax.lax.optimization_barrier(vals * xg)
        return acc + jnp.sum(jnp.where(valid, prods, 0), axis=0)

    f = functools.partial(_group_decode, params=params, pattern=pattern,
                          max_nseg=max_nseg, lane_width=lane_width,
                          out_dtype=x.dtype, emit=emit,
                          init=jnp.zeros((N,), x.dtype))
    return y + _vmap_groups(f, mats).reshape(-1)[:y.shape[0]]


def spmv_ref(pm: PackedMatrix, x: np.ndarray,
             y: np.ndarray | None = None) -> jax.Array:
    """Oracle y = A x + y with on-the-fly dtANS decode (pure jnp)."""
    dt = out_dtype(pm)
    if y is None:
        y = jnp.zeros((pm.shape[0],), dtype=dt)
    return _spmv_ref_jit(packed_arrays(pm), jnp.asarray(x, dtype=dt),
                         jnp.asarray(y, dtype=dt), params=pm.params,
                         pattern=pm.pattern, max_nseg=pm.max_nseg,
                         lane_width=pm.lane_width)


def decode_ref(pm: PackedMatrix) -> tuple[jax.Array, jax.Array]:
    """Oracle decompression: (cols, vals) as (S, L, max_nnz) padded arrays
    (cols == -1 marks padding)."""
    dt = out_dtype(pm)
    h = pm.params.l // 2
    N = pm.lanes
    max_nnz = pm.max_nseg * h

    def emit(j, cols, vals, valid, acc):
        out_c, out_v = acc
        out_c = jax.lax.dynamic_update_slice(
            out_c, jnp.where(valid, cols, -1).T, (jnp.int32(0), j * h))
        out_v = jax.lax.dynamic_update_slice(
            out_v, jnp.where(valid, vals, 0).T, (jnp.int32(0), j * h))
        return out_c, out_v

    f = functools.partial(_group_decode, params=pm.params,
                          pattern=pm.pattern, max_nseg=pm.max_nseg,
                          lane_width=pm.lane_width, out_dtype=dt, emit=emit,
                          init=(jnp.zeros((N, max_nnz), jnp.int32),
                                jnp.zeros((N, max_nnz), dt)))
    cols, vals = jax.jit(lambda m: _vmap_groups(f, m))(packed_arrays(pm))
    return slice_major(pm, cols), slice_major(pm, vals)

