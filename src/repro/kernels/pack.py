"""Pack a CSRdtANS matrix into dense, kernel-ready tensors.

The production format stores one flat stream with per-slice offsets. The
Pallas kernel wants *static* block shapes that meet the TPU's (8, 128)
tiling, so the pack groups ``P`` consecutive slices per kernel program
(`repro.kernels.common.slices_per_program`: 128 lanes in all when the
lane width divides 128), cuts each group's contiguous run of the flat
stream (and of every escape stream) into rows of 128 words, and pads the
rows to the matrix-wide maximum, a multiple of 8.  Each lane carries its
slice's word offset inside its group (``stream_base`` / ``esc_base``).
The padding is address padding only — it is NOT counted in the format's
compressed size (CSRdtANS.nbytes), exactly like the paper's kernels,
which DMA whole cache lines regardless of stream length.

Every integer is 32-bit: stream words are uint32 (they are < 2^32 by
construction), symbols are uint32 for float32 matrices (float64 symbols
need uint64 and run on CPU only), and a slot's digit, base and escape
flag pack into one uint32 ``tab_meta`` word.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.csr_dtans import CSRdtANS
from repro.core.params import DtansParams
from repro.kernels.common import (LANES, META_BASE_SHIFT, META_ESC_SHIFT,
                                  slices_per_program)


@dataclasses.dataclass
class PackedMatrix:
    """Kernel-ready CSR-dtANS, G programs of N = P * L lanes. All arrays
    are numpy; ops.py moves them to jnp."""
    stream: np.ndarray       # (G, Rw, 128) uint32 — group word streams
    stream_base: np.ndarray  # (G, 1, N) int32 — lane's slice offset
    esc: np.ndarray          # (T, G, Re, 128) symbols — escape streams
    esc_base: np.ndarray     # (T, G, 1, N) int32
    nsegs: np.ndarray        # (G, 1, N) int32 — segments per lane
    nnz: np.ndarray          # (G, 1, N) int32 — nonzeros per lane
    tab_symbol: np.ndarray   # (T, Rk, 128) symbols per slot
    tab_meta: np.ndarray     # (T, Rk, 128) uint32 digit|base|escape
    pattern: tuple          # static, length l
    params: DtansParams     # static
    shape: tuple
    dtype: np.dtype
    lane_width: int
    max_nseg: int           # static loop bound
    # Block-filled encode (BCSR-dtANS at lane_width == block height):
    # every in-bounds lane of a slice decodes the SAME column sequence,
    # so the fused shared-column contraction applies (ops.spmv/spmm
    # ``fused=`` knob; see dtans_spmv.py ``shared_cols``).
    shared_cols: bool = False

    @property
    def lanes(self) -> int:
        """Lanes per kernel program (N)."""
        return int(self.nsegs.shape[2])


def _rows(n_words: int) -> int:
    """Rows of 128 words holding ``n_words``, rounded up to 8 (>= 8)."""
    rows = -(-int(n_words) // LANES)
    return max(8, -(-rows // 8) * 8)


def _group_rows(flat: np.ndarray, offsets: np.ndarray, starts, dtype):
    """Cut ``flat`` at group boundaries into ``(G, R, 128)`` rows."""
    lens = offsets[starts[1:]] - offsets[starts[:-1]]
    R = _rows(lens.max() if lens.size else 0)
    out = np.zeros((len(lens), R * LANES), dtype=dtype)
    for g in range(len(lens)):
        lo = offsets[starts[g]]
        out[g, :lens[g]] = flat[lo:lo + lens[g]]
    return out.reshape(len(lens), R, LANES)


def _lane_base(offsets: np.ndarray, starts, S: int, P: int, L: int):
    """(G, 1, P*L) word offset of each lane's slice within its group."""
    G = len(starts) - 1
    sl = np.minimum(np.arange(G * P).reshape(G, P), S)
    base = offsets[sl] - offsets[starts[:-1]][:, None]
    return np.repeat(base, L, axis=1).astype(np.int32)[:, None, :]


def _table_rows(t: np.ndarray, dtype) -> np.ndarray:
    T, K = t.shape
    R = _rows(K)
    out = np.zeros((T, R * LANES), dtype=dtype)
    out[:, :K] = t
    return out.reshape(T, R, LANES)


def pack_matrix(mat: CSRdtANS) -> PackedMatrix:
    S = mat.n_slices
    L = mat.lane_width
    T = len(mat.tables)
    l = mat.params.l
    m = mat.shape[0]
    P = slices_per_program(L)
    G = max(1, -(-S // P))
    N = P * L
    starts = np.minimum(np.arange(G + 1) * P, S)

    if mat.params.m_bits >= META_BASE_SHIFT:
        raise ValueError(f"m_bits={mat.params.m_bits} does not fit the "
                         f"{META_BASE_SHIFT}-bit digit field of tab_meta")
    sym_dtype = np.uint64 if np.dtype(mat.dtype) == np.float64 \
        else np.uint32
    st = mat.stacked
    if sym_dtype == np.uint32 and (
            int(st.symbol.max(initial=0)) >> 32
            or any(int(e.max(initial=0)) >> 32 for e in mat.esc_streams)):
        raise ValueError("float32 matrix carries symbols >= 2^32")

    stream = _group_rows(mat.stream, mat.slice_offsets, starts, np.uint32)
    stream_base = _lane_base(mat.slice_offsets, starts, S, P, L)
    esc = np.stack([_group_rows(mat.esc_streams[t], mat.esc_offsets[:, t],
                                starts, sym_dtype) for t in range(T)])
    Re = max(e.shape[1] for e in esc)
    esc = np.stack([np.pad(e, ((0, 0), (0, Re - e.shape[1]), (0, 0)))
                    for e in esc])
    esc_base = np.stack([_lane_base(mat.esc_offsets[:, t], starts, S, P, L)
                         for t in range(T)])

    nnz = np.zeros(G * N, dtype=np.int32)
    nnz[:m] = mat.row_nnz
    nsegs = (2 * nnz + l - 1) // l
    max_nseg = max(int(nsegs.max()) if S else 0, 1)

    meta = (st.digit.astype(np.uint32)
            | (st.base.astype(np.uint32) << META_BASE_SHIFT)
            | (st.is_esc.astype(np.uint32) << META_ESC_SHIFT))

    return PackedMatrix(
        stream=stream,
        stream_base=stream_base,
        esc=esc,
        esc_base=esc_base,
        nsegs=nsegs.astype(np.int32).reshape(G, 1, N),
        nnz=nnz.reshape(G, 1, N),
        tab_symbol=_table_rows(st.symbol, sym_dtype),
        tab_meta=_table_rows(meta, np.uint32),
        pattern=tuple(int(p) for p in mat.pattern),
        params=mat.params,
        shape=mat.shape,
        dtype=np.dtype(mat.dtype),
        lane_width=L,
        max_nseg=max_nseg,
        # BCSRdtANS (the only block-filled encode) carries block_shape;
        # duck-typed so pack.py needs no core.bcsr_dtans import.
        shared_cols=getattr(mat, "block_shape", None) is not None,
    )


def slice_major(pm: PackedMatrix, a):
    """(G, N, k) group-major rows -> (S, L, k) slice-major rows."""
    L = pm.lane_width
    S = -(-pm.shape[0] // L)
    return a.reshape(-1, L, a.shape[-1])[:S]
