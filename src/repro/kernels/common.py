"""Shared jnp building blocks for the dtANS decode kernels.

`segment_step` is the lock-step decode of ONE segment across all lanes —
the same function is traced by the pure-jnp oracle (ref.py) and by the
Pallas kernel bodies (dtans_spmv.py / dtans_decode.py), so the kernel and
its oracle cannot drift apart.

Layout: one kernel program decodes ``P`` consecutive slices of ``L``
lanes each, ``N = P * L`` lanes in all (``N == 128`` — one vreg row — for
every lane width that divides 128).  Every per-lane quantity is a
``(1, N)`` row; streams, escape streams and coding tables are ``(R, 128)``
row blocks with ``R`` a multiple of 8, so every block meets the TPU's
(8, 128) tiling.

Everything here lowers to Mosaic (TPU Pallas):

* Integers are 32-bit.  The decoder state ``d`` (and radix ``r``) is
  three uint32 limbs; a limb multiply takes the high half of the 64-bit
  product from 16-bit halves — the paper's ``mul.lo`` / ``__umul_hi``
  pair.  Digits are accumulated in groups whose radix product stays
  below 2^32 ("accumulate returned digits into a digit/base pair",
  paper Section IV-F) and folded into the limbs once per group.
* Gathers (stream claims, table lookups, escape claims) are in-tile
  ``take_along_axis`` over each (8, 128) block, selected across blocks
  (`lookup`).  ``value * x[col]`` is ``x`` times a one-hot matrix that
  carries the value, on the MXU at ``HIGHEST`` precision (`gather_mul`).
* Claim ranks (the warp ballot + popc of the paper) are a triangular
  ones matmul over the lanes, segmented per slice; counts are <= 128,
  so f32 holds them exactly.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.params import DtansParams

#: Row width of every stream / table block (the TPU lane count).
LANES = 128

#: Rows of the one-hot block `gather_mul` builds per MXU product.
X_CHUNK = 2048

#: Largest ``x`` length the compiled one-hot gather serves: beyond it the
#: statically unrolled chunks stop paying (`repro.kernels.ops` raises).
MAX_X_ROWS = 8 * X_CHUNK

# ``tab_meta`` bit layout: digit | base << 15 | is_escape << 31.
META_DIGIT_MASK = 0x7FFF
META_BASE_SHIFT = 15
META_BASE_MASK = 0xFFFF
META_ESC_SHIFT = 31


def fori(n: int, body, init):
    """``lax.fori_loop(0, n)`` with an int32 index: kernel code stays
    32-bit even where the process enables x64 (Mosaic has no 64-bit
    integers)."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, init)


def kernel_x64(interpret: bool):
    """Context in which to trace a kernel.  Compiled, x64 is off: Python
    constants in kernel bodies and index maps then trace as 32-bit, which
    is all Mosaic accepts, even where the process enables x64 (compiled
    kernels have no 64-bit operands).  Interpreted, the process's own
    setting stands, so float64 matrices keep working on a CPU host."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def _f32(mask):
    return jnp.where(mask, jnp.float32(1), jnp.float32(0))


def slices_per_program(lane_width: int) -> int:
    """Slices one program decodes side by side: enough to fill 128
    lanes when the lane width divides 128, else one."""
    L = int(lane_width)
    return LANES // L if L <= LANES and LANES % L == 0 else 1


class DecodeArrays(NamedTuple):
    """One program's arrays, already loaded from VMEM."""
    stream: jax.Array       # (Rw, 128) uint32 — the program's slices' words
    stream_base: jax.Array  # (1, N) int32 — lane's slice offset in stream
    esc: jax.Array          # (T, Re, 128) symbol words
    esc_base: jax.Array     # (T, 1, N) int32
    tab_symbol: jax.Array   # (T, Rk, 128) symbol per slot
    tab_meta: jax.Array     # (T, Rk, 128) uint32 digit/base/escape bits
    nsegs: jax.Array        # (1, N) int32 — segments per lane
    nnz: jax.Array          # (1, N) int32 — nonzeros per lane
    tri: jax.Array          # (N, N) f32 — inclusive same-slice prefix
    grp: jax.Array          # (N, N) f32 — same-slice indicator


class DecodeState(NamedTuple):
    w: tuple             # o x (1, N) uint32 — unpack words
    d: tuple             # 3 x (1, N) uint32 limbs, least significant first
    r: tuple             # 3 x (1, N) uint32 limbs
    cursor: jax.Array    # (1, N) int32 — slice-common stream cursor
    esc_cur: tuple       # T x (1, N) int32
    col: jax.Array       # (1, N) int32 — running column per lane


def lane_matrices(n_lanes: int, lane_width: int):
    """``(tri, grp)`` for the segmented lane prefix sums: ``tri[i, j]``
    is 1 where lane ``i`` precedes or is lane ``j`` of the same slice,
    ``grp[i, j]`` where the two lanes share a slice."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 1)
    if n_lanes == lane_width:
        same = jnp.full((n_lanes, n_lanes), True)
    else:  # several slices per program: lane_width is a power of two
        sh = int(lane_width).bit_length() - 1
        same = (i >> sh) == (j >> sh)
    return _f32(same & (i <= j)), _f32(same)


def lookup(table, idx):
    """``table.reshape(-1)[idx]`` for a ``(R, 128)`` table (``R`` a
    multiple of 8) and in-range ``(1, N)`` int32 indices: an in-tile
    lane gather and sublane gather per (8, 128) block, then a select of
    the block each lane's row falls in."""
    row, col = idx >> 7, idx & (LANES - 1)
    n = idx.shape[1]
    colb = jnp.broadcast_to(col, (8, n))
    out = None
    for b in range(table.shape[0] // 8):
        g = jnp.take_along_axis(table[8 * b:8 * b + 8], colb, axis=1)
        rb = jnp.broadcast_to(jnp.clip(row - 8 * b, 0, 7), (8, n))
        v = jnp.take_along_axis(g, rb, axis=0)[0:1]
        out = v if out is None else jnp.where((row >> 3) == b, v, out)
    return out


def gather_mul(xt, col, val):
    """``val * xt[:, col]`` for ``xt: (B, n)`` and ``(1, N)`` columns and
    values (a column outside ``[0, n)`` contributes 0): ``xt`` times a
    one-hot matrix carrying ``val``, on the MXU in ``X_CHUNK``-row pieces.

    Each output is one product plus exact zeros (for finite ``x``), so it
    is rounded once, whatever the caller adds it to.  A separate multiply
    would not be: XLA:CPU fuses a multiply and the caller's add into an
    FMA in some fusions and not in others, which breaks the bit-identity
    of the schedules in interpret mode.  The final select is a no-op
    that keeps XLA:CPU from folding the caller's add into a
    matrix-vector product (its output fusion) for the same reason."""
    n = xt.shape[1]
    zero = jnp.zeros((), xt.dtype)
    val = val.astype(xt.dtype)
    out = None
    for c0 in range(0, n, X_CHUNK):
        cn = min(X_CHUNK, n - c0)
        rows = jax.lax.broadcasted_iota(jnp.int32, (cn, col.shape[1]), 0)
        oh = jnp.where(rows + c0 == col, val, zero)
        part = jnp.dot(xt[:, c0:c0 + cn] if cn < n else xt, oh,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=xt.dtype)
        out = part if out is None else out + part
    return jnp.where(col >= 0, out, zero)


def _ranks(take, arr: DecodeArrays):
    """Exclusive claim rank of each taking lane within its slice, and the
    slice's claim count broadcast to its lanes (ballot + popc)."""
    f = _f32(take)
    incl = jnp.dot(f, arr.tri, preferred_element_type=jnp.float32)
    tot = jnp.dot(f, arr.grp, preferred_element_type=jnp.float32)
    return incl.astype(jnp.int32) - 1, tot.astype(jnp.int32)


def _claim(words, base, cursor, take, arr: DecodeArrays):
    """Consumption-order claim: the lanes with ``take`` read consecutive
    words of their slice's stream starting at ``cursor``."""
    rank, total = _ranks(take, arr)
    idx = jnp.clip(base + cursor + rank, 0, words.size - 1)
    return lookup(words, idx), cursor + total


def _u32(v):
    return jnp.uint32(v)


def _mul_wide(a, b):
    """(lo, hi) 32-bit halves of the 64-bit product of uint32 ``a, b``."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return a * b, hi


def _limb_mul_add(d, m, a):
    """``d * m + a`` over three uint32 limbs (mod 2^96), ``m, a < 2^32``."""
    out, carry = [], a
    for limb in d:
        lo, hi = _mul_wide(limb, m)
        s = lo + carry
        carry = hi + jnp.where(s < carry, _u32(1), _u32(0))
        out.append(s)
    return tuple(out)


def _limb_ge_w(r, w_bits: int):
    hi = (r[1] > 0) | (r[2] > 0)
    if w_bits == 32:
        return hi
    return hi | ((r[0] >> w_bits) > 0)


def _limb_shr(d, w_bits: int):
    if w_bits == 32:
        return (d[1], d[2], jnp.zeros_like(d[2]))
    up = 32 - w_bits
    return ((d[0] >> w_bits) | (d[1] << up),
            (d[1] >> w_bits) | (d[2] << up), d[2] >> w_bits)


def _where3(c, a, b):
    return tuple(jnp.where(c, x, y) for x, y in zip(a, b))


def init_state(arr: DecodeArrays, params: DtansParams) -> DecodeState:
    live = arr.nsegs > 0
    zero = jnp.zeros(arr.nsegs.shape, jnp.int32)
    zu = jnp.zeros(arr.nsegs.shape, jnp.uint32)
    cursor = zero
    w = []
    for _ in range(params.o):
        words, cursor = _claim(arr.stream, arr.stream_base, cursor, live,
                               arr)
        w.append(jnp.where(live, words, zu))
    return DecodeState(
        w=tuple(w), d=(zu, zu, zu), r=(zu + 1, zu, zu), cursor=cursor,
        esc_cur=tuple(zero for _ in range(arr.esc.shape[0])), col=zero)


def segment_step(j, state: DecodeState, arr: DecodeArrays,
                 params: DtansParams, pattern: tuple):
    """Decode segment ``j`` on all lanes.

    Returns (new_state, cols, vals_bits, valid), each of the last three a
    list of ``l // 2`` rows of shape (1, N):
      cols      int32  — absolute column index per nonzero
      vals_bits        — raw value bit patterns (symbol dtype)
      valid     bool   — nonzero exists (tail masking)
    """
    W_bits, K_bits = params.w_bits, params.k_bits
    l, o, f = params.l, params.o, params.f
    active = j < arr.nsegs

    # ---- unpack + table lookups (static unroll over l positions) --------
    wle = state.w[::-1]  # little-endian word view
    syms, digs, bass = [], [], []
    esc_cur = list(state.esc_cur)
    for k in range(l):
        lo = k * K_bits
        wi, sh = lo // W_bits, lo % W_bits
        v = wle[wi] >> sh if sh else wle[wi]
        if wi + 1 < o and sh + K_bits > W_bits:
            v = v | (wle[wi + 1] << (W_bits - sh))
        slot = (v & (params.K - 1)).astype(jnp.int32)
        t = pattern[k]
        sym = lookup(arr.tab_symbol[t], slot)
        meta = lookup(arr.tab_meta[t], slot)
        is_esc = ((meta >> META_ESC_SHIFT) > 0) & active
        rank, total = _ranks(is_esc, arr)
        esc_t = arr.esc[t]
        esym = lookup(esc_t, jnp.clip(arr.esc_base[t] + esc_cur[t] + rank,
                                      0, esc_t.size - 1))
        syms.append(jnp.where(is_esc, esym, sym))
        esc_cur[t] = esc_cur[t] + total
        digs.append(jnp.where(active, meta & META_DIGIT_MASK, _u32(0)))
        bass.append(jnp.where(
            active, (meta >> META_BASE_SHIFT) & META_BASE_MASK, _u32(1)))

    # ---- positions: even = delta, odd = value bits -----------------------
    cols, vals_bits, valid = [], [], []
    col = state.col
    for i in range(l // 2):
        q = j * (l // 2) + i                      # nonzero index in row
        ok = (q < arr.nnz) & active
        col = col + jnp.where(ok, syms[2 * i].astype(jnp.int32), 0)
        cols.append(col)
        vals_bits.append(syms[2 * i + 1])
        valid.append(ok)

    # ---- fold digits into limb state (group radix < 2^32) ---------------
    d, r = state.d, state.r
    g = max(1, 31 // params.m_bits)
    zu = jnp.zeros_like(d[0])
    for g0 in range(0, l, g):
        gacc, racc = zu, zu + 1
        for k in range(g0, min(g0 + g, l)):
            gacc = gacc * bass[k] + digs[k]
            racc = racc * bass[k]
        d = _limb_mul_add(d, racc, gacc)
        r = _limb_mul_add(r, racc, zu)

    # ---- refill ----------------------------------------------------------
    refill = active & (j < arr.nsegs - 1)
    w = list(state.w)
    cursor = state.cursor
    for k in range(o):
        if k < f:
            cond = _limb_ge_w(r, W_bits) & refill
            wk = d[0] if W_bits == 32 else d[0] & (params.W - 1)
            d = _where3(cond, _limb_shr(d, W_bits), d)
            r = _where3(cond, _limb_shr(r, W_bits), r)
            popl = refill & ~cond
        else:
            wk = zu
            popl = refill
        popped, cursor = _claim(arr.stream, arr.stream_base, cursor, popl,
                                arr)
        wk = jnp.where(popl, popped, wk)
        w[k] = jnp.where(refill, wk, w[k])

    new_state = DecodeState(w=tuple(w), d=d, r=r, cursor=cursor,
                            esc_cur=tuple(esc_cur), col=col)
    return new_state, cols, vals_bits, valid


def bits_to_value(bits: jax.Array, dtype) -> jax.Array:
    """Reinterpret raw symbol bits (uint32, or uint64 for float64) as
    values."""
    if dtype == jnp.float64:
        return jax.lax.bitcast_convert_type(bits, jnp.float64)
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(
            bits.astype(jnp.uint32), jnp.float32)
    raise TypeError(f"unsupported dtype {dtype}")
