"""Column tiling of the multi-RHS (SpMM) kernels — the grid-blocked
execution layer.

The PR-5 SpMM kernels hold ALL B columns of x (and the slice's y rows)
in VMEM per program: fine for serving pools (B in the tens), a capacity
wall for training-shaped B in the thousands.  This module blocks the
RHS dimension into ``bn``-column tiles so one program touches an
``(n, bn)`` x tile and a ``(rows, bn)`` y tile:

* `choose_bn` picks the widest tile whose x+y columns fit a VMEM
  budget (`DEFAULT_VMEM_BYTES` x `TILE_FRACTION`), rounded down to the
  TPU lane width; ``None`` means the whole batch fits — the untiled
  kernel IS the fast path and tiling must not tax it.
* `blocked_spmm` drives a family's kernel over the column tiles in one
  of two equivalent schedules:

  - ``grid``: a 2-D pallas grid ``(slice s, column block j)`` — the
    TPU-native layout; matrix blocks re-index by ``s`` only, the x
    BlockSpec walks ``j``, and Mosaic's automatic block double-
    buffering prefetches tile ``j+1`` while ``j`` contracts.
  - ``loop``: ``lax.map`` over column tiles around the 1-D-grid
    pallas_call — the same blocked computation with J x fewer grid
    programs, which is what interpret mode (this CPU container) wants:
    its per-program emulation overhead scales with program count.

  ``tile_mode="auto"`` resolves to ``loop`` under ``interpret=True``
  and ``grid`` when compiled.

Bit-identity contract: tiling splits only the B axis.  Every output
column sees exactly the per-column arithmetic of the untiled kernel
(same decode, same gather, same accumulation order), so blocked
results are REQUIRED to be bitwise equal to the unblocked kernels at
every ``bn`` — the conformance suite pins both schedules with exact
``==``.  Ragged tails zero-pad x to ``J*bn`` columns and slice back.

The pure sizing helpers (`choose_bn` / `n_col_tiles`) are numpy-free
and jax-free so `repro.autotune.cost_model` can price tiling without
importing the kernel stack.
"""

from __future__ import annotations

#: Stand-in for one v5e core's usable VMEM (the real core has 128 MiB
#: CMEM + ~16 MiB VMEM-class scratch; the kernels' matrix blocks and
#: coding tables also live there, hence `TILE_FRACTION` below).
DEFAULT_VMEM_BYTES = 16 * 2 ** 20

#: Fraction of the VMEM budget the x/y column tiles may claim; the
#: rest holds the program's matrix block (stream + tables / indices).
TILE_FRACTION = 0.5

#: TPU lane width — tile widths snap down to a multiple of this when
#: they can, so the minor dimension stays register-aligned.
LANE = 128

#: Floor tile width: below this the per-tile overhead dwarfs the work.
MIN_BN = 8


def choose_bn(n: int, rows: int, batch: int, itemsize: int,
              vmem_bytes: int | float | None = None) -> int | None:
    """Widest column-tile width ``bn`` whose x tile ``(n, bn)`` plus y
    tile ``(rows, bn)`` fit the VMEM tile budget, or ``None`` when the
    whole batch fits (untiled is the fast path).  Pure arithmetic — no
    jax — shared by the kernels and the cost model."""
    if batch <= 0:
        return None
    budget = (vmem_bytes if vmem_bytes is not None
              else DEFAULT_VMEM_BYTES) * TILE_FRACTION
    per_col = (int(n) + int(rows)) * int(itemsize)
    if per_col <= 0:
        return None
    bn = int(budget // per_col)
    if bn >= batch:
        return None
    if bn >= LANE:
        bn = (bn // LANE) * LANE
    return max(bn, MIN_BN)


def n_col_tiles(n: int, rows: int, batch: int, itemsize: int,
                vmem_bytes: int | float | None = None) -> int:
    """Number of column tiles one SpMM pass runs at batch ``batch`` —
    the multiplier on per-tile matrix traffic and decode work that
    `cost_model.spmm_bytes` / `cost_model.work_time` charge."""
    bn = choose_bn(n, rows, batch, itemsize, vmem_bytes)
    return 1 if bn is None else -(-int(batch) // bn)


def resolve_tile_mode(tile_mode: str, interpret: bool) -> str:
    """``auto`` -> ``loop`` in interpret mode (program-count-bound),
    ``grid`` compiled (Mosaic double-buffers the 2-D grid's x tiles)."""
    if tile_mode == "auto":
        return "loop" if interpret else "grid"
    if tile_mode not in ("grid", "loop"):
        raise ValueError(f"tile_mode must be 'auto', 'grid' or 'loop'; "
                         f"got {tile_mode!r}")
    return tile_mode


def blocked_spmm(kernel, mat_args, mat_specs, x, *, lanes: int,
                 grid_s: int, bn: int | None, tile_mode: str = "auto",
                 interpret: bool | None = None):
    """Run a family's SpMM kernel over ``bn``-column tiles of ``x``.

    ``mat_specs`` is a list of ``(block_shape, index_map)`` pairs for
    the matrix operands, with 1-D (program-only) index maps — the helper
    lifts them to the 2-D grid itself.  The kernel sees ``x`` transposed,
    as a ``(Bt, n)`` tile, and writes a ``(Bt, lanes)`` block of output
    rows; the result is ``(grid_s * lanes, B)`` rows.

    ``bn=None`` (or ``bn >= B``) is the untiled single-tile call.  A
    tile is a multiple of 8 columns (the TPU sublane tiling), so ``bn``
    rounds up to one.  ``interpret=None`` follows the backend
    (`repro.backend.interpret_mode`).
    """
    from repro.backend import interpret_mode
    from repro.kernels.common import kernel_x64

    if interpret is None:
        interpret = interpret_mode()
    with kernel_x64(interpret):
        return _blocked_spmm(kernel, mat_args, mat_specs, x, lanes=lanes,
                             grid_s=grid_s, bn=bn, tile_mode=tile_mode,
                             interpret=interpret)


def _blocked_spmm(kernel, mat_args, mat_specs, x, *, lanes, grid_s, bn,
                  tile_mode, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels.common import LANES, X_CHUNK

    n, B = x.shape
    S = int(grid_s)
    xt = x.T                                              # (B, n)
    if n > X_CHUNK and n % LANES:   # gather_mul slices x in 128-aligned chunks
        xt = jnp.pad(xt, ((0, 0), (0, LANES - n % LANES)))
        n = xt.shape[1]

    def call(xt, bt, J):
        if J == 1:
            lift = lambda f: f                            # noqa: E731
            x_spec = pl.BlockSpec((bt, n), lambda s: (0, 0))
            out_spec = pl.BlockSpec((None, bt, lanes), lambda s: (s, 0, 0))
        else:
            lift = lambda f: lambda s, j: f(s)            # noqa: E731
            x_spec = pl.BlockSpec((bt, n), lambda s, j: (j, 0))
            out_spec = pl.BlockSpec((None, bt, lanes),
                                    lambda s, j: (s, j, 0))
        in_specs = [pl.BlockSpec(shape, lift(fn)) for shape, fn in mat_specs]
        return pl.pallas_call(
            kernel,
            grid=(S,) if J == 1 else (S, J),
            in_specs=in_specs + [x_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((S, xt.shape[0], lanes),
                                           xt.dtype),
            interpret=interpret,
        )(*mat_args, xt)

    bn = None if bn is None else -(-int(bn) // 8) * 8
    if bn is None or bn >= B:
        y = call(xt, B, 1)
    else:
        J = -(-B // bn)
        if B % bn:
            xt = jnp.pad(xt, ((0, J * bn - B), (0, 0)))
        if resolve_tile_mode(tile_mode, interpret) == "loop":
            ys = jax.lax.map(lambda xj: call(xj, bn, 1),
                             xt.reshape(J, bn, n))        # (J, S, bn, N)
            y = jnp.moveaxis(ys, 0, 1).reshape(S, J * bn, lanes)
        else:
            y = call(xt, bn, J)
    return jnp.transpose(y[:, :B, :], (0, 2, 1)).reshape(S * lanes, B)
