"""Public jit'd entry points for the kernels.

`spmv` is the user-facing  y = A x + y  on a CSR-dtANS matrix: it packs the
format once (cached on the object), moves tensors to device, and dispatches
to the fused Pallas kernel — interpreted on a CPU host, compiled on a TPU
(`repro.backend.interpret_mode`; any other backend raises).  What the
compiled kernels cannot serve raises on TPU instead of falling back:
float64 matrices, lane widths that do not divide 128, and ``x`` longer
than `repro.kernels.common.MAX_X_ROWS`.

Every single-vector entry point has a multi-RHS sibling (`spmm`,
`sell_spmm`, `rgcsr_spmm`, `bcsr_spmm`): ``x`` is (n, B), the result
(m, B), and the matrix (for the dtANS family: the *decode*) is paid once
for all B columns — the batched serving path `SparseLinear.apply`
routes through. All eight share the ``(mat, x, y=None)`` signature;
B == 1 delegates to the single-vector kernel, so spmm results at B=1
are bit-identical to spmv.

`spmv` / `spmm` additionally take ``mesh=`` / ``n_shards=``: with more
than one shard the matrix is row-partitioned along decode-slice
boundaries (`repro.sparse.shard`, cached on the object like the packed
artifact) and executed by `repro.kernels.shard_ops` — `shard_map` +
psum over the mesh ``model`` axis, or a sequential per-shard loop when
no mesh is given.  Results are bit-identical to the single-device
kernels at every shard count, and shards == 1 IS the single-device
path (no plan is built).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.backend import interpret_mode
from repro.core.csr_dtans import CSRdtANS
from repro.kernels import tiling
from repro.kernels.bcsr_spmv import PackedBCSR, bcsr_spmm_pallas
from repro.kernels.common import LANES, MAX_X_ROWS
from repro.kernels.dtans_decode import dtans_decode_pallas
from repro.kernels.dtans_spmv import dtans_spmm_pallas, dtans_spmv_pallas
from repro.kernels.pack import PackedMatrix, pack_matrix, slice_major
from repro.kernels.rgcsr_spmv import PackedRGCSR, rgcsr_spmm_pallas
from repro.kernels.sell_spmv import PackedSELL, sell_spmm_pallas

_PACK_CACHE_FIELD = "_packed_cache"
_OBS_NBYTES_FIELD = "_obs_nbytes"
_SHARD_PLAN_FIELD = "_shard_plans"


def _packed_nbytes(pm) -> int:
    """Total bytes of every ndarray field of a packed artifact — the
    matrix-side traffic one kernel pass DMAs (padded kernel-ready
    tensors, not the compressed wire size; the kernels move whole
    padded slices exactly like the paper's cache-line DMA). Memoized on
    the object: the hot path must not re-walk fields per call."""
    b = getattr(pm, _OBS_NBYTES_FIELD, None)
    if b is None:
        b = sum(int(v.nbytes) for v in vars(pm).values()
                if isinstance(v, np.ndarray))
        object.__setattr__(pm, _OBS_NBYTES_FIELD, b)
    return b


def _record_pass(kind: str, pm, n: int, m: int, batch: int,
                 itemsize: int, *, decodes: bool = False,
                 col_tiles: int = 1) -> None:
    """One SpMV/SpMM pass into the default metrics registry: call and
    byte counters (matrix once per pass, x/y per RHS) plus the
    batch-size histogram. `spmm` entry points delegate B == 1 to their
    spmv sibling, so exactly one record happens per pass.

    The byte counters are PER PASS, never per column tile: a blocked
    pass (``col_tiles > 1``) records x/y bytes exactly once — each RHS
    column still enters and leaves the chip once however the B axis is
    tiled — so tiled and untiled runs of the same workload stay
    byte-comparable.  The tile count itself lands in its own
    histogram (the re-streamed matrix traffic a tiled pass pays is
    what the cost model's ``col_tiles`` term prices)."""
    r = obs.default_registry()
    r.counter("kernels.spmm_calls").add(1)
    r.counter(f"kernels.{kind}_calls").add(1)
    if decodes:
        r.counter("kernels.decode_invocations").add(1)
    r.counter("kernels.matrix_bytes").add(_packed_nbytes(pm))
    r.counter("kernels.x_bytes").add(n * batch * itemsize)
    r.counter("kernels.y_bytes").add(m * batch * itemsize)
    r.histogram("kernels.batch_size").observe(batch)
    r.histogram("kernels.col_tiles").observe(col_tiles)


def _resolve_bn(n: int, rows: int, batch: int, itemsize: int,
                bn, vmem_budget) -> int | None:
    """Effective column-tile width of one SpMM pass: an explicit ``bn``
    wins (clamped to untiled when it covers the whole batch); otherwise
    the VMEM-budget auto choice (`repro.kernels.tiling.choose_bn`,
    ``vmem_budget=None`` = the default budget)."""
    if bn is not None:
        b = int(bn)
        if b < 1:
            raise ValueError(f"bn must be >= 1; got {bn}")
        return None if b >= batch else b
    return tiling.choose_bn(n, rows, batch, itemsize, vmem_budget)


def _n_tiles(batch: int, bn: int | None) -> int:
    return 1 if bn is None else -(-batch // bn)


def out_dtype(pm: PackedMatrix):
    """Accumulator dtype of the decode kernels for a packed matrix."""
    return jnp.float64 if pm.dtype == np.float64 else jnp.float32


def check_compiled(kind: str, dtype, n: int, lanes: int = LANES) -> None:
    """Raise for what the compiled (TPU) kernels cannot serve; nothing
    to check in interpret mode."""
    if interpret_mode():
        return
    if np.dtype(dtype) == np.float64:
        raise TypeError(f"{kind}: float64 matrices have no TPU kernel "
                        "(the chip has no 64-bit floats); use float32")
    if lanes != LANES:
        raise ValueError(f"{kind}: a kernel program spans {lanes} lanes; "
                         f"the TPU kernel needs {LANES} (a lane width "
                         f"that divides {LANES})")
    if n > MAX_X_ROWS:
        raise ValueError(f"{kind}: x has {n} rows; the compiled x gather "
                         f"serves at most {MAX_X_ROWS}")


def get_packed(mat: CSRdtANS) -> PackedMatrix:
    pm = getattr(mat, _PACK_CACHE_FIELD, None)
    if pm is None:
        pm = pack_matrix(mat)
        object.__setattr__(mat, _PACK_CACHE_FIELD, pm)
    return pm


def packed_arrays(pm: PackedMatrix):
    """The kernel operands of a pack on the device, in `PackedMatrix`
    field order. The pack holds them on the host, so every call copies
    them all (`_packed_nbytes`): counted in ``kernels.h2d_bytes`` and
    timed as the ``kernels.upload`` span."""
    nbytes = _packed_nbytes(pm)
    obs.default_registry().counter("kernels.h2d_bytes").add(nbytes)
    with obs.span("kernels.upload", bytes=nbytes):
        return tuple(jnp.asarray(a) for a in (
            pm.stream, pm.stream_base, pm.esc, pm.esc_base, pm.nsegs,
            pm.nnz, pm.tab_symbol, pm.tab_meta))


def _statics(pm: PackedMatrix) -> dict:
    return dict(params=pm.params, pattern=pm.pattern, max_nseg=pm.max_nseg,
                lane_width=pm.lane_width)


def _resolve_shards(mesh, n_shards) -> int:
    """Shard count from the (mesh=, n_shards=) knobs: an explicit
    ``n_shards`` wins, else the mesh ``model`` axis, else 1."""
    if n_shards is not None:
        if int(n_shards) < 1:
            raise ValueError(f"n_shards must be >= 1; got {n_shards}")
        return int(n_shards)
    if mesh is not None:
        from repro.launch.mesh import model_axis_size
        return model_axis_size(mesh)
    return 1


def get_shard_plan(mat: CSRdtANS, n_shards: int):
    """The ``n_shards``-way shard plan for a CSR-dtANS matrix, built
    through the registry seam at the matrix's own encode knobs and
    cached on the object (one plan per shard count) like `get_packed`.
    Decode is lossless, so re-encoding each row block at the same
    ``lane_width`` reproduces the single-device decode values exactly."""
    plans = getattr(mat, _SHARD_PLAN_FIELD, None)
    if plans is None:
        plans = {}
        object.__setattr__(mat, _SHARD_PLAN_FIELD, plans)
    plan = plans.get(n_shards)
    if plan is None:
        from repro.core.csr_dtans import decode_matrix
        from repro.sparse.registry import get_format
        plan = get_format("dtans").shard(
            decode_matrix(mat), n_shards, params=mat.params,
            lane_width=mat.lane_width,
            shared_table=len(mat.tables) == 1)
        plans[n_shards] = plan
    return plan


def _sharded_dtans(mat, x, y, *, mesh, k, spmm: bool, bn=None,
                   pipeline: bool = False):
    from repro.kernels import shard_ops
    if not isinstance(mat, CSRdtANS):
        raise TypeError(
            "sharded spmv/spmm needs the CSRdtANS matrix (a bare packed "
            "artifact carries no bitstream to re-partition); pass the "
            "matrix object or shards=1")
    plan = get_shard_plan(mat, k)
    if spmm:
        return shard_ops.shard_spmm(plan, x, y=y, mesh=mesh, bn=bn,
                                    pipeline=pipeline)
    return shard_ops.shard_spmv(plan, x, y=y, mesh=mesh, pipeline=pipeline)


def _resolve_fused(pm: PackedMatrix, fused) -> bool:
    """Whether this pass runs the shared-column (fused block-decode)
    contraction: ``fused=None`` follows the pack's ``shared_cols``
    flag (BCSR-dtANS encodes fuse, everything else doesn't);
    ``fused=False`` forces the generic path (the benchmark comparator);
    ``fused=True`` on a non-block-filled pack is an error — lanes with
    distinct columns cannot share lane 0's gather."""
    shared = bool(getattr(pm, "shared_cols", False))
    if fused is None:
        return shared
    if fused and not shared:
        raise ValueError(
            "fused=True needs a block-filled (shared-column) pack — "
            "only BCSR-dtANS encodes set PackedMatrix.shared_cols")
    return bool(fused)


def _add_y(out, y):
    return out if y is None else out + jnp.asarray(y, dtype=out.dtype)


def spmv(mat: CSRdtANS | PackedMatrix, x, y=None, *, mesh=None,
         n_shards=None, pipeline: bool = False, fused=None) -> jax.Array:
    """y = A x + y with on-the-fly dtANS decoding (fused Pallas kernel).

    With ``mesh=`` (model axis > 1) or ``n_shards= > 1`` the matrix is
    row-partitioned along decode-slice boundaries and each device
    decodes only its shard (`repro.kernels.shard_ops`); results stay
    bit-identical to the single-device kernel.

    ``pipeline=True`` overlaps each segment's decode with the previous
    segment's contraction; ``fused`` selects the shared-column
    block-decode contraction (default: the pack's own ``shared_cols``
    flag).  Both preserve bit-identity (docs/kernels.md)."""
    k = _resolve_shards(mesh, n_shards)
    if k > 1:
        return _sharded_dtans(mat, x, y, mesh=mesh, k=k, spmm=False,
                              pipeline=pipeline)
    pm = get_packed(mat) if isinstance(mat, CSRdtANS) else mat
    shared = _resolve_fused(pm, fused)
    dt = out_dtype(pm)
    m, n = pm.shape
    check_compiled("dtans_spmv", pm.dtype, n, pm.lanes)
    _record_pass("dtans_spmv", pm, n, m, 1, pm.dtype.itemsize,
                 decodes=True)
    acc = dtans_spmv_pallas(packed_arrays(pm), jnp.asarray(x, dtype=dt),
                            pipeline=pipeline, shared_cols=shared,
                            **_statics(pm))
    return _add_y(acc[:m], y)


def _check_rhs(x, n: int) -> None:
    if x.ndim != 2:
        raise ValueError(f"spmm expects x of shape (n, B); got {x.shape} "
                         f"(use spmv for a single 1-D vector)")
    if x.shape[0] != n:
        raise ValueError(f"spmm rhs has {x.shape[0]} rows; matrix has "
                         f"{n} columns")


def _empty_y(m: int, y, dt):
    """B == 0 result: a serving pool with zero active requests is a
    legal input and must not reach the kernels (a zero-size grid
    dimension is not)."""
    return _add_y(jnp.zeros((m, 0), dtype=dt), y)


def spmm(mat: CSRdtANS | PackedMatrix, x, y=None, *, mesh=None,
         n_shards=None, bn=None, vmem_budget=None, tile_mode: str = "auto",
         pipeline: bool = False, fused=None) -> jax.Array:
    """Y = A X + Y, X: (n, B) — decode once, contract all B columns in
    the fused kernel. B == 1 runs the single-vector `spmv` kernel, so
    the results are bit-identical to it.  ``mesh=`` / ``n_shards=``
    shard the rows across devices exactly as in `spmv`.

    Tiling knobs (docs/kernels.md): ``bn`` pins the column-tile width
    (None = auto from ``vmem_budget``, untiled when the whole batch
    fits); ``tile_mode`` picks the blocked schedule (``"grid"`` = 2-D
    pallas grid, ``"loop"`` = lax.map column loop, ``"auto"`` = loop
    under interpret / grid compiled); ``pipeline`` overlaps decode with
    contraction; ``fused`` selects the shared-column block-decode
    contraction.  Every combination is bit-identical to the untiled
    serial kernel — the conformance suite pins them with exact ==."""
    k = _resolve_shards(mesh, n_shards)
    if k > 1:
        return _sharded_dtans(mat, x, y, mesh=mesh, k=k, spmm=True, bn=bn,
                              pipeline=pipeline)
    pm = get_packed(mat) if isinstance(mat, CSRdtANS) else mat
    shared = _resolve_fused(pm, fused)
    dt = out_dtype(pm)
    m, n = pm.shape
    x = jnp.asarray(x, dtype=dt)
    _check_rhs(x, n)
    if x.shape[1] == 0:
        return _empty_y(m, y, dt)
    if x.shape[1] == 1:
        return _add_y(spmv(pm, x[:, 0], pipeline=pipeline,
                           fused=fused)[:, None], y)
    check_compiled("dtans_spmm", pm.dtype, n, pm.lanes)
    B = x.shape[1]
    bn_eff = _resolve_bn(n, pm.lane_width, B, pm.dtype.itemsize, bn,
                         vmem_budget)
    _record_pass("dtans_spmm", pm, n, m, B, pm.dtype.itemsize,
                 decodes=True, col_tiles=_n_tiles(B, bn_eff))
    acc = dtans_spmm_pallas(packed_arrays(pm), x, bn=bn_eff,
                            tile_mode=tile_mode, pipeline=pipeline,
                            shared_cols=shared, **_statics(pm))
    return _add_y(acc[:m], y)


def decode(mat: CSRdtANS | PackedMatrix):
    """Decompress to padded (S, L, max_nnz) (cols, vals); cols==-1 pads."""
    pm = get_packed(mat) if isinstance(mat, CSRdtANS) else mat
    check_compiled("dtans_decode", pm.dtype, 0, pm.lanes)
    obs.default_registry().counter("kernels.decode_invocations").add(1)
    cols, vals = dtans_decode_pallas(packed_arrays(pm),
                                     out_dtype=out_dtype(pm),
                                     **_statics(pm))
    return slice_major(pm, cols), slice_major(pm, vals)


def _plain_spmm(kind: str, packed, x, y, *, rows: int, run, bn=None,
                vmem_budget=None):
    """Shared body of the uncoded multi-RHS entry points: ``run(x, bn)``
    calls the family's kernel on ``x: (n, B)``; B == 1 is the spmv
    pass (the same kernel at one column)."""
    m, n = packed.shape
    dt = packed.values.dtype
    x = jnp.asarray(x, dtype=dt)
    _check_rhs(x, n)
    if x.shape[1] == 0:
        return _empty_y(m, y, x.dtype)
    check_compiled(kind, dt, n)
    B = x.shape[1]
    if B == 1:
        _record_pass(f"{kind}_spmv", packed, n, m, 1, dt.itemsize)
        return _add_y(run(x, None)[:m], y)
    bn_eff = _resolve_bn(n, rows, B, dt.itemsize, bn, vmem_budget)
    _record_pass(f"{kind}_spmm", packed, n, m, B, dt.itemsize,
                 col_tiles=_n_tiles(B, bn_eff))
    return _add_y(run(x, bn_eff)[:m], y)


def sell_spmv(ps: PackedSELL, x, y=None) -> jax.Array:
    """Baseline SELL SpMVM: y = A x + y.

    Same ``(mat, x, y=None)`` signature as `spmv` / `rgcsr_spmv` — the
    timing harness (`repro.autotune.measure`) and the conformance suite
    drive all three entry points interchangeably."""
    return sell_spmm(ps, jnp.asarray(x)[:, None],
                     None if y is None else jnp.asarray(y)[:, None])[:, 0]


def sell_spmm(ps: PackedSELL, x, y=None, *, bn=None, vmem_budget=None,
              tile_mode: str = "auto") -> jax.Array:
    """Multi-RHS SELL: Y = A X + Y, X: (n, B). Shares the `spmm`
    signature; B == 1 is bit-identical to `sell_spmv`.
    ``bn`` / ``vmem_budget`` / ``tile_mode`` column-tile the B axis
    exactly as in `spmm` (bit-identical at every tile width)."""
    idx, val = jnp.asarray(ps.indices), jnp.asarray(ps.values)
    return _plain_spmm(
        "sell", ps, x, y, rows=ps.lane_width, bn=bn,
        vmem_budget=vmem_budget,
        run=lambda x, bn: sell_spmm_pallas(idx, val, x, bn=bn,
                                           tile_mode=tile_mode))


def rgcsr_spmv(pr: PackedRGCSR, x, y=None) -> jax.Array:
    """Row-grouped CSR SpMVM: y = A x + y (delta prefix-sum in kernel).

    Shares the `spmv` / `sell_spmv` signature; see `sell_spmv`."""
    return rgcsr_spmm(pr, jnp.asarray(x)[:, None],
                      None if y is None else jnp.asarray(y)[:, None])[:, 0]


def rgcsr_spmm(pr: PackedRGCSR, x, y=None, *, bn=None, vmem_budget=None,
               tile_mode: str = "auto") -> jax.Array:
    """Multi-RHS RGCSR: Y = A X + Y, X: (n, B). Shares the `spmm`
    signature; B == 1 is bit-identical to `rgcsr_spmv`.
    ``bn`` / ``vmem_budget`` / ``tile_mode`` column-tile the B axis
    exactly as in `spmm` (bit-identical at every tile width)."""
    arrs = (jnp.asarray(pr.deltas), jnp.asarray(pr.values),
            jnp.asarray(pr.nnz))
    return _plain_spmm(
        "rgcsr", pr, x, y, rows=pr.group_size, bn=bn,
        vmem_budget=vmem_budget,
        run=lambda x, bn: rgcsr_spmm_pallas(*arrs, x, bn=bn,
                                            tile_mode=tile_mode))


def bcsr_spmv(pb: PackedBCSR, x, y=None) -> jax.Array:
    """Blocked-CSR SpMVM: y = A x + y (dense r x c tiles in kernel).

    Shares the `spmv` / `sell_spmv` signature; see `sell_spmv`."""
    return bcsr_spmm(pb, jnp.asarray(x)[:, None],
                     None if y is None else jnp.asarray(y)[:, None])[:, 0]


def bcsr_spmm(pb: PackedBCSR, x, y=None, *, bn=None, vmem_budget=None,
              tile_mode: str = "auto") -> jax.Array:
    """Multi-RHS BCSR: Y = A X + Y, X: (n, B). Shares the `spmm`
    signature; B == 1 is bit-identical to `bcsr_spmv`.
    ``bn`` / ``vmem_budget`` / ``tile_mode`` column-tile the B axis
    exactly as in `spmm` (bit-identical at every tile width)."""
    cols, val = jnp.asarray(pb.block_cols), jnp.asarray(pb.values)
    return _plain_spmm(
        "bcsr", pb, x, y, rows=pb.block_shape[0], bn=bn,
        vmem_budget=vmem_budget,
        run=lambda x, bn: bcsr_spmm_pallas(cols, val, x, bn=bn,
                                           tile_mode=tile_mode))
