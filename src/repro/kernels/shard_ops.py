"""Execute a `repro.sparse.shard.ShardPlan` — `jax.shard_map` over the
mesh ``model`` axis, or a sequential per-shard loop without devices.

The multi-device contract (ROADMAP item 2): each device holds ONE
shard's packed artifact (its row block's bitstream / index arrays),
decodes and contracts it against a broadcast ``x``, and the per-device
partial ``y``'s — disjoint row ranges, zero elsewhere — reduce via
``psum`` into the replicated result.  Per-shard packed tensors are
zero-padded to the fleet-wide max block shape and stacked on a leading
``n_shards`` axis sharded over ``model`` (the same address-padding-only
trick as `pack.py`: padded slices carry ``ns == 0`` / column ``-1`` /
``nnz == 0`` and decode to nothing, and a row mask kills any residue
before the reduction).

Bit-identity: a shard's kernel is EXACTLY the single-device kernel on
its row block — decode is lossless and each row accumulates in column
order regardless of its neighbours — and the psum adds the true row
values to zeros, so sharded results equal the single-device results at
every shard count (conformance-pinned at shards in {1, 2, 4}).

The sequential loop path (``mesh=None``, or a packed type without a
registered adapter) runs each shard through the family's own
single-device runner and concatenates rows — every registered format,
third-party specs included, has a sharded path; the four kernel-backed
families additionally get the collective path via the adapters below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.bcsr_spmv import PackedBCSR, bcsr_spmm_pallas
from repro.kernels.common import LANES
from repro.kernels.dtans_spmv import dtans_spmm_pallas
from repro.kernels.pack import PackedMatrix
from repro.kernels.rgcsr_spmv import PackedRGCSR, rgcsr_spmm_pallas
from repro.kernels.sell_spmv import PackedSELL, sell_spmm_pallas


def _pad_stack(arrs, fill=0):
    """Stack ndarrays on a new leading axis, zero-padding every
    dimension to the fleet-wide max (address padding only — the padded
    region is masked in-kernel, exactly like `pack.py`)."""
    nd = arrs[0].ndim
    shape = tuple(max(int(a.shape[i]) for a in arrs) for i in range(nd))
    out = np.full((len(arrs),) + shape, fill, dtype=arrs[0].dtype)
    for k, a in enumerate(arrs):
        out[k][tuple(slice(0, s) for s in a.shape)] = a
    return out


# --------------------------------------------------------------------------
# Per-family adapters: stack per-shard packs + run one shard's kernel.
# ``stack`` -> (arrays, static, rows_cap, out_dtype); ``run`` takes the
# device-local (leading-axis-stripped) arrays and x: (n, B), returns a
# (rows_cap, B) partial — the same kernel `ops.spmm` runs at every B,
# so sharded results stay bit-identical to the single-device kernels.
# --------------------------------------------------------------------------


def _stack_dtans(packs):
    p0 = packs[0]
    for p in packs:
        if (p.lane_width != p0.lane_width or p.params != p0.params
                or tuple(p.pattern) != tuple(p0.pattern)
                or p.esc.shape[0] != p0.esc.shape[0]
                or p.lanes != p0.lanes
                or p.shared_cols != p0.shared_cols):
            raise ValueError("dtans shards disagree on static layout "
                             "(lane_width / params / tables)")
    arrays = [_pad_stack([getattr(p, f) for p in packs]) for f in (
        "stream", "stream_base", "esc", "esc_base", "nsegs", "nnz",
        "tab_symbol", "tab_meta")]
    dt = jnp.float64 if p0.dtype == np.float64 else jnp.float32
    static = dict(params=p0.params, pattern=tuple(p0.pattern),
                  lane_width=int(p0.lane_width),
                  max_nseg=max(int(p.max_nseg) for p in packs),
                  shared_cols=bool(p0.shared_cols))
    return arrays, static, arrays[0].shape[1] * p0.lanes, dt


def _run_dtans(arrs, x, st, tile):
    return dtans_spmm_pallas(tuple(arrs), x, bn=tile["bn"],
                             tile_mode=tile["tile_mode"],
                             pipeline=tile["pipeline"], **st)


def _stack_sell(packs):
    p0 = packs[0]
    if any(p.lane_width != p0.lane_width for p in packs):
        raise ValueError("sell shards disagree on slice_height")
    arrays = [_pad_stack([p.indices for p in packs], fill=-1),
              _pad_stack([p.values for p in packs])]
    return arrays, {}, arrays[0].shape[2], p0.values.dtype


def _run_sell(arrs, x, st, tile):
    idx, val = arrs
    return sell_spmm_pallas(idx, val, x, bn=tile["bn"],
                            tile_mode=tile["tile_mode"])


def _stack_rgcsr(packs):
    p0 = packs[0]
    if any(p.group_size != p0.group_size for p in packs):
        raise ValueError("rgcsr shards disagree on group_size")
    arrays = [_pad_stack([p.deltas for p in packs]),
              _pad_stack([p.values for p in packs]),
              _pad_stack([p.nnz for p in packs])]
    return arrays, {}, arrays[0].shape[2], p0.values.dtype


def _run_rgcsr(arrs, x, st, tile):
    deltas, val, nnz = arrs
    return rgcsr_spmm_pallas(deltas, val, nnz, x, bn=tile["bn"],
                             tile_mode=tile["tile_mode"])


def _stack_bcsr(packs):
    p0 = packs[0]
    if any(p.block_shape != p0.block_shape for p in packs):
        raise ValueError("bcsr shards disagree on block_shape")
    arrays = [_pad_stack([p.block_cols for p in packs], fill=-1),
              _pad_stack([p.values for p in packs])]
    return arrays, {}, arrays[0].shape[2], p0.values.dtype


def _run_bcsr(arrs, x, st, tile):
    cols, val = arrs
    return bcsr_spmm_pallas(cols, val, x, bn=tile["bn"],
                            tile_mode=tile["tile_mode"])


#: packed-artifact type -> (stack, run).  A family (or third-party
#: spec) joins the collective path by registering here; everything else
#: falls back to the sequential loop.
SHARD_MAP_ADAPTERS = {
    PackedMatrix: (_stack_dtans, _run_dtans),
    PackedSELL: (_stack_sell, _run_sell),
    PackedRGCSR: (_stack_rgcsr, _run_rgcsr),
    PackedBCSR: (_stack_bcsr, _run_bcsr),
}


def supports_shard_map(plan) -> bool:
    """Whether this plan's packed artifacts have a collective-path
    adapter (the four kernel-backed families do)."""
    return bool(plan.shards) and type(plan.shards[0]) in \
        SHARD_MAP_ADAPTERS


# --------------------------------------------------------------------------
# Execution
# --------------------------------------------------------------------------


def _record_shard_pass(plan, batch: int, *, collective: bool) -> None:
    """One sharded pass into the default metrics registry: per-shard
    matrix bytes plus the collective count (one x broadcast + one y
    psum per collective pass) — the obs contract of the sharded path."""
    r = obs.default_registry()
    r.counter("kernels.shard_passes").add(1)
    r.counter("kernels.shard_matrix_bytes").add(plan.total_nbytes)
    r.histogram("kernels.n_shards").observe(plan.n_shards)
    for b in plan.shard_nbytes:
        r.histogram("kernels.shard_bytes").observe(int(b))
    if collective:
        r.counter("kernels.collectives.broadcast").add(1)
        r.counter("kernels.collectives.psum").add(1)


def _tile_opts(bn=None, tile_mode="auto", pipeline=False):
    """The per-shard tile/pipeline knobs threaded into the run
    adapters.  ``bn`` column-tiles each device's local kernel call
    (`repro.kernels.tiling`); ``pipeline`` double-buffers the dtANS
    decode (ignored by the plain families)."""
    return dict(bn=bn, tile_mode=tile_mode, pipeline=bool(pipeline))


def _loop_spmm(plan, x2, *, tile):
    """Sequential fallback: every shard in turn on one device, rows
    concatenated — no mesh needed, every registered format supported.

    Kernel-backed families run through the SAME stacked adapters as the
    collective path (the kernels accumulate in stored-position order, so
    the extra padding of the stack adds only trailing zeros).  Other
    formats go through their registry `spmm_runner` per shard."""
    zero_dt = jnp.float64 if plan.dtype == np.float64 else jnp.float32
    blocks = []
    if supports_shard_map(plan):
        stack, run = SHARD_MAP_ADAPTERS[type(plan.shards[0])]
        arrays, static, rows_cap, dt = stack(plan.shards)
        dt = jnp.float64 if np.dtype(dt) == np.float64 else jnp.float32
        xj = jnp.asarray(x2, dtype=dt)
        for k in range(plan.n_shards):
            rows = plan.boundaries[k + 1] - plan.boundaries[k]
            if rows == 0:
                continue                  # empty shard: zero rows
            local = [jnp.asarray(a[k]) for a in arrays]
            blocks.append(run(local, xj, static, tile)[:rows])
    else:
        from repro.sparse.registry import get_format
        spec = get_format(plan.fmt)
        for k in range(plan.n_shards):
            if plan.boundaries[k + 1] == plan.boundaries[k]:
                continue
            blocks.append(jnp.asarray(spec.spmm_runner(
                plan.shards[k], x2)()))
    if not blocks:
        return jnp.zeros((0, x2.shape[1]), zero_dt)
    return jnp.concatenate(blocks, axis=0)


def place_on_mesh(plan, mesh):
    """The plan's stacked shard tensors placed over the mesh ``model``
    axis — each device holds its own shard only — with the adapter's
    ``(static, rows_cap, dtype)``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    stack, _ = SHARD_MAP_ADAPTERS[type(plan.shards[0])]
    arrays, static, rows_cap, dt = stack(plan.shards)
    placed = [jax.device_put(a, NamedSharding(
        mesh, P("model", *([None] * (a.ndim - 1))))) for a in arrays]
    return placed, static, rows_cap, dt


def _shard_map_spmm(plan, x2, mesh, *, tile):
    """The collective path: stacked shard tensors sharded over the mesh
    ``model`` axis, x broadcast (replicated in-spec), per-device kernel,
    row-masked partials placed at each shard's row offset, psum."""
    from jax.sharding import PartitionSpec as P

    _, run = SHARD_MAP_ADAPTERS[type(plan.shards[0])]
    arrs, static, rows_cap, dt = place_on_mesh(plan, mesh)
    dt = jnp.float64 if np.dtype(dt) == np.float64 else jnp.float32
    m, _ = plan.shape
    B = x2.shape[1]
    r0 = np.asarray(plan.boundaries[:-1], np.int32)
    rows = np.asarray(np.diff(np.asarray(plan.boundaries)), np.int32)
    m_pad = max(m, int(r0.max()) + rows_cap)
    xj = jnp.asarray(x2, dtype=dt)

    def body(r0_k, rows_k, x, *arrs_k):
        local = [a[0] for a in arrs_k]
        part = run(local, x, static, tile).astype(dt)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows_cap, 1), 0)
        part = jnp.where(lane < rows_k[0], part, 0)
        out = jnp.zeros((m_pad, B), dt)
        out = jax.lax.dynamic_update_slice(
            out, part, (r0_k[0], jnp.int32(0)))
        return jax.lax.psum(out, "model")

    specs = [P("model"), P("model"), P(None, None)] + \
        [P("model", *([None] * (a.ndim - 1))) for a in arrs]
    f = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                      out_specs=P(None, None), check_vma=False)
    return f(jnp.asarray(r0), jnp.asarray(rows), xj, *arrs)[:m]


def _validate_mesh(plan, mesh):
    from repro.launch.mesh import model_axis_size
    k = model_axis_size(mesh)
    if k != plan.n_shards:
        raise ValueError(
            f"plan has {plan.n_shards} shards but the mesh model axis "
            f"holds {k} devices; build the plan with "
            f"n_shards=model_axis_size(mesh)")


def shard_spmm(plan, x, y=None, *, mesh=None, bn=None,
               tile_mode: str = "auto",
               pipeline: bool = False) -> jax.Array:
    """Y = A X + Y from a shard plan, X: (n, B) — the sharded analogue
    of `ops.spmm`.  With a mesh (model axis == ``plan.n_shards``) and a
    kernel-backed family: `shard_map` + psum; otherwise the sequential
    per-shard loop.  Results are bit-identical to the single-device
    kernels either way.  ``bn`` / ``tile_mode`` column-tile each
    device's local kernel and ``pipeline`` double-buffers the dtANS
    decode — both pass straight into the per-shard kernels, so the
    sharded bit-identity contract is the single-device one."""
    m, n = plan.shape
    x2 = jnp.asarray(x)
    if x2.ndim != 2:
        raise ValueError(f"shard_spmm expects x of shape (n, B); got "
                         f"{x2.shape} (use shard_spmv for 1-D)")
    if x2.shape[0] != n:
        raise ValueError(f"shard_spmm rhs has {x2.shape[0]} rows; "
                         f"matrix has {n} columns")
    dt = jnp.float64 if plan.dtype == np.float64 else jnp.float32
    if plan.shards:
        from repro.kernels.ops import check_compiled
        check_compiled("shard_spmm", plan.dtype, n,
                       getattr(plan.shards[0], "lanes", LANES))
    if x2.shape[1] == 0 or m == 0:
        out = jnp.zeros((m, x2.shape[1]), dt)
    else:
        collective = (mesh is not None and plan.n_shards > 1
                      and supports_shard_map(plan))
        if mesh is not None:
            _validate_mesh(plan, mesh)
        _record_shard_pass(plan, x2.shape[1], collective=collective)
        tile = _tile_opts(bn=bn, tile_mode=tile_mode, pipeline=pipeline)
        if collective:
            out = _shard_map_spmm(plan, x2, mesh, tile=tile)
        else:
            out = _loop_spmm(plan, x2, tile=tile)
    if y is not None:
        out = out + jnp.asarray(y, dtype=out.dtype)
    return out


def shard_spmv(plan, x, y=None, *, mesh=None,
               pipeline: bool = False) -> jax.Array:
    """y = A x + y from a shard plan, 1-D ``x`` — the sharded analogue
    of `ops.spmv`.  Routes through the spmv kernels (B == 1), so the
    result is bit-identical to the single-device `ops.spmv`."""
    x1 = jnp.asarray(x)
    if x1.ndim != 1:
        raise ValueError(f"shard_spmv expects 1-D x; got {x1.shape}")
    out = shard_spmm(plan, x1[:, None], mesh=mesh, pipeline=pipeline)[:, 0]
    if y is not None:
        out = out + jnp.asarray(y, dtype=out.dtype)
    return out
