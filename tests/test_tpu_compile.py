"""Compile the main path's Pallas kernels for a TPU v5e that is described
but not attached (`jax.experimental.topologies`).

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks off the (8, 128) tiling, 64-bit operands,
gathers and scans Mosaic does not lower.  These tests compile each kernel
at the shapes it serves — the smollm-135m LM head (49152 x 576, sparsity
0.8, lane width 128), the yi9b-batch cell's head (8000 x 4096) and an MLP
down projection (576 x 1536) — and check
that the program holds a ``tpu_custom_call`` whose operands and results
are all 32-bit.  The engine's pooled decode step and slot insert are
compiled at both benchmark cells' sizes, to check that the donated f32
cache is updated in place.  Nothing runs; a compile that passes is not a
chip run.

The topology is described inside a fixture, never while a module is
imported, so every test worker collects the same tests and only the one
that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core.params import PAPER

#: smollm-135m's LM head as packed at lane width 128 and sparsity 0.8:
#: 384 programs of 128 rows, a 6750-word stream each (56 rows of 128),
#: one shared 4096-slot table (32 rows), at most 40 segments per row.
HEAD_M, HEAD_N = 49152, 576
GROUPS, STREAM_ROWS, ESC_ROWS, TABLE_ROWS, MAX_NSEG = 384, 56, 8, 32, 40
#: Per head: x rows, programs, stream rows, segments per row.  The
#: yi9b-batch cell's head (8000 x 4096, the same pack settings) has 63
#: programs of a 352-row stream and at most 227 segments per row.
HEADS = {"smollm": (HEAD_N, GROUPS, STREAM_ROWS, MAX_NSEG),
         "yi": (4096, 63, 352, 227)}
#: Uncoded packs of the same head: max row nnz, BCSR 4x4 block slots.
ELL_WIDTH, BLOCK_SLOTS, BLOCK = 160, 144, 4
#: smollm-135m's MLP down projection (d_model x d_ff).
MLP_M, MLP_N = 576, 1536

_WIDE = re.compile(r"\b[usf]64\[")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def spec(one_chip, no_compile_cache):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _dtans_mats(spec, groups=GROUPS, stream_rows=STREAM_ROWS):
    """`PackedMatrix` operand shapes (field order) of a lane-width-128
    pack with one shared table."""
    lanes = (groups, 1, 128)
    return (spec((groups, stream_rows, 128), jnp.uint32),
            spec(lanes, jnp.int32),
            spec((1, groups, ESC_ROWS, 128), jnp.uint32),
            spec((1,) + lanes, jnp.int32),
            spec(lanes, jnp.int32),
            spec(lanes, jnp.int32),
            spec((1, TABLE_ROWS, 128), jnp.uint32),
            spec((1, TABLE_ROWS, 128), jnp.uint32))


def _compile(fn, *args):
    """Compile for the described chip; return the kernels' HLO lines."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls, "no tpu_custom_call in the compiled program"
    for ln in calls:
        # Result type, then the operand types in operand_layout_constraints.
        types = ln.split(", custom_call_target=")[0] + " " + (
            ln.split("operand_layout_constraints=")[1].split("}, ")[0]
            if "operand_layout_constraints=" in ln else "")
        assert not _WIDE.search(types), f"64-bit kernel operand: {types}"
    return calls


_STATICS = dict(params=PAPER, pattern=(0,) * PAPER.l, max_nseg=MAX_NSEG,
                lane_width=128, interpret=False)


@pytest.mark.parametrize("head,variant,B", [
    pytest.param("smollm", v, b, id=f"{v}-{b}") for v, b in [
        ("plain", 1), ("plain", 4), ("pipeline", 4), ("shared_cols", 4),
        ("grid_tiles", 32)]] + [
    pytest.param("yi", v, 16, id=f"yi-{v}-16") for v in ["plain",
                                                         "pipeline"]])
def test_dtans_head_compiles(spec, head, variant, B):
    from repro.kernels.dtans_spmv import dtans_spmm_pallas
    kw = {"plain": {}, "pipeline": {"pipeline": True},
          "shared_cols": {"shared_cols": True},
          "grid_tiles": {"bn": 8, "tile_mode": "grid"}}[variant]
    n, groups, stream_rows, max_nseg = HEADS[head]
    statics = {**_STATICS, "max_nseg": max_nseg, **kw}
    _compile(lambda m, x: dtans_spmm_pallas(m, x, **statics),
             _dtans_mats(spec, groups, stream_rows), spec((n, B), jnp.float32))


def test_dtans_spmv_head_compiles(spec):
    from repro.kernels.dtans_spmv import dtans_spmv_pallas
    _compile(lambda m, x: dtans_spmv_pallas(m, x, **_STATICS),
             _dtans_mats(spec), spec((HEAD_N,), jnp.float32))


def test_dtans_mlp_projection_compiles(spec):
    """x of d_ff = 1536 rows, smollm-135m's widest projection input."""
    from repro.kernels.dtans_spmv import dtans_spmm_pallas
    _compile(lambda m, x: dtans_spmm_pallas(m, x, **_STATICS),
             _dtans_mats(spec, groups=MLP_M // 128, stream_rows=144),
             spec((MLP_N, 4), jnp.float32))


def test_dtans_decode_compiles(spec):
    from repro.kernels.dtans_decode import dtans_decode_pallas
    _compile(lambda m: dtans_decode_pallas(m, out_dtype=jnp.float32,
                                           **_STATICS), _dtans_mats(spec))


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("family", ["sell", "rgcsr", "bcsr"])
def test_uncoded_head_compiles(spec, family, B):
    from repro.kernels.bcsr_spmv import bcsr_spmm_pallas
    from repro.kernels.rgcsr_spmv import rgcsr_spmm_pallas
    from repro.kernels.sell_spmv import sell_spmm_pallas
    x = spec((HEAD_N, B), jnp.float32)
    ell = (ELL_WIDTH, HEAD_M)
    if family == "sell":
        _compile(lambda i, v, x: sell_spmm_pallas(i, v, x, interpret=False),
                 spec(ell, jnp.int32), spec(ell, jnp.float32), x)
    elif family == "rgcsr":
        _compile(lambda d, v, z, x: rgcsr_spmm_pallas(d, v, z, x,
                                                      interpret=False),
                 spec(ell, jnp.int32), spec(ell, jnp.float32),
                 spec((1, HEAD_M), jnp.int32), x)
    else:
        _compile(lambda c, v, x: bcsr_spmm_pallas(c, v, x, interpret=False),
                 spec((BLOCK_SLOTS, HEAD_M), jnp.int32),
                 spec((BLOCK_SLOTS, BLOCK, HEAD_M), jnp.float32), x)


def test_sell_grid_tiles_compile(spec):
    from repro.kernels.sell_spmv import sell_spmm_pallas
    ell = (ELL_WIDTH, HEAD_M)
    _compile(lambda i, v, x: sell_spmm_pallas(i, v, x, interpret=False,
                                              bn=8, tile_mode="grid"),
             spec(ell, jnp.int32), spec(ell, jnp.float32),
             spec((HEAD_N, 32), jnp.float32))


#: The benchmark cells' pooled caches: configuration, layers, slots,
#: max_seq, vocabulary (smollm135m-chat and yi9b-batch).
CELLS = {"smollm": ("smollm-135m", 30, 32, 2048, 49152),
         "yi": ("yi-9b", 8, 16, 1280, 8000)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_pooled_decode_updates_cache_in_place(spec, cell):
    """With the cache donated, the decode step and the slot insert hand
    its buffers to their results and hold no cache-sized temporary: no
    relayout of the cache for a scatter, no bf16 copy of it hoisted out
    of the layer scan, no rebuilt cache per step."""
    from repro.configs import get
    from repro.models import api
    name, layers, slots, max_seq, vocab = CELLS[cell]
    cfg = get(name).with_(n_layers=layers, vocab=vocab)

    def shapes(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shapes(jax.eval_shape(lambda: api.make_decode_cache(
        cfg, slots, max_seq, dtype=jnp.float32)))
    req = shapes(jax.eval_shape(lambda: api.make_decode_cache(
        cfg, 1, max_seq, dtype=cfg.param_dtype)))
    cache_bytes = sum(a.size * 4 for a in jax.tree.leaves(cache))
    step = jax.jit(lambda p, c, t, q: api.decode_hidden(p, cfg, c, t, q),
                   donate_argnums=1)
    insert = jax.jit(lambda c, r, s: api.cache_insert_slot(cfg, c, r, s),
                     donate_argnums=0)
    for fn, args in (
            (step, (params, cache, spec((slots, 1), jnp.int32),
                    spec((slots,), jnp.int32))),
            (insert, (cache, req, spec((), jnp.int32)))):
        mem = fn.lower(*args).compile().memory_analysis()
        assert mem.alias_size_in_bytes == cache_bytes
        assert mem.temp_size_in_bytes < cache_bytes / 100
