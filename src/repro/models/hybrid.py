"""Zamba2-style hybrid: a stack of Mamba2 blocks with ONE shared
attention+MLP block applied every ``attn_every`` SSM layers
[arXiv:2411.15242]. The shared block concatenates the current hidden state
with the original embedding (Zamba's residual trick) through an input
projection. Weights of the shared block are stored once; each of its
applications has its own KV cache slot at decode time.

Layout: the first ``n_groups * attn_every`` SSM layers run as a nested scan
(groups outer, layers inner, shared-attention applied between groups); the
remaining ``n_tail`` SSM layers run as one trailing scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (attention, attention_init, embed,
                                 embedding_init, lm_head, matmul, mlp,
                                 mlp_init, pos_vector, rmsnorm,
                                 rmsnorm_init, write_rows, _dense_init)
from repro.models.sharding import shard
from repro.models.ssm import ssm_block, ssm_cache_init, ssm_init


def _plan(cfg: ArchConfig):
    every = cfg.attn_every or cfg.n_layers + 1
    n_groups = cfg.n_layers // every
    n_tail = cfg.n_layers - n_groups * every
    return every, n_groups, n_tail


def init_params(cfg: ArchConfig, rng):
    every, n_groups, n_tail = _plan(cfg)
    ks = jax.random.split(rng, 5)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)

    def one(k):
        k1, k2 = jax.random.split(k)
        return {"ln": rmsnorm_init(cfg), "ssm": ssm_init(cfg, k1)}

    layers = jax.vmap(one)(layer_keys)
    out = {
        "embed": embedding_init(cfg, ks[4]),
        "layers": layers,
        "final_norm": rmsnorm_init(cfg),
    }
    if cfg.attn_every:
        out["shared_attn"] = {
            "in_proj": _dense_init(ks[1], (2 * cfg.d_model, cfg.d_model),
                                   cfg.param_dtype),
            "ln1": rmsnorm_init(cfg),
            "attn": attention_init(cfg, ks[2]),
            "ln2": rmsnorm_init(cfg),
            "mlp": mlp_init(cfg, ks[3]),
        }
    return out


def _ssm_layer(cfg, p, x, cache=None):
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    out, new_cache = ssm_block(p["ssm"], cfg, h, cache=cache)
    return x + out, new_cache


def _shared_block(cfg, p, x, x0, positions, kv_cache=None, cache_pos=None,
                  return_cache=False):
    h = jnp.concatenate([x, x0], axis=-1)
    h = matmul(h, p["in_proj"])
    h = rmsnorm(p["ln1"], h, cfg.norm_eps)
    attn_out, new_cache = attention(p["attn"], cfg, h, positions,
                                    causal=True, kv_cache=kv_cache,
                                    cache_pos=cache_pos,
                                    return_cache=return_cache)
    x = x + attn_out
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], h), new_cache


def _slice_layers(layers, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], layers)


def _group_layers(layers, n_groups, every):
    return jax.tree.map(
        lambda a: a[:n_groups * every].reshape((n_groups, every)
                                               + a.shape[1:]), layers)


def forward(params, cfg: ArchConfig, batch):
    every, n_groups, n_tail = _plan(cfg)
    x = embed(params["embed"], batch["inputs"])
    B, S, _ = x.shape
    x0 = x
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    shared = params.get("shared_attn")

    def inner(x, lp):
        x, _ = _ssm_layer(cfg, lp, x)
        return x, None

    inner_fn = jax.checkpoint(inner) if cfg.remat else inner

    def group(x, gp):
        x, _ = jax.lax.scan(inner_fn, x, gp)
        x, _ = _shared_block(cfg, shared, x, x0, positions)
        return x, None

    if n_groups:
        gstack = _group_layers(params["layers"], n_groups, every)
        x, _ = jax.lax.scan(group, x, gstack)
    if n_tail:
        tail = _slice_layers(params["layers"], n_groups * every,
                             cfg.n_layers)
        x, _ = jax.lax.scan(inner_fn, x, tail)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_head(params["embed"], x), jnp.float32(0.0)


def prefill(params, cfg: ArchConfig, batch, max_seq=None):
    """Prefill for SSM/hybrid: forward pass that also emits the decode
    cache (final SSD states + conv tails; per-application KV for the
    shared attention block)."""
    every, n_groups, n_tail = _plan(cfg)
    x = embed(params["embed"], batch["inputs"])
    B, S, _ = x.shape
    x0 = x
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    shared = params.get("shared_attn")

    def inner(x, lp):
        h = rmsnorm(lp["ln"], x, cfg.norm_eps)
        out, cache = ssm_block(lp["ssm"], cfg, h, return_cache=True)
        return x + out, cache

    ssm_parts = []
    attn_kv = None
    if n_groups:
        gstack = _group_layers(params["layers"], n_groups, every)

        def group(x, gp):
            x, gcache = jax.lax.scan(inner, x, gp)
            x, kv = _shared_block(cfg, shared, x, x0, positions,
                                  return_cache=True)
            return x, (gcache, kv)

        x, (gc, kvs) = jax.lax.scan(group, x, gstack)
        ssm_parts.append(jax.tree.map(
            lambda a: a.reshape((n_groups * every,) + a.shape[2:]), gc))
        attn_kv = kvs                      # {k,v}: (n_groups, B, S, Hk, hd)
    if n_tail:
        tail = _slice_layers(params["layers"], n_groups * every,
                             cfg.n_layers)
        x, tc = jax.lax.scan(inner, x, tail)
        ssm_parts.append(tc)
    ssm_cache = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                             *ssm_parts)
    caches = {"ssm": ssm_cache,
              "x0": jnp.zeros((B, 1, cfg.d_model), dtype=cfg.param_dtype)}
    if attn_kv is not None:
        if max_seq is not None and max_seq > S:
            attn_kv = jax.tree.map(
                lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, max_seq - S),
                                      (0, 0), (0, 0))), attn_kv)
        caches["attn"] = attn_kv
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params["embed"], x[:, -1:, :])
    return logits, caches, jnp.int32(S)


def make_decode_cache(cfg: ArchConfig, batch, seq_len, dtype=None):
    every, n_groups, n_tail = _plan(cfg)
    dtype = dtype or cfg.param_dtype
    ssm0 = ssm_cache_init(cfg, batch)
    out = {
        "ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), ssm0),
        "x0": jnp.zeros((batch, 1, cfg.d_model), dtype=cfg.param_dtype),
    }
    if n_groups:
        out["attn"] = {
            "k": jnp.zeros((n_groups, batch, seq_len, cfg.n_kv_heads,
                            cfg.hd), dtype=dtype),
            "v": jnp.zeros((n_groups, batch, seq_len, cfg.n_kv_heads,
                            cfg.hd), dtype=dtype),
        }
    return out


def decode_hidden(params, cfg: ArchConfig, caches, token, pos):
    """One serving step up to the final norm — the hidden states the
    LM head (dense or sparse) consumes; `decode_step` == lm_head of
    this (same contract as `transformer.decode_hidden`). ``pos`` may be
    a () scalar (all slots in lock step) or a (B,) vector of per-slot
    positions; entries of -1 mark inactive slots, whose SSM state, conv
    tail and attention KV lines all pass through unmodified. The shared
    block's K/V rows go into the attention cache after the scan, in one
    write (`write_rows`)."""
    every, n_groups, n_tail = _plan(cfg)
    x = embed(params["embed"], token)
    B = token.shape[0]
    pos = pos_vector(pos, B)          # (B,); -1 marks an inactive slot
    x0 = x
    positions = pos[:, None]
    shared = params.get("shared_attn")

    def inner(x, inp):
        lp, cache = inp
        x, new_cache = _ssm_layer(cfg, lp, x, cache=cache)
        return x, new_cache

    ssm_caches = caches["ssm"]

    def group(x, inp):
        gp, gcache, kv = inp
        x, new_gcache = jax.lax.scan(inner, x, (gp, gcache))
        x, rows = _shared_block(cfg, shared, x, x0, positions,
                                kv_cache=kv, cache_pos=pos)
        return x, (new_gcache, rows)

    new_attn = caches.get("attn")
    if n_groups:
        gstack = _group_layers(params["layers"], n_groups, every)
        gcaches = jax.tree.map(
            lambda a: a[:n_groups * every].reshape(
                (n_groups, every) + a.shape[1:]), ssm_caches)
        x, (ng, rows) = jax.lax.scan(group, x, (gstack, gcaches,
                                                caches["attn"]))
        new_head = jax.tree.map(
            lambda a: a.reshape((n_groups * every,) + a.shape[2:]), ng)
        new_attn = write_rows(caches["attn"], rows, pos)
    if n_tail:
        tail_p = _slice_layers(params["layers"], n_groups * every,
                               cfg.n_layers)
        tail_c = jax.tree.map(lambda a: a[n_groups * every:], ssm_caches)
        x, new_tail = jax.lax.scan(inner, x, (tail_p, tail_c))
    parts = []
    if n_groups:
        parts.append(new_head)
    if n_tail:
        parts.append(new_tail)
    new_ssm = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    # Inactive-slot write mask: the single-token SSM recurrence advances
    # state and conv tail for every batch row unconditionally — a pooled
    # step must not corrupt the state of slots that are not decoding
    # (`write_rows` already leaves the attention lines of inactive
    # slots as they are).
    active = pos >= 0
    new_ssm = jax.tree.map(
        lambda new, old: jnp.where(
            active.reshape((1, -1) + (1,) * (new.ndim - 2)), new, old),
        new_ssm, ssm_caches)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    new_caches = {"ssm": new_ssm, "x0": caches["x0"]}
    if new_attn is not None:
        new_caches["attn"] = new_attn
    return x, new_caches


def decode_step(params, cfg: ArchConfig, caches, token, pos):
    x, new_caches = decode_hidden(params, cfg, caches, token, pos)
    return lm_head(params["embed"], x), new_caches


def cache_insert_slot(cfg: ArchConfig, pool, req, slot: int):
    """Insert a batch-size-1 decode cache (from `prefill`) into batch
    slot ``slot`` of a pooled cache. SSM states and attention KV carry
    the batch on axis 1 (layer/group-stacked); the pass-through ``x0``
    buffer on axis 0. Every cache line of the slot is overwritten —
    stale SSM state from the slot's previous occupant cannot leak."""
    def ins(axis):
        return lambda p, r: jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=axis)

    out = {"ssm": jax.tree.map(ins(1), pool["ssm"], req["ssm"]),
           "x0": ins(0)(pool["x0"], req["x0"])}
    if "attn" in pool:
        out["attn"] = jax.tree.map(ins(1), pool["attn"], req["attn"])
    return out
