"""Encoder-decoder backbone (SeamlessM4T-style): bidirectional encoder over
precomputed modality frame embeddings (the speech frontend is a stub per the
assignment) + causal decoder with cross-attention.

Shape conventions for the assigned LM shapes (DESIGN.md §6):
  train_4k    : encoder S frames + decoder S tokens (S = seq_len)
  prefill_32k : encoder seq_len frames + decoder prefill of 1024 tokens
  decode_32k  : decoder KV cache of seq_len, encoder memory of
                cfg.n_frontend_tokens frames
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (attention, attention_init, embed,
                                 embedding_init, lm_head, mlp, mlp_init,
                                 pos_vector, rmsnorm, rmsnorm_init,
                                 write_rows)
from repro.models.sharding import shard

DEC_PREFILL_LEN = 1024


def _enc_layer_init(cfg, rng):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": rmsnorm_init(cfg),
        "attn": attention_init(cfg, k1),
        "ln2": rmsnorm_init(cfg),
        "mlp": mlp_init(cfg, k2),
    }


def _dec_layer_init(cfg, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": rmsnorm_init(cfg),
        "self_attn": attention_init(cfg, k1),
        "lnx": rmsnorm_init(cfg),
        "cross_attn": attention_init(cfg, k2),
        "ln2": rmsnorm_init(cfg),
        "mlp": mlp_init(cfg, k3),
    }


def init_params(cfg: ArchConfig, rng):
    ks = jax.random.split(rng, 3)
    ne = cfg.n_enc_layers or cfg.n_layers
    nd = cfg.n_dec_layers or cfg.n_layers
    enc = jax.vmap(lambda k: _enc_layer_init(cfg, k))(
        jax.random.split(ks[0], ne))
    dec = jax.vmap(lambda k: _dec_layer_init(cfg, k))(
        jax.random.split(ks[1], nd))
    return {
        "embed": embedding_init(cfg, ks[2]),
        "enc_layers": enc,
        "enc_norm": rmsnorm_init(cfg),
        "dec_layers": dec,
        "dec_norm": rmsnorm_init(cfg),
    }


def encode(params, cfg: ArchConfig, frames):
    """frames: (B, Se, d) precomputed frontend embeddings."""
    x = frames.astype(cfg.param_dtype)
    x = shard(x, "batch", "seq", "d_model")
    B, Se, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(x, lp):
        h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
        a, _ = attention(lp["attn"], cfg, h, positions, causal=False)
        x = x + a
        h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
        return x + mlp(lp["mlp"], h), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _dec_layer(cfg, lp, x, positions, memory, kv_cache=None, cache_pos=None,
               return_cache=False):
    h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    a, new_cache = attention(lp["self_attn"], cfg, h, positions,
                             causal=True, kv_cache=kv_cache,
                             cache_pos=cache_pos, return_cache=return_cache)
    x = x + a
    h = rmsnorm(lp["lnx"], x, cfg.norm_eps)
    a, _ = attention(lp["cross_attn"], cfg, h, positions, causal=False,
                     kv=memory)
    x = x + a
    h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    return x + mlp(lp["mlp"], h), new_cache


def forward(params, cfg: ArchConfig, batch):
    """Training: batch = {frontend: (B,Se,d), inputs: (B,S), targets}."""
    memory = encode(params, cfg, batch["frontend"])
    x = embed(params["embed"], batch["inputs"])
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        x, _ = _dec_layer(cfg, lp, x, positions, memory)
        return x, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dec_layers"])
    x = rmsnorm(params["dec_norm"], x, cfg.norm_eps)
    return lm_head(params["embed"], x), jnp.float32(0.0)


def prefill(params, cfg: ArchConfig, batch, max_seq=None):
    memory = encode(params, cfg, batch["frontend"])
    x = embed(params["embed"], batch["inputs"])
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, lp):
        x, cache = _dec_layer(cfg, lp, x, positions, memory,
                              return_cache=True)
        return x, cache

    x, caches = jax.lax.scan(body, x, params["dec_layers"])
    if max_seq is not None and max_seq > S:
        caches = jax.tree.map(
            lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, max_seq - S),
                                  (0, 0), (0, 0))), caches)
    x = rmsnorm(params["dec_norm"], x, cfg.norm_eps)
    return (lm_head(params["embed"], x[:, -1:, :]),
            {"kv": caches, "memory": memory}, jnp.int32(S))


def decode_hidden(params, cfg: ArchConfig, caches, token, pos):
    """One decoder step up to the final norm — the hidden states the
    LM head (dense or sparse) consumes; `decode_step` == lm_head of
    this (same contract as `transformer.decode_hidden`). ``pos`` may be
    () or (B,) per-slot positions (-1 = inactive slot, KV write
    masked). The step's K/V rows go into the cache after the scan, in
    one write (`write_rows`)."""
    x = embed(params["embed"], token)
    B = token.shape[0]
    pos = pos_vector(pos, B)          # (B,); -1 marks an inactive slot
    positions = pos[:, None]
    memory = caches["memory"]

    def body(x, inp):
        lp, cache = inp
        x, rows = _dec_layer(cfg, lp, x, positions, memory,
                             kv_cache=cache, cache_pos=pos)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["dec_layers"], caches["kv"]))
    x = rmsnorm(params["dec_norm"], x, cfg.norm_eps)
    return x, {"kv": write_rows(caches["kv"], rows, pos), "memory": memory}


def decode_step(params, cfg: ArchConfig, caches, token, pos):
    x, new_caches = decode_hidden(params, cfg, caches, token, pos)
    return lm_head(params["embed"], x), new_caches


def cache_insert_slot(cfg: ArchConfig, pool, req, slot: int):
    """Insert a batch-size-1 decode cache (from `prefill`) into batch
    slot ``slot``: decoder self-attention KV carries the batch on axis 1
    (layer-stacked), the encoder memory on axis 0."""
    return {
        "kv": jax.tree.map(
            lambda p, r: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=1),
            pool["kv"], req["kv"]),
        "memory": jax.lax.dynamic_update_slice_in_dim(
            pool["memory"], req["memory"].astype(pool["memory"].dtype),
            slot, axis=0),
    }


def make_decode_cache(cfg: ArchConfig, batch, seq_len, memory_len=None,
                      dtype=None):
    dtype = dtype or cfg.param_dtype
    nd = cfg.n_dec_layers or cfg.n_layers
    ml = memory_len or cfg.n_frontend_tokens
    return {
        "kv": {
            "k": jnp.zeros((nd, batch, seq_len, cfg.n_kv_heads, cfg.hd),
                           dtype=dtype),
            "v": jnp.zeros((nd, batch, seq_len, cfg.n_kv_heads, cfg.hd),
                           dtype=dtype),
        },
        "memory": jnp.zeros((batch, ml, cfg.d_model),
                            dtype=cfg.param_dtype),
    }
