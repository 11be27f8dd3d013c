"""Decoder-only LM backbone (llama-style), covering the dense, MoE and
VLM/frontend-stub families. Layers are stacked and driven by `lax.scan`
(compile-time O(1) in depth — required for the 126-layer 405B config);
each layer is rematerialized in training when cfg.remat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (attention, attention_init, embed,
                                 embedding_init, lm_head, mlp, mlp_init,
                                 pos_vector, rmsnorm, rmsnorm_init,
                                 write_rows)
from repro.models.moe import moe, moe_init
from repro.models.sharding import shard


def _layer_init(cfg: ArchConfig, rng):
    ks = jax.random.split(rng, 4)
    p = {
        "ln1": rmsnorm_init(cfg),
        "attn": attention_init(cfg, ks[0]),
        "ln2": rmsnorm_init(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_init(cfg, ks[1])
    else:
        p["mlp"] = mlp_init(cfg, ks[1])
    return p


def init_params(cfg: ArchConfig, rng):
    k_emb, k_layers = jax.random.split(rng)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(cfg, k))(layer_keys)
    return {
        "embed": embedding_init(cfg, k_emb),
        "layers": layers,
        "final_norm": rmsnorm_init(cfg),
    }


def _layer_apply(cfg: ArchConfig, p, x, positions, kv_cache=None,
                 cache_pos=None, return_cache=False):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, new_cache = attention(
        p["attn"], cfg, h, positions, causal=True, kv_cache=kv_cache,
        cache_pos=cache_pos, return_cache=return_cache)
    x = x + attn_out
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        ff, aux = moe(p["moe"], cfg, h)
    else:
        ff, aux = mlp(p["mlp"], h), jnp.float32(0.0)
    return x + ff, aux, new_cache


def _embed_inputs(cfg: ArchConfig, params, batch):
    """Token embedding, with frontend-stub embeddings prepended for the
    vlm family (precomputed patch/frame embeddings, DESIGN.md §4)."""
    x = embed(params["embed"], batch["inputs"])
    if cfg.family == "vlm" and "frontend" in batch:
        fe = batch["frontend"].astype(x.dtype)       # (B, P, d)
        fe = shard(fe, "batch", "seq", "d_model")
        x = jnp.concatenate([fe, x], axis=1)
    return x


def forward_hidden(params, cfg: ArchConfig, batch):
    """Training/eval hidden states: the (B, S, d_model) LM-head input
    (post final norm). Returns (hidden, aux) — `forward` is
    ``lm_head(params["embed"], hidden)``; callers that swap the unembed
    for a compressed head (`repro.serving.SparseLinear`) start here."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(carry, layer_p):
        x, aux = carry
        x, a, _ = _layer_apply(cfg, layer_p, x, positions)
        return (x, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                               params["layers"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.family == "vlm" and "frontend" in batch:
        x = x[:, batch["frontend"].shape[1]:, :]      # text positions only
    return x, aux


def forward(params, cfg: ArchConfig, batch):
    """Training/eval forward. Returns (logits over token positions, aux)."""
    x, aux = forward_hidden(params, cfg, batch)
    return lm_head(params["embed"], x), aux


def prefill(params, cfg: ArchConfig, batch, max_seq: int | None = None):
    """Prefill pass: returns (last-position logits, kv cache, next pos)."""
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(x, layer_p):
        x, _, cache = _layer_apply(cfg, layer_p, x, positions,
                                   return_cache=True)
        return x, cache

    x, caches = jax.lax.scan(body, x, params["layers"])
    if max_seq is not None and max_seq > S:
        pad = max_seq - S
        caches = jax.tree.map(
            lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0),
                                  (0, 0))), caches)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params["embed"], x[:, -1:, :])
    return logits, caches, jnp.int32(S)


def decode_hidden(params, cfg: ArchConfig, caches, token, pos):
    """One serving step up to (and including) the final norm: the
    (B, 1, d) hidden states the LM head — dense `lm_head` or a
    compressed `SparseLinear` — consumes. `decode_step` is exactly
    ``lm_head(decode_hidden(...))``; the serving engine calls this
    directly when the output projection is sparse. The layers only
    read ``caches``; the step's new K/V rows are written into it once,
    after the scan (`write_rows`), in place where the caller's jit
    donates it."""
    x = embed(params["embed"], token)
    B = token.shape[0]
    pos = pos_vector(pos, B)          # (B,); -1 marks an inactive slot
    positions = pos[:, None]

    def body(x, inp):
        layer_p, cache = inp
        x, _, rows = _layer_apply(cfg, layer_p, x, positions,
                                  kv_cache=cache, cache_pos=pos)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["layers"], caches))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, write_rows(caches, rows, pos)


def decode_step(params, cfg: ArchConfig, caches, token, pos):
    """One serving step: token (B, 1) int32, pos () int32 — the write
    position (number of tokens already in the cache) — or (B,) int32
    per-slot write positions (entry -1 = inactive slot, no cache
    write)."""
    x, new_caches = decode_hidden(params, cfg, caches, token, pos)
    return lm_head(params["embed"], x), new_caches


def cache_insert_slot(cfg: ArchConfig, pool, req, slot: int):
    """Insert a single-request decode cache (batch size 1 — e.g. the
    cache `prefill(..., max_seq=pool length)` returns) into batch slot
    ``slot`` of a pooled decode cache. The slot's whole cache line is
    overwritten, so stale K/V from the slot's previous occupant cannot
    leak into the new request."""
    return jax.tree.map(
        lambda p, r: jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=1), pool, req)


def make_decode_cache(cfg: ArchConfig, batch, seq_len, dtype=None):
    """Allocate (or spec) the stacked KV cache for decode shapes."""
    dtype = dtype or cfg.param_dtype
    return {
        "k": jnp.zeros((cfg.n_layers, batch, seq_len, cfg.n_kv_heads,
                        cfg.hd), dtype=dtype),
        "v": jnp.zeros((cfg.n_layers, batch, seq_len, cfg.n_kv_heads,
                        cfg.hd), dtype=dtype),
    }
