"""Family dispatcher: one uniform API over the 10-arch zoo.

  init_params(cfg, rng)                     -> params pytree
  forward(params, cfg, batch)               -> (logits, aux)
  loss_fn(params, cfg, batch)               -> (loss, metrics)
  prefill(params, cfg, batch, max_seq)      -> (logits, cache, pos)
  decode_step(params, cfg, cache, tok, pos) -> (logits, cache)
  decode_hidden(params, cfg, cache, tok, pos) -> (hidden, cache)
  make_decode_cache(cfg, batch_size, seq)   -> cache pytree
  cache_insert_slot(cfg, pool, req, slot)   -> pool cache pytree

``pos`` in the decode entry points is either a () scalar (every batch
row decodes at the same position — the classic lock-step call) or a
(B,) int32 vector of *per-slot* positions: row b writes its KV at
``pos[b]`` and attends keys ``<= pos[b]``; entry ``-1`` marks an
inactive slot whose cache lines (KV, SSM state, conv tail) pass
through unmodified.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, transformer
from repro.models.config import ArchConfig

_MOE_AUX_WEIGHT = 0.01


def _mod(cfg: ArchConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer
    if cfg.family in ("ssm", "hybrid"):
        return hybrid
    if cfg.family == "encdec":
        return encdec
    raise ValueError(f"unknown family {cfg.family}")


def init_params(cfg: ArchConfig, rng):
    return _mod(cfg).init_params(cfg, rng)


def forward(params, cfg: ArchConfig, batch):
    return _mod(cfg).forward(params, cfg, batch)


def forward_hidden(params, cfg: ArchConfig, batch):
    """(hidden, aux): the LM-head input over all token positions — for
    callers that supply their own unembed (e.g. a compressed
    `SparseLinear` head contracting all B*S rows through the blocked
    SpMM kernel). Transformer families only."""
    m = _mod(cfg)
    if not hasattr(m, "forward_hidden"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no forward_hidden")
    return m.forward_hidden(params, cfg, batch)


def loss_fn(params, cfg: ArchConfig, batch):
    """Masked next-token cross entropy (+ MoE load-balance aux)."""
    logits, aux = forward(params, cfg, batch)
    logits = logits.astype(jnp.float32)
    targets = batch["targets"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(targets, dtype=jnp.float32)
    mask = mask.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction, NOT take_along_axis: gathering along the
    # model-sharded vocab axis would force an all-gather of full fp32
    # logits (observed +12 GiB/chip on smollm dry-run); the iota-compare
    # form fuses into a local reduction + tiny all-reduce.
    V = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == targets[..., None], logits, 0.0),
                   axis=-1)
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    metrics = {"nll": loss, "aux": aux,
               "tokens": mask.sum().astype(jnp.float32)}
    if cfg.family == "moe":
        loss = loss + _MOE_AUX_WEIGHT * aux
    return loss, metrics


def prefill(params, cfg: ArchConfig, batch, max_seq=None):
    m = _mod(cfg)
    if not hasattr(m, "prefill"):
        raise NotImplementedError(f"{cfg.family} has no prefill")
    return m.prefill(params, cfg, batch, max_seq=max_seq)


def decode_step(params, cfg: ArchConfig, caches, token, pos):
    return _mod(cfg).decode_step(params, cfg, caches, token, pos)


def decode_hidden(params, cfg: ArchConfig, caches, token, pos):
    """One serving step stopping at the final norm: (hidden, cache)
    with hidden (B, 1, d_model) — what a compressed LM head
    (`repro.serving.sparse_linear.SparseLinear`) consumes in place of
    `decode_step`'s dense-logits path. ``decode_step(...) ==
    (lm_head(params["embed"], hidden), cache)`` for every family.

    The returned cache is ``caches`` with this step's state of each
    active slot written in: one K/V row per attention layer (and the
    new SSM state where the family has one). The serving engine
    donates ``caches`` to its jit, so the write is in place: a caller
    that donates must not reuse the cache it passed in."""
    return _mod(cfg).decode_hidden(params, cfg, caches, token, pos)


def make_decode_cache(cfg: ArchConfig, batch_size: int, seq_len: int,
                      dtype=None):
    return _mod(cfg).make_decode_cache(cfg, batch_size, seq_len,
                                       dtype=dtype)


def cache_insert_slot(cfg: ArchConfig, pool, req, slot: int):
    """Insert a batch-size-1 decode cache ``req`` (e.g. returned by
    `prefill(..., max_seq=<pool seq len>)`) into batch slot ``slot`` of
    the pooled decode cache ``pool``. Every cache line of the slot is
    overwritten — the serving engine uses this to admit a freshly
    prefilled request into a slot whose previous occupant finished,
    without leaking the old request's KV/SSM state. The engine runs
    it in a jit (``jit_engine_insert_slot``) that donates ``pool``, so
    only the slot's lines are written; a caller that donates must not
    reuse the pool it passed in."""
    return _mod(cfg).cache_insert_slot(cfg, pool, req, slot)


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
