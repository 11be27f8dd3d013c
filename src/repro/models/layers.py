"""Shared functional layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Params are plain nested dicts of jnp arrays; every layer is a pair
(init_fn, apply_fn). Matmuls accumulate in fp32 (preferred_element_type)
and cast back to the activation dtype — standard large-model numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.sharding import shard


def _dense_init(rng, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else float(1.0 / np.sqrt(fan_in))
    return (jax.random.normal(rng, shape, dtype=jnp.float32)
            * scale).astype(dtype)


def matmul(x, w):
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


# --- RMSNorm ----------------------------------------------------------------

def rmsnorm_init(cfg: ArchConfig, d=None):
    return {"scale": jnp.ones((d or cfg.d_model,), dtype=cfg.param_dtype)}


def rmsnorm(p, x, eps):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --- RoPE -------------------------------------------------------------------

def rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def pos_vector(pos, batch: int):
    """Normalize a decode position to a per-slot (B,) int32 vector.

    Scalar positions (the classic all-slots-in-lock-step call) broadcast
    to every batch row; a (B,) vector passes through. Convention shared
    by every family backend: entry ``-1`` marks an *inactive* slot —
    attention skips its cache write and masks out every key, and the
    SSM recurrence keeps its previous state.
    """
    p = jnp.asarray(pos, dtype=jnp.int32)
    return jnp.broadcast_to(p, (batch,)) if p.ndim == 0 else p


# --- GQA attention ------------------------------------------------------------

def attention_init(cfg: ArchConfig, rng, d=None, n_heads=None,
                   n_kv_heads=None):
    d = d or cfg.d_model
    H = n_heads or cfg.n_heads
    Hk = n_kv_heads or cfg.n_kv_heads
    hd = cfg.hd
    ks = jax.random.split(rng, 4)
    dt = cfg.param_dtype
    return {
        "wq": _dense_init(ks[0], (d, H * hd), dt),
        "wk": _dense_init(ks[1], (d, Hk * hd), dt),
        "wv": _dense_init(ks[2], (d, Hk * hd), dt),
        "wo": _dense_init(ks[3], (H * hd, d), dt),
    }


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    b, s, hk, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, hk, n_rep, hd)).reshape(b, s, hk * n_rep,
                                                           hd)


def attention(p, cfg: ArchConfig, x, positions, *, causal=True,
              kv_cache=None, cache_pos=None, kv=None,
              n_heads=None, n_kv_heads=None, return_cache=False):
    """GQA attention.

    x: (B, S, d). kv: optional cross-attention memory (B, Sk, d).
    kv_cache: optional dict {k, v: (B, Smax, Hk, hd)}; cache_pos: () int
    or (B,) int32 — write position for the current step; returns
    (out, new_cache). A () cache_pos returns the whole updated cache.
    A (B,) cache_pos serves batch slots holding requests of unequal
    length: slot b's K/V row belongs at ``cache_pos[b]``, and the slot
    attends keys ``<= cache_pos[b]`` only, its new row included. The
    cache is only read: new_cache is just the rows, {k, v: (B, 1, Hk,
    hd)} in the cache's dtype, which the caller writes into its stacked
    cache once per step (`write_rows`). A *negative* position marks an
    inactive slot: it matches no cache row, and `write_rows` leaves its
    lines as they are, so the slot's live cache survives pooled steps
    it does not take part in.
    return_cache=True (prefill): return this call's {k, v} as the cache.
    """
    H = n_heads or cfg.n_heads
    Hk = n_kv_heads or cfg.n_kv_heads
    hd = cfg.hd
    B, S, _ = x.shape
    q = matmul(x, p["wq"]).reshape(B, S, H, hd)
    src = x if kv is None else kv
    k = matmul(src, p["wk"]).reshape(B, src.shape[1], Hk, hd)
    v = matmul(src, p["wv"]).reshape(B, src.shape[1], Hk, hd)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)

    cp = None if cache_pos is None \
        else jnp.asarray(cache_pos, dtype=jnp.int32)
    if kv is None:  # self-attention: rotary embedding
        if kv_cache is None:
            kpos = positions
        else:
            kpos = jnp.broadcast_to(cp[:, None] if cp.ndim else cp,
                                    (B, src.shape[1]))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kpos, cfg.rope_theta)

    new_cache = {"k": k, "v": v} if return_cache else None
    if kv_cache is not None:
        if cp.ndim == 0:
            z = jnp.int32(0)
            idx = (z, cp, z, z)
            ck = jax.lax.dynamic_update_slice(kv_cache["k"], k.astype(
                kv_cache["k"].dtype), idx)
            cv = jax.lax.dynamic_update_slice(kv_cache["v"], v.astype(
                kv_cache["v"].dtype), idx)
            new_cache = {"k": ck, "v": cv}
        else:
            # Per-slot decode (S == 1): slot b's new row sits at its own
            # position cp[b] for this step's attention. The select only
            # feeds the dots below; the cache itself is not rewritten,
            # and only the rows go back to the caller. A negative cp[b]
            # (inactive slot) matches no cache row.
            Smax = kv_cache["k"].shape[1]
            hit = (jnp.arange(Smax, dtype=jnp.int32)[None, :]
                   == cp[:, None])[:, :, None, None]
            new_cache = {"k": k.astype(kv_cache["k"].dtype),
                         "v": v.astype(kv_cache["v"].dtype)}
            ck = jnp.where(hit, new_cache["k"], kv_cache["k"])
            cv = jnp.where(hit, new_cache["v"], kv_cache["v"])
        k, v = ck, cv

    n_rep = H // Hk
    Sk = k.shape[1]

    if kv_cache is not None:
        # decode: grouped-GQA attention straight against the cache, in
        # the cache's dtype (the engine keeps it in f32) — no
        # head-replicated K/V materialization (16x for 128q/8kv heads);
        # the dots accumulate in fp32 via preferred_element_type.
        # Per-slot dots run at HIGHEST: at DEFAULT precision the TPU
        # rounds an f32 operand to bf16, and since the layer scan only
        # reads the stacked cache, XLA hoists that conversion of the
        # WHOLE cache out of the scan (a full read and a half-size copy
        # every step). At HIGHEST each layer reads its slice as it is.
        prec = None if cp.ndim == 0 else jax.lax.Precision.HIGHEST
        scale = float(1.0 / np.sqrt(hd))
        qg = q.reshape(B, S, Hk, n_rep, hd)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, precision=prec,
                            preferred_element_type=jnp.float32) * scale
        kpos_ids = jnp.arange(Sk, dtype=jnp.int32)
        if cp.ndim == 0:
            mask = (kpos_ids <= cp)[None, None, None, None, :]
        else:
            # per-slot causal horizon: slot b attends keys <= cp[b] only
            mask = (kpos_ids[None, :]
                    <= cp[:, None])[:, None, None, None, :]
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(x.dtype), v,
                         precision=prec, preferred_element_type=jnp.float32)
        out = out.astype(x.dtype).reshape(B, S, H, hd)
    elif S > _FLASH_THRESHOLD:
        # long-sequence prefill/training: blocked online-softmax attention
        # (never materializes the S x Sk score matrix)
        kf = _repeat_kv(k, n_rep)
        vf = _repeat_kv(v, n_rep)
        out = _flash_attention(q, kf, vf,
                               causal=causal and kv is None)
    else:
        kf = _repeat_kv(k, n_rep)
        vf = _repeat_kv(v, n_rep)
        scale = float(1.0 / np.sqrt(hd))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            kf.astype(jnp.float32)) * scale
        if causal and kv is None:
            qi = jnp.arange(S, dtype=jnp.int32)[:, None]
            ki = jnp.arange(Sk, dtype=jnp.int32)[None, :]
            logits = jnp.where((ki <= qi)[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         vf.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, S, H * hd)
    out = matmul(out, p["wo"])
    return shard(out, "batch", "seq", "d_model"), new_cache


def write_rows(cache, rows, pos):
    """Write one decode step's K/V rows into a layer-stacked cache.

    cache: {k, v: (L, B, Smax, Hk, hd)}; rows: {k, v: (L, B, 1, Hk, hd)},
    what the per-slot `attention` returns, stacked by a scan over the
    L layers; pos: (B,) int32. Slot b's rows of every layer land at
    (:, b, pos[b]) by one dynamic-update-slice per slot, and nothing
    else of the cache is touched: under a jit that donates ``cache``
    each write is in place, whatever layout the device keeps the cache
    in (a scatter would have the TPU relayout the whole cache). An
    inactive slot (pos -1) writes back the row it already holds."""
    L, B, _, Hk, hd = cache["k"].shape
    z = jnp.int32(0)
    out = dict(cache)
    for b in range(B):
        live = pos[b] >= 0
        at = (z, jnp.int32(b), jnp.where(live, pos[b], z), z, z)
        for n in out:
            old = jax.lax.dynamic_slice(out[n], at, (L, 1, 1, Hk, hd))
            out[n] = jax.lax.dynamic_update_slice(
                out[n], jnp.where(live, rows[n][:, b:b + 1], old), at)
    return out


_FLASH_THRESHOLD = 2048   # above this, use blocked attention
_FLASH_BLOCK_Q = 2048
_FLASH_BLOCK_K = 1024


def _flash_attention(q, k, v, *, causal, block_q=None, block_k=None):
    """Blocked attention with online softmax (Flash-style, pure JAX).

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd). Peak memory per step is
    O(block_q x block_k) instead of O(Sq x Sk).

    Numerics/memory (§Perf iterations 405B-2a/2b): q/k/v stay in their
    input dtype (bf16) — dots accumulate in fp32 via
    preferred_element_type; the probability block is cast back to the
    input dtype for the PV matmul (standard flash practice). Masks are
    iota-compares computed inline per step (fusible), never carried
    through the scan.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq = min(block_q or _FLASH_BLOCK_Q, Sq)
    bk = min(block_k or _FLASH_BLOCK_K, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pad_q = nq * bq - Sq
    pad_k = nk * bk - Sk
    scale = float(1.0 / np.sqrt(hd))
    qpad = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kpad = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qb = qpad.reshape(B, nq, bq, H, hd).transpose(1, 0, 3, 2, 4)
    kb = kpad.reshape(B, nk, bk, H, hd).transpose(1, 0, 3, 2, 4)
    vb = vpad.reshape(B, nk, bk, H, hd).transpose(1, 0, 3, 2, 4)

    def q_block(args):
        qi, qblk = args                                   # (), (B,H,bq,hd)
        qpos = qi * bq + jnp.arange(bq, dtype=jnp.int32)  # (bq,)

        def kv_step(carry, inp):
            m, s, acc = carry
            ki, kblk, vblk = inp
            logits = jnp.einsum(
                "bhqd,bhkd->bhqk", qblk, kblk,
                preferred_element_type=jnp.float32) * scale
            kpos = ki * bk + jnp.arange(bk, dtype=jnp.int32)
            mask = kpos[None, :] < Sk
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            logits = jnp.where(mask[None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            s_new = s * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, s_new, acc_new), None

        m0 = jnp.full((B, H, bq), -jnp.inf, dtype=jnp.float32)
        s0 = jnp.zeros((B, H, bq), dtype=jnp.float32)
        a0 = jnp.zeros((B, H, bq, hd), dtype=jnp.float32)
        (m, s, acc), _ = jax.lax.scan(
            kv_step, (m0, s0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kb, vb))
        return acc / jnp.maximum(s[..., None], 1e-30)

    out = jax.lax.map(q_block, (jnp.arange(nq, dtype=jnp.int32), qb))
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, nq * bq, H, hd)
    return out[:, :Sq].astype(q.dtype)


# --- SwiGLU MLP ---------------------------------------------------------------

def mlp_init(cfg: ArchConfig, rng, d=None, d_ff=None):
    d = d or cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    dt = cfg.param_dtype
    p = {
        "wi": _dense_init(ks[0], (d, ff), dt),
        "wo": _dense_init(ks[2], (ff, d), dt),
    }
    if cfg.mlp_gated:
        p["wg"] = _dense_init(ks[1], (d, ff), dt)
    return p


def mlp(p, x):
    if "wg" in p:     # SwiGLU
        h = jax.nn.silu(matmul(x, p["wg"]).astype(jnp.float32)
                        ).astype(x.dtype)
        h = h * matmul(x, p["wi"])
    else:             # 2-matrix GELU (GPT-BigCode / granite-code style)
        h = jax.nn.gelu(matmul(x, p["wi"]).astype(jnp.float32)
                        ).astype(x.dtype)
    h = shard(h, "batch", "seq", "ff")
    return shard(matmul(h, p["wo"]), "batch", "seq", "d_model")


# --- Embedding / LM head --------------------------------------------------------

def embedding_init(cfg: ArchConfig, rng):
    dt = cfg.param_dtype
    ks = jax.random.split(rng, 2)
    p = {"tok": _dense_init(ks[0], (cfg.vocab, cfg.d_model), dt, scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(ks[1], (cfg.d_model, cfg.vocab), dt)
    return p


def embed(p, tokens):
    out = jnp.take(p["tok"], tokens, axis=0)
    return shard(out, "batch", "seq", "d_model")


def lm_head(p, x):
    w = p["head"] if "head" in p else p["tok"].T
    logits = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return shard(logits, "batch", "seq", "vocab")
