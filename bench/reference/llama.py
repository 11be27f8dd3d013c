"""Plain reference of a llama-style decoder, and the weights it is run on.

Imports nothing of the system under test.  Follows the published llama
description (arXiv:2302.13971, with grouped-query attention as in Yi,
arXiv:2403.04652): pre-norm RMSNorm blocks, rotary position embedding on
queries and keys (the two halves of each head rotated against each
other), causal grouped-query attention, a SwiGLU feed-forward, a final
RMSNorm and the output head.

The head is what the configuration states it serves: magnitude-pruned to
``head.sparsity`` (every entry whose magnitude is at or below the k-th
smallest is dropped) and its surviving values snapped to ``2**value_bits``
centroids at uniform quantiles of those values (nearest centroid, the
lower one on a tie).  Entropy coding is lossless, so it has no part here.

`Reference.logits` computes every position of one sequence in float32 at
HIGHEST matmul precision.  ``precision="fp8"`` / ``"int8"`` compute every
matrix product with both operands in that type (the control, which must
fail the comparison).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                Hk=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), ff=cfg["intermediate_size"],
                V=cfg["vocab_size"], tied=bool(cfg["tie_word_embeddings"]),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def seed_key(seed: int) -> jax.Array:
    """Any whole number, however large, to a threefry key."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("shape_key", "dtype"))
def _init(key_data, head_key_data, shape_key, dtype):
    g = dict(shape_key)
    L, d, H, Hk, hd, ff, V = (g[k] for k in ("L", "d", "H", "Hk", "hd",
                                             "ff", "V"))
    keys = iter(jax.random.split(jax.random.wrap_key_data(key_data), 16))
    head_key = jax.random.wrap_key_data(head_key_data)

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norm_scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def head(shape, scale):
        return (jax.random.normal(head_key, shape, jnp.float32)
                * scale).astype(dtype)

    w = {
        "embed": {"tok": head((V, d), 0.02) if g["tied"]
                  else normal((V, d), 0.02)},
        "layers": {
            "ln1": {"scale": norm_scale((L, d))},
            "attn": {"wq": normal((L, d, H * hd), d ** -0.5),
                     "wk": normal((L, d, Hk * hd), d ** -0.5),
                     "wv": normal((L, d, Hk * hd), d ** -0.5),
                     "wo": normal((L, H * hd, d), (H * hd) ** -0.5)},
            "ln2": {"scale": norm_scale((L, d))},
            "mlp": {"wg": normal((L, d, ff), d ** -0.5),
                    "wi": normal((L, d, ff), d ** -0.5),
                    "wo": normal((L, ff, d), ff ** -0.5)},
        },
        "final_norm": {"scale": norm_scale((d,))},
    }
    if not g["tied"]:
        w["embed"]["head"] = head((d, V), d ** -0.5)
    return w


def make_weights(cfg: dict, seed: int) -> dict:
    """The weights of a run, on the device, in one jitted call, in the type
    the configuration serves (``dtype``).  The output head (the token
    embedding when tied) comes from the configuration's ``head.seed``, the
    same in every run, so a checkout encodes it once; the rest comes from
    the run's seed."""
    g = dims(cfg)
    shape_key = tuple(sorted((k, g[k]) for k in ("L", "d", "H", "Hk", "hd",
                                                  "ff", "V", "tied")))
    return _init(seed_key(seed), seed_key(cfg["head"]["seed"]), shape_key,
                 jnp.dtype(cfg["dtype"]))


def head_weight(weights: dict) -> jax.Array:
    """The output head as (d_model, vocab)."""
    e = weights["embed"]
    return e["head"] if "head" in e else e["tok"].T


def prune_quantize(w_out_in: np.ndarray, sparsity: float,
                   value_bits: int) -> np.ndarray:
    """The served head, dense: (vocab, d_model) float32 with the pruned
    entries zero and the kept ones snapped to their centroid."""
    w = np.asarray(w_out_in, dtype=np.float32)
    mag = np.abs(w)
    k = int(round(sparsity * mag.size))
    keep = mag > np.partition(mag.ravel(), k - 1)[k - 1]
    vals = w[keep].astype(np.float64)
    cents = np.unique(np.quantile(vals, np.linspace(0.0, 1.0,
                                                    1 << value_bits)))
    hi = np.clip(np.searchsorted(cents, vals), 1, cents.size - 1)
    lo_v, hi_v = cents[hi - 1], cents[hi]
    snapped = np.where(np.abs(vals - lo_v) <= np.abs(hi_v - vals), lo_v, hi_v)
    out = np.zeros_like(w)
    out[keep] = snapped.astype(np.float32)
    return out


def _quant(x, precision):
    """Round a matmul operand to the control's type; returns the operand in
    that type (fp8) or its int8 codes with a per-row scale (int8)."""
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn), None
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).astype(jnp.int8), scale


def _mm(x, w, precision):
    """x (S, k) @ w (k, n) in float32 at HIGHEST, or with both operands in
    the control's type (int8: per-row scales of x, per-column of w)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "f32":
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.dot(_quant(x, "fp8")[0], _quant(w, "fp8")[0],
                       preferred_element_type=jnp.float32)
    qx, sx = _quant(x, "int8")
    qw, sw = _quant(w.T, "int8")
    acc = jax.lax.dot_general(qx, qw, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw.T


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, heads, hd); position p rotates pair (i, i + hd/2) by
    p * theta**(-i / (hd/2))."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("g", "precision"))
def _logits(weights, head, tokens, g, precision):
    g = dict(g)
    H, Hk, hd, eps = g["H"], g["Hk"], g["hd"], g["eps"]
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = _rope(_mm(h, p["attn"]["wq"], precision).reshape(S, H, hd),
                  g["theta"])
        k = _rope(_mm(h, p["attn"]["wk"], precision).reshape(S, Hk, hd),
                  g["theta"])
        v = _mm(h, p["attn"]["wv"], precision).reshape(S, Hk, hd)
        k = jnp.repeat(k, H // Hk, axis=1)
        v = jnp.repeat(v, H // Hk, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(S, H * hd), p["attn"]["wo"], precision)
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        f = jax.nn.silu(_mm(h, p["mlp"]["wg"], precision)) \
            * _mm(h, p["mlp"]["wi"], precision)
        return x + _mm(f, p["mlp"]["wo"], precision), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"]["scale"], eps)
    return _mm(x, head.T, precision)


class Reference:
    """The reference of one run: its weights made anew from the seed, its
    served head pruned and quantized here."""

    def __init__(self, cfg: dict, seed: int):
        self.g = tuple(sorted(dims(cfg).items()))
        self.weights = make_weights(cfg, seed)
        hc = cfg["head"]
        w = np.asarray(head_weight(self.weights).astype(jnp.float32)).T
        self.head = jnp.asarray(prune_quantize(w, hc["sparsity"],
                                               hc["value_bits"]))

    def logits(self, tokens: np.ndarray, pad_to: int,
               precision: str = "f32") -> np.ndarray:
        """(len(tokens), vocab) float32 logits of every position.  The
        sequence is padded at its end to ``pad_to`` so one program serves
        every length; causal attention keeps the padding out."""
        n = len(tokens)
        t = np.zeros(pad_to, dtype=np.int32)
        t[:n] = tokens
        out = _logits(self.weights, self.head, jnp.asarray(t), self.g,
                      precision)
        return np.asarray(out[:n])


def flops_per_token(cfg: dict, context: int) -> float:
    """Model FLOPs of one decoded token at ``context`` cached positions:
    every matrix product of the body, the head as its dense d x vocab
    product, and attention's scores and weighted sum over the context."""
    g = dims(cfg)
    d, hd, H, Hk = g["d"], g["hd"], g["H"], g["Hk"]
    per_layer = d * H * hd * 2 + d * Hk * hd * 2 + 3 * d * g["ff"]
    return (2.0 * (g["L"] * per_layer + d * g["V"])
            + 4.0 * g["L"] * H * hd * context)
