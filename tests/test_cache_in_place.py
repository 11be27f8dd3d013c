"""The pooled decode cache is written one row per slot, in place.

Per-slot decode used to rewrite every layer's whole ``(B, max_seq)`` K/V
slice with a ``jnp.where`` inside the layer scan. Now `layers.attention`
returns only the step's rows and `layers.write_rows` puts them into the
stacked cache after the scan. The reference kept here is the old write,
so each family's ``decode_hidden`` is held to it bit for bit: the same
hidden states and the same cache, with slots at mixed positions, an
inactive slot (``pos == -1``) whose lines must survive, and a slot that
writes the last row. The engine's slot insert runs under a donated jit
and must leave every other slot as it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import api, encdec, hybrid, layers, transformer

SLOTS, MAX_SEQ = 4, 16
# Mixed positions, an inactive slot, and a write of the last row.
POS = np.array([3, 9, -1, MAX_SEQ - 1], dtype=np.int32)
INACTIVE = 2

FAMILIES = {
    "dense": ("smollm-135m", {}),
    "dense-bf16": ("smollm-135m", {"dtype": "bfloat16"}),
    "hybrid": ("zamba2-7b", {}),
    "encdec": ("seamless-m4t-large-v2", {}),
}


def _where_attention(p, cfg, x, positions, *, kv_cache=None,
                     cache_pos=None, **kw):
    """Per-slot decode as the old `layers.attention` did it: every layer
    returns its whole cache slice, rebuilt by a ``jnp.where`` over all
    ``max_seq`` rows, and attends against that."""
    if kv_cache is None or np.ndim(cache_pos) == 0:
        return layers.attention(p, cfg, x, positions, kv_cache=kv_cache,
                                cache_pos=cache_pos, **kw)
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B = x.shape[0]
    cp = jnp.asarray(cache_pos, dtype=jnp.int32)
    q = layers.matmul(x, p["wq"]).reshape(B, 1, H, hd)
    k = layers.matmul(x, p["wk"]).reshape(B, 1, Hk, hd)
    v = layers.matmul(x, p["wv"]).reshape(B, 1, Hk, hd)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, jnp.broadcast_to(cp[:, None], (B, 1)),
                    cfg.rope_theta)
    Smax = kv_cache["k"].shape[1]
    hit = (jnp.arange(Smax, dtype=jnp.int32)[None, :]
           == cp[:, None])[:, :, None, None]
    ck = jnp.where(hit, k.astype(kv_cache["k"].dtype), kv_cache["k"])
    cv = jnp.where(hit, v.astype(kv_cache["v"].dtype), kv_cache["v"])
    scale = float(1.0 / np.sqrt(hd))
    qg = q.reshape(B, 1, Hk, H // Hk, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(Smax, dtype=jnp.int32)[None, :]
            <= cp[:, None])[:, None, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(x.dtype), cv,
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(B, 1, H * hd)
    return layers.matmul(out, p["wo"]), {"k": ck, "v": cv}


def _reference(cfg, params, cache, tok, pos):
    """``decode_hidden`` with the old whole-slice write in every family."""
    with pytest.MonkeyPatch.context() as mp:
        for mod in (transformer, hybrid, encdec):
            mp.setattr(mod, "attention", _where_attention)
            mp.setattr(mod, "write_rows", lambda c, full, pos: full)
        return jax.jit(lambda p, c, t, q: api.decode_hidden(
            p, cfg, c, t, q))(params, cache, tok, pos)


def _random_like(tree, seed):
    """Every leaf of ``tree`` filled with seeded random values."""
    leaves, tdef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tdef.unflatten([
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


def _slot_axis(pool_leaf, req_leaf):
    return next(i for i, (a, b) in enumerate(zip(pool_leaf.shape,
                                                 req_leaf.shape)) if a != b)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    arch, kw = FAMILIES[request.param]
    cfg = get_smoke(arch).with_(**kw)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cache = _random_like(api.make_decode_cache(cfg, SLOTS, MAX_SEQ,
                                               dtype=jnp.float32), 1)
    tok = jnp.asarray(np.arange(SLOTS, dtype=np.int32)[:, None] + 5)
    return cfg, params, cache, tok


def _in_place_step(cfg, params, cache, tok, pos):
    step = jax.jit(lambda p, c, t, q: api.decode_hidden(p, cfg, c, t, q),
                   donate_argnums=1)
    return step(params, _copy(cache), tok, pos)


def test_decode_hidden_matches_the_whole_slice_write(family):
    cfg, params, cache, tok = family
    pos = jnp.asarray(POS)
    want_h, want_c = _reference(cfg, params, cache, tok, pos)
    got_h, got_c = _in_place_step(cfg, params, cache, tok, pos)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    assert jax.tree.structure(got_c) == jax.tree.structure(want_c)
    for got, want in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _kv_leaves(cache):
    """The layer-stacked attention K/V leaves: (L, B, Smax, Hk, hd)."""
    kv = cache.get("attn", cache.get("kv", cache))
    return [kv["k"], kv["v"]]


def test_only_active_rows_change(family):
    """Each active slot's K/V changes in its one row ``pos[b]`` of every
    layer, the last row included; the inactive slot's lines (its last
    row too, which a wrapped ``-1`` index would hit) stay as they were."""
    cfg, params, cache, tok = family
    _, new = _in_place_step(cfg, params, cache, tok, jnp.asarray(POS))
    for old, got in zip(_kv_leaves(cache), _kv_leaves(new)):
        old, got = np.asarray(old), np.asarray(got)
        changed = np.any(old != got, axis=(0, 3, 4))       # (B, Smax)
        want = np.zeros_like(changed)
        for b, p in enumerate(POS):
            if p >= 0:
                want[b, p] = True
        np.testing.assert_array_equal(changed, want)
        np.testing.assert_array_equal(got[:, INACTIVE], old[:, INACTIVE])


@pytest.mark.parametrize("slot", [0, 2, SLOTS - 1])
def test_insert_slot_under_donated_jit_leaves_other_slots(family, slot):
    cfg, _, pool, _ = family
    req = _random_like(api.make_decode_cache(cfg, 1, MAX_SEQ,
                                             dtype=cfg.param_dtype), 7)
    insert = jax.jit(lambda c, r, s: api.cache_insert_slot(cfg, c, r, s),
                     donate_argnums=0)
    given = _copy(pool)
    got = insert(given, req, slot)
    assert all(a.is_deleted() for a in jax.tree.leaves(given))
    for old, r, new in zip(jax.tree.leaves(pool), jax.tree.leaves(req),
                           jax.tree.leaves(got)):
        ax = _slot_axis(old, r)
        old, new = np.asarray(old), np.asarray(new)
        keep = [s for s in range(SLOTS) if s != slot]
        np.testing.assert_array_equal(np.take(new, keep, axis=ax),
                                      np.take(old, keep, axis=ax))
        np.testing.assert_array_equal(
            np.take(new, [slot], axis=ax),
            np.asarray(r).astype(new.dtype))
