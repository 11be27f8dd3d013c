"""Chip smoke: the system's main path, once, on one TPU.

Not a benchmark: the seconds it prints are set-up and first-call times.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips  # the sharded head on 4 chips only

(a) Kernels.  The smollm-135m LM head (49152 x 576, random weights from
    ``--seed``), pruned to sparsity 0.8 and codebook-quantized in f32, runs
    through ``ops.spmm`` at B = 1 and B = 4 as CSR-dtANS and as each
    uncoded family (SELL, RGCSR, BCSR).  Every result is checked against a
    plain f32 reference: decode to dense, then ``jnp.dot`` at HIGHEST.
    Also reported: whether the ``pipeline`` and ``shared_cols`` schedules
    equal the plain kernel bit for bit.
(b) Serving.  The full-width smollm-135m ``Engine`` (30 layers, d_model
    576, 9/3 heads, d_ff 1536, vocab 49152), dense and with the
    compressed head, slots 4, max_seq 256, answers 8 requests (prompt
    lengths 16-64, 16 new tokens each).  One pooled step's compressed-head
    logits are checked against ``SparseLinear.apply_dense_reference``.

``--four-chips`` builds the head sharded over a 4-device ``model`` mesh,
checks that each device holds exactly one shard, and compares its
``apply`` with the single-device kernel and the f32 reference.

The script fails (exit != 0, no result line) at the first problem, and
when JAX finds no TPU.  Its last line is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: Tolerance of a kernel result against the f32 reference, relative to
#: the row's magnitude sum_j |a_ij x_j|.  The kernel and the reference add
#: the same f32 products (<= 576 per row) in different orders; each order
#: is within n * 2^-24 ~ 3.4e-5 of the exact sum, relative to that
#: magnitude, so 1e-4 leaves 3x headroom and still catches any wrong
#: column, value or row (each of which moves a row by O(|a_ij x_j|)).
RTOL = 1e-4
ATOL = 1e-6

SMOLLM = "smollm-135m"
SPARSITY = 0.8
PROMPT_LENS = (16, 64, 32, 48)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_close(name, got, ref, mag):
    import numpy as np
    got, ref, mag = (np.asarray(a, dtype=np.float64) for a in (got, ref,
                                                               mag))
    if got.shape != ref.shape:
        fail(f"{name}: shape {got.shape} != reference {ref.shape}")
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite output")
    err = np.abs(got - ref)
    bound = RTOL * mag + ATOL
    worst = float((err / bound).max()) if err.size else 0.0
    if worst > 1.0:
        i = np.unravel_index(int(np.argmax(err / bound)), err.shape)
        fail(f"{name}: |got - ref| = {err[i]:.3e} > bound {bound[i]:.3e} "
             f"at {i}")
    log(f"{name}: ok (max error / bound = {worst:.3f})")


def reference(w_dense, x):
    """f32 reference y = W x and its magnitude |W| |x|, both at HIGHEST."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    hi = jax.lax.Precision.HIGHEST
    w, xj = jnp.asarray(w_dense), jnp.asarray(x)
    return (np.asarray(jnp.dot(w, xj, precision=hi)),
            np.asarray(jnp.dot(jnp.abs(w), jnp.abs(xj), precision=hi)))


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def build_head(seed: int):
    """smollm-135m params (random, from ``seed``) and its compressed
    head; returns (cfg, params, head)."""
    import jax

    from repro import configs
    from repro.models import api
    from repro.serving.engine import Engine

    cfg = configs.get(SMOLLM)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    head = Engine.compress_lm_head(cfg, params, sparsity=SPARSITY)
    log(f"encode: head {head.d_out}x{head.d_in} -> CSR-dtANS "
        f"{head.compressed_bytes:,} B ({head.compression_vs_dense:.2f}x vs "
        f"dense) in {time.perf_counter() - t0:.1f} s")
    return cfg, params, head


def phase_kernels(head, rng):
    import numpy as np

    from repro.core.bcsr_dtans import encode_bcsr_matrix
    from repro.core.csr_dtans import decode_matrix
    from repro.kernels import ops
    from repro.kernels.bcsr_spmv import pack_bcsr
    from repro.kernels.rgcsr_spmv import pack_rgcsr
    from repro.kernels.sell_spmv import pack_sell
    from repro.sparse.bcsr import BCSR
    from repro.sparse.formats import CSR
    from repro.sparse.rgcsr import RGCSR

    a = decode_matrix(head.mat)                      # (49152, 576) CSR
    w = a.to_dense()
    t0 = time.perf_counter()
    families = {
        "csr_dtans": (head.mat, ops.spmm),
        "sell": (pack_sell(a, lane_width=128), ops.sell_spmm),
        "rgcsr": (pack_rgcsr(RGCSR.from_csr(a, 8)), ops.rgcsr_spmm),
        "bcsr": (pack_bcsr(BCSR.from_csr(a, (4, 4))), ops.bcsr_spmm),
    }
    log(f"pack: uncoded families in {time.perf_counter() - t0:.1f} s")
    for B in (1, 4):
        x = rng.standard_normal((a.shape[1], B)).astype(np.float32)
        ref, mag = reference(w, x)
        for name, (mat, spmm) in families.items():
            y, first = timed(lambda: spmm(mat, x))
            _, warm = timed(lambda: spmm(mat, x))
            log(f"{name} B={B}: first call {first:.2f} s (compile + run), "
                f"second {warm * 1e3:.1f} ms")
            check_close(f"{name} B={B}", y, ref, mag)
        y = ops.spmm(head.mat, x)
        yp = ops.spmm(head.mat, x, pipeline=True)
        check_close(f"csr_dtans pipeline B={B}", yp, ref, mag)
        log(f"csr_dtans pipeline B={B}: bit-identical to plain = "
            f"{bool(np.array_equal(np.asarray(y), np.asarray(yp)))}")

    # shared_cols needs a block-filled encode: a block-sparse matrix of
    # the head's width (4x4 blocks, 20% of them nonzero).
    m, n, r = 4096, a.shape[1], 4
    mask = np.kron(rng.random((m // r, n // r)) < 0.2, np.ones((r, r)))
    vals = rng.choice(np.unique(a.values)[:256], size=(m, n))
    bm = encode_bcsr_matrix(CSR.from_dense((mask * vals).astype(np.float32)),
                            block_shape=(r, r))
    for B in (1, 4):
        x = rng.standard_normal((n, B)).astype(np.float32)
        ref, mag = reference((mask * vals).astype(np.float32), x)
        yg = ops.spmm(bm, x, fused=False)
        yf = ops.spmm(bm, x, fused=True)
        check_close(f"bcsr_dtans generic B={B}", yg, ref, mag)
        check_close(f"bcsr_dtans shared_cols B={B}", yf, ref, mag)
        log(f"bcsr_dtans shared_cols B={B}: bit-identical to generic = "
            f"{bool(np.array_equal(np.asarray(yg), np.asarray(yf)))}")


def phase_serving(cfg, params, head, rng):
    import numpy as np

    from repro.core.csr_dtans import decode_matrix
    from repro.serving.engine import Engine

    pooled = []
    apply = head.apply

    def apply_and_keep(x, **kw):
        if not pooled and x.shape[0] == 4:
            pooled.append(x)
        return apply(x, **kw)

    head.apply = apply_and_keep
    prompts = [rng.integers(0, cfg.vocab, size=PROMPT_LENS[k % 4])
               for k in range(8)]
    for label, sparse in (("dense", None), ("compressed", head)):
        eng = Engine(cfg, params, slots=4, max_seq=256, sparse_head=sparse)
        reqs = [eng.submit(p, 16) for p in prompts]
        t0 = time.perf_counter()
        done = eng.run_until_drained()
        dt = time.perf_counter() - t0
        if eng.truncated or len(done) != len(reqs):
            fail(f"{label}: {len(done)}/{len(reqs)} requests finished")
        if any(not r.done or len(r.out) != 16 for r in reqs):
            fail(f"{label}: a request did not produce 16 tokens")
        toks = sum(len(r.out) for r in reqs)
        log(f"serving {label}: {len(done)}/{len(reqs)} requests, {toks} "
            f"tokens in {dt:.1f} s including compiles")
    head.apply = apply
    if not pooled:
        fail("no pooled step with 4 active slots reached the head")
    h = np.asarray(pooled[0], dtype=np.float32).reshape(4, head.d_in)
    got = head.apply(h)
    ref = head.apply_dense_reference(h)
    _, mag = reference(decode_matrix(head.mat).to_dense(), h.T)
    check_close("serving pooled-step logits", got, ref, mag.T)


def phase_four_chips(seed: int, rng):
    import jax
    import numpy as np

    from repro import configs
    from repro.core.csr_dtans import decode_matrix
    from repro.kernels import ops, shard_ops
    from repro.models import api
    from repro.serving.engine import Engine

    cfg = configs.get(SMOLLM)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    mesh = jax.make_mesh((4,), ("model",))
    t0 = time.perf_counter()
    sharded = Engine.compress_lm_head(cfg, params, sparsity=SPARSITY,
                                      mesh=mesh)
    log(f"encode: 4 head shards in {time.perf_counter() - t0:.1f} s")
    placed, *_ = shard_ops.place_on_mesh(sharded.plan, mesh)
    for a in placed:
        devs = [s.device for s in a.addressable_shards]
        if len(set(devs)) != 4 or any(s.data.shape[0] != 1
                                      for s in a.addressable_shards):
            fail(f"shards not one per device: {devs}")
    log("placement: every stacked operand holds one shard per device")
    wd = decode_matrix(sharded.mat).to_dense()
    for B in (1, 4):
        x = rng.standard_normal((B, sharded.d_in)).astype(np.float32)
        y4, first = timed(lambda: sharded.apply(x))
        log(f"sharded B={B}: first call {first:.2f} s")
        y1 = ops.spmm(sharded.mat, x.T).T
        ref, mag = reference(wd, x.T)
        check_close(f"sharded B={B}", y4, ref.T, mag.T)
        check_close(f"single-device B={B}", y1, ref.T, mag.T)
        log(f"sharded B={B}: bit-identical to single-device = "
            f"{bool(np.array_equal(np.asarray(y4), np.asarray(y1)))}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded head on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        import jax

        from repro.backend import device_label, enable_compilation_cache
    except ImportError as e:
        fail(f"cannot import the program ({e}); run from the repo root")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices only")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"--four-chips needs 4 TPU devices; found {len(devices)}")
    cache = enable_compilation_cache()
    dev = device_label()
    log(f"smoke, not a benchmark: {dev['kind']} x{dev['count']}, kernels "
        f"{'interpreted' if dev['interpret'] else 'compiled'}, "
        f"compile cache {cache}")

    import numpy as np
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed, rng)
    else:
        cfg, params, head = build_head(args.seed)
        phase_kernels(head, rng)
        phase_serving(cfg, params, head, rng)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))


if __name__ == "__main__":
    main()
