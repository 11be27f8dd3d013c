"""Serving launcher: batched greedy decoding with the slot engine,
optionally with a CSR-dtANS-compressed (pruned + entropy-coded) LM head —
the paper's technique in the serving path.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --smoke --requests 8 --sparse-head
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.backend import device_label, enable_compilation_cache
from repro.models import api
from repro.serving.engine import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--sparse-head", action="store_true",
                    help="prune + CSR-dtANS-encode the LM head and report "
                         "its compression (paper technique)")
    ap.add_argument("--sparsity", type=float, default=0.8)
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    sparse_head = None
    if args.sparse_head:
        sparse_head = Engine.compress_lm_head(cfg, params,
                                              sparsity=args.sparsity)
        print(f"LM head: {sparse_head.dense_bytes:,} B dense -> "
              f"{sparse_head.compressed_bytes:,} B CSR-dtANS "
              f"({sparse_head.compression_vs_dense:.2f}x vs dense, "
              f"{sparse_head.compression_vs_best_sparse:.2f}x vs best "
              f"sparse format)")

    eng = Engine(cfg, params, slots=args.slots, max_seq=args.max_seq,
                 sparse_head=sparse_head)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, size=args.prompt_len),
                       args.max_new_tokens) for _ in range(args.requests)]
    t0 = time.time()
    eng.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    dev = device_label()
    mode = "interpreted" if dev["interpret"] else "compiled"
    print(f"served {sum(r.done for r in reqs)}/{len(reqs)} requests, "
          f"{toks} tokens in {dt:.1f}s ({toks/max(dt,1e-9):.1f} tok/s, "
          f"{dev['platform']} {dev['kind']} x{dev['count']}, "
          f"kernels {mode})")


if __name__ == "__main__":
    main()
