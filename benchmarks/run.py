"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

  fig4    — delta-encoding entropy reduction (random graph models)
  fig6    — compression vs best of CSR/COO/SELL + Table I success rates
  fig7/8  — modeled SpMVM speedup, warm (Table II) & cold (Table III)
  fig9    — vs oracle format selector (AlphaSparse stand-in), including
            measured-refinement regret (wall-clock timed kernels)
  batch   — batched selection: selector-vs-oracle regret with B right-
            hand sides per pass (B in {1, 8, 32, 128}; the winning
            format flips once per-RHS contraction work overtakes the
            amortized per-pass costs)
  shard   — sharded selection: selector-vs-oracle regret at pinned
            shard counts {1, 4} plus the ``select(mesh=)`` sweep that
            lets the argmin pick the chip count per matrix
  calib   — MachineModel calibration: fit cost-model constants to
            measured kernel times; ``--profile-json`` persists the
            fitted machine profile (CI uploads it as an artifact)
  tiles   — kernel tile/pipeline microbench (fig9tile rows): grid-
            blocked SpMM best-tile-config vs untiled over a batch
            sweep, fused BCSR-dtANS block-decode vs the generic
            gather path, pipelined decode vs serial — every row
            carries a bit_identical flag the tile-smoke CI leg gates on
  roofline— summary of the dry-run roofline table when present

``--only`` accepts a comma-separated list (``--only fig9,batch``) so
one smoke JSON can carry several sections.
"""

from __future__ import annotations

import argparse
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="trimmed sizes (CI)")
    ap.add_argument("--only", default=None,
                    help="run only these sections (comma-separated, "
                         "e.g. 'fig9,batch')")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a JSON list of "
                         "{name, us_per_call, derived} objects (CI "
                         "artifact)")
    ap.add_argument("--no-measure", action="store_true",
                    help="skip wall-clock kernel timing in fig9 "
                         "(modeled-only rows)")
    ap.add_argument("--profile-json", default=None, metavar="PATH",
                    help="persist the calib section's fitted machine "
                         "profile to this JSON file (CI artifact)")
    ap.add_argument("--mtx-dir", default=None, metavar="PATH",
                    help="directory of MatrixMarket files (.mtx / "
                         ".mtx.gz, e.g. SuiteSparse downloads) fed "
                         "through repro.sparse.io into the fig9 "
                         "selection suite")
    ap.add_argument("--max-nnz", default=2_000_000, type=int,
                    help="skip --mtx-dir files with more stored "
                         "nonzeros than this (default 2e6; the "
                         "exhaustive oracle encodes every candidate)")
    args, _ = ap.parse_known_args()

    from repro.backend import enable_compilation_cache
    enable_compilation_cache()

    from benchmarks import (bench_batch_selection, bench_calibration,
                            bench_compression, bench_delta_entropy,
                            bench_format_selection, bench_kernel_tiles,
                            bench_shard_selection, bench_spmv)

    print("name,us_per_call,derived")
    sections = {
        "fig4": lambda: bench_delta_entropy.run(small=args.small),
        "fig6": lambda: bench_compression.run(small=args.small),
        "fig7": lambda: bench_spmv.run(small=args.small, warm=True),
        "fig8": lambda: bench_spmv.run(small=args.small, warm=False,
                                       measure=False),
        "fig9": lambda: bench_format_selection.run(
            small=args.small, measure=not args.no_measure,
            mtx_dir=args.mtx_dir, max_nnz=args.max_nnz),
        "batch": lambda: bench_batch_selection.run(small=args.small),
        "shard": lambda: bench_shard_selection.run(small=args.small),
        "calib": lambda: bench_calibration.run(
            small=args.small, profile_json=args.profile_json),
        "tiles": lambda: bench_kernel_tiles.run(small=args.small),
    }
    only = set(args.only.split(",")) if args.only else None
    collected = []
    for name, fn in sections.items():
        if only is not None and name not in only:
            continue
        for row in fn():
            collected.append(row)
            print(",".join(str(x) for x in row), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump([{"name": r[0], "us_per_call": r[1],
                        "derived": r[2]} for r in collected], f, indent=1)

    # roofline summary from dry-run artifacts, if present
    ddir = os.path.join(os.path.dirname(__file__), "..",
                        "experiments", "dryrun")
    if os.path.isdir(ddir) and not args.only:
        for f in sorted(os.listdir(ddir)):
            if not f.endswith(".json"):
                continue
            rec = json.load(open(os.path.join(ddir, f)))
            if rec.get("status") != "ok":
                continue
            r = rec["roofline"]
            print(f"roofline/{rec['arch']}_{rec['shape']}_{rec['mesh']},"
                  f"0.0,dom={r['dominant']};compute_s={r['compute_s']:.3e};"
                  f"memory_s={r['memory_s']:.3e};"
                  f"collective_s={r['collective_s']:.3e}", flush=True)


if __name__ == "__main__":
    main()
