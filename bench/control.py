"""Readings that a cell's correctness limit is set from, on the chip.

    python3 -m bench.control --workload <cell> --seconds <s> --seeds <n> ...
        [--fault token_altered|state_unchanged|half_batch]

For each seed, in one process: a whole run of the cell (set-up, a window
at the cell's own load, the comparison), with the named fault planted
under the timed path if one is given (`bench.faults`).  Then, on the same
served requests that the run compared, each control: the reference
computed with every matrix product in a lower precision than the
configuration states (fp8, int8), put in the program's place.  Every set
of gaps goes through the run's own judge (`bench.check.judge`).  Prints
one JSON line per seed: for the program and each control, ``correct``, the
widest gap and the tokens compared.  The limit goes between the program's
largest sound reading and the controls' smallest.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

from bench import check, faults, run, spec  # noqa: E402

PRECISIONS = ("fp8", "int8")


def reading(verdict: dict) -> dict:
    c = verdict["compared"]
    return {"correct": verdict["correct"],
            "max": c["max_logit_gap"]["value"],
            "tokens": c["tokens_compared"]["value"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = p.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    run.require_chips(cell["chips"])
    run.enable_compile_cache()
    cfg, mix = spec.config_of(bench, cell), spec.traffic_of(cell)
    ref_mod = spec.load_module("reference", cfg["architecture"])

    t = T_START
    for seed in args.seeds:
        run.import_program()
        with faults.planted(args.fault):
            seen = run.execute(cell, cfg, mix, seed, args.seconds, False, t)
        line = {"seed": seed, "fault": args.fault,
                "set_up_s": seen.setup_s,
                "reference_s": seen.phases["reference_s"],
                "program": reading(seen.verdict)}
        ref = ref_mod.Reference(cfg, seed)
        for prec in PRECISIONS:
            gaps = check.control_gaps(ref, seen.compared_requests,
                                      mix["max_seq"], prec)
            line[prec] = reading(check.judge(gaps, cfg["correct"]))
        ref = seen = None
        print(json.dumps(line), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
