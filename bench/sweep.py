"""Find the knee of an open-loop cell: the highest rate the system sustains.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> --rates <r> ...

One set-up, then one window per rate (the cell's mix with ``rate_rps``
replaced), the engine drained between them.  Prints one JSON line per rate:
the offered and completed output tokens per second, the cell's end-to-end
readers, and the requests still queued when the window closed.  Past the
knee the completed rate stops following the offered one and the queue
grows all through the window.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

from bench import run, spec  # noqa: E402
from bench.generator import Traffic  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    run.require_chips(cell["chips"])
    run.enable_compile_cache()
    cfg, mix = spec.config_of(bench, cell), spec.traffic_of(cell)
    run.import_program()
    driver = spec.load_module("drivers", mix["driver"])
    system = driver.setup(cfg, mix, args.seed, T_START)
    readers = {m["name"]: spec.load_module("metrics", m["name"])
               for m in spec.metrics_of(bench, cell, False)}
    for rate in args.rates:
        m = dict(mix, rate_rps=rate)
        traffic = Traffic(m, system.vocab, args.seed,
                          float(m.get("lead_s", 0.0)) + args.seconds)
        seen = driver.window(system, traffic, args.seconds, False,
                             time.perf_counter())
        queued = sum(1 for r in seen.due if not r.token_times
                     or r.token_times[0] > seen.t1)
        offered = sum(r.max_new_tokens for r in seen.due) / args.seconds
        line = {"rate_rps": rate, "offered_tok_s": offered,
                "due": len(seen.due), "waiting_at_close": queued,
                "failed": seen.failed}
        for name, mod in readers.items():
            if name != "setup_s":
                line[name] = mod.read(seen)
        print(json.dumps(line), flush=True)
        system.engine.run_until_drained(max_steps=10**6)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
