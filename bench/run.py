"""One run of one benchmark cell.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Needs a TPU with as many chips as the cell
asks for; without one it exits non-zero and prints no result.  Set-up is
timed from the start of this module to the first timed request.  The last
line of standard output is the result, one JSON object; the numbers that
decided ``correct`` are also the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench import spec, trace  # noqa: E402

#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so that only a checkout's first run of a cell compiles.
JAX_CACHE = spec.BENCH / ".cache" / "jax"


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chips(n: int):
    """The devices of this run; exits non-zero unless JAX finds at least
    ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs


def enable_compile_cache() -> None:
    import jax
    JAX_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def import_program() -> None:
    """Put the checkout's ``src/`` first on the path; the program is
    imported from there or not at all."""
    src = spec.ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise FileNotFoundError(f"no program under {src}")
    sys.path.insert(0, str(src))


def execute(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
            traced: bool, t_start: float):
    """Run the cell (no device check here) and return what it observed."""
    import jax

    import_program()
    driver = spec.load_module("drivers", mix["driver"])
    run = driver.run(cell, cfg, mix, seed, seconds, traced, t_start)
    run.device_kind = jax.devices()[0].device_kind
    return run


def measure(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
            seconds: float, traced: bool, t_start: float) -> dict:
    """Run the cell and build its result line."""
    return result_line(bench, cell, execute(cell, cfg, mix, seed, seconds,
                                            traced, t_start), traced)


def result_line(bench: dict, cell: dict, run, traced: bool) -> dict:
    """The result line of one observed run, with ``notes`` for stdout."""
    import jax
    import numpy as np

    metrics = {}
    for m in spec.metrics_of(bench, cell, traced):
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d), "memory_peak_bytes": run.memory_peak}
    result = {"correct": run.verdict["correct"],
              "attempted": len(run.due), "failed": run.failed,
              "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace.top(run.trace["op_s"]),
            "idle_gaps": trace.top(run.trace["idle_s_by_host"])}
    late = np.asarray(run.lateness) * 1e3 if run.lateness else np.zeros(1)
    result["notes"] = {
        "set_up_s": run.setup_s, "head_cached": run.head_cache["cached"],
        "encode_s": run.head_cache["encode_s"],
        "window_compiles": run.compiles,
        "lateness_ms_p50": float(np.percentile(late, 50)),
        "lateness_ms_p95": float(np.percentile(late, 95)),
        "lateness_ms_max": float(late.max()),
        "steps": len(run.steps), "requests": len(run.requests),
        "phases_s": run.phases}
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in run.verdict["compared"].items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    require_chips(cell["chips"])
    enable_compile_cache()
    result = measure(bench, cell, spec.config_of(bench, cell),
                     spec.traffic_of(cell), args.seed, args.seconds,
                     bool(args.trace), T_START)
    notes = result.pop("notes")
    print("bench: " + json.dumps(notes), flush=True)
    for k, v in result["compared"].items():
        print(f"bench: compared {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
