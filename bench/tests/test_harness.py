"""The harness's contract outside a run: no chip, no program, no names."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run, spec


def _bench(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "smollm135m-chat",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(p):
    last = (p.stdout.strip().splitlines() or [""])[-1]
    try:
        return not isinstance(json.loads(last), dict)
    except ValueError:
        return True


def test_without_a_tpu_it_fails_and_prints_no_result():
    p = _bench(spec.ROOT)
    assert p.returncode != 0
    assert _no_result(p)
    assert "TPU" in p.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)


def test_program_is_imported_from_the_checkout_only(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    with pytest.raises(FileNotFoundError, match="no program"):
        run.import_program()


def test_every_name_resolves_to_its_own_file():
    bench = spec.benchmark()
    for cell in bench["workloads"]:
        cfg = spec.config_of(bench, cell)
        assert cfg["name"] == cell["config"]
        assert spec.traffic_of(cell)["driver"]
        assert (spec.BENCH / "reference" /
                f"{cfg['architecture']}.py").is_file()
        assert spec.metrics_of(bench, cell, False)
        assert spec.metrics_of(bench, cell, True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_harness_code_names_no_cell_config_mix_or_metric():
    bench = spec.benchmark()
    names = {c["name"] for c in bench["workloads"]} \
        | {c["name"] for c in bench["configs"]} \
        | {c["traffic"] for c in bench["workloads"]} \
        | {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for f in ["run.py", "spec.py", "generator.py", "check.py", "trace.py",
              "control.py", "faults.py", "drivers/serving.py"]:
        text = (spec.BENCH / f).read_text()
        assert not [n for n in names if f'"{n}"' in text
                    or f"'{n}'" in text], f
