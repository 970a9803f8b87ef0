"""The entry point refuses to measure without a chip: a non-zero exit
and no result line, here on the CPU and in a directory that holds only
the benchmark's own files."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "serve_backlog", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p.stdout)


def test_every_cell_resolves_to_its_files():
    from bench import run
    from repro.core import bnn
    spec = run.load_spec()
    for w in spec["workloads"]:
        cell = run.make_cell(spec, w["name"], 1, 1.0, False, 0.0)
        assert (ROOT / "bench" / "drivers" / f"{cell.config['kind']}.py").exists()
        assert {"arrivals", "keep_queued", "sizes"} <= set(cell.mix)
        assert callable(getattr(bnn, cell.config["deployment"]["packer"]))
        assert cell.config["deployment"]["chips"] == cell.chips
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert set(m.get("workloads", [])) <= {w["name"] for w in spec["workloads"]}
