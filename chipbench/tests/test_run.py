"""The command refuses to run anywhere but on a TPU."""
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as ei:
        run.main(["--workload", "smollm-360m.chat", "--seed", str(2**40), "--seconds", "1"])
    assert ei.value.code not in (0, None)
    assert "no TPU" in str(ei.value.code)
    assert not any(line.startswith("{") for line in capsys.readouterr().out.splitlines())


def test_arguments_are_checked():
    with pytest.raises(SystemExit) as ei:
        run.parse(["--workload", "x", "--seed", "-1", "--seconds", "1"])
    assert ei.value.code not in (0, None)


def test_benchmark_files_alone_do_not_run(tmp_path):
    # a directory with only BENCHMARK.json and the benchmark's own files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "smollm-360m.chat",
                        "--seed", "5", "--seconds", "1"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120, env={"PATH": "/usr/bin:/bin",
                                                    "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_finds_its_files():
    from chipbench import harness
    from chipbench.tests import small

    bench = small.bench()  # BENCHMARK.json and the prefill cell
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert (harness.HERE / "drivers" / f"{cell.mix['driver']}.py").is_file()
        for m in cell.end_to_end + cell.per_layer:
            assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        harness.program_config(cell)  # the program's config is the file's
