"""The harness refuses to run without a TPU it knows."""
from types import SimpleNamespace

import pytest

from chipbench import env
from chipbench.tests.conftest import run_cell


def _dev(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_a_known_tpu_is_accepted():
    rec = env.device_check([_dev("tpu", "TPU v5 lite")], 1)
    assert rec["platform"] == "tpu" and rec["count"] == 1
    assert rec["peaks"]["int8_ops_per_s"] == 393e12
    assert rec["peaks"]["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("devices,chips", [
    ([_dev("cpu", "cpu")], 1),
    ([_dev("gpu", "NVIDIA H100")], 1),
    ([_dev("tpu", "TPU v9 imaginary")], 1),
    ([_dev("tpu", "TPU v5 lite")], 4),
])
def test_no_chip_or_an_unknown_one_is_refused(devices, chips):
    with pytest.raises(env.NoChip):
        env.device_check(devices, chips)


def test_a_run_on_the_cpu_exits_3_without_a_result(capsys):
    rc, last, err = run_cell(env.HERE, capsys, "--workload",
                             "mobilenetv1-224.closed", "--seed", "1",
                             "--seconds", "1", "--trace", "0")
    assert rc == 3 and last is None
    assert "no TPU" in err


def test_a_run_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark: no result."""
    import os
    import shutil
    import subprocess
    import sys

    shutil.copytree(env.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(env.REPO / "BENCHMARK.json", tmp_path)
    clean = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "mobilenetv1-224.closed", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=clean, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
