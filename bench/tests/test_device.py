"""The peaks table and the hard failure without a TPU."""
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from harness import device, registry


def chip(kind="TPU v5 lite", platform="tpu"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_peaks_of_v5e_as_published():
    pk = device.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9


def test_unknown_kind_is_an_error():
    with pytest.raises(device.DeviceError, match="no entry in the peaks"):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(device.DeviceError, match="no entry in the peaks"):
        device.require_tpu([chip("TPU v9 imaginary")], 1)


def test_no_tpu_or_too_few_chips_is_an_error():
    with pytest.raises(device.DeviceError, match="no TPU"):
        device.require_tpu([chip("cpu", "cpu")], 1)
    with pytest.raises(device.DeviceError, match="no TPU"):
        device.require_tpu([], 1)
    with pytest.raises(device.DeviceError, match="needs 4 chips"):
        device.require_tpu([chip()], 4)
    assert device.require_tpu([chip()] * 4, 4) == [chip()] * 4


def run_bench(root, *extra):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lubm20-1chip.c16",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})


def test_run_without_a_tpu_exits_nonzero_before_any_work():
    res = run_bench(registry.ROOT)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "no TPU" in res.stderr


def test_run_from_the_benchmark_files_alone_exits_nonzero(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    res = run_bench(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
