"""The harness finds every cell's files by name, and refuses to run
without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import run as R

ROOT = R.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    setup = R.load_cell(cell)
    assert setup["traffic"]["name"] == setup["cell"]["traffic"]
    assert setup["config"]["name"] == setup["cell"]["config"]
    assert setup["limits"] is not None
    assert {m["name"] for m in setup["end_to_end"]} >= {"setup_s"}
    assert len(setup["end_to_end"]) >= 2 and setup["per_layer"]
    for m in setup["per_layer"]:
        assert os.path.exists(os.path.join(setup["metrics_dir"], f"{m['name']}.py"))


def test_names_and_files():
    for entry in BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_peak_table_knows_the_chip():
    peaks = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
    assert R._peaks(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        R._peaks(peaks, "TPU v9 imaginary")


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        BENCH["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


def test_device_peak_counts_the_reserved_region():
    """A TPU keeps a program's temporaries in a reserved region that
    ``peak_bytes_in_use`` leaves out; the peak read adds both."""
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    tpu = Device({"peak_bytes_in_use": 1_241_112_064,
                  "peak_bytes_reserved": 7_470_432_256})
    assert R.device_peak_bytes(tpu) == 8_711_544_320
    assert R.device_peak_bytes(Device(None)) == 0
