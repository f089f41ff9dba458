"""A run with the timed path broken underneath comes out not correct.

Each case drives the whole harness (set-up, window, comparison) at a
test size on the CPU, skipping only the look for a chip, with one
fault planted in the program where its answers are made:

* ``state_unchanged``: the successor step returns its state unchanged;
* ``half_batch``: half of the successor lanes are left out;
* ``answer_altered``: one fingerprint of the answer is altered.

Every cell in ``BENCHMARK.json`` gets every case; a cell that asks for
more chips than the CPU run has devices is skipped, and says so. The
exchange between chips has no case yet. ``none`` is the sound run,
which must come out correct.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tests.small import workloads

HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.fixture(scope="module")
def cpu_devices():
    """The devices a CPU run in ``ENV`` has."""
    proc = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.device_count())"],
        capture_output=True, text=True, env=ENV, timeout=120, check=True)
    return int(proc.stdout.split()[-1])


def _run(cell, fault):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), cell, fault],
        capture_output=True, text=True, env=ENV, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", list(workloads()))
@pytest.mark.parametrize("fault", ["none", "state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_caught(cell, fault, cpu_devices):
    chips = int(workloads()[cell]["chips"])
    if chips > cpu_devices:
        pytest.skip(f"{cell} asks for {chips} chips; the CPU run has "
                    f"{cpu_devices} devices")
    res = _run(cell, fault)
    print(cell, fault, res["compared"])
    assert res["correct"] is (fault == "none"), res["compared"]
