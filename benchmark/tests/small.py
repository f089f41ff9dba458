"""Each cell at a size a test run holds, shared by ``fault_run.py`` (the
program's runs), ``test_control.py`` (the control's) and
``test_layout.py``.

The size is the configuration file's own ``small`` block: ``args`` (the
model's and the reference's), ``tpu_options``, an optional
``target_state_count``, and ``fp_bits``, which narrows the control's
fingerprint enough to merge states at this size. Nothing here knows any
one cell."""

from __future__ import annotations

import os
from typing import Any, Dict

from harness import cells


def benchmark() -> Dict[str, Any]:
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def workloads() -> Dict[str, Dict[str, Any]]:
    """Each cell's entry in ``BENCHMARK.json``, by name."""
    return {w["name"]: w for w in benchmark()["workloads"]}


def config_files() -> Dict[str, str]:
    """Each configuration's file, by name."""
    return {c["name"]: os.path.join(cells.ROOT, c["file"])
            for c in benchmark()["configs"]}


def at_small_size(config: Dict[str, Any], path: str) -> Dict[str, Any]:
    """``config`` (read from ``path``) at its ``small`` size."""
    size = config.get("small")
    if size is None:
        raise ValueError(
            f"{path} has no \"small\" block: every configuration states "
            "its size for the benchmark's tests (args, tpu_options, "
            "fp_bits, and target_state_count where it has a cap)")
    cfg = dict(config, args=size["args"], tpu_options=size["tpu_options"],
               control={"fp_bits": size["fp_bits"]})
    cfg["reference"] = dict(cfg["reference"], args=size["args"])
    if "target_state_count" in size:
        cfg["target_state_count"] = size["target_state_count"]
    return cfg


def small_cell(workload: str) -> cells.Cell:
    """The cell as ``BENCHMARK.json`` has it, its configuration at test
    size."""
    cell = cells.load_cell(workload, False)
    path = config_files()[workloads()[workload]["config"]]
    cell.config = at_small_size(cell.config, path)
    return cell
