"""What the harness and the chip runs rely on in ``BENCHMARK.json`` and
the files it names, checked before any run: a cell added as data alone
meets it, or fails here with the file and the key at fault."""

from __future__ import annotations

import os

import numpy as np
import pytest

from harness import cells
from tests.small import at_small_size, benchmark, config_files, workloads

#: the keys ``run.py``, ``harness/compare.py`` and ``tests/small.py``
#: read, and the source and the cuts every configuration file states
CONFIG_KEYS = ("model", "args", "properties", "limits", "control",
               "reference", "small", "source", "reduced")
SMALL_KEYS = ("args", "tpu_options", "fp_bits")
#: the most cells ``BENCHMARK.json`` may hold
MAX_CELLS = 24


def metrics():
    bench = benchmark()
    return {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}


def reported(workload):
    """The end-to-end metrics a cell reports, as the harness picks them."""
    return {m["name"] for m in cells.load_cell(workload, False).metrics}


@pytest.mark.parametrize("workload", list(workloads()))
def test_cell_files_exist(workload):
    w = workloads()[workload]
    assert w["config"] in config_files(), \
        f"{workload}: no configuration {w['config']!r} in BENCHMARK.json"
    path = config_files()[w["config"]]
    assert os.path.isfile(path), f"{workload}: no configuration file {path}"
    traffic = os.path.join(cells.BENCH_DIR, "traffic", w["traffic"] + ".json")
    assert os.path.isfile(traffic), f"{workload}: no traffic file {traffic}"


@pytest.mark.parametrize("config", list(config_files()))
def test_config_has_keys(config):
    path = config_files()[config]
    cfg = cells.load_json(path)
    missing = [k for k in CONFIG_KEYS if k not in cfg]
    assert not missing, f"{path} lacks {missing}"
    missing = [k for k in SMALL_KEYS if k not in cfg["small"]]
    assert not missing, f"{path}: its \"small\" block lacks {missing}"
    assert "fp_bits" in cfg["control"], f"{path}: control lacks fp_bits"


@pytest.mark.parametrize("config", list(config_files()))
def test_reference_inits_at_small_size(config):
    path = config_files()[config]
    cfg = at_small_size(cells.load_json(path), path)
    cells.resolve(cfg["model"])
    ref = cells.make_reference(cfg)
    _items, rows = ref.init()
    rows = np.asarray(rows)
    assert rows.ndim == 2 and len(rows) > 0 and rows.dtype == np.uint32, \
        f"{path}: the reference's init() gave rows {rows.dtype} {rows.shape}"


@pytest.mark.parametrize("metric", list(metrics()))
def test_metric_has_reader(metric):
    path = os.path.join(cells.BENCH_DIR, "metrics", metric + ".py")
    assert os.path.isfile(path), f"metric {metric}: no reader {path}"


@pytest.mark.parametrize("metric", list(metrics()))
def test_metric_names_existing_cells(metric):
    m = metrics()[metric]
    unknown = sorted(set(m.get("workloads", [])) - set(workloads()))
    assert not unknown, f"metric {metric} lists unknown cells {unknown}"
    if "moves" in m:  # a per-layer metric moves what its cells report
        for w in m.get("workloads", []):
            assert m["moves"] in reported(w), \
                f"metric {metric} moves {m['moves']}, which {w} does not report"


@pytest.mark.parametrize("workload", list(workloads()))
def test_cell_reports_an_end_to_end_metric(workload):
    assert reported(workload) - {"setup_s"}, \
        f"{workload} reports no end-to-end metric but setup_s"


def test_cell_count():
    assert len(workloads()) <= MAX_CELLS


def test_four_chip_share():
    n = len(workloads())
    four = [w for w, e in workloads().items() if int(e["chips"]) == 4]
    assert len(four) <= max(1, n // 2), \
        f"{len(four)} of {n} cells ask for 4 chips: {four}"
