"""Device idle by program phase (``harness/phases.py``): the order of
attribution on a synthetic trace, and on traces recorded on a v5e chip
that idle plus busy time is the traced window and that a trace without
the program's spans attributes exactly as ``tracing.reduce`` does."""

from __future__ import annotations

import gzip
import hashlib
import json
import os

import pytest

from harness import phases, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
#: one 2pc4 check traced on a v5e chip by a program without spans
OLD = os.path.join(HERE, "data", "small_2pc4.xplane.pb.gz")
#: one raced 2pc5 check traced on a v5e chip by a program with spans
#: (the device engine won; the host racer ran beside it)
SPANS = os.path.join(HERE, "data", "small_2pc5_spans.xplane.pb.gz")


def _unpack(path, tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(path, "rb") as src:
        (d / "chip.xplane.pb").write_bytes(src.read())
    return str(tmp_path)


def test_attribution_order():
    # idle over [0, 100): sr spans first (innermost), then the racer,
    # then bench annotations (innermost), then none
    host = [["bench.window", 0, 100], ["bench.join", 10, 50]]
    spans = [["sr.race.host", 5, 50], ["sr.decode", 20, 10],
             ["sr.shadow", 25, 3], ["sr.finish", 70, 10]]
    got = phases.attribute([(0, 100)], host, spans)
    assert got == {"bench.window": 5 + 10 + 20, "sr.race.host": 40,
                   "sr.decode": 7, "sr.shadow": 3, "bench.join": 5,
                   "sr.finish": 10}
    assert phases.attribute([(0, 10)], [], []) == {"none": 10}


def test_busy_time_is_never_attributed():
    got = phases.attribute([(0, 10), (20, 30)], [["bench.window", 0, 30]],
                           [["sr.decode", 5, 20]])
    assert got == {"bench.window": 10, "sr.decode": 10}


@pytest.mark.skipif(not os.path.exists(OLD), reason="no recorded trace")
def test_old_trace_reduces_as_before(tmp_path):
    """The fixture the benchmark's reduction was first checked on
    reduces byte for byte as it did, and the phase attribution of a
    trace without program spans is the reduction's own."""
    d = _unpack(OLD, tmp_path)
    trace = tracing.collect(d)
    red = tracing.reduce(trace)
    digest = hashlib.sha256(
        json.dumps(red, sort_keys=True).encode()).hexdigest()
    assert digest == ("a69a392a9738484a0f6d5835b5f39e16"
                      "9185a6399b5b4c08d1a2da39a50f1623")
    spans = phases.collect_spans(d)
    assert spans == []
    got = phases.breakdown(trace, red, spans)
    assert got["idle_gaps"][:10] == red["idle_gaps"]
    assert got["unattributed_ns"] == got["idle_ns"]


@pytest.mark.skipif(not os.path.exists(SPANS),
                    reason="no recorded trace with spans")
def test_spans_trace_attributes_all_idle(tmp_path):
    d = _unpack(SPANS, tmp_path)
    trace = tracing.collect(d)
    red = tracing.reduce(trace)
    spans = phases.collect_spans(d)
    names = {n for n, _s, _d in spans}
    assert {"sr.spawn", "sr.init", "sr.seed", "sr.program",
            "sr.dispatch", "sr.sync", "sr.pull", "sr.decode",
            "sr.finish", "sr.mirror.pull", "sr.mirror.index", "sr.paths",
            "sr.race.host"} <= names
    got = phases.breakdown(trace, red, spans)
    busy = red["devices"][red["fullest"]]["busy_ns"]
    assert got["idle_ns"] == red["window_ns"] - busy
    attributed = sum(v for k, v in got["idle_gaps"]
                     if k.startswith(phases.PREFIX))
    assert attributed + got["unattributed_ns"] == got["idle_ns"]
    # the program's phases hold most of the device's idle time
    assert attributed > 9 * got["unattributed_ns"]


@pytest.mark.skipif(not os.path.exists(SPANS),
                    reason="no recorded trace with spans")
def test_phase_run_reads_the_spans_beside_the_reduction(tmp_path):
    """``phase_run.py``'s wrapped trace read: the reduction ``run.py``
    prints is untouched, and the phases are ``phases.breakdown`` of the
    same trace directory."""
    import phase_run
    d = _unpack(SPANS, tmp_path)
    trace = phase_run._collect_with_spans(d)
    red = phase_run._reduce_with_phases(trace)
    assert red == tracing.reduce(tracing.collect(d))
    got = phase_run._box.pop("phases")
    want = phases.breakdown(trace, red, phases.collect_spans(d))
    assert got == dict(want,
                       busy_ns=red["devices"][red["fullest"]]["busy_ns"],
                       window_ns=red["window_ns"])
    assert got["unattributed_ns"] < got["idle_ns"]
