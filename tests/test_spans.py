"""Overlap-aware span profiler with critical-path stall attribution
(PR 18).

The load-bearing guarantees:

* **intervals, not durations** — engines emit ``span`` trace events
  (``name``/``t0``/``t1`` on the shared trace clock) for every phase
  of the chunk anatomy, schema-valid and identity-tagged;
* **buckets sum to wall** — :func:`stateright_tpu.obs.spans.analyze`
  sweeps the merged timeline and splits wall time into exclusively-
  attributed buckets (``device``/``pull``/``exchange``, ``overlap``,
  ``host:<phase>``, ``idle``) that partition the wall interval by
  construction;
* **the pipeline shift is visible** — a ``pipeline=False`` run has
  zero ``overlap`` (nothing in flight while the host works), a
  ``pipeline=True`` run has ``overlap > 0`` (chunk N+1's device time
  hides chunk N's host time) — the end-to-end pin;
* **one consumer** — ``tools/stall_report.py`` renders single-run and
  ``--fleet`` merged reports from committed fixture traces, and
  ``bench_history --check`` tolerates pre-span rounds (informational)
  while failing rounds that LOSE attribution after it landed.
"""

import io
import json
import os
import sys
import time

import pytest

from stateright_tpu.obs import (RunTrace, SpanRecorder, analyze,
                                attach_attribution, ranked,
                                shard_imbalance, spans_from_events,
                                top_stalls, validate_event)

pytestmark = pytest.mark.obs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: committed fixture traces (satellite: stall_report renders both a
#: single-run and a --fleet merged report from committed fixtures)
FIXTURE = os.path.join(_DATA, "span_trace.jsonl")
FLEET_DIR = os.path.join(_DATA, "span_fleet")


def _tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _span(name, t0, t1, **fields):
    s = {"name": name, "t0": float(t0), "t1": float(t1)}
    s.update(fields)
    return s


# --- the critical-path sweep on synthetic timelines ------------------------

class TestAnalyze:
    def test_empty_input_is_all_zeros(self):
        attr = analyze([])
        assert attr["wall_s"] == 0.0
        assert attr["buckets"] == {}
        assert attr["bubble_frac"] == 0.0
        assert attr["spans"] == 0

    def test_full_overlap_is_free(self):
        """Host work entirely hidden under an in-flight chunk is
        attributed to ``overlap`` — zero bubble."""
        attr = analyze([_span("device", 0.0, 6.0),
                        _span("host", 2.0, 6.0)])
        assert attr["buckets"] == {"device": 2.0, "overlap": 4.0}
        assert attr["overlap_s"] == 4.0
        assert attr["bubble_frac"] == 0.0
        assert attr["wall_s"] == 6.0

    def test_zero_overlap_is_all_bubble(self):
        """Strictly sequential device-then-host: every host second
        blocked an idle device."""
        attr = analyze([_span("device", 0.0, 5.0),
                        _span("host", 5.0, 9.0)])
        assert attr["buckets"] == {"device": 5.0, "host:host": 4.0}
        assert attr["overlap_s"] == 0.0
        assert attr["bubble_frac"] == pytest.approx(4.0 / 9.0)

    def test_innermost_device_span_wins(self):
        """A ``pull`` nested inside the ``device`` interval names its
        own segment — the umbrella does not swallow it."""
        attr = analyze([_span("device", 0.0, 6.0),
                        _span("pull", 2.0, 4.0)])
        assert attr["buckets"] == {"device": 4.0, "pull": 2.0}

    def test_innermost_host_span_wins(self):
        attr = analyze([_span("host", 0.0, 10.0),
                        _span("props", 4.0, 6.0)])
        assert attr["buckets"] == {"host:host": 8.0, "host:props": 2.0}

    def test_idle_span_counts_for_neither_side(self):
        """The scheduler's queue-wait ``idle`` span marks dead air: it
        must not read as host work (that would fake a bubble source)
        nor suppress device attribution under it."""
        attr = analyze([_span("idle", 0.0, 5.0)])
        assert attr["buckets"] == {"idle": 5.0}
        assert attr["bubble_frac"] == 1.0
        attr = analyze([_span("idle", 0.0, 10.0),
                        _span("device", 2.0, 4.0)])
        assert attr["buckets"] == {"device": 2.0, "idle": 8.0}

    def test_gap_between_spans_is_idle(self):
        attr = analyze([_span("device", 0.0, 2.0),
                        _span("device", 5.0, 6.0)])
        assert attr["buckets"] == {"device": 3.0, "idle": 3.0}
        assert attr["idle_s"] == 3.0

    def test_buckets_sum_to_wall_on_messy_timeline(self):
        """The core invariant: buckets partition [min t0, max t1)
        exactly, whatever the nesting/overlap structure."""
        spans = [
            _span("dispatch", 0.0, 0.3),
            _span("device", 0.3, 2.1),
            _span("pull", 2.1, 2.4),
            _span("host", 2.2, 3.7),          # partially overlapped
            _span("host_probe", 2.5, 3.0),    # nested host phase
            _span("device", 2.6, 4.8),        # next chunk in flight
            _span("idle", 5.0, 5.5),          # trailing dead air
            _span("exchange", 4.9, 5.0),
        ]
        attr = analyze(spans)
        assert sum(attr["buckets"].values()) == \
            pytest.approx(attr["wall_s"], rel=1e-12)
        assert attr["wall_s"] == pytest.approx(5.5)
        # every classification kind appears on this timeline
        kinds = set(attr["buckets"])
        assert "overlap" in kinds and "idle" in kinds
        assert any(k.startswith("host:") for k in kinds)
        assert kinds & {"device", "pull", "exchange"}

    def test_pipeline_shift_synthetic(self):
        """The signature the e2e pin looks for, in miniature: same
        phase durations, sequential vs double-buffered schedule."""
        sequential = [
            _span("device", 0.0, 2.0), _span("host", 2.0, 3.0),
            _span("device", 3.0, 5.0), _span("host", 5.0, 6.0),
        ]
        pipelined = [
            _span("device", 0.0, 2.0), _span("host", 2.0, 3.0),
            _span("device", 2.0, 4.0), _span("host", 4.0, 5.0),
        ]
        a_seq = analyze(sequential)
        a_pipe = analyze(pipelined)
        assert a_seq["overlap_s"] == 0.0
        # host1 hides under chunk2's device time; the final host span
        # has nothing in flight, so it stays a bubble in both schedules
        assert a_pipe["overlap_s"] == pytest.approx(1.0)
        assert a_pipe["bubble_frac"] < a_seq["bubble_frac"]
        assert a_pipe["wall_s"] < a_seq["wall_s"]

    def test_ranked_and_top_stalls(self):
        attr = analyze([_span("device", 0.0, 5.0),
                        _span("host", 5.0, 9.0)])
        rows = ranked(attr)
        assert [r[0] for r in rows] == ["device", "host:host"]
        assert sum(share for _n, _s, share in rows) == \
            pytest.approx(1.0)
        assert top_stalls(attr, n=1) == [["device", 5.0]]


# --- the recorder: clock bridge, ring, trace emission ----------------------

class TestSpanRecorder:
    def test_record_emits_schema_valid_event(self):
        events = []
        rec = SpanRecorder(RunTrace(events, engine="E"))
        t = time.perf_counter()
        rec.record("device", t, t + 0.01, chunk=3, shard=None)
        assert len(rec) == 1
        spans = [e for e in events if e["ev"] == "span"]
        assert len(spans) == 1
        validate_event(spans[0])
        assert spans[0]["name"] == "device"
        assert spans[0]["chunk"] == 3
        assert "shard" not in spans[0]  # None identity is dropped
        assert spans[0]["t1"] >= spans[0]["t0"] >= 0.0

    def test_clock_bridge_lands_on_trace_axis(self):
        """perf_counter stamps must convert onto the trace's relative
        axis: the span's t1 lands near the emit-time event t."""
        events = []
        rec = SpanRecorder(RunTrace(events, engine="E"))
        t = time.perf_counter()
        rec.record("host", t, t)
        ev = [e for e in events if e["ev"] == "span"][0]
        assert abs(ev["t1"] - ev["t"]) < 0.25

    def test_span_context_records_on_exception(self):
        rec = SpanRecorder(None)
        with pytest.raises(RuntimeError):
            with rec.span("mirror"):
                raise RuntimeError("boom")
        assert [s["name"] for s in rec.spans()] == ["mirror"]

    def test_traceless_ring_still_feeds_attribution(self):
        rec = SpanRecorder(None)
        t = time.perf_counter()
        rec.record("dispatch", t, t + 0.01)
        rec.record("device", t + 0.01, t + 0.5)
        rec.record("decode", t + 0.5, t + 0.7)
        snap = attach_attribution({"chunks": 2}, rec)
        assert "attribution" in snap
        assert snap["bubble_frac"] > 0.0
        assert snap["idle_s"] >= 0.0
        assert snap["chunks"] == 2  # existing keys untouched

    def test_spanless_snapshot_left_untouched(self):
        snap = attach_attribution({"chunks": 0}, SpanRecorder(None))
        assert "attribution" not in snap
        assert "bubble_frac" not in snap

    def test_ring_is_bounded(self):
        rec = SpanRecorder(None, limit=4)
        t = time.perf_counter()
        for i in range(10):
            rec.record("host", t + i, t + i + 0.1)
        assert len(rec) == 4


# --- the consumer side: event streams, imbalance, the CLI ------------------

def _load_fixture(path=FIXTURE):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


class TestConsumers:
    def test_fixture_events_are_schema_valid(self):
        for ev in _load_fixture():
            validate_event(ev)

    def test_spans_from_events_filters_and_anchors(self):
        events = _load_fixture()
        spans = spans_from_events(events)
        assert len(spans) == 9
        assert all(s["t1"] >= s["t0"] for s in spans)
        # wall anchoring is a no-op request on a raw (un-merged)
        # stream: no "wall" annotation -> nothing joins the wall axis
        assert spans_from_events(events, wall=True) == []
        annotated = [dict(ev, wall=100.0 + ev["t"]) for ev in events]
        walled = spans_from_events(annotated, wall=True)
        assert len(walled) == 9
        assert all(s["t0"] >= 100.0 for s in walled)

    def test_shard_imbalance_from_chunk_vectors(self):
        imb = shard_imbalance(_load_fixture())
        assert imb["per_shard_new"] == [112, 48]
        assert imb["imbalance"] == pytest.approx(112 / 80.0)
        # width change mid-run (degradation) skips the odd vector
        events = [{"ev": "chunk", "shard_new": [4, 4]},
                  {"ev": "chunk", "shard_new": [8]}]
        assert shard_imbalance(events)["per_shard_new"] == [4, 4]
        assert shard_imbalance([{"ev": "chunk", "new": 5}]) is None

    def test_stall_report_single_run(self, capsys):
        sr = _tool("stall_report")
        assert sr.main([FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "bucket" in out and "sum" in out
        assert "bubble_frac=" in out
        assert "overlap" in out
        assert "shard imbalance" in out and "1.40" in out

    def test_stall_report_fleet(self, capsys):
        sr = _tool("stall_report")
        assert sr.main(["--fleet", FLEET_DIR]) == 0
        out = capsys.readouterr().out
        assert "fleet summary" in out
        assert "job:j0" in out
        assert "merged (wall-anchored, all lanes)" in out
        # the scheduler's queue-wait idle span rides the service lane
        assert "idle" in out

    def test_stall_report_summary_line(self):
        sr = _tool("stall_report")
        attr, imb = sr.attribution_from_events(_load_fixture())
        line = sr.summary_line(attr, imb)
        assert line.startswith("stall: ")
        assert "bubble=" in line and "imbalance=" in line
        assert sr.summary_line({}, None) == "stall: no spans"

    def test_stall_report_pre_span_trace(self, tmp_path, capsys):
        """A pre-span trace (no span events) renders the explicit
        no-spans notice, not a crash or an empty table."""
        p = tmp_path / "old.jsonl"
        p.write_text(json.dumps(
            {"t": 0.0, "ev": "run_start", "engine": "E", "model": "M",
             "wall": 1.0}) + "\n")
        sr = _tool("stall_report")
        assert sr.main([str(p)]) == 0
        assert "no span events" in capsys.readouterr().out

    def test_attribution_sums_to_wall_on_fixture(self):
        sr = _tool("stall_report")
        attr, _imb = sr.attribution_from_events(_load_fixture())
        assert sum(attr["buckets"].values()) == \
            pytest.approx(attr["wall_s"], rel=1e-9)


# --- live consoles fold spans into a top-stall line ------------------------

class TestConsoles:
    def test_watch_progress_line_carries_top_stall(self):
        watch = _tool("watch")
        buf = io.StringIO()
        console = watch.Console(out=buf)
        for ev in _load_fixture():
            console.feed(ev)
        out = buf.getvalue()
        assert "stall=" in out and "bubble=" in out
        # spans accumulate; they never render as intervention lines
        assert console.rendered_events == 0
        assert console.rendered_progress == 2

    def test_fleetboard_stall_line(self):
        fleetboard = _tool("fleetboard")
        board = fleetboard.Board()
        out = board.feed({
            "jobs": [{"id": "j0", "state": "done",
                      "result": {"profile": {
                          "attribution": {"host:dispatch": 1.5,
                                          "overlap": 0.4,
                                          "device": 0.2},
                          "bubble_frac": 0.6}}}],
            "profile": {}, "utilization": {}})
        assert "stall: host:dispatch=1.50s" in out
        assert "bubble=60% mean" in out
        assert "overlap=" not in out  # overlap is not a stall


# --- bench_history tolerates pre-span rounds, flags regressions ------------

class TestBenchHistoryAttribution:
    @staticmethod
    def _art(tmp_path, name, metrics):
        row = {"workload": "tpu 2pc7 full 296448", "unit": "uniq/s",
               "best": 1000.0, "uniq": 1, "gen": 2, "gen_per_uniq": 2.0,
               "metrics": metrics}
        (tmp_path / name).write_text(json.dumps({
            "n": 1, "rc": 0, "tail": json.dumps(row),
            "parsed": {"metric": "m", "value": 100.0,
                       "unit": "uniq/s", "backend": "tpu"}}))

    def test_pre_span_rounds_flagged_informationally(self, tmp_path,
                                                     capsys):
        bench_history = _tool("bench_history")
        self._art(tmp_path, "BENCH_r01.json", {})
        self._art(tmp_path, "BENCH_r02.json",
                  {"stalls": [["host:dispatch", 1.2]],
                   "bubble_frac": 0.4})
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r01.json"),
             str(tmp_path / "BENCH_r02.json")])
        pre = [f for f in report["flags"] if f["kind"] == "pre_span"]
        assert len(pre) == 1 and pre[0]["round"] == "r01"
        assert pre[0]["info"] is True
        # informational flags never fail the gate
        assert bench_history.main([str(tmp_path), "--check"]) == 0
        out = io.StringIO()
        bench_history.render_markdown(report, out)
        assert "(informational)" in out.getvalue()
        capsys.readouterr()

    def test_losing_attribution_after_it_landed_is_fatal(self, tmp_path,
                                                         capsys):
        bench_history = _tool("bench_history")
        self._art(tmp_path, "BENCH_r01.json",
                  {"stalls": [["device", 0.8]], "bubble_frac": 0.2})
        self._art(tmp_path, "BENCH_r02.json", {})
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r01.json"),
             str(tmp_path / "BENCH_r02.json")])
        missing = [f for f in report["flags"]
                   if f["kind"] == "missing_attribution"]
        assert len(missing) == 1 and missing[0]["round"] == "r02"
        assert bench_history.main([str(tmp_path), "--check"]) == 1
        capsys.readouterr()

    def test_all_pre_span_history_stays_green(self, tmp_path, capsys):
        """The committed pre-span BENCH artifacts: no attribution
        anywhere means no flags at all — the gate must not punish
        history for predating the instrument."""
        bench_history = _tool("bench_history")
        self._art(tmp_path, "BENCH_r01.json", {})
        self._art(tmp_path, "BENCH_r02.json", {})
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r01.json"),
             str(tmp_path / "BENCH_r02.json")])
        kinds = {f["kind"] for f in report["flags"]}
        assert "pre_span" not in kinds
        assert "missing_attribution" not in kinds
        assert bench_history.main([str(tmp_path), "--check"]) == 0
        capsys.readouterr()


# --- end-to-end: a real pipelined run on the device engine -----------------

@pytest.fixture(scope="module")
def pipeline_runs():
    """One 2pc run per pipeline mode (shapes shared with
    tests/test_fleetobs.py for compile-cache reuse): (events, profile)
    keyed by the pipeline flag."""
    pytest.importorskip("jax")
    from stateright_tpu.models.twopc import TwoPhaseSys
    runs = {}
    for pipeline in (False, True):
        events = []
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(capacity=1 << 12, race=False, trace=events,
                           pipeline=pipeline, chunk_steps=2)
              .spawn_tpu().join())
        runs[pipeline] = (events, ck.profile())
    return runs


class TestEndToEnd:
    def test_spans_validate_and_cover_the_chunk_anatomy(self,
                                                        pipeline_runs):
        events, _prof = pipeline_runs[True]
        spans = [e for e in events if e["ev"] == "span"]
        assert spans, "pipelined run emitted no span events"
        for ev in spans:
            validate_event(ev)
        names = {e["name"] for e in spans}
        assert {"dispatch", "device", "sync", "pull", "decode"} <= names
        # the chunk's spans carry its ordinal for correlation
        assert all("chunk" in e for e in spans
                   if e["name"] in ("device", "sync", "pull", "decode"))

    def test_attribution_sums_to_wall(self, pipeline_runs):
        """Acceptance: buckets sum to within 5% of wall on the
        pipelined CPU smoke (exact by construction; 5% is the
        acceptance bound)."""
        for pipeline in (False, True):
            events, _prof = pipeline_runs[pipeline]
            attr = analyze(spans_from_events(events))
            assert attr["spans"] > 0
            total = sum(attr["buckets"].values())
            assert total == pytest.approx(attr["wall_s"], rel=1e-6)
            assert abs(total - attr["wall_s"]) <= 0.05 * attr["wall_s"]

    def test_pipeline_toggle_shifts_overlap(self, pipeline_runs):
        """Acceptance pin: pipeline=False has NO overlap (nothing in
        flight while the host works), pipeline=True hides host time
        under the next chunk's device time."""
        a_off = analyze(spans_from_events(pipeline_runs[False][0]))
        a_on = analyze(spans_from_events(pipeline_runs[True][0]))
        assert a_off["overlap_s"] == 0.0
        assert a_on["overlap_s"] > 0.0

    def test_profile_carries_attribution(self, pipeline_runs):
        for pipeline in (False, True):
            _events, prof = pipeline_runs[pipeline]
            attr = prof.get("attribution")
            assert isinstance(attr, dict) and attr
            assert 0.0 <= prof["bubble_frac"] <= 1.0
            assert prof["idle_s"] >= 0.0
        # the pipelined profile attributes some overlap; the
        # sequential one attributes none
        assert "overlap" in pipeline_runs[True][1]["attribution"]
        assert "overlap" not in pipeline_runs[False][1]["attribution"]

    def test_stall_report_exits_zero_on_run_artifact(self, tmp_path,
                                                     pipeline_runs,
                                                     capsys):
        events, _prof = pipeline_runs[True]
        p = tmp_path / "run_trace.jsonl"
        p.write_text("".join(json.dumps(e) + "\n" for e in events))
        sr = _tool("stall_report")
        assert sr.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "wall" in out and "bucket" in out
        assert "overlap" in out
