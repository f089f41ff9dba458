"""Flight recorder, live streaming, device-time attribution, and the
bench-trend gate (the PR-8 observability layer).

The load-bearing guarantees:

* a run that dies with NO trace configured still leaves a schema-valid
  JSONL postmortem artifact (the flight recorder's whole point);
* the Explorer's ``/.events`` SSE stream and ``tools/watch.py`` render
  a live run without perturbing it (a slow client drops, never blocks);
* the ``device``/``pull`` spans split each chunk's sync into device
  time and the stats transfer;
* ``tools/bench_history.py`` flags the BENCH_r05-style empty artifact
  and synthetic regressions machine-readably.
"""

import io
import json
import os
import sys

import pytest

from stateright_tpu.obs import (EVENT_SCHEMA, FlightRecorder, GLOSSARY,
                                validate_event)

pytestmark = pytest.mark.obs

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _twopc(n=3, **opts):
    from stateright_tpu.models.twopc import TwoPhaseSys
    return TwoPhaseSys(n).checker().tpu_options(
        capacity=1 << 12, race=False, **opts)


def _unavailable_hook(chunk, shards=None):
    raise RuntimeError(
        "UNAVAILABLE: injected transient backend fault (flight test)")


# --- the ring itself -------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounds_and_counters(self):
        rec = FlightRecorder(limit=16)
        for i in range(40):
            rec.record({"t": i, "ev": "compile", "engine": "E",
                        "reason": "x"})
        snap = rec.snapshot()
        assert len(snap) == 16
        assert snap[0]["t"] == 24  # oldest surviving
        assert rec.recorded == 40
        assert rec.dropped == 24

    def test_dump_roundtrip(self, tmp_path):
        rec = FlightRecorder()
        rec.record({"t": 0.0, "ev": "grow", "engine": "E",
                    "capacity": 8})
        path = tmp_path / "flight.jsonl"
        assert rec.dump(path) == 1
        evs = [json.loads(l) for l in path.read_text().splitlines()]
        assert evs[0]["capacity"] == 8
        validate_event(evs[0])


# --- zero-config crash artifacts -------------------------------------------

class TestFlightArtifacts:
    def test_single_chip_crash_leaves_artifact(self, tmp_path):
        """No trace configured; the engine dies on an injected
        transient fault — the artifact lands, validates against the
        schema, and trace_report --validate accepts it."""
        path = tmp_path / "boom.flight.jsonl"
        ck = _twopc(fault_hook=_unavailable_hook,
                    flight_path=str(path)).spawn_tpu()
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            ck.join()
        assert ck.flight_path() == str(path)
        assert ck.profile().get("recorder_dumps", 0) >= 1
        evs = [json.loads(l) for l in path.read_text().splitlines()]
        for ev in evs:
            validate_event(ev)
        kinds = [e["ev"] for e in evs]
        assert "run_start" in kinds and "error" in kinds
        assert "recorder_dump" in kinds  # the artifact names itself
        trace_report = _tool("trace_report")
        assert trace_report.main([str(path), "--validate"]) == 0

    def test_sharded_fault_exhausted_retries_artifact(self, tmp_path):
        """Acceptance: a sharded run killed by an injected transient
        fault (retry budget exhausted, ladder off) leaves a validating
        artifact with the retry burst in it — zero config beyond the
        pinned destination."""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("shards",))
        path = tmp_path / "sharded.flight.jsonl"
        ck = _twopc(mesh=mesh, retries=1, backoff=0.0, degrade=False,
                    fault_hook=_unavailable_hook,
                    flight_path=str(path)).spawn_tpu()
        with pytest.raises(RuntimeError, match="transient device fault"):
            ck.join()
        evs = [json.loads(l) for l in path.read_text().splitlines()]
        for ev in evs:
            validate_event(ev)
        kinds = [e["ev"] for e in evs]
        assert "retry" in kinds and "error" in kinds
        # both triggers fired on the same stable path: the later dump
        # (error) superseded the exhausted-retries one in place
        dumps = [e for e in evs if e["ev"] == "recorder_dump"]
        assert dumps and dumps[0]["path"] == str(path)

    def test_flight_false_restores_null_trace(self):
        from stateright_tpu.obs import NULL_TRACE
        ck = _twopc(flight=False).spawn_tpu()
        assert ck._trace is NULL_TRACE
        ck.join()
        assert ck.flight_path() is None
        assert "recorder_dumps" not in ck.profile()

    def test_clean_run_dumps_nothing(self):
        ck = _twopc().spawn_tpu().join()
        assert ck.flight_path() is None
        assert ck.unique_state_count() == 288  # recorder changes nothing


# --- device-time attribution -----------------------------------------------

class TestDeviceTime:
    def test_device_xfer_split_rides_profile_and_chunks(self):
        events = []
        ck = _twopc(trace=events).spawn_tpu().join()
        prof = ck.profile()
        assert prof.get("span_s.device", -1) >= 0.0
        assert prof.get("span_s.pull", -1) >= 0.0
        assert "span_s.device" in GLOSSARY and "span_s.pull" in GLOSSARY
        # the split rides the span events, one pair per chunk
        last = [e for e in events if e["ev"] == "chunk"][-1]["chunk"]
        split = {e["name"]: e for e in events if e["ev"] == "span"
                 and e.get("chunk") == last
                 and e["name"] in ("device", "pull")}
        assert set(split) == {"device", "pull"}
        assert all(e["t1"] >= e["t0"] for e in split.values())
        # both components are bounded by the run's wall time
        assert prof["span_s.device"] <= prof["search"] + 1.0

    @pytest.mark.slow  # the profiler session costs ~10s on CPU
    def test_profile_dir_capture_smoke(self, tmp_path):
        # jax.profiler capture is best-effort (never kills the run);
        # on the CPU backend it should produce a trace directory
        prof_dir = tmp_path / "jaxprof"
        ck = _twopc(profile_dir=str(prof_dir)).spawn_tpu().join()
        assert ck.unique_state_count() == 288


# --- live streaming: SSE + watch console -----------------------------------

class TestLiveStreaming:
    def test_events_sse_and_metrics_history(self):
        import urllib.request

        from stateright_tpu.checker.explorer import serve
        from stateright_tpu.models.twopc import TwoPhaseSys
        checker, server = serve(TwoPhaseSys(3).checker(),
                                ("127.0.0.1", 0), block=False)
        host, port = server.server_address
        try:
            checker.join()
            # flight-recorder backlog replays even post-done, so a
            # late client still reads the whole run
            with urllib.request.urlopen(
                    f"http://{host}:{port}/.events", timeout=30) as r:
                assert r.headers["Content-Type"].startswith(
                    "text/event-stream")
                body = r.read().decode()
            evs = [json.loads(l[len("data:"):])
                   for l in body.splitlines() if l.startswith("data:")]
            kinds = [e["ev"] for e in evs]
            assert kinds[0] == "run_start" and "done" in kinds
            for ev in evs:
                validate_event(ev)
            with urllib.request.urlopen(
                    f"http://{host}:{port}/.metrics?history",
                    timeout=30) as r:
                hist = json.loads(r.read())
            assert hist["samples"], "sampler recorded nothing"
            assert "unique_state_count" in hist["samples"][0]
            assert "wall" in hist["samples"][0]
        finally:
            server.shutdown()
            server.server_close()

    def test_watch_renders_committed_fixture(self, capsys):
        watch = _tool("watch")
        fixture = os.path.join(_DATA, "trace_fixture.jsonl")
        assert watch.main([fixture, "--once"]) == 0
        out = capsys.readouterr().out
        assert "uniq/s" in out            # chunk throughput
        assert "dedup=" in out            # dedup hit-rate
        assert "retry" in out             # the resilience event
        assert "== done" in out

    def test_watch_attached_to_live_run(self):
        """Acceptance: watch.py attached to a live (faulted, recovered)
        run renders chunk throughput, dedup hit-rate, and a resilience
        event before the run completes."""
        watch = _tool("watch")
        state = {"fired": False}

        def hook(chunk):
            if chunk >= 1 and not state["fired"]:
                state["fired"] = True
                raise RuntimeError("UNAVAILABLE: injected (watch test)")

        ck = _twopc(4, chunk_steps=4, retries=2, backoff=0.0,
                    retry_seed=0, fault_hook=hook).spawn_tpu()
        buf = io.StringIO()
        console = watch.attach(ck, out=buf)
        ck.join()
        out = buf.getvalue()
        assert console.rendered_progress >= 1
        assert "uniq/s" in out and "dedup=" in out
        assert "retry" in out
        assert "== done" in out


# --- bench-trend gate ------------------------------------------------------

class TestBenchHistory:
    def test_flags_empty_artifact(self, tmp_path, capsys):
        # the round-5 hole this tool exists to catch: an artifact whose
        # bench process died (rc=1, parsed=null) next to a clean round
        bench_history = _tool("bench_history")
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        (tmp_path / "BENCH_r05.json").write_text(json.dumps(
            {"n": 1, "rc": 1, "tail": "", "parsed": None}))
        with open(os.path.join(root, "BENCH_r06.json")) as f:
            (tmp_path / "BENCH_r06.json").write_text(f.read())
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r05.json"),
             str(tmp_path / "BENCH_r06.json")])
        empty = [f for f in report["flags"]
                 if f["kind"] == "empty_artifact"]
        assert [f["round"] for f in empty] == ["r05"], report["flags"]
        # markdown renders without blowing up
        out = io.StringIO()
        bench_history.render_markdown(report, out)
        assert "empty_artifact" in out.getvalue()
        # --check turns flags into a failing exit code (the gate)
        assert bench_history.main([str(tmp_path), "--check"]) == 1
        capsys.readouterr()

    def test_flags_synthetic_regression(self, tmp_path):
        bench_history = _tool("bench_history")

        def art(name, rows, value=100.0):
            tail = "\n".join(json.dumps(r) for r in rows)
            (tmp_path / name).write_text(json.dumps({
                "n": 1, "rc": 0, "tail": tail,
                "parsed": {"metric": "m", "value": value,
                           "unit": "uniq/s", "backend": "tpu"}}))

        row = {"workload": "tpu 2pc7 full 296448", "unit": "uniq/s",
               "uniq": 1, "gen": 2, "gen_per_uniq": 2.0, "metrics": {}}
        art("BENCH_r01.json", [dict(row, best=1000.0)], value=100.0)
        art("BENCH_r02.json", [dict(row, best=400.0),
                               {"workload": "extra", "error": "boom"}],
            value=95.0)
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r01.json"),
             str(tmp_path / "BENCH_r02.json")])
        kinds = {f["kind"] for f in report["flags"]}
        assert "regression" in kinds, report["flags"]
        assert "workload_error" in kinds
        reg = [f for f in report["flags"] if f["kind"] == "regression"][0]
        assert reg["workload"] == "tpu 2pc7"
        assert reg["drop"] == pytest.approx(0.6)
        # contract value within threshold: no flag for it
        assert not any(f.get("workload") == bench_history.CONTRACT
                       for f in report["flags"]
                       if f["kind"] == "regression")

    def test_committed_artifacts_pass_gate(self, capsys):
        # THE tier-1 bench-trend gate: the committed BENCH_*.json set
        # must be clean. A new empty/partial/regressed artifact in a
        # future round fails the suite right here.
        bench_history = _tool("bench_history")
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        assert bench_history.main([root, "--check"]) == 0
        out = capsys.readouterr().out
        assert "none" in out  # the flags section reports no flag

    def test_allow_does_not_mask_new_flags(self, tmp_path, capsys):
        bench_history = _tool("bench_history")
        (tmp_path / "BENCH_r05.json").write_text(json.dumps(
            {"n": 1, "rc": 1, "tail": "", "parsed": None}))
        (tmp_path / "BENCH_r07.json").write_text(json.dumps(
            {"n": 1, "rc": 1, "tail": "", "parsed": None}))
        # the acknowledged r05 alone passes; a NEW empty r07 still fails
        assert bench_history.main(
            [str(tmp_path), "--check", "--allow",
             "empty_artifact:r05,empty_artifact:r06"]) == 1
        capsys.readouterr()

    def test_spilled_tag_rides_trend_and_flags(self, tmp_path):
        # a primary metric that survived its HBM budget via host-tier
        # spills must surface in the trend table tags and as a flag —
        # a spilled rate is not comparable to an all-HBM rate
        bench_history = _tool("bench_history")
        row = {"workload": "tpu 2pc7 full 296448", "best": 900.0,
               "unit": "uniq/s", "uniq": 1, "gen": 2,
               "gen_per_uniq": 2.0, "spilled": True,
               "metrics": {"spills": 3, "host_tier_keys": 123}}
        (tmp_path / "BENCH_r09.json").write_text(json.dumps({
            "n": 1, "rc": 0, "tail": json.dumps(row),
            "parsed": {"metric": "m", "value": 100.0, "unit": "uniq/s",
                       "backend": "tpu", "spilled": True,
                       "host_tier_keys": 123}}))
        report = bench_history.build_report(
            [str(tmp_path / "BENCH_r09.json")])
        wl = report["rounds"][0]["workloads"]
        assert "spilled" in wl["tpu 2pc7"]["tags"]
        assert "spilled" in wl[bench_history.CONTRACT]["tags"]
        spilled = [f for f in report["flags"] if f["kind"] == "spilled"]
        assert spilled and "123" in spilled[0]["detail"]

    def test_normalization_keeps_model_sizes(self):
        bench_history = _tool("bench_history")
        norm = bench_history.normalize_workload
        assert norm("tpu 2pc7 full 296448") == "tpu 2pc7"
        assert norm("tpu 2pc10 capped 1M-gen") == "tpu 2pc10"
        assert norm("tpu paxos3 capped 500k") \
            == norm("tpu paxos3 capped 40000")
        assert norm("tpu 2pc7 full 296448") != norm(
            "tpu 2pc10 capped 1M-gen")
