"""Unified observability (stateright_tpu/obs/): metrics registry,
run-trace schema, trace-on/off parity, and overhead smoke.

The load-bearing guarantee is PARITY: enabling ``tpu_options(trace=...)``
must not change a single observable result — state counts, unique
counts, discoveries, reached fingerprints — on the single-chip device
engine, the sharded engine, and the host engines. Everything else
(schema, consumers) builds on that.
"""

import io
import json
import time

import pytest

from stateright_tpu.obs import (EVENT_SCHEMA, GLOSSARY, NULL_TRACE,
                                Metrics, RunTrace, make_trace,
                                validate_event)

pytestmark = pytest.mark.obs


# --- Metrics registry ------------------------------------------------------

class TestMetrics:
    def test_counters_timers_maxima(self):
        m = Metrics()
        m.inc("chunks")
        m.inc("chunks", 2)
        m.add_time("grow", 0.5)
        m.add_time("grow", 0.25)
        m.observe_max("vmax", 10)
        m.observe_max("vmax", 7)  # lower: ignored
        with m.timed("seed"):
            pass
        snap = m.snapshot()
        assert snap["chunks"] == 3
        assert snap["grow"] == 0.75
        assert snap["vmax"] == 10
        assert snap["seed"] >= 0.0
        # snapshot is a copy
        snap["chunks"] = 99
        assert m.get("chunks") == 3

    def test_merge_semantics(self):
        a, b = Metrics(), Metrics()
        a.inc("chunks", 2)
        a.observe_max("vmax", 5)
        b.inc("chunks", 3)
        b.observe_max("vmax", 9)
        a.merge(b)
        assert a.get("chunks") == 5  # counters add
        assert a.get("vmax") == 9  # maxima take max

    def test_merge_gauges_last_writer_wins(self):
        # gauges were SUMMED on merge: a raced-run fold could report
        # lanes=2 or a mesh width no mesh ever had — pinned here
        from stateright_tpu.obs import GAUGES, GLOSSARY
        assert GAUGES <= set(GLOSSARY)
        a, b = Metrics(), Metrics()
        a.set("lanes", 1)
        a.set("mesh_shards", 4)
        a.set("shard_balance", 0.9)
        a.set("fault_device", 3)
        b.set("lanes", 1)
        b.set("mesh_shards", 2)
        b.set("history_ok", 1)
        a.merge(b)
        assert a.get("lanes") == 1  # NOT 2
        assert a.get("mesh_shards") == 2  # the incoming width, not 6
        assert a.get("shard_balance") == 0.9  # absent in b: untouched
        assert a.get("fault_device") == 3
        assert a.get("history_ok") == 1
        # non-gauges still accumulate alongside
        b2 = Metrics()
        b2.inc("retries", 2)
        a.merge(b2)
        assert a.get("retries") == 2

    def test_glossary_covers_engine_keys(self):
        # the canonical keys every engine emits must stay documented
        for key in ("span_s.dispatch", "span_s.sync", "span_s.decode",
                    "span_s.grow", "span_s.hgrow", "chunks", "grows",
                    "compiles", "vmax",
                    "dmax", "rmax", "levels", "jobs", "search",
                    "shard_balance"):
            assert key in GLOSSARY, key


# --- RunTrace sinks and schema ---------------------------------------------

class TestRunTrace:
    def test_disabled_is_falsy_noop(self):
        assert not NULL_TRACE
        NULL_TRACE.emit("chunk", anything=1)  # no-op, no error
        assert make_trace(None, engine="X") is NULL_TRACE
        with pytest.raises(RuntimeError, match="disabled trace"):
            NULL_TRACE.subscribe(lambda e: None)

    def test_list_sink_and_base_fields(self):
        events = []
        tr = RunTrace(events, engine="E")
        assert tr
        tr.emit("compile", reason="initial")
        assert events == [{"t": events[0]["t"], "ev": "compile",
                           "engine": "E", "reason": "initial"}]
        validate_event(events[0])

    def test_callable_and_file_sinks(self, tmp_path):
        got = []
        RunTrace(got.append, engine="E").emit("grow", capacity=4)
        assert got[0]["capacity"] == 4

        path = tmp_path / "t.jsonl"
        tr = RunTrace(str(path), engine="E")
        tr.emit("grow", capacity=8)
        tr.close()
        lines = path.read_text().splitlines()
        assert json.loads(lines[0])["capacity"] == 8

        buf = io.StringIO()
        RunTrace(buf, engine="E").emit("egrow", ecap=2)
        assert json.loads(buf.getvalue())["ev"] == "egrow"

    def test_bad_sink_rejected(self):
        with pytest.raises(TypeError, match="trace"):
            RunTrace(42, engine="E")

    def test_subscribers_receive_events(self):
        events = []
        tr = RunTrace(None, engine="E")
        assert not tr  # no sink, no subscribers: still off
        tr.subscribe(events.append)
        assert tr  # a subscriber enables it
        tr.emit("compile", reason="x")
        assert events[0]["reason"] == "x"

    def test_subscriber_runs_outside_lock(self):
        """Callbacks fire OUTSIDE the sink lock: a subscriber that
        itself emits (the SSE relay shape) must not deadlock on the
        non-reentrant lock, and a slow subscriber must not block
        another thread's writer. The old code held the lock across
        callbacks — this test hung under it."""
        import threading

        events = []
        tr = RunTrace(events, engine="E")

        def reentrant(ev):
            if ev["ev"] == "compile":
                tr.emit("grow", capacity=1)  # deadlocks if lock is held

        tr.subscribe(reentrant)
        t = threading.Thread(target=lambda: tr.emit("compile",
                                                    reason="x"),
                             daemon=True)
        t.start()
        t.join(5.0)
        assert not t.is_alive(), "emit deadlocked on its own subscriber"
        assert [e["ev"] for e in events] == ["compile", "grow"]

    def test_subscribe_during_live_emit_is_safe(self):
        """Subscribing to a live raced run raced the un-locked list
        append against emit's iteration; now appends happen under the
        lock onto a fresh list (copy-on-write) while emits iterate a
        snapshot — hammer both sides concurrently."""
        import threading

        tr = RunTrace([], engine="E")
        tr.subscribe(lambda ev: None)  # keep it truthy throughout
        stop = threading.Event()
        errors = []

        def emitter():
            try:
                while not stop.is_set():
                    tr.emit("compile", reason="x")
            except BaseException as exc:  # pragma: no cover
                errors.append(exc)

        t = threading.Thread(target=emitter, daemon=True)
        t.start()
        got = []
        for _ in range(200):
            fn = got.append
            tr.subscribe(fn)
            tr.unsubscribe(fn)
        stop.set()
        t.join(5.0)
        assert not errors
        assert not t.is_alive()

    def test_unsubscribe(self):
        got = []
        tr = RunTrace([], engine="E")
        tr.subscribe(got.append)
        tr.emit("compile", reason="a")
        tr.unsubscribe(got.append)  # different bound object: no-op...
        assert len(got) == 1
        fn = got.append
        tr.subscribe(fn)
        tr.unsubscribe(fn)
        tr.emit("compile", reason="b")
        assert len(got) == 2  # only the still-subscribed first append

    def test_validate_rejects_bad_events(self):
        with pytest.raises(ValueError, match="unknown trace event"):
            validate_event({"t": 0, "ev": "nope", "engine": "E"})
        with pytest.raises(ValueError, match="missing fields"):
            validate_event({"t": 0, "ev": "chunk", "engine": "E"})
        with pytest.raises(ValueError, match="base fields"):
            validate_event({"ev": "compile", "reason": "x"})


# --- emitted-stream schema validation --------------------------------------

def _twopc(n=3, **opts):
    from stateright_tpu.models.twopc import TwoPhaseSys
    return TwoPhaseSys(n).checker().tpu_options(
        capacity=1 << 12, race=False, **opts)


class TestEmittedSchema:
    def test_device_jsonl_schema(self, tmp_path):
        path = tmp_path / "run.jsonl"
        ck = _twopc(trace=str(path)).spawn_tpu().join()
        events = [json.loads(line)
                  for line in path.read_text().splitlines()]
        assert events, "no events emitted"
        for ev in events:
            validate_event(ev)
        kinds = {e["ev"] for e in events}
        assert {"run_start", "chunk", "done"} <= kinds
        # fingerprints must be JSON-safe strings (uint64 > 2^53)
        for ev in events:
            if ev["ev"] == "discovery":
                fp = ev["fp"]
                assert isinstance(fp, (str, list))
        done = [e for e in events if e["ev"] == "done"][-1]
        assert done["unique"] == ck.unique_state_count()
        assert done["gen"] == ck.state_count()

    def test_every_emitted_kind_is_in_schema(self):
        events = []
        _twopc(trace=events).spawn_tpu().join()
        assert {e["ev"] for e in events} <= set(EVENT_SCHEMA)

    def test_host_engines_emit(self):
        from stateright_tpu.models.fixtures import LinearEquation
        events = []
        (LinearEquation(2, 10, 14).checker()
         .tpu_options(trace=events).spawn_bfs().join())
        kinds = [e["ev"] for e in events]
        assert kinds[0] == "run_start"
        assert "discovery" in kinds and kinds[-1] == "done"
        for ev in events:
            validate_event(ev)

        events_dfs = []
        (LinearEquation(2, 10, 14).checker()
         .tpu_options(trace=events_dfs).spawn_dfs().join())
        assert any(e["ev"] == "discovery" for e in events_dfs)
        for ev in events_dfs:
            validate_event(ev)

    def test_fault_injection_event(self):
        from stateright_tpu.examples.write_once_packed import (
            PackedWriteOnce)
        events = []
        model = PackedWriteOnce(1, durable=True).crash_restart(
            1, actors=[0])
        (model.checker().tpu_options(capacity=1 << 12, race=False,
                                     trace=events)
         .spawn_tpu().join())
        fi = [e for e in events if e["ev"] == "fault_injection"]
        assert fi and fi[0]["max_crashes"] == 1
        assert fi[0]["actors"] == [0]


# --- parity: trace on/off must be bit-identical ----------------------------

class TestTraceParity:
    def _assert_parity(self, ck_off, ck_on):
        assert ck_on.unique_state_count() == ck_off.unique_state_count()
        assert ck_on.state_count() == ck_off.state_count()
        assert (sorted(ck_on.discoveries()) ==
                sorted(ck_off.discoveries()))
        assert (ck_on.generated_fingerprints() ==
                ck_off.generated_fingerprints())

    def test_twopc_single_chip(self):
        ck_off = _twopc().spawn_tpu().join()
        ck_on = _twopc(trace=[]).spawn_tpu().join()
        assert ck_on.unique_state_count() == 288
        self._assert_parity(ck_off, ck_on)

    def test_paxos_capped(self):
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        def mk(**opts):
            return (PackedPaxos(2).checker()
                    .tpu_options(capacity=1 << 14, race=False, **opts)
                    .target_state_count(2000).spawn_tpu().join())

        mk()  # warm: pin the observed-size memo for both runs
        self._assert_parity(mk(), mk(trace=[]))

    def test_sharded(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        def mk(**opts):
            mesh = Mesh(np.array(jax.devices()[:2]), ("shards",))
            return _twopc(mesh=mesh, **opts).spawn_tpu().join()

        events = []
        ck_off, ck_on = mk(), mk(trace=events)
        self._assert_parity(ck_off, ck_on)
        chunk = [e for e in events if e["ev"] == "chunk"][-1]
        assert len(chunk["shard_new"]) == 2  # per-shard volumes ride

    def test_host_bfs_parity(self):
        from stateright_tpu.models.twopc import TwoPhaseSys
        ck_off = TwoPhaseSys(3).checker().spawn_bfs().join()
        ck_on = (TwoPhaseSys(3).checker().tpu_options(trace=[])
                 .spawn_bfs().join())
        self._assert_parity(ck_off, ck_on)


# --- overhead smoke --------------------------------------------------------

class TestOverhead:
    def test_disabled_emit_is_trivial(self):
        t0 = time.perf_counter()
        for _ in range(100_000):
            if NULL_TRACE:
                NULL_TRACE.emit("chunk", gen=1)
        assert time.perf_counter() - t0 < 0.5

    def test_traced_run_overhead_smoke(self):
        """Loose CI bound (CPU timing is noisy); the <2% contract is
        measured on the bench workload via bench.py's metrics lines."""
        def mk(**opts):
            return _twopc(4, **opts).spawn_tpu().join()

        mk()  # warm compile
        off = min(self._clock(mk), self._clock(mk))
        on = min(self._clock(lambda: mk(trace=[])),
                 self._clock(lambda: mk(trace=[])))
        assert on < off * 1.5 + 0.25, (on, off)

    @staticmethod
    def _clock(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0


# --- schema drift lint -----------------------------------------------------

class TestSchemaDriftLint:
    """New instrumentation cannot silently bypass the canonical
    registries: every literal ``trace.emit("<ev>", ...)`` event name in
    the source tree must be in EVENT_SCHEMA, and every literal metrics
    key (``inc``/``set``/``observe_max``/``add_time``/``timed``) must
    be in GLOSSARY. This is the check that kept PR 3's unification from
    rotting — a drive-by `self._metrics.inc("my_counter")` fails here,
    not in a code review six rounds later."""

    def _sources(self):
        import glob
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        files = glob.glob(os.path.join(root, "stateright_tpu", "**",
                                       "*.py"), recursive=True)
        files += glob.glob(os.path.join(root, "tools", "*.py"))
        files.append(os.path.join(root, "bench.py"))
        assert len(files) > 40, "source scan found too few files"
        for path in files:
            with open(path) as f:
                yield path, f.read()

    def test_emitted_event_names_are_in_schema(self):
        import re
        emit_re = re.compile(r'\.emit\(\s*[\'"]([a-z_0-9]+)[\'"]')
        bad = [(path, name)
               for path, src in self._sources()
               for name in emit_re.findall(src)
               if name not in EVENT_SCHEMA]
        assert not bad, f"trace events missing from EVENT_SCHEMA: {bad}"

    def test_metrics_keys_are_in_glossary(self):
        import re
        key_res = (
            re.compile(r'(?:metrics|_metrics)\.'
                       r'(?:inc|set|observe_max|add_time|timed)'
                       r'\(\s*[\'"]([a-z_0-9]+)[\'"]'),
        )
        # every engine span times itself under span_s.<name>
        span_re = re.compile(r'spans\.(?:span|record)'
                             r'\(\s*[\'"]([a-z_.0-9]+)[\'"]')
        bad = [(path, key)
               for path, src in self._sources()
               for rx in key_res
               for key in rx.findall(src)
               if key not in GLOSSARY]
        bad += [(path, "span_s." + name)
                for path, src in self._sources()
                for name in span_re.findall(src)
                if "span_s." + name not in GLOSSARY]
        assert not bad, f"metrics keys missing from GLOSSARY: {bad}"

    def test_schema_events_all_have_emit_sites(self):
        """The reverse lint (PR 14): every EVENT_SCHEMA entry must be
        emitted somewhere in the source tree — schema entries nothing
        emits are dead weight that silently bless typo'd names. This
        is what guarantees the lint actually COVERS the service
        (job_*), fleet (host_join/mesh_init), and lifecycle/
        aggregation emit sites rather than merely not rejecting
        them."""
        import re
        emit_re = re.compile(r'\.emit\(\s*[\'"]([a-z_0-9]+)[\'"]')
        emitted = set()
        for _path, src in self._sources():
            emitted.update(emit_re.findall(src))
        dead = set(EVENT_SCHEMA) - emitted
        assert not dead, f"EVENT_SCHEMA events nothing emits: {dead}"

    def test_lifecycle_and_fleet_families_are_pinned(self):
        """The service/fleet/observability-plane event families and
        the PR-14 glossary keys must stay registered — a drive-by
        rename breaks every recorded artifact's consumers."""
        for ev in ("trace_header",
                   "job_submit", "job_grant", "job_start",
                   "job_first_chunk", "job_pause", "job_resume",
                   "job_done", "pool_util",
                   "mesh_init", "host_join", "host_drop",
                   "bucket_flush", "batch_form", "lane_retire"):
            assert ev in EVENT_SCHEMA, ev
        for key in ("queue_wait_s", "first_chunk_s", "pool_busy_frac",
                    "jobs_per_min", "sse_dropped", "queue_depth",
                    "jobs_submitted", "jobs_done", "hosts", "procs"):
            assert key in GLOSSARY, key
        # the exposition typing derives from GAUGES: the new gauges
        # must be registered there or /metrics would type them counter
        from stateright_tpu.obs import GAUGES
        assert {"pool_busy_frac", "jobs_per_min"} <= GAUGES


# --- consumers -------------------------------------------------------------

class TestConsumers:
    def test_race_profile_tags_winner(self):
        # satellite fix: a host-won race used to report {}
        from stateright_tpu.examples.increment_lock import IncrementLock
        ck = IncrementLock(2).checker().spawn_tpu().join()
        prof = ck.profile()
        assert prof["engine"] in ("host", "device")
        assert "search" in prof  # the winner's real metrics rode along

    def test_profile_keys_stay_in_glossary(self):
        ck = _twopc().spawn_tpu().join()
        unknown = set(ck.profile()) - set(GLOSSARY)
        assert not unknown, f"undocumented profile keys: {unknown}"

    def test_report_metrics_line(self):
        w = io.StringIO()
        _twopc().spawn_tpu().report(w)
        out = w.getvalue()
        assert "\n# " in out and "chunks=" in out, out

    def test_subscribe_live_progress(self):
        seen = []
        ck = _twopc(trace=[]).spawn_tpu()
        ck.subscribe(seen.append)
        ck.join()
        assert any(e["ev"] == "chunk" for e in seen)

    def test_explorer_metrics_endpoint(self):
        import urllib.request

        from stateright_tpu.checker.explorer import serve
        from stateright_tpu.models.twopc import TwoPhaseSys
        checker, server = serve(TwoPhaseSys(2).checker(),
                                ("127.0.0.1", 0), block=False)
        host, port = server.server_address
        try:
            checker.join()
            with urllib.request.urlopen(
                    f"http://{host}:{port}/.metrics") as r:
                payload = json.loads(r.read())
            assert payload["done"] is True
            assert payload["unique_state_count"] > 0
            assert "search" in payload["profile"]
        finally:
            server.shutdown()
            server.server_close()

    def test_trace_report_tool(self, tmp_path, capsys):
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            import trace_report
        finally:
            sys.path.pop(0)
        path = tmp_path / "run.jsonl"
        _twopc(trace=str(path)).spawn_tpu().join()
        assert trace_report.main([str(path), "--validate"]) == 0
        out = capsys.readouterr().out
        assert "=== engine: TpuChecker" in out
        assert "done:" in out and "timeline:" in out
