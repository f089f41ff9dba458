"""Program spans on the device trace's clock.

The load-bearing guarantees:

* **one call per phase** — ``SpanRecorder.span(name)`` records the
  interval, times it under ``span_s.<name>`` and annotates it as
  ``sr.<name>`` in any ``jax.profiler`` trace, so a profiler capture of
  a check shows the engine's phases beside the device ops;
* **the loop's bubble is the loop's** — ``profile()['bubble_frac']``
  sweeps the chunk loop only (first ``dispatch`` to last ``decode``),
  so the verdict read after it cannot move it;
* **the counters read what they say** — ``gc_s`` / ``gc_collections``
  follow the process's garbage collector, ``race_losers_alive`` the
  retired racer threads, whose list holds live threads only.
"""

import gc
import glob
import io
import os
import threading
import time

import pytest

from stateright_tpu.obs import SpanRecorder, loop_spans

pytestmark = pytest.mark.obs


def _twopc(n=3, **opts):
    from stateright_tpu.models.twopc import TwoPhaseSys
    opts.setdefault("race", False)
    return TwoPhaseSys(n).checker().tpu_options(capacity=1 << 12, **opts)


def _span(name, t0, t1):
    return {"name": name, "t0": float(t0), "t1": float(t1)}


class TestProfilerClock:
    def test_profiler_capture_holds_the_engine_phases(self, tmp_path):
        """A CPU ``jax.profiler`` capture of a small 2pc check, with the
        profiler options a benchmark's traced run uses: the ``/host:``
        plane carries the engine's phases as ``sr.*`` events."""
        import jax
        from jax.profiler import ProfileData

        _twopc().spawn_tpu().join()  # compile outside the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            ck = _twopc().spawn_tpu()
            ck.join()
            ck.report(io.StringIO())  # the verdict read: mirror, paths
        finally:
            jax.profiler.stop_trace()
        names = set()
        for path in glob.glob(os.path.join(str(tmp_path), "**",
                                           "*.xplane.pb"), recursive=True):
            for plane in ProfileData.from_file(path).planes:
                if plane.name.startswith("/host:"):
                    names.update(ev.name for ln in plane.lines
                                 for ev in ln.events
                                 if ev.name.startswith("sr."))
        assert {"sr.seed", "sr.dispatch", "sr.mirror.pull",
                "sr.mirror.index"} <= names, sorted(names)
        # an after-the-fact interval is no annotation: the device
        # trace itself shows the device side
        assert "sr.device" not in names

    def test_span_seconds_equal_the_timer_seconds(self):
        ck = _twopc().spawn_tpu()
        ck.join()
        ck.report(io.StringIO())
        prof = ck.profile()
        sums, counts = {}, {}
        for s in ck._spans.spans():
            sums[s["name"]] = sums.get(s["name"], 0.0) + s["t1"] - s["t0"]
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        assert {"spawn", "init", "seed", "program", "dispatch", "device",
                "sync", "pull", "decode", "finish", "mirror.pull",
                "mirror.index", "paths"} <= set(sums)
        for name, secs in sums.items():
            # the ring keeps microsecond-rounded stamps
            assert prof["span_s." + name] == pytest.approx(
                secs, abs=2e-6 * counts[name]), name
        assert "spans_dropped" not in prof
        # spawn -> first dispatch covers engine set-up and seeding
        assert prof["first_dispatch_s"] >= prof["span_s.seed"]

    def test_wrapped_ring_keeps_the_seconds(self):
        from stateright_tpu.obs import Metrics
        metrics = Metrics()
        rec = SpanRecorder(None, limit=4, metrics=metrics)
        t = time.perf_counter()
        for i in range(10):
            rec.record("decode", t + i, t + i + 0.5)
        assert len(rec) == 4 and rec.dropped == 6
        assert metrics.get("span_s.decode") == pytest.approx(5.0)

    def test_concurrent_spans_lose_no_seconds(self):
        """Spans from several threads into one recorder (the racer's,
        the decider's, the engine's): no timer update is lost."""
        import sys
        from stateright_tpu.obs import Metrics
        metrics = Metrics()
        rec = SpanRecorder(None, limit=64, metrics=metrics)
        t = time.perf_counter()

        def work():
            for _ in range(2000):
                rec.record("decode", t, t + 0.001)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work) for _ in range(8)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(60.0)
        finally:
            sys.setswitchinterval(old)
        assert not any(th.is_alive() for th in threads)
        assert metrics.get("span_s.decode") == pytest.approx(16.0)
        assert rec.dropped == 16000 - 64

    def test_held_spans_follow_the_run_header(self):
        events = []
        ck = _twopc(trace=events).spawn_tpu().join()
        kinds = [(e["ev"], e.get("name")) for e in events]
        assert kinds[0][0] == "run_start"
        assert ("span", "spawn") in kinds and ("span", "init") in kinds
        assert ck.profile()["span_s.init"] > 0.0


class TestLoopWindow:
    def test_loop_spans_clip_to_dispatch_through_decode(self):
        spans = [_span("init", 0.0, 1.0), _span("seed", 1.0, 2.0),
                 _span("dispatch", 2.0, 2.5), _span("device", 2.5, 4.0),
                 _span("decode", 4.0, 5.0), _span("race.host", 0.5, 6.0),
                 _span("finish", 5.0, 5.5), _span("mirror.pull", 6.0, 9.0)]
        got = {s["name"]: (s["t0"], s["t1"]) for s in loop_spans(spans)}
        assert got == {"dispatch": (2.0, 2.5), "device": (2.5, 4.0),
                       "decode": (4.0, 5.0)}
        assert loop_spans([_span("seed", 0.0, 1.0)]) == []

    def test_bubble_frac_is_unchanged_by_a_following_report(self):
        ck = _twopc().spawn_tpu()
        ck.join()
        before = ck.profile()
        assert "span_s.mirror.pull" not in before
        ck.report(io.StringIO())
        after = ck.profile()
        assert after["span_s.mirror.pull"] > 0.0
        assert after["bubble_frac"] == before["bubble_frac"]
        assert after["attribution"] == before["attribution"]

    def test_wait_spans_count_on_neither_side(self):
        from stateright_tpu.obs import analyze
        attr = analyze([_span("device", 0.0, 4.0), _span("sync", 1.0, 4.0),
                        _span("decode", 4.0, 5.0)])
        assert attr["buckets"] == {"device": 4.0, "host:decode": 1.0}


class TestCounters:
    def test_gc_seconds_rise_across_a_forced_collection(self):
        ck = _twopc().spawn_tpu()
        ck.join()
        before = ck.profile()
        junk = [[i] for i in range(200_000)]
        for i in range(0, len(junk) - 1, 2):
            junk[i].append(junk[i + 1])
        del junk
        gc.collect()
        after = ck.profile()
        assert after["gc_collections"] > before["gc_collections"]
        assert after["gc_s"] > before["gc_s"]

    def test_loser_list_holds_no_dead_threads(self):
        from stateright_tpu.checker import race
        for _ in range(3):
            _twopc(race=True).spawn_tpu().join()
        with race._LOSER_LOCK:
            losers = list(race._LOSER_THREADS)
        for t in losers:
            t.join(60.0)
        assert not any(t.is_alive() for t in losers)
        ck = _twopc(race=True).spawn_tpu().join()
        with race._LOSER_LOCK:
            kept = list(race._LOSER_THREADS)
        # the finished losers were pruned by this race's append
        assert not set(kept) & set(losers)
        assert all(isinstance(t, threading.Thread) for t in kept)
        assert len(kept) <= 1
        assert 0 <= ck.profile()["race_losers_alive"] <= len(kept)

    def test_race_decision_span_precedes_the_decision(self, monkeypatch):
        """``race.decide`` is recorded before the decision is published,
        so a profile read straight after ``join()`` always carries it,
        however slowly the decider records it."""
        record = SpanRecorder.record

        def slow_record(self, name, *args, **fields):
            if name == "race.decide":
                time.sleep(0.2)
            return record(self, name, *args, **fields)

        monkeypatch.setattr(SpanRecorder, "record", slow_record)
        for _ in range(2):
            prof = _twopc(race=True).spawn_tpu().join().profile()
            assert prof["span_s.race.decide"] >= 0.0

    def test_first_dispatch_counts_from_the_spawn_entry(self):
        ck = _twopc().spawn_tpu().join()
        spawn = [s for s in ck._spans.spans() if s["name"] == "spawn"]
        assert len(spawn) == 1
        assert spawn[0]["t0"] == round(ck._spans.rel(ck._spawn_t0), 6)
        assert ck.profile()["first_dispatch_s"] >= \
            ck.profile()["span_s.spawn"]
