"""Checking-as-a-service (stateright_tpu/service + tools/jobs.py).

The load-bearing guarantees, all pinned on the CPU-forced virtual mesh:

* **concurrency parity** — two jobs running concurrently on DISJOINT
  power-of-two device subsets each produce results bit-identical to a
  solo run at the same mesh width (fingerprint-set digests match);
* **pause/resume parity** — a paused job's checkpoint resumes (in this
  process or after a service restart) to the identical reached set;
* **preemption parity** — a D=4 job paused by the scheduler and
  resumed on a D=2 subset equals an uninterrupted D=2 run (the
  degradation ladder's guarantee, now scheduler-driven);
* **restart survival** — a service killed (SIGKILL) mid-run resumes
  the RUNNING job from its last autosave on the next boot and finishes
  with the identical fingerprint set (subprocess test);
* ``bench.py --service-smoke`` lands a crash-proof ``"service": true``
  contract line, rc=0, CPU only.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402
from stateright_tpu.service import (DONE, PAUSED, RUNNING,  # noqa: E402
                                    DevicePool, JobSpec, JobStore,
                                    Scheduler, StepDriver, serve_jobs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: pinned engine shapes (shared with tests/test_resilience.py so the
#: persistent compile cache is reused): small, multi-chunk runs
OPTS = {"capacity": 1 << 12, "fmax": 64, "chunk_steps": 2}


def _digest(checker) -> str:
    fps = sorted(int(f) for f in checker.generated_fingerprints())
    return hashlib.sha256("\n".join(map(str, fps)).encode()).hexdigest()


def _solo(n: int, **extra):
    return (TwoPhaseSys(n).checker()
            .tpu_options(race=False, **OPTS, **extra)
            .spawn_tpu().join())


@pytest.fixture(scope="module")
def solo_2pc3():
    return _solo(3)


@pytest.fixture(scope="module")
def solo_2pc4():
    return _solo(4)


def _mesh(n):
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]), ("shards",))


# --- DevicePool: the ladder's subset carving as capacity allocation ----

class TestDevicePool:
    def test_carve_disjoint_and_merge(self):
        pool = DevicePool(list(range(8)))
        l4 = pool.acquire(4)
        l2a = pool.acquire(2)
        l2b = pool.acquire(2)
        assert l4.width == 4 and l2a.width == l2b.width == 2
        # power-of-two aligned, pairwise disjoint
        spans = [(l.offset, l.offset + l.width) for l in (l4, l2a, l2b)]
        for lease in (l4, l2a, l2b):
            assert lease.offset % lease.width == 0
        for i, (a0, a1) in enumerate(spans):
            for b0, b1 in spans[i + 1:]:
                assert a1 <= b0 or b1 <= a0
        assert pool.acquire(1) is None  # fully carved
        pool.release(l2a)
        assert pool.acquire(2).offset == l2a.offset
        # release everything: buddies merge back to the full mesh
        pool2 = DevicePool(list(range(8)))
        leases = [pool2.acquire(2) for _ in range(4)]
        assert all(leases)
        for lease in leases:
            pool2.release(lease)
        assert pool2.largest_free() == 8

    def test_pow2_floor_and_rejects(self):
        pool = DevicePool(list(range(5)))  # floor -> 4
        assert pool.width == 4
        assert pool.acquire(8) is None
        assert pool.acquire(3) is None  # not a power of two
        lease = pool.acquire(4)
        assert lease.devices == (0, 1, 2, 3)
        pool.release(lease)
        assert pool.free_width() == 4


# --- two-level pool: slices within hosts, hosts within the fleet ------

class TestTwoLevelPool:
    def test_two_hosts_carve_and_merge(self):
        pool = DevicePool(list(range(8)), hosts=[0] * 4 + [1] * 4)
        assert pool.host_count == 2 and pool.host_width == 4
        assert pool.width == 8
        # fleet-wide lease: both hosts, whole
        l8 = pool.acquire(8)
        assert l8.width == 8 and l8.hosts == (0, 1)
        assert pool.acquire(1) is None
        pool.release(l8)
        assert pool.largest_free() == 8
        # slice leases never straddle hosts; best-fit packs the
        # partially-carved host first, preserving whole hosts
        l2 = pool.acquire(2)
        assert l2.hosts == (0,)
        l4 = pool.acquire(4)
        assert l4.hosts == (1,)  # host 0 is carved; host 1 goes whole
        l2b = pool.acquire(2)
        assert l2b.hosts == (0,)  # packs into host 0's remainder
        assert pool.acquire(2) is None
        for lease in (l2, l4, l2b):
            pool.release(lease)
        assert pool.largest_free() == 8  # both levels merged back

    def test_wide_leases_take_whole_free_hosts_only(self):
        pool = DevicePool(list(range(4)), hosts=[0, 0, 1, 1])
        lone = pool.acquire(1)
        assert lone.hosts == (0,)
        # width == host_width needs a FULLY-FREE host, not host 0's
        # fragmented remainder
        l2 = pool.acquire(2)
        assert l2.hosts == (1,)
        assert l2.offset % l2.width == 0
        assert pool.acquire(2) is None  # host 0 has 1 free, fragmented
        assert pool.acquire(4) is None  # no fleet-wide block either
        pool.release(lone)
        pool.release(l2)
        assert pool.acquire(4).hosts == (0, 1)

    def test_unequal_hosts_trim_to_common_pow2(self):
        # 3+3 devices: per-host floor 2, fleet width 4 — and a slice
        # lease can never span the host boundary (the old flat floor
        # of 6 -> 4 would have straddled it)
        pool = DevicePool(list(range(6)), hosts=[0, 0, 0, 1, 1, 1])
        assert pool.host_width == 2 and pool.width == 4
        la = pool.acquire(2)
        lb = pool.acquire(2)
        spans = sorted([la.devices, lb.devices])
        assert spans == [(0, 1), (3, 4)]

    def test_plain_device_list_is_one_anonymous_host(self):
        # hosts=None on non-jax objects: process_index defaults to 0,
        # preserving the original single-level behavior
        pool = DevicePool(list(range(8)))
        assert pool.host_count == 1 and pool.host_width == 8
        assert pool.acquire(8).hosts == (0,)

    def test_per_host_free_accounting(self):
        pool = DevicePool(list(range(8)), hosts=["a"] * 4 + ["b"] * 4)
        assert pool.per_host_free() == {"a": 4, "b": 4}
        lease = pool.acquire(2)
        assert pool.per_host_free() == {"a": 2, "b": 4}
        pool.release(lease)
        assert pool.per_host_free() == {"a": 4, "b": 4}


class TestElasticPool:
    """Rolling host join/retire on the two-level pool: joined width
    buddy-merges into the fleet level, retired FREE width is withdrawn
    immediately, and busy slices on a retiring host drain without
    ever re-entering the free lists."""

    def test_add_host_doubles_fleet_width(self):
        pool = DevicePool(list(range(4)), hosts=["h0"] * 4)
        assert pool.acquire(8) is None
        assert pool.add_host("h1", [4, 5, 6, 7]) == 1
        assert pool.width == 8 and pool.active_host_count == 2
        l8 = pool.acquire(8)
        assert l8 is not None and l8.hosts == ("h0", "h1")
        assert l8.devices == (0, 1, 2, 3, 4, 5, 6, 7)
        pool.release(l8)
        assert pool.largest_free() == 8

    def test_add_host_rejects_duplicates_and_narrow_hosts(self):
        pool = DevicePool(list(range(4)), hosts=["h0"] * 4)
        with pytest.raises(ValueError, match="already"):
            pool.add_host("h0", [9, 10, 11, 12])
        with pytest.raises(ValueError, match="host_width"):
            pool.add_host("h1", [9])

    def test_retire_withdraws_free_width_immediately(self):
        pool = DevicePool(list(range(8)), hosts=["h0"] * 4 + ["h1"] * 4)
        assert pool.retire_host("h1") == [4, 5, 6, 7]
        assert pool.width == 4 and pool.active_host_count == 1
        assert pool.per_host_free() == {"h0": 4}
        assert pool.acquire(4).hosts == ("h0",)
        assert pool.acquire(1) is None
        with pytest.raises(ValueError, match="already retired"):
            pool.retire_host("h1")

    def test_retire_drains_busy_leases_without_refreeing_them(self):
        # the 8-device carve: h1's half is BUSY at retire time — its
        # eventual release is discarded, while h0's merges back whole
        pool = DevicePool(list(range(8)), hosts=["h0"] * 4 + ["h1"] * 4)
        on_h0 = pool.acquire(4)
        on_h1 = pool.acquire(4)
        assert on_h0.hosts == ("h0",) and on_h1.hosts == ("h1",)
        pool.retire_host("h1")
        assert pool.free_width() == 0
        pool.release(on_h1)  # drained, NOT re-freed
        assert pool.free_width() == 0
        pool.release(on_h0)
        assert pool.free_width() == 4
        assert pool.acquire(4).hosts == ("h0",)

    def test_retire_breaks_the_spanning_block_keeping_survivors(self):
        pool = DevicePool(list(range(8)), hosts=["h0"] * 4 + ["h1"] * 4)
        assert pool.largest_free() == 8  # one merged fleet-level block
        pool.retire_host("h0")
        assert pool.width == 4
        lease = pool.acquire(4)
        assert lease is not None and lease.hosts == ("h1",)
        assert pool.per_host_free() == {"h1": 0}


class TestTwoHostScheduler:
    def test_grants_jobs_across_two_simulated_hosts(self, tmp_path,
                                                    solo_2pc3):
        # ACCEPTANCE: one scheduler packs jobs across the whole fleet —
        # four width-1 jobs over a 2-host × 2-device pool land on BOTH
        # hosts (recorded per job), every result bit-identical to the
        # solo oracle, and the buddies merge back on completion
        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:4],
                          hosts=["h0", "h0", "h1", "h1"])
        jobs = [sched.submit(JobSpec("twopc", args=[3], options=OPTS))
                for _ in range(4)]
        by_host = {}
        for job in jobs:
            assert sched.wait(job.id, timeout=180.0) == "done"
            result = job.read_result()
            assert result["fingerprints_sha256"] == _digest(solo_2pc3)
            for h in job.status["hosts"]:
                by_host[h] = by_host.get(h, 0) + 1
        assert by_host == {"h0": 2, "h1": 2}
        prof = sched.profile()
        assert prof["jobs_done"] == 4
        assert prof["hosts"] == 2
        # completion merged the carves back through both levels. The
        # state flip deliberately precedes the worker's lease release
        # (wait() unblocks on the status artifact), so give the last
        # finally block a moment to land its release
        deadline = time.monotonic() + 5.0
        while sched._pool.largest_free() != 4 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched._pool.largest_free() == 4
        assert sched._pool.per_host_free() == {"h0": 2, "h1": 2}
        sched.shutdown()


# --- elastic flex: promote-on-freed-width, demote-under-pressure ------

class TestFlexController:
    """SLO-driven flex (``Scheduler(flex=True)``): width freed by a
    finishing job promotes the hungriest RUNNING job in place (the
    release path re-checks running jobs, not only the queue); queue
    pressure demotes the over-width job first; a rolling host join
    widens a running job without a restart. Digests stay pinned to the
    solo oracles through every width change."""

    def test_release_promotes_running_job_in_place(self, tmp_path,
                                                   solo_2pc3,
                                                   solo_2pc4):
        # the 8-device carve: B holds half the pool, so A (wants 8)
        # lands on 4; when B finishes and its buddies merge free, the
        # flex pass doubles A mid-run — promotes == 1, digest pinned.
        # A's step delay keeps it running past B's finish (at 0.02 s a
        # step it sometimes finished first, and nothing was promoted;
        # at 0.1 s it still did, now and then, on a loaded machine)
        if len(jax.devices()) < 8:
            pytest.skip("need 8 devices")
        sched = Scheduler(JobStore(tmp_path), devices=jax.devices(),
                          hosts=["h0"] * 8, flex=True,
                          flex_interval=0.0, step_budget=1)
        try:
            b = sched.submit(JobSpec("twopc", args=[3], options=OPTS,
                                     width=4))
            a = sched.submit(JobSpec("twopc", args=[4], options=OPTS,
                                     width=8, step_delay=0.25))
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and not \
                    sched.job(a.id).status.get("granted_width"):
                time.sleep(0.05)
            assert sched.job(a.id).status["granted_width"] == 4
            assert sched.wait(b.id, timeout=180.0) == "done"
            assert sched.wait(a.id, timeout=240.0) == "done"
            assert sched.job(a.id).status["granted_width"] == 8
            prof = sched.profile()
            assert prof.get("promotes") == 1
            assert prof.get("demotes", 0) == 0
            # wait() returned after the worker's exit path, which
            # releases the promote lease
            assert sched.profile().get("flex_width") == 0
            assert sched.job(b.id).read_result()[
                "fingerprints_sha256"] == _digest(solo_2pc3)
            assert sched.job(a.id).read_result()[
                "fingerprints_sha256"] == _digest(solo_2pc4)
        finally:
            sched.shutdown()

    def test_queue_pressure_demotes_the_overwidth_job(self, tmp_path,
                                                      solo_2pc3,
                                                      solo_2pc4):
        # C runs wide and alone; a higher-priority arrival finds the
        # pool fully carved — flex picks the width>1 job to DEMOTE
        # (checkpoint, release, requeue narrower) rather than a blind
        # preempt; both resume/finish with pinned digests
        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:4], hosts=["h0"] * 4,
                          flex=True, flex_interval=0.0, step_budget=1)
        try:
            c = sched.submit(JobSpec("twopc", args=[4], options=OPTS,
                                     width=4, step_delay=0.05))
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline \
                    and not sched.job(c.id).status.get("first_chunk_at"):
                time.sleep(0.05)
            d = sched.submit(JobSpec("twopc", args=[3], options=OPTS,
                                     width=4, priority=5))
            assert sched.wait(d.id, timeout=180.0) == "done"
            assert sched.wait(c.id, timeout=240.0) == "done"
            prof = sched.profile()
            assert prof.get("demotes") == 1
            assert prof.get("preemptions") == 1
            assert sched.job(c.id).status.get("resume") is True
            assert sched.job(d.id).read_result()[
                "fingerprints_sha256"] == _digest(solo_2pc3)
            assert sched.job(c.id).read_result()[
                "fingerprints_sha256"] == _digest(solo_2pc4)
            demotes = [json.loads(ln) for ln in open(
                sched.store.service_trace_path)
                if '"job_demote"' in ln]
            assert demotes and demotes[0]["job"] == c.id
            assert demotes[0]["width"] == 4
        finally:
            sched.shutdown()

    def test_host_join_widens_a_running_job(self, tmp_path, solo_2pc4):
        # rolling join: the fleet starts one host wide; h1 joins
        # mid-run and the under-granted job is promoted onto it
        if len(jax.devices()) < 8:
            pytest.skip("need 8 devices")
        devs = jax.devices()
        sched = Scheduler(JobStore(tmp_path), devices=devs[:4],
                          hosts=["h0"] * 4, flex=True,
                          flex_interval=0.0, step_budget=1)
        try:
            a = sched.submit(JobSpec("twopc", args=[4], options=OPTS,
                                     width=8, step_delay=0.02))
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and not \
                    sched.job(a.id).status.get("granted_width"):
                time.sleep(0.05)
            assert sched.job(a.id).status["granted_width"] == 4
            assert sched.join_host("h1", devs[4:8]) == 1
            assert sched.pool_width() == 8
            assert sched.wait(a.id, timeout=240.0) == "done"
            assert sched.job(a.id).status["granted_width"] == 8
            assert sorted(sched.job(a.id).status["hosts"]) \
                == ["h0", "h1"]
            prof = sched.profile()
            assert prof.get("promotes") == 1
            assert prof.get("hosts") == 2
            assert sched.job(a.id).read_result()[
                "fingerprints_sha256"] == _digest(solo_2pc4)
            # the job is done: h1 retires with nothing to drain
            assert len(sched.leave_host("h1")) == 4
            assert sched.pool_width() == 4
            assert sched.profile().get("hosts") == 1
        finally:
            sched.shutdown()


# --- StepDriver: start -> step(budget) -> ... -> finish ---------------

class TestStepDriver:
    def test_stepped_run_matches_blocking(self, solo_2pc3):
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, **OPTS).spawn_tpu())
        driver = StepDriver(ck).start()
        with pytest.raises(RuntimeError, match="start"):
            driver.start()
        while driver.step(2) == RUNNING:
            pass
        assert driver.status == DONE
        assert ck.is_done()
        assert _digest(ck) == _digest(solo_2pc3)
        assert ck.unique_state_count() == 288
        # a claimed run cannot also start its background thread, but
        # join()/report() after the driver finished still work
        assert ck.join() is ck

    def test_pause_checkpoint_resumes_bit_identical(self, tmp_path,
                                                    solo_2pc3):
        path = tmp_path / "pause.npz"
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, **{**OPTS, "chunk_steps": 1})
              .spawn_tpu())
        driver = StepDriver(ck).start()
        assert driver.step(1) == RUNNING  # genuinely mid-run
        ckpt = driver.pause(os.fspath(path))
        assert driver.status == PAUSED and ck.paused()
        assert ckpt == os.fspath(path) and path.exists()
        assert ck.profile()["pauses"] == 1
        assert 0 < ck.unique_state_count() < 288
        resumed = (TwoPhaseSys(3).checker()
                   .tpu_options(race=False, **OPTS)
                   .resume_from(path).spawn_tpu().join())
        assert resumed.unique_state_count() == 288
        assert _digest(resumed) == _digest(solo_2pc3)

    def test_pause_after_finish_reports_done(self, tmp_path):
        ck = (TwoPhaseSys(2).checker()
              .tpu_options(race=False, **OPTS).spawn_tpu())
        driver = StepDriver(ck).start()
        driver.drain()
        assert driver.status == DONE
        assert driver.pause(os.fspath(tmp_path / "p.npz")) is None
        assert driver.status == DONE and not ck.paused()

    def test_pause_needs_a_destination(self):
        ck = (TwoPhaseSys(2).checker()
              .tpu_options(race=False, **OPTS).spawn_tpu())
        with pytest.raises(ValueError, match="artifact_dir"):
            ck.request_pause()


# --- job-scoped artifacts ---------------------------------------------

class TestArtifactDir:
    def test_expands_and_isolates(self, tmp_path):
        a_dir, b_dir = tmp_path / "a", tmp_path / "b"
        runs = []
        for d in (a_dir, b_dir):
            runs.append(
                TwoPhaseSys(2).checker()
                .tpu_options(race=False, **OPTS,
                             artifact_dir=os.fspath(d),
                             autosave_interval=1)
                .spawn_tpu().join())
        for d in (a_dir, b_dir):
            assert (d / "trace.jsonl").exists()
            assert (d / "autosave.npz").exists()
        # the two runs' artifacts are fully separate files
        assert (a_dir / "trace.jsonl").read_text() \
            != "" != (b_dir / "trace.jsonl").read_text()
        prof = runs[0].profile()
        assert prof.get("autosaves", 0) >= 1

    def test_explicit_knob_wins(self, tmp_path):
        explicit = tmp_path / "elsewhere.jsonl"
        ck = (TwoPhaseSys(2).checker()
              .tpu_options(race=False, **OPTS,
                           artifact_dir=os.fspath(tmp_path / "job"),
                           trace=os.fspath(explicit))
              .spawn_tpu().join())
        assert ck.is_done()
        assert explicit.exists()
        assert not (tmp_path / "job" / "trace.jsonl").exists()


# --- the scheduler -----------------------------------------------------

class TestScheduler:
    def test_concurrent_jobs_disjoint_subsets_bit_identical(
            self, tmp_path, solo_2pc3, solo_2pc4):
        # ACCEPTANCE: two jobs submitted concurrently to a 2-device
        # (CPU-forced) pool run on disjoint width-1 subsets and each
        # returns results bit-identical to a solo run
        if len(jax.devices()) < 2:
            pytest.skip("need 2 devices")
        sched = Scheduler(JobStore(tmp_path), devices=jax.devices()[:2])
        j1 = sched.submit(JobSpec("twopc", args=[3], options=OPTS,
                                  step_delay=0.25))
        j2 = sched.submit(JobSpec("twopc", args=[4], options=OPTS,
                                  step_delay=0.25))
        assert sched.wait(j1.id, timeout=120.0) == "done"
        assert sched.wait(j2.id, timeout=120.0) == "done"
        r1, r2 = j1.read_result(), j2.read_result()
        assert r1["unique_state_count"] == 288
        assert r2["unique_state_count"] == solo_2pc4.unique_state_count()
        assert r1["fingerprints_sha256"] == _digest(solo_2pc3)
        assert r2["fingerprints_sha256"] == _digest(solo_2pc4)
        # they really ran side by side on their own devices
        assert j1.status["granted_width"] == 1
        assert j2.status["granted_width"] == 1
        assert j1.status["running_at"] < j2.status["done_at"]
        assert j2.status["running_at"] < j1.status["done_at"]
        prof = sched.profile()
        assert prof["jobs_submitted"] == 2 and prof["jobs_done"] == 2
        sched.shutdown()

    def test_pause_restart_resume_parity(self, tmp_path, solo_2pc4):
        # pause -> (new scheduler on the same store = a service
        # restart) -> resume: the finished job equals the solo run
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:1])
        job = sched.submit(JobSpec("twopc", args=[4],
                                   options={**OPTS, "chunk_steps": 1,
                                            "autosave_interval": 1},
                                   step_delay=0.2))
        assert sched.wait(job.id, timeout=60.0,
                          states=("running",)) == "running"
        assert sched.pause(job.id)
        assert sched.wait(job.id, timeout=60.0,
                          states=("paused",)) == "paused"
        assert job.has_checkpoint()
        sched.shutdown()

        sched2 = Scheduler(JobStore(tmp_path),
                           devices=jax.devices()[:1])
        job2 = sched2.job(job.id)
        assert job2.state == "paused"  # paused jobs wait for resume
        assert sched2.resume(job.id)
        assert sched2.wait(job.id, timeout=120.0) == "done"
        result = sched2.job(job.id).read_result()
        assert result["unique_state_count"] == \
            solo_2pc4.unique_state_count()
        assert result["fingerprints_sha256"] == _digest(solo_2pc4)
        sched2.shutdown()

    @pytest.mark.slow
    def test_preempt_d4_resumes_at_d2_equals_uninterrupted_d2(
            self, tmp_path):
        # ACCEPTANCE: preemption = pause the lowest-priority job,
        # resume on a smaller subset — a D=4 job paused mid-run and
        # resumed at D=2 equals an uninterrupted D=2 run (the ladder's
        # parity guarantee, now scheduler-driven)
        # (-m slow since round 11: the slowest service pin after the
        # sigkill subprocess; cross-width pause/resume parity stays in
        # tier-1 via test_pause_restart_resume_parity, and the batch
        # storm pin needed the budget headroom)
        if len(jax.devices()) < 4:
            pytest.skip("need 4 devices")
        clean_d2 = (TwoPhaseSys(3).checker()
                    .tpu_options(race=False, **OPTS, mesh=_mesh(2))
                    .spawn_tpu().join())
        sched = Scheduler(JobStore(tmp_path), devices=jax.devices()[:4])
        lo = sched.submit(JobSpec("twopc", args=[3],
                                  options={**OPTS, "chunk_steps": 1},
                                  width=4, priority=0, step_delay=0.25))
        assert sched.wait(lo.id, timeout=60.0,
                          states=("running",)) == "running"
        hi = sched.submit(JobSpec("twopc", args=[2], options=OPTS,
                                  width=2, priority=5))
        assert sched.wait(hi.id, timeout=120.0) == "done"
        assert sched.wait(lo.id, timeout=180.0) == "done"
        prof = sched.profile()
        assert prof.get("preemptions", 0) >= 1
        assert lo.status.get("preempted") is True
        assert lo.status["granted_width"] == 2  # resumed SMALLER
        result = lo.read_result()
        assert result["unique_state_count"] == \
            clean_d2.unique_state_count() == 288
        assert result["fingerprints_sha256"] == _digest(clean_d2)
        assert set(p["name"] for p in result["properties"]) == \
            set(p.name for p in clean_d2.model().properties())
        sched.shutdown()

    def test_cancel_running_job(self, tmp_path):
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:1])
        job = sched.submit(JobSpec("twopc", args=[4],
                                   options={**OPTS, "chunk_steps": 1},
                                   step_delay=0.25))
        sched.wait(job.id, timeout=60.0, states=("running",))
        assert sched.cancel(job.id)
        assert sched.wait(job.id, timeout=60.0) == "cancelled"
        sched.shutdown()

    def test_unknown_model_fails_loudly(self, tmp_path):
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:1])
        job = sched.submit(JobSpec("no-such-model", args=[]))
        assert sched.wait(job.id, timeout=60.0) == "failed"
        assert "unknown model" in job.status["error"]
        assert sched.profile()["jobs_failed"] == 1
        sched.shutdown()


# --- fleet quarantine (silent-corruption defense) ----------------------

class TestQuarantine:
    """A device the chunk auditor caught lying is withheld from every
    future grant (persisted in the service root, so it survives
    restarts, and surfaced in ``/utilization``); re-admission only
    through :meth:`Scheduler.audit_probe`."""

    def test_lying_device_quarantined_persisted_probed(self, tmp_path,
                                                       solo_2pc3):
        if len(jax.devices()) < 2:
            pytest.skip("need 2 devices")
        sched = Scheduler(JobStore(tmp_path), devices=jax.devices()[:2])
        job = sched.submit(JobSpec(
            "twopc", args=[3],
            options={**OPTS, "audit": 1, "retries": 2, "backoff": 0.0,
                     "corrupt_hook": lambda o, d: 0 if o == 2 else None}))
        assert sched.wait(job.id, timeout=120.0) == "done", job.state
        result = job.read_result()
        # the lying chip did not poison the artifact: digest parity
        # with a solo run, bound into the integrity chain
        assert result["fingerprints_sha256"] == _digest(solo_2pc3)
        assert result["chain_head"] and result["integrity"]
        quarantined = sched.quarantined()
        assert len(quarantined) == 1
        assert sched.utilization()["quarantined"] == quarantined
        assert os.path.exists(os.path.join(str(tmp_path),
                                           "quarantine.json"))
        # the pool never grants the blamed chip again: a clean job
        # still completes on the surviving device
        j2 = sched.submit(JobSpec("twopc", args=[3], options=OPTS))
        assert sched.wait(j2.id, timeout=120.0) == "done"
        assert sched.quarantined() == quarantined
        sched.shutdown()

        # restart survival: the blame record reloads from the service
        # root and the chip is carved out of the fresh pool
        sched2 = Scheduler(JobStore(tmp_path),
                           devices=jax.devices()[:2])
        j3 = sched2.submit(JobSpec("twopc", args=[3], options=OPTS))
        assert sched2.wait(j3.id, timeout=120.0) == "done"
        assert sched2.quarantined() == quarantined

        # probation: a FAILING audit probe keeps it out, a passing one
        # buddy-merges the width-1 block back and drops the record
        assert sched2.audit_probe(
            quarantined[0], oracle=lambda rows, dev: [1]) is False
        assert sched2.quarantined() == quarantined
        assert sched2.audit_probe(quarantined[0]) is True
        assert sched2.quarantined() == []
        with open(os.path.join(str(tmp_path), "quarantine.json")) as f:
            assert json.load(f) == {}
        # the freed device really is grantable: two jobs run
        # concurrently on the 2-device pool again
        a = sched2.submit(JobSpec("twopc", args=[3], options=OPTS,
                                  step_delay=0.25))
        b = sched2.submit(JobSpec("twopc", args=[3], options=OPTS,
                                  step_delay=0.25))
        assert sched2.wait(a.id, timeout=120.0) == "done"
        assert sched2.wait(b.id, timeout=120.0) == "done"
        assert a.status["running_at"] < b.status["done_at"]
        assert b.status["running_at"] < a.status["done_at"]
        sched2.shutdown()

    def test_probe_unknown_device_raises(self, tmp_path):
        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="not quarantined"):
            sched.audit_probe("999")
        sched.shutdown()


# --- HTTP API + CLI artifacts ------------------------------------------

class TestServiceApi:
    def test_http_end_to_end(self, tmp_path):
        from stateright_tpu.obs import validate_event

        sched = Scheduler(JobStore(tmp_path),
                          devices=jax.devices()[:1])
        handle = serve_jobs(sched, ("127.0.0.1", 0))
        base = handle.url
        try:
            body = json.dumps({"model": "twopc", "args": [3],
                               "options": OPTS}).encode()
            req = urllib.request.Request(
                f"{base}/jobs", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                job_id = json.loads(resp.read())["id"]

            deadline = time.monotonic() + 120
            state = None
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        f"{base}/jobs/{job_id}") as resp:
                    view = json.loads(resp.read())
                state = view["state"]
                if state in ("done", "failed", "cancelled"):
                    break
                time.sleep(0.1)
            assert state == "done", view
            assert view["result"]["unique_state_count"] == 288

            with urllib.request.urlopen(f"{base}/jobs") as resp:
                listing = json.loads(resp.read())
            assert any(j["id"] == job_id for j in listing["jobs"])
            assert listing["profile"]["jobs_done"] >= 1

            with urllib.request.urlopen(
                    f"{base}/jobs/{job_id}/metrics") as resp:
                metrics = json.loads(resp.read())
            assert metrics["profile"].get("chunks", 0) >= 1

            # finished job: SSE replays the recorded trace and ends
            with urllib.request.urlopen(
                    f"{base}/jobs/{job_id}/events", timeout=10) as resp:
                sse = resp.read().decode()
            events = [json.loads(line[6:])
                      for line in sse.splitlines()
                      if line.startswith("data: ")]
            assert any(e["ev"] == "done" for e in events)

            # unknown job -> 404; bad submit -> 400
            for url, data in ((f"{base}/jobs/nope", None),
                              (f"{base}/jobs", b"{}")):
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        url, data=data,
                        headers={"Content-Type": "application/json"}
                        if data else {}))
                    raise AssertionError("expected an HTTP error")
                except urllib.error.HTTPError as exc:
                    assert exc.code in (400, 404)
        finally:
            handle.shutdown()

        # the service's own trace validates against the event schema
        # and records the whole lifecycle
        service_events = []
        with open(os.path.join(tmp_path, "service.jsonl")) as f:
            for line in f:
                if line.strip():
                    service_events.append(json.loads(line))
        # the full SLO lifecycle (PR 14): submit -> grant -> start ->
        # first-chunk -> done
        assert [e["ev"] for e in service_events
                if e["ev"].startswith("job_")] == \
            ["job_submit", "job_grant", "job_start",
             "job_first_chunk", "job_done"]
        for ev in service_events:
            validate_event(ev)
            assert ev["engine"] == "service"

        # tools/trace_report.py --job renders both the job directory
        # and the service root without errors
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(REPO, "tools",
                                         "trace_report.py"))
        trace_report = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trace_report)
        job_dir = os.path.join(tmp_path, job_id)
        import contextlib
        import io
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = trace_report.main(["--job", os.fspath(tmp_path),
                                    "--validate"])
        assert rc == 0
        assert "jobs:" in out.getvalue()
        assert "job_submit" in out.getvalue() \
            or "submit" in out.getvalue()
        located = trace_report.job_traces(job_dir)
        assert any(p.endswith("trace.jsonl") for p in located)


# --- restart survival (subprocess, SIGKILL) ----------------------------

class TestServiceRestart:
    def _serve(self, root, env):
        proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tools", "jobs.py"),
             "serve", "--root", os.fspath(root), "--cpu",
             "--cpu-devices", "2"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=REPO)
        line = proc.stdout.readline()
        assert "jobs-service listening on" in line, (
            line, proc.stderr.read() if proc.poll() is not None else "")
        url = [tok for tok in line.split() if tok.startswith("http")][0]
        return proc, url

    @pytest.mark.slow
    def test_sigkill_midrun_resumes_to_identical_fingerprints(
            self, tmp_path, solo_2pc4):
        # ACCEPTANCE: service killed -9 mid-run; on the next boot the
        # RUNNING job resumes from its last autosave and finishes with
        # the identical fingerprint set
        # (-m slow since round 11: the second-slowest service pin; the
        # in-process restart-resume parity pin — pause_restart_resume
        # — keeps boot recovery in tier-1, and the batch storm pin
        # needed the budget headroom)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the serve --cpu flags rebuild it
        root = tmp_path / "svc"
        proc, url = self._serve(root, env)
        try:
            body = json.dumps({
                "model": "twopc", "args": [4],
                "options": {**OPTS, "chunk_steps": 1,
                            "autosave_interval": 1},
                "step_delay": 0.3}).encode()
            req = urllib.request.Request(
                f"{url}/jobs", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                job_id = json.loads(resp.read())["id"]
            # wait until it is RUNNING with an autosave on disk, then
            # kill the whole service dead
            autosave = root / job_id / "autosave.npz"
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        f"{url}/jobs/{job_id}", timeout=10) as resp:
                    state = json.loads(resp.read())["state"]
                if state == "running" and autosave.exists():
                    break
                assert state not in ("done", "failed"), state
                time.sleep(0.05)
            else:
                pytest.fail("job never reached running+autosave")
        finally:
            proc.kill()  # SIGKILL: no cleanup, no checkpoint-on-exit
            proc.wait()

        # boot a fresh service on the same root: the RUNNING job must
        # re-enqueue and resume from the autosave
        proc2, url2 = self._serve(root, env)
        try:
            deadline = time.monotonic() + 180
            state = None
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        f"{url2}/jobs/{job_id}", timeout=10) as resp:
                    view = json.loads(resp.read())
                state = view["state"]
                if state in ("done", "failed", "cancelled"):
                    break
                time.sleep(0.2)
            assert state == "done", view
            assert view.get("resume") is True  # it RESUMED, not re-ran
            result = view["result"]
            assert result["unique_state_count"] == \
                solo_2pc4.unique_state_count()
            assert result["fingerprints_sha256"] == _digest(solo_2pc4)
        finally:
            proc2.send_signal(signal.SIGTERM)
            try:
                proc2.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc2.kill()
                proc2.wait()


# --- bench contract ----------------------------------------------------

class TestBenchServiceSmoke:
    def test_contract_line_lands_rc0(self):
        # ACCEPTANCE: --service-smoke lands a contract line, rc=0,
        # with no JAX devices beyond CPU
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--service-smoke"],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        contract = json.loads(proc.stdout.strip().splitlines()[-1])
        assert contract["service"] is True
        assert contract["unit"] == "uniq/s"
        assert "jobs" in contract
        if "partial" not in contract:
            assert contract["value"] and contract["value"] > 0
            assert len(contract["jobs"]) == 2
            assert all(row["state"] == "done"
                       for row in contract["jobs"])
        # tools/bench_history.py understands the service tag
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "bench_history", os.path.join(REPO, "tools",
                                              "bench_history.py"))
            bh = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(bh)
        finally:
            sys.path.pop(0)
        import tempfile
        with tempfile.TemporaryDirectory() as tdir:
            art = os.path.join(tdir, "BENCH_r99.json")
            with open(art, "w") as f:
                json.dump({"rc": 0, "parsed": contract, "tail": ""}, f)
            report = bh.build_report([art])
        entry = report["trend"][bh.CONTRACT][0]
        assert "service" in entry["tags"]


class TestBenchFlexSmoke:
    @pytest.mark.slow
    def test_contract_line_lands_rc0(self):
        # ACCEPTANCE (elastic fleet): --flex-smoke runs the rolling
        # join -> in-place promote -> pressure -> leave storyline and
        # ALWAYS lands a JSON contract line, rc=0; a full (non-partial)
        # round pins digest parity and bounded promote/demote churn
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--flex-smoke"],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        contract = json.loads(proc.stdout.strip().splitlines()[-1])
        assert contract["flex"] is True
        assert contract["unit"] == "uniq/s"
        if "partial" not in contract:
            assert contract["value"] and contract["value"] > 0
            assert contract["promotes"] >= 1  # the join was USED
            assert contract["promotes"] <= 8  # ... without thrashing
            assert contract["demotes"] <= 8
            assert all(row["digest_ok"] for row in contract["jobs"])
        # tools/bench_history.py tags the flex round
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_history", os.path.join(REPO, "tools",
                                          "bench_history.py"))
        bh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bh)
        import tempfile
        with tempfile.TemporaryDirectory() as tdir:
            art = os.path.join(tdir, "BENCH_r98.json")
            with open(art, "w") as f:
                json.dump({"rc": 0, "parsed": contract, "tail": ""}, f)
            report = bh.build_report([art])
        entry = report["trend"][bh.CONTRACT][0]
        assert "flex" in entry["tags"]
