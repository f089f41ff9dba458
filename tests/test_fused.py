"""Fused Pallas expand→fingerprint→dedup kernel (ops/fused.py).

Parity is pinned on CPU through Pallas **interpret mode**
(``tpu_options(fused=True)`` resolves to the interpreter off TPU), so
tier-1 verifies bit-identical behavior — same discovery sets, same
visited-fingerprint sets, same unique counts — without hardware. The
``fused='auto'`` contract (attempt → a failed build raises, classified;
the default is the staged path) is pinned by monkeypatching the build
probe.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402


def _run(model, fused, **opts):
    return (model.checker()
            .tpu_options(race=False, fused=fused, **opts)
            .spawn_tpu().join())


@pytest.fixture(scope="module")
def host_2pc3():
    model = TwoPhaseSys(3)
    return model.checker().spawn_bfs().join()


class TestFusedParity:
    def test_2pc_full_parity(self, host_2pc3):
        # full enumeration: the fused kernel must reproduce the staged
        # path's reached set, discoveries and counts exactly (2pc n=3:
        # 288 unique, `2pc.rs:128`). cc_dedup=False isolates the
        # kernel itself so even the probe-round telemetry is
        # bit-identical (the ring legitimately SHRINKS probe rounds —
        # its own pins live in TestCcDedup)
        staged = _run(TwoPhaseSys(3), False, capacity=1 << 12, fmax=64)
        fused = _run(TwoPhaseSys(3), True, capacity=1 << 12, fmax=64,
                     cc_dedup=False)
        assert staged.unique_state_count() == 288
        assert fused.unique_state_count() == 288
        assert (fused.generated_fingerprints()
                == staged.generated_fingerprints()
                == host_2pc3.generated_fingerprints())
        assert set(fused.discoveries()) == set(staged.discoveries())
        # the dedup telemetry rides both paths and must agree on this
        # deterministic workload; the path tag must not
        ps, pf = staged.profile(), fused.profile()
        assert ps["fused"] == 0 and pf["fused"] == 1
        assert pf["fused_chunks"] == pf["chunks"] > 0
        assert pf["predup_hits"] == ps["predup_hits"] > 0
        assert pf["probe_rounds"] == ps["probe_rounds"] > 0
        assert not pf.get("cc_dedup_hits")

    def test_discovery_paths_replay_fused(self):
        # mirror integrity: witness reconstruction through the fused
        # path's (fp -> parent fp) log must replay real transitions
        # (the witnesses are now selected by the IN-KERNEL property
        # eval — the sticky per-block registers)
        model = TwoPhaseSys(3)
        fused = _run(model, True, capacity=1 << 12, fmax=64)
        for name, path in fused.discoveries().items():
            prop = model.property(name)
            assert prop.condition(model, path.last_state())

    @pytest.mark.slow
    def test_sharded_parity(self, host_2pc3):
        # sharded engines fuse up to the exchange boundary; reached
        # sets and discoveries must match host BFS across the D=2 mesh
        from jax.sharding import Mesh
        devices = jax.devices()
        if len(devices) < 2:
            pytest.skip("need 2 devices")
        mesh = Mesh(np.array(devices[:2]), ("shards",))
        staged = _run(TwoPhaseSys(3), False, mesh=mesh,
                      capacity=1 << 12, fmax=64)
        fused = _run(TwoPhaseSys(3), True, mesh=mesh,
                     capacity=1 << 12, fmax=64)
        assert fused.unique_state_count() == 288
        assert (fused.generated_fingerprints()
                == staged.generated_fingerprints()
                == host_2pc3.generated_fingerprints())
        assert set(fused.discoveries()) == set(staged.discoveries())
        assert fused.profile()["fused_chunks"] > 0

    @pytest.mark.slow
    def test_symmetry_parity(self):
        # Increment's representative is value-complete (full-word
        # sort), so reduced counts are engine-independent — the fused
        # which-duplicate-wins race cannot move them
        from stateright_tpu.examples.increment import Increment
        model = Increment(2)
        staged = (model.checker().symmetry_fn(model.representative)
                  .tpu_options(race=False, fused=False,
                               capacity=1 << 12)
                  .spawn_tpu().join())
        model2 = Increment(2)
        fused = (model2.checker().symmetry_fn(model2.representative)
                 .tpu_options(race=False, fused=True, capacity=1 << 12)
                 .spawn_tpu().join())
        assert (fused.unique_state_count()
                == staged.unique_state_count())
        assert (fused.generated_fingerprints()
                == staged.generated_fingerprints())
        assert set(fused.discoveries()) == set(staged.discoveries())

    @pytest.mark.slow
    @pytest.mark.faults
    def test_crash_restart_parity(self):
        # packed crash-nibble lanes ride packed_step, so the kernel
        # (which vmaps packed_step) covers fault injection for free —
        # pin it against host BFS. (The write-once/paxos crash models
        # declare host-evaluated properties, which the fused path does
        # not cover — see supports(); PackedTimerCount is pure-device.)
        from stateright_tpu.actor.test_util import PackedTimerCount

        def mk():
            return PackedTimerCount(2, 2).crash_restart(2)

        host = mk().checker().spawn_bfs().join()
        fused = _run(mk(), True, capacity=1 << 14)
        assert (host.unique_state_count() == fused.unique_state_count()
                == 49)
        assert (host.generated_fingerprints()
                == fused.generated_fingerprints())
        assert set(fused.discoveries()) == set(host.discoveries())

    @pytest.mark.slow
    def test_growth_preserves_enumeration_fused(self):
        # mid-run table growth rebuilds the fused chunk program at the
        # new capacity (fresh kernel shapes) — enumeration must survive
        model = TwoPhaseSys(5)
        fused = _run(model, True, capacity=1 << 12, fmax=32)
        assert fused.profile().get("grows", 0) > 0
        assert fused.unique_state_count() == 8832
        host = model.checker().spawn_bfs().join()
        assert (fused.generated_fingerprints()
                == host.generated_fingerprints())


class TestFusedSelection:
    @pytest.mark.parametrize("mode", [None, "auto"])
    def test_default_and_cpu_auto_stay_staged(self, mode):
        # the default is the staged path (neither kernel lowers for
        # v5e yet); off-TPU, 'auto' resolves to staged with no attempt
        # — the interpreter would be slower than XLA
        opts = {} if mode is None else {"fused": mode}
        trace = []
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, capacity=1 << 12, trace=trace,
                           **opts)
              .spawn_tpu().join())
        assert ck.unique_state_count() == 288
        prof = ck.profile()
        assert prof["fused"] == 0
        assert not prof.get("fused_chunks")
        assert "fused_unsupported" not in prof

    def test_auto_build_failure_raises_classified(self, monkeypatch):
        # the 'auto' contract: a failing Pallas build is never swallowed
        # — the run raises FusedUnavailable naming the resilience class
        # of the cause, and the cause stays chained for classify_error
        from stateright_tpu.checker.resilience import (FaultKind,
                                                       classify_error)
        from stateright_tpu.ops import fused as fused_mod

        def boom(*a, **k):
            raise RuntimeError(
                "UNAVAILABLE: mosaic lowering not supported on this "
                "backend (injected)")

        monkeypatch.setattr(fused_mod, "verify_build", boom)
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, fused="auto", fused_attempt=True,
                           capacity=1 << 12)
              .spawn_tpu())
        with pytest.raises(fused_mod.FusedUnavailable,
                           match=r"failed \(transient\).*UNAVAILABLE") \
                as info:
            ck.join()
        assert classify_error(info.value) is FaultKind.TRANSIENT
        assert "fused" not in ck.profile() or ck.profile()["fused"] == 0

    def test_forced_fused_unsupported_raises(self):
        # fused=True is an explicit instruction: a configuration the
        # kernel cannot cover must fail loudly, not silently downgrade
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, fused=True, hint=2,
                           capacity=1 << 12)
              .spawn_tpu())
        with pytest.raises(ValueError, match="fused=True"):
            ck.join()

    def test_unknown_fused_value_rejected(self):
        with pytest.raises(ValueError, match="fused"):
            (TwoPhaseSys(3).checker()
             .tpu_options(race=False, fused="maybe")
             .spawn_tpu())

    def test_verify_build_memoizes_failure(self):
        # a known-bad build must not re-pay the attempt every run: the
        # memo replays the failure as FusedUnavailable
        from stateright_tpu.ops import fused as fused_mod
        model = TwoPhaseSys(3)
        probe = dict(symmetry=False, probe=True, interpret=True)

        calls = []
        orig = fused_mod.build_fused_block_fn

        def counting(*a, **k):
            calls.append(1)
            raise RuntimeError("UNAVAILABLE: injected build failure")

        try:
            fused_mod.build_fused_block_fn = counting
            with pytest.raises(RuntimeError, match="UNAVAILABLE"):
                fused_mod.verify_build(model, 32, 1 << 10, **probe)
            with pytest.raises(fused_mod.FusedUnavailable,
                               match="UNAVAILABLE"):
                fused_mod.verify_build(model, 32, 1 << 10, **probe)
            assert len(calls) == 1
        finally:
            fused_mod.build_fused_block_fn = orig


class TestInKernelProps:
    """Property-predicate evaluation fused INTO the step kernel: the
    per-block sticky (hit, witness fp) registers must reproduce the
    staged path's discovery selection exactly — same properties, same
    witness paths, not just the same names."""

    def test_witness_replay_identical_to_staged(self, host_2pc3):
        model = TwoPhaseSys(3)
        staged = _run(TwoPhaseSys(3), False, capacity=1 << 12, fmax=64)
        fused = _run(model, True, capacity=1 << 12, fmax=64)
        assert set(fused.discoveries()) == set(staged.discoveries())
        for name, path in fused.discoveries().items():
            # identical witness REPLAY: the same state sequence, ending
            # in a state that really satisfies/violates the property
            assert (path.into_states()
                    == staged.discoveries()[name].into_states()), name
            assert model.property(name).condition(model,
                                                  path.last_state())

    def test_eventually_terminal_flush_in_kernel(self):
        # EVENTUALLY discoveries come from the terminal-flush mask
        # (terminal rows with pending ebits) — evaluated in-kernel too
        from stateright_tpu.actor.test_util import PackedTimerCount
        host = PackedTimerCount(2, 2).checker().spawn_bfs().join()
        fused = _run(PackedTimerCount(2, 2), True, capacity=1 << 12)
        assert set(fused.discoveries()) == set(host.discoveries())
        assert (fused.generated_fingerprints()
                == host.generated_fingerprints())


class TestShardedProbeKernel:
    """The sharded fused pipeline's SECOND Pallas kernel: the owner-side
    post-exchange probe/insert (previously a staged program between the
    all-to-all and the append) must be digest-identical to the staged
    path on every mesh width and both exchanges."""

    @staticmethod
    def _mesh(d):
        from jax.sharding import Mesh
        devices = jax.devices()
        if len(devices) < d:
            pytest.skip(f"need {d} devices")
        return Mesh(np.array(devices[:d]), ("shards",))

    def _digest(self, ck):
        import hashlib
        fps = sorted(ck.generated_fingerprints())
        return hashlib.sha256(
            ",".join(str(f) for f in fps).encode()).hexdigest()

    def test_d2_bucket_digest_identical_to_staged(self, host_2pc3):
        mesh = self._mesh(2)
        staged = _run(TwoPhaseSys(3), False, mesh=mesh,
                      capacity=1 << 12, fmax=64)
        fused = _run(TwoPhaseSys(3), True, mesh=mesh,
                     capacity=1 << 12, fmax=64)
        assert fused.unique_state_count() == 288
        assert self._digest(fused) == self._digest(staged) \
            == self._digest(host_2pc3)
        assert set(fused.discoveries()) == set(staged.discoveries())
        # probe telemetry rides the second kernel's flags
        assert fused.profile()["probe_rounds"] > 0

    @pytest.mark.slow
    def test_d4_digest_identical_to_staged(self, host_2pc3):
        mesh = self._mesh(4)
        staged = _run(TwoPhaseSys(3), False, mesh=mesh,
                      capacity=1 << 12, fmax=64)
        fused = _run(TwoPhaseSys(3), True, mesh=mesh,
                     capacity=1 << 12, fmax=64)
        assert self._digest(fused) == self._digest(staged) \
            == self._digest(host_2pc3)

    @pytest.mark.slow
    def test_d2_ring_exchange_probe_kernel(self, host_2pc3):
        # the ring exchange probes per hop — the kernel replaces every
        # hop's staged insert
        mesh = self._mesh(2)
        fused = _run(TwoPhaseSys(3), True, mesh=mesh, exchange="ring",
                     capacity=1 << 12, fmax=64)
        assert self._digest(fused) == self._digest(host_2pc3)


class TestCcDedup:
    """Cross-chunk in-kernel dedup ring (`tpu_options(cc_dedup=...)`):
    soundness property — the cache may only kill lanes whose
    fingerprint already committed to the visited set, so the enumerated
    fingerprint set, unique counts and discoveries are IDENTICAL to the
    staged path (a false miss only costs a table probe, never drops a
    fresh key — `pre_dedup`'s argument, one tier up)."""

    def test_never_drops_fresh_fingerprint_2pc(self, host_2pc3):
        staged = _run(TwoPhaseSys(3), False, capacity=1 << 12, fmax=64)
        fused = _run(TwoPhaseSys(3), True, capacity=1 << 12, fmax=64)
        assert fused.unique_state_count() == 288
        assert (fused.generated_fingerprints()
                == staged.generated_fingerprints()
                == host_2pc3.generated_fingerprints())
        assert set(fused.discoveries()) == set(staged.discoveries())
        pf, ps = fused.profile(), staged.profile()
        # the ring actually fired on this duplicate-heavy model, the
        # in-batch share stayed exact, and ring kills can only REDUCE
        # table probe pressure
        assert pf["cc_dedup_hits"] > 0
        assert pf["cc_dedup_capacity"] > 0
        assert pf["predup_hits"] == ps["predup_hits"]
        assert pf["probe_rounds"] <= ps["probe_rounds"]
        # generated counts are pre-dedup semantics: untouched by cc
        assert fused.state_count() == staged.state_count()

    def test_sharded_cc_kills_before_exchange(self, host_2pc3):
        from jax.sharding import Mesh
        devices = jax.devices()
        if len(devices) < 2:
            pytest.skip("need 2 devices")
        mesh = Mesh(np.array(devices[:2]), ("shards",))
        fused = _run(TwoPhaseSys(3), True, mesh=mesh,
                     capacity=1 << 12, fmax=64)
        assert (fused.generated_fingerprints()
                == host_2pc3.generated_fingerprints())
        assert fused.profile()["cc_dedup_hits"] > 0

    def test_cc_option_validation(self):
        with pytest.raises(ValueError, match="cc_dedup"):
            (TwoPhaseSys(3).checker()
             .tpu_options(race=False, cc_dedup=1000)  # not a pow2
             .spawn_tpu())

    def test_custom_ring_size(self, host_2pc3):
        # a deliberately TINY ring: heavy slot eviction, so most probes
        # miss — misses must only cost table probes, never keys
        fused = _run(TwoPhaseSys(3), True, capacity=1 << 12, fmax=64,
                     cc_dedup=64)
        assert (fused.generated_fingerprints()
                == host_2pc3.generated_fingerprints())
        assert fused.profile()["cc_dedup_capacity"] == 64

    @pytest.mark.slow
    def test_2pc6_full_parity(self):
        # a bigger duplicate-heavy space (2pc n=6, 35k unique): host
        # oracle + staged + fused-with-ring all agree, and the ring
        # catches a meaningful share of the cross-chunk re-expansion.
        # (paxos models declare the host-evaluated `linearizable`
        # property, which supports() keeps staged — pinned by
        # TestFusedUnsupported::test_paxos_auto_reports_host_props.)
        host = TwoPhaseSys(6).checker().spawn_bfs().join()
        fused = _run(TwoPhaseSys(6), True, capacity=1 << 16, fmax=128)
        assert (fused.generated_fingerprints()
                == host.generated_fingerprints())
        assert set(fused.discoveries()) == set(host.discoveries())
        assert fused.profile()["cc_dedup_hits"] > 0

    @pytest.mark.slow
    @pytest.mark.faults
    def test_crash_restart_cc_parity(self):
        # crash-nibble lanes + the ring: a restart re-reaches earlier
        # states (genuine cross-chunk duplicates) — parity must hold
        from stateright_tpu.actor.test_util import PackedTimerCount

        def mk():
            return PackedTimerCount(2, 2).crash_restart(2)

        host = mk().checker().spawn_bfs().join()
        fused = _run(mk(), True, capacity=1 << 14, cc_dedup=256)
        assert (host.generated_fingerprints()
                == fused.generated_fingerprints())
        assert set(fused.discoveries()) == set(host.discoveries())


class TestFusedUnsupported:
    def test_auto_unsupported_emits_reason_once(self):
        # supports() exclusions no longer "quietly stay staged": one
        # fused_unsupported event names the reason, the gauge rides
        # profile(), and report()'s metrics line renders it
        import io
        trace = []
        ck = (TwoPhaseSys(3).checker()
              .tpu_options(race=False, fused="auto", hint=2,
                           capacity=1 << 12, trace=trace)
              .spawn_tpu().join())
        assert ck.unique_state_count() == 288
        prof = ck.profile()
        assert prof["fused"] == 0
        assert prof["fused_unsupported"] == 1
        events = [e for e in trace if e["ev"] == "fused_unsupported"]
        assert len(events) == 1
        assert "hint" in events[0]["reason"]
        out = io.StringIO()
        ck.report(out)
        assert "fused=unsupported" in out.getvalue()

    def test_supported_auto_run_has_no_unsupported_marker(self):
        trace = []
        ck = _run(TwoPhaseSys(3), "auto", capacity=1 << 12,
                  trace=trace)
        assert "fused_unsupported" not in ck.profile()
        assert not [e for e in trace
                    if e["ev"] == "fused_unsupported"]

    def test_paxos_auto_reports_host_props(self):
        # the real-world exclusion: register-protocol models (paxos,
        # abd, single-copy) declare the host-evaluated `linearizable`
        # property — 'auto' stays staged and now SAYS so
        from stateright_tpu.examples.paxos_packed import PackedPaxos
        trace = []
        ck = (PackedPaxos(2).checker()
              .tpu_options(race=False, fused="auto", trace=trace,
                           capacity=1 << 14)
              .target_state_count(2000)
              .spawn_tpu().join())
        events = [e for e in trace if e["ev"] == "fused_unsupported"]
        assert len(events) == 1
        assert "host-evaluated" in events[0]["reason"]
        assert ck.profile()["fused_unsupported"] == 1


class TestPreDedupSoundness:
    """`ops.expand.pre_dedup` arena-collision property: a lane is ONLY
    dropped when an earlier valid lane carries the SAME fingerprint —
    distinct keys colliding on an arena cell must both survive, so the
    retained fingerprint SET always equals the valid input set."""

    @staticmethod
    def _check(chi, clo, cvalid):
        import jax.numpy as jnp

        from stateright_tpu.ops.expand import pre_dedup
        keep = np.asarray(pre_dedup(jnp.asarray(chi), jnp.asarray(clo),
                                    jnp.asarray(cvalid)))
        fps = [(int(h), int(l)) for h, l in zip(chi, clo)]
        valid_set = {fp for fp, v in zip(fps, cvalid) if v}
        kept_set = {fp for fp, k in zip(fps, keep) if k}
        # soundness: no fingerprint vanishes, no invalid lane appears
        assert kept_set == valid_set
        # a dropped lane always has an EARLIER kept duplicate
        for i, (fp, v) in enumerate(zip(fps, cvalid)):
            if v and not keep[i]:
                assert any(keep[j] and fps[j] == fp for j in range(i))
        return keep

    def test_random_batches(self):
        rng = np.random.default_rng(7)
        for n in (8, 64, 257):
            chi = rng.integers(0, 2**32, n, dtype=np.uint32)
            clo = rng.integers(0, 2**32, n, dtype=np.uint32)
            # force heavy duplication: sample lanes from few keys
            pick = rng.integers(0, max(n // 4, 1), n)
            chi, clo = chi[pick], clo[pick]
            cvalid = rng.random(n) < 0.8
            self._check(chi, clo, cvalid)

    def test_engineered_arena_collisions(self):
        # distinct keys crafted onto the SAME arena cell: slot is
        # (clo ^ chi*PHI) & (acells-1) with acells = 2^ceil(log2(2n)),
        # so with chi=0, clo values differing only above the mask bits
        # collide. Both must be kept (dropping either would lose a
        # unique state — unsound).
        n = 8
        acells = 1 << max((2 * n - 1).bit_length(), 0)
        chi = np.zeros(n, np.uint32)
        clo = (np.arange(n, dtype=np.uint32) * np.uint32(acells)
               + np.uint32(3))  # all lanes -> arena cell 3
        cvalid = np.ones(n, bool)
        keep = self._check(chi, clo, cvalid)
        assert keep.all()  # distinct keys: nothing may be dropped

    def test_collision_with_duplicates_mixed(self):
        # colliding distinct keys on one cell (all must survive — a
        # collision loser is only dropped when the winner VERIFIES
        # equal) plus a true-duplicate pair alone on another cell
        # (the later lane dies in favor of the earlier). Duplicates
        # hiding behind a foreign collision winner survive pre-dedup —
        # that's the documented soundness trade; the table probe
        # resolves them.
        n = 16
        acells = 1 << max((2 * n - 1).bit_length(), 0)
        chi = np.zeros(n, np.uint32)
        # lanes 0..11: distinct keys, all on arena cell 3
        clo = (np.arange(n, dtype=np.uint32) * np.uint32(acells)
               + np.uint32(3))
        # lanes 12..15: ONE key on its own cell 7 — true duplicates
        clo[12:] = np.uint32(7)
        cvalid = np.ones(n, bool)
        keep = self._check(chi, clo, cvalid)
        assert keep[:13].all()          # distinct keys + first dup
        assert not keep[13:].any()      # later duplicates die


@pytest.mark.slow
def test_kernel_bench_emits_json(tmp_path):
    # tools/kernel_bench.py: the staged-vs-fused microbenchmark must
    # land parseable per-stage JSON (the PR-report artifact)
    import json
    import subprocess
    import sys
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "kb.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "kernel_bench.py"),
         "--model", "2pc4", "--fmax", "64", "--iters", "2",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(out.read_text())
    assert line["interpret"] is True
    for key in ("expand_ms", "hash_ms", "pre_dedup_ms", "probe_ms",
                "probe_kernel_ms"):
        assert line["stages"][key] >= 0
    assert line["fused_ms"] > 0 and line["staged_ms"] > 0
    # the sharded two-kernel path (step kernel + owner-side probe
    # kernel, exchange excluded) reports its own composed numbers
    assert line["sharded_fused_ms"] > 0
    assert line["sharded_staged_ms"] > 0
    assert line["sharded_fused_over_staged"] > 0
    assert 0 <= line["dup_lane_frac"] <= 1
