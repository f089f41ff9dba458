"""Pipelined vs synchronous chunk-loop parity (``tpu_options(pipeline=...)``).

The double-buffered dispatch (PR 2) may only change WHEN the host learns
things, never WHAT the search finds: on full-enumeration and
counterexample workloads the two modes must agree bit-for-bit on unique
counts, reached fingerprint sets, discoveries, and replayed
counterexample paths — on both the single-chip and the sharded engine,
including a crash-restart fault config. Also covers the new
``profile()`` overlap timers and the refcounted visitor replay
(``_visit_reached`` drops decoded states at backtrack instead of
retaining one per unique state).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stateright_tpu.core import Property  # noqa: E402
from stateright_tpu.models.packed import PackedModel  # noqa: E402
from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402


def _run(mk, **opts):
    return (mk().checker()
            .tpu_options(race=False, **opts)
            .spawn_tpu().join())


def _mesh(n):
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]), ("shards",))


def _assert_full_parity(on, off):
    assert on.unique_state_count() == off.unique_state_count()
    assert on.generated_fingerprints() == off.generated_fingerprints()
    assert set(on.discoveries()) == set(off.discoveries())


class TestSingleChipParity:
    def test_2pc_full_enumeration(self):
        # 288 unique states; no host props — the pure device-loop path
        on = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64)
        off = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                   pipeline=False)
        assert on.unique_state_count() == 288
        _assert_full_parity(on, off)
        for name, path in on.discoveries().items():
            assert (path.into_actions()
                    == off.discoveries()[name].into_actions())

    def test_paxos_full_enumeration_with_host_props(self):
        # 265 unique; 'linearizable' is host-evaluated, so this drives
        # the in-carry history dedup + the stats-window representative
        # consumption (offset-anchored under pipelining)
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        on = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64)
        off = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                   pipeline=False)
        assert on.unique_state_count() == 265
        _assert_full_parity(on, off)
        on.assert_properties()
        off.assert_properties()

    def test_write_once_crash_restart_full(self):
        # the PR-1 fault config: durable write-once under crash_restart
        from stateright_tpu.examples.write_once_packed import \
            PackedWriteOnce

        def mk():
            return PackedWriteOnce(2, durable=True).crash_restart(
                1, actors=[0])

        on = _run(mk, capacity=1 << 12)
        off = _run(mk, capacity=1 << 12, pipeline=False)
        assert on.unique_state_count() == 51
        _assert_full_parity(on, off)

    def test_write_once_volatile_counterexample_path(self):
        # early exit through a host-property discovery: the replayed
        # counterexample must be action-identical across modes (counts
        # may differ — the pipeline's speculative chunk is documented
        # extra exploration past a host-only exit)
        from stateright_tpu.examples.write_once_packed import \
            PackedWriteOnce

        def mk():
            return PackedWriteOnce(2, durable=False).crash_restart(
                1, actors=[0])

        on = _run(mk, capacity=1 << 12)
        off = _run(mk, capacity=1 << 12, pipeline=False)
        p_on = on.assert_any_discovery("linearizable")
        p_off = off.assert_any_discovery("linearizable")
        assert p_on.into_actions() == p_off.into_actions()

    def test_growth_parity(self):
        # capacity (and fmax, which bounds the pre-loop headroom bump)
        # small enough to force mid-run growth in both modes
        on = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16)
        off = _run(lambda: TwoPhaseSys(4), capacity=1 << 8, fmax=16,
                   pipeline=False)
        assert on.profile().get("span_s.grow", 0) > 0
        _assert_full_parity(on, off)

    def test_profile_overlap_timers(self):
        on = _run(lambda: TwoPhaseSys(3), capacity=1 << 12)
        prof = on.profile()
        for key in ("span_s.dispatch", "span_s.sync", "span_s.decode",
                    "chunks"):
            assert key in prof, key
        off = _run(lambda: TwoPhaseSys(3), capacity=1 << 12,
                   pipeline=False)
        assert "span_s.sync" in off.profile()


class TestShardedParity:
    @pytest.mark.parametrize("n_shards", [2, 8])
    def test_2pc_full_enumeration(self, n_shards):
        mesh = _mesh(n_shards)
        on = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                  mesh=mesh)
        off = _run(lambda: TwoPhaseSys(3), capacity=1 << 12, fmax=64,
                   mesh=mesh, pipeline=False)
        assert on.unique_state_count() == 288
        _assert_full_parity(on, off)

    def test_paxos_host_props_sharded(self):
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        mesh = _mesh(2)
        on = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                  mesh=mesh)
        off = _run(lambda: PackedPaxos(1), capacity=1 << 12, fmax=64,
                   mesh=mesh, pipeline=False)
        assert on.unique_state_count() == 265
        _assert_full_parity(on, off)
        on.assert_properties()

    def test_write_once_crash_restart_sharded(self):
        from stateright_tpu.examples.write_once_packed import \
            PackedWriteOnce

        def mk():
            return PackedWriteOnce(2, durable=True).crash_restart(
                1, actors=[0])

        mesh = _mesh(2)
        on = _run(mk, capacity=1 << 12, mesh=mesh)
        off = _run(mk, capacity=1 << 12, mesh=mesh, pipeline=False)
        assert on.unique_state_count() == 51
        _assert_full_parity(on, off)

    def test_hint_with_mesh_raises(self):
        # satellite: the sharded engine must not silently ignore the
        # single-chip per-row compaction knob
        with pytest.raises(ValueError, match="hint"):
            (TwoPhaseSys(3).checker()
             .tpu_options(mesh=_mesh(2), hint=4)
             .spawn_tpu())


class TestHostPropFnsGuard:
    def test_mismatched_fns_fail_loudly(self):
        # satellite: a subclass changing properties without updating the
        # packed fast-path evaluators must not silently use stale
        # lambdas. The canonical form is name-keyed: an unknown name
        # (e.g. a renamed property whose evaluator key was not updated)
        # fails at spawn
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        model = PackedPaxos(1)
        assert isinstance(model.host_property_fns, dict)
        model.host_property_fns = {**model.host_property_fns,
                                   "bogus": lambda row: True}
        with pytest.raises(ValueError, match="host_property_fns"):
            model.checker().tpu_options(race=False).spawn_tpu()

    def test_legacy_positional_list_length_guard(self):
        # the legacy positional-list form keeps the PR 2 length guard
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        model = PackedPaxos(1)
        model.host_property_fns = [lambda row: True, lambda row: True]
        with pytest.raises(ValueError, match="host_property_fns"):
            model.checker().tpu_options(race=False).spawn_tpu()

    def test_name_keyed_fns_bind_by_name(self):
        # a dict missing a declared host property also fails loudly
        from stateright_tpu.examples.paxos_packed import PackedPaxos

        model = PackedPaxos(1)
        model.host_property_fns = {"wrong name": lambda row: True}
        with pytest.raises(ValueError, match="missing"):
            model.checker().tpu_options(race=False).spawn_tpu()


class _CombModel(PackedModel):
    """Deep chain with one leaf per spine node: spine 0..depth, leaves
    depth+1+x. The adversarial shape for visitor-replay memory — the old
    ``_visit_reached`` retained one decoded state per unique state for
    the whole replay; the refcounted DFS drops each leaf (and each
    completed spine suffix) at backtrack."""

    packed_width = 1
    max_actions = 2

    def __init__(self, depth: int):
        self.depth = depth

    def cache_key(self):
        return ("comb", self.depth)

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        if state < self.depth:
            actions.extend(["step", "leaf"])

    def next_state(self, state, action):
        return state + 1 if action == "step" else state + self.depth + 1

    def properties(self):
        def at_end(model, state):
            return state == model.depth
        return [Property.sometimes("reaches end", at_end)]

    def encode(self, state):
        return np.array([state], dtype=np.uint32)

    def decode(self, words):
        return int(words[0])

    def packed_step(self, words):
        x = words[0]
        succ = jnp.stack([
            jnp.stack([x + 1]),
            jnp.stack([x + self.depth + 1]),
        ]).astype(jnp.uint32)
        on_spine = x < self.depth
        valid = jnp.stack([on_spine, on_spine])
        return succ, valid

    def packed_properties(self, words):
        return jnp.stack([words[0] == self.depth])


class TestVisitorReplayMemory:
    def test_deep_chain_refcounted_drop(self):
        from stateright_tpu.checker.visitor import StateRecorder

        depth = 96
        total = 2 * depth + 1  # spine 0..depth plus depth leaves
        rec, states = StateRecorder.new_with_accessor()
        ck = (_CombModel(depth).checker().visitor(rec)
              .tpu_options(race=False, capacity=1 << 12, fmax=32)
              .spawn_tpu().join())
        assert ck.unique_state_count() == total
        assert set(states()) == set(range(total))
        peak = ck.profile()["visit_peak_resident"]
        # the spine itself is a real path, so O(depth) states are live
        # at the deepest visit — but never one per unique state
        assert peak <= depth + 3
        assert peak < total

    def test_deep_chain_paths_valid(self):
        from stateright_tpu.checker.visitor import PathRecorder

        # PathRecorder re-validates every visited path on construction;
        # one path per reached state, each ending at its state
        rec, paths = PathRecorder.new_with_accessor()
        ck = (_CombModel(24).checker().visitor(rec)
              .tpu_options(race=False, capacity=1 << 10, fmax=16)
              .spawn_tpu().join())
        got = paths()
        assert len(got) == ck.unique_state_count()
        ends = {p.last_state() for p in got}
        assert ends == set(range(2 * 24 + 1))
