"""The sharded engine against the single-chip engine, state for state.

The two chunk-loop drivers (``checker/tpu.py`` and
``parallel/engine.py``) each carry the whole pipeline. Here they must
agree on the searches the benchmark runs: the same unique count, the
same fingerprint set and the same discoveries (each path replaying
through the host model), on the ordered network (where one chip takes
the lane step and the mesh the dense ordered step) over 1, 2 and 8
shards and both exchanges, and at D = 1 and 2 on the benchmark's
complete configurations at their test size. An encoding overflow stops
the sharded run as loudly as the single-chip one.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from stateright_tpu.examples.abd_packed import PackedAbd  # noqa: E402
from stateright_tpu.examples.paxos_packed import PackedPaxos  # noqa: E402
from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402


def _mesh(n: int) -> Mesh:
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]), ("shards",))


def _run(model, n_shards=0, **opts):
    """A complete device check: one chip when ``n_shards`` is 0, else
    the sharded engine over that many devices."""
    if n_shards:
        opts = dict(opts, mesh=_mesh(n_shards), degrade=False, retries=0)
    return (model.checker().tpu_options(race=False, **opts)
            .spawn_tpu().join())


def _assert_same_search(model, sharded, single, unique):
    assert sharded.error() is None and single.error() is None
    assert single.unique_state_count() == unique
    assert sharded.unique_state_count() == unique
    assert (sharded.generated_fingerprints()
            == single.generated_fingerprints())
    assert set(sharded.discoveries()) == set(single.discoveries())
    for name, path in sharded.discoveries().items():
        # the path is rebuilt through the host model, so a wrong parent
        # link raises before this line; its last state must hold the
        # property's condition
        assert model.property(name).condition(model, path.last_state())
        assert len(path) == len(single.discoveries()[name])


def _abd_ordered():
    return PackedAbd(2, server_count=2, ordered=True)


@pytest.fixture(scope="module")
def abd_single():
    model = _abd_ordered()
    assert model.offers_lane_step  # one chip takes the lane step
    return _run(model, capacity=1 << 12)


@pytest.mark.parametrize("n_shards,exchange", [
    (1, "bucket"), (2, "bucket"), (2, "ring"), (8, "bucket"), (8, "ring")])
def test_ordered_network_equals_the_lane_step(abd_single, n_shards,
                                              exchange):
    model = _abd_ordered()
    sharded = _run(model, n_shards, capacity=1 << 12, exchange=exchange)
    _assert_same_search(model, sharded, abd_single, 564)


def _abd_depth1():
    return PackedAbd(2, server_count=2, ordered=True, channel_depth=1)


@pytest.fixture(scope="module")
def overflow_single():
    with pytest.raises(RuntimeError, match="capacity overflow") as info:
        _run(_abd_depth1(), capacity=1 << 12)
    return info.value


@pytest.mark.parametrize("n_shards", [1, 2])
def test_channel_overflow_stops_the_sharded_run(overflow_single, n_shards):
    # depth-1 channels overflow on 2 clients x 2 servers: the sharded
    # run must raise as the single-chip one does, never drop the
    # unencodable successors and report a smaller complete search
    with pytest.raises(RuntimeError, match="capacity overflow"):
        _run(_abd_depth1(), n_shards, capacity=1 << 12)


# the benchmark's complete configurations at their test size
# (benchmark/configs/2pc8.json and paxos2.json, ``small``)
COMPLETE = {
    "2pc4": (lambda: TwoPhaseSys(4), 1568, {"capacity": 1 << 12}),
    "paxos2": (lambda: PackedPaxos(2), 16668, {}),
}


@pytest.fixture(scope="module")
def complete_single():
    """The single-chip check of each complete configuration, run once."""
    runs = {}

    def get(config):
        if config not in runs:
            make, _unique, opts = COMPLETE[config]
            runs[config] = _run(make(), **opts)
        return runs[config]
    return get


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("config", sorted(COMPLETE))
def test_complete_configs_equal_one_chip(complete_single, config,
                                         n_shards):
    make, unique, opts = COMPLETE[config]
    sharded = _run(make(), n_shards, **opts)
    _assert_same_search(make(), sharded, complete_single(config), unique)
