"""The device engines' ``materialized`` counter in ``profile()``: the
full-width successor rows the chunk loop's steps built. A dense step
builds a row for every lane of its frontier block, so the count is at
least the rows expanded times the lanes per row; the single-chip loop
on an ordered actor model builds rows only for the lanes it keeps."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.examples.abd_packed import PackedAbd  # noqa: E402
from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402


def _mesh(n):
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"need {n} devices, have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]), ("shards",))


def _profile(model, shards=1, cap=None, **opts):
    if shards > 1:
        opts["mesh"] = _mesh(shards)
    builder = model.checker()
    if cap:
        builder = builder.target_state_count(cap)
    return builder.tpu_options(race=False, **opts).spawn_tpu().join() \
        .profile()


def _lanes(prof):
    return prof["expanded"] * prof["action_lanes"]


MODELS = {"abd-ordered": lambda: PackedAbd(2, server_count=2,
                                           ordered=True),
          "2pc": lambda: TwoPhaseSys(4)}


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_present_on_both_engines(name, shards):
    prof = _profile(MODELS[name](), shards, capacity=1 << 12)
    assert prof["materialized"] > 0
    if shards > 1 or name == "2pc":
        # the sharded engine and unordered models take the dense step
        assert prof["materialized"] >= _lanes(prof)


def test_ordered_abd_builds_fewer_rows_than_lanes():
    prof = _profile(PackedAbd(2, server_count=3, ordered=True), cap=4000,
                    capacity=1 << 14, fmax=512, kraw=1024, chunk_steps=4)
    assert 0 < prof["materialized"] < _lanes(prof)


def test_dense_step_counts_whole_blocks():
    """One step size (fmax under the 256-row small step): every step
    builds fmax x max_actions rows."""
    model = TwoPhaseSys(4)
    prof = _profile(model, capacity=1 << 12, fmax=64, chunk_steps=2)
    block = 64 * model.max_actions
    assert prof["materialized"] % block == 0
    assert prof["materialized"] >= _lanes(prof)
