"""The device engines' expansion counters in ``profile()``:
``expanded`` (frontier rows expanded, from the queue head's advance per
chunk, summed over shards), ``action_lanes`` (the model's
``max_actions``) and ``row_words`` (its ``packed_width``). A complete
check expands every unique state once."""

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


def _check(model, shards=1, **opts):
    if shards > 1:
        opts["mesh"] = _mesh(shards)
    return (model.checker().tpu_options(race=False, **opts)
            .spawn_tpu().join())


def _assert_counters(ck, model):
    prof = ck.profile()
    assert prof["expanded"] == ck.unique_state_count()
    assert prof["action_lanes"] == model.max_actions
    assert prof["row_words"] == model.packed_width
    assert ck.state_count() <= prof["expanded"] * prof["action_lanes"]


@pytest.mark.parametrize("shards", [1, 2])
def test_abd_ordered_complete_check(shards):
    model = PackedAbd(2, server_count=2, ordered=True)
    ck = _check(model, shards, capacity=1 << 12)
    assert ck.unique_state_count() == 564
    assert model.max_actions == 10
    _assert_counters(ck, model)


@pytest.mark.parametrize("shards", [1, 2])
def test_twopc_complete_check(shards):
    model = TwoPhaseSys(4)
    ck = _check(model, shards, capacity=1 << 12, fmax=64, chunk_steps=2)
    _assert_counters(ck, model)


@pytest.mark.parametrize("shards", [1, 2])
def test_expanded_only_rises(shards):
    """Read at every chunk sync, the count never falls, also across a
    re-seed after a transient fault (which restarts the queue head at
    0); the rows re-expanded after it count again."""
    seen = []
    box = {}

    def hook(chunk):
        if "ck" in box:
            seen.append(box["ck"].profile().get("expanded", 0))
        if chunk == 3:
            raise RuntimeError("UNAVAILABLE: fake backend drop (injected)")

    opts = dict(capacity=1 << 12, fmax=16, chunk_steps=1)
    if shards > 1:
        opts["mesh"] = _mesh(shards)
    clean = _check(TwoPhaseSys(3), **opts)
    ck = (TwoPhaseSys(3).checker()
          .tpu_options(race=False, retries=2, backoff=0.0, fault_hook=hook,
                       **opts)
          .spawn_tpu())
    box["ck"] = ck
    ck.join()
    assert ck.profile()["retries"] == 1
    assert len(seen) > 3 and seen == sorted(seen)
    assert ck.unique_state_count() == clean.unique_state_count()
    assert ck.profile()["expanded"] >= clean.profile()["expanded"] \
        == clean.unique_state_count()
