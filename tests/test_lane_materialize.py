"""Successor rows built after lane compaction, on the ordered network.

An ordered ``PackedActorModel`` offers its step one lane at a time
(``packed_step_lane``) and a validity pass that builds no row
(``packed_lane_valid``); the single-chip chunk loop then builds only the
rows of the lanes it keeps. Here: each lane equals the dense step bit
for bit (a full channel's poisoned row and an empty channel's lane
included), the validity pass equals the dense validity, whole device
checks agree with the dense path, and the compiled chunk program holds
no successor tensor over every lane — while models without the lane step
keep the dense one."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stateright_tpu.checker.device_loop import (  # noqa: E402
    build_chunk_core, seed_carry)
from stateright_tpu.examples.abd_packed import PackedAbd  # noqa: E402
from stateright_tpu.examples.paxos_packed import PackedPaxos  # noqa: E402
from stateright_tpu.models.twopc import TwoPhaseSys  # noqa: E402
from stateright_tpu.ops.expand import expand_frontier  # noqa: E402

POISON = np.uint32(0xDEADBEEF)


class DenseAbd(PackedAbd):
    """The same protocol with ``packed_boundary`` overridden (keeping
    every state, as the default does): a boundary reads the built row,
    so the engine takes the dense step for this model."""

    def packed_boundary(self, words):
        return jnp.bool_(True)


def _reachable_rows(model, limit, batch=256):
    """Up to ``limit`` distinct reachable rows, breadth first, through
    the dense step (one compiled batch shape)."""
    step = jax.jit(jax.vmap(model.packed_step))
    rows = [model.encode(s) for s in model.init_states()]
    seen = {r.tobytes() for r in rows}
    frontier = list(rows)
    while frontier and len(rows) < limit:
        nxt = []
        for i in range(0, len(frontier), batch):
            blk = np.stack(frontier[i:i + batch])
            pad = np.concatenate(
                [blk, np.repeat(blk[:1], batch - len(blk), axis=0)])
            succ, valid, _ovf = (np.asarray(x) for x in step(pad))
            for r in succ[:len(blk)][valid[:len(blk)]]:
                if r.tobytes() not in seen:
                    seen.add(r.tobytes())
                    nxt.append(r)
        rows.extend(nxt)
        frontier = nxt
    return np.stack(rows[:limit])


def _lanes(model, rows):
    """Every (row, action) lane through ``packed_step_lane``, shaped like
    the dense step's outputs."""
    n, a = rows.shape[0], model.max_actions
    ridx = np.repeat(np.arange(n), a)
    aidx = np.tile(np.arange(a), n)
    out = jax.jit(jax.vmap(model.packed_step_lane))(rows[ridx], aidx)
    return [np.asarray(x).reshape((n, a) + x.shape[1:]) for x in out]


MODELS = {
    "2x2": lambda: PackedAbd(2, server_count=2, ordered=True),
    "2x3": lambda: PackedAbd(2, server_count=3, ordered=True),
    # one message a channel: a broadcast into an occupied channel
    # overflows, so poisoned lanes are reachable
    "2x2-depth1": lambda: PackedAbd(2, server_count=2, ordered=True,
                                    channel_depth=1),
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_lane_step_equals_the_dense_step(name):
    model = MODELS[name]()
    assert model.offers_lane_step
    rows = _reachable_rows(model, 300)
    dense = [np.asarray(x) for x in jax.jit(jax.vmap(model.packed_step))(
        rows)]
    lane = _lanes(model, rows)
    for d, lv in zip(dense, lane):
        assert d.dtype == lv.dtype
        np.testing.assert_array_equal(d, lv)
    valid, ovf = (np.asarray(x) for x in jax.jit(
        jax.vmap(model.packed_lane_valid))(rows))
    np.testing.assert_array_equal(valid, dense[1])
    np.testing.assert_array_equal(ovf, dense[2])
    # lanes on an empty channel are compared, and yield nothing
    lens = rows[:, model._net_off:model._net_off + model._n_chan]
    empty = lens == 0
    assert empty.any()
    assert not dense[1][empty].any()
    assert dense[1].any()
    if name == "2x2-depth1":
        assert ovf.any()
        assert (dense[0][ovf] == POISON).all()
        assert not valid[ovf].any()
    else:
        assert not ovf.any()


def test_overflow_on_any_lane_is_fatal_without_rows():
    """The expansion's encoding-overflow flag, from the validity pass
    alone, equals the dense expansion's; a frontier row past the valid
    count does not raise it."""
    model = MODELS["2x2-depth1"]()
    rows = _reachable_rows(model, 300)
    ovf = np.asarray(jax.vmap(model.packed_lane_valid)(rows)[1])
    bad = np.flatnonzero(ovf.any(axis=1))
    good = np.flatnonzero(~ovf.any(axis=1))
    assert len(bad) and len(good)
    expand = {m: jax.jit(lambda block, fvalid, m=m: expand_frontier(
        model, block, fvalid, jnp.zeros((2,), jnp.uint32), [],
        child_fp=False, materialize=m)) for m in (True, False)}
    for block, fvalid, want in (
            (rows[[good[0], bad[0]]], [True, True], True),
            (rows[[good[0], bad[0]]], [True, False], False),
            (rows[[good[0], good[1]]], [True, True], False)):
        got = [expand[m](block, np.asarray(fvalid)) for m in (True, False)]
        assert got[1].flat is None
        for e in got:
            assert bool(e.xovf) is want
        np.testing.assert_array_equal(got[0].avalid, got[1].avalid)
        np.testing.assert_array_equal(got[0].terminal, got[1].terminal)


def _check(model, cap, opts):
    builder = model.checker()
    if cap:
        builder = builder.target_state_count(cap)
    return builder.tpu_options(race=False, **opts).spawn_tpu().join()


def _chains(ck):
    model = ck.model()
    return {name: [model.fingerprint(s) for s in path.into_states()]
            for name, path in ck.discoveries().items()}


CHECKS = {
    "2x2-complete": (2, 2, None, {"capacity": 1 << 12}),
    "2x3-capped": (2, 3, 4000, {"capacity": 1 << 14}),
    # a narrow block and candidate buffer: many steps, both step sizes,
    # and the buffer's overflow-and-rebuild
    "2x3-capped-narrow": (2, 3, 4000, {"capacity": 1 << 14, "fmax": 512,
                                       "kraw": 1024, "chunk_steps": 4}),
}


@pytest.mark.parametrize("case", sorted(CHECKS))
def test_device_check_equals_the_dense_path(case):
    clients, servers, cap, opts = CHECKS[case]
    lanes = _check(PackedAbd(clients, server_count=servers, ordered=True),
                   cap, opts)
    dense = _check(DenseAbd(clients, server_count=servers, ordered=True),
                   cap, opts)
    if case == "2x2-complete":
        assert lanes.unique_state_count() == 564
    else:
        assert lanes.state_count() >= cap
    assert lanes.unique_state_count() == dense.unique_state_count()
    assert lanes.state_count() == dense.state_count()
    chains = _chains(lanes)
    assert chains and chains == _chains(dense)


# --- the compiled chunk program ------------------------------------------

_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def _shapes(model, fmax, kraw, kmax):
    """Every value shape of the compiled single-chip chunk program. The
    compiled program, not the lowered one: the validity pass computes
    every lane's row as the lane step does, and the compiler drops those
    rows because nothing reads them."""
    capacity, qcap = 1 << 12, 1 << 14
    rows = [model.encode(s) for s in model.init_states()]
    carry = seed_carry(model, qcap, capacity, rows, 0)
    chunk = build_chunk_core(model, qcap, capacity, fmax, kmax, False,
                             kraw=kraw)
    text = jax.jit(chunk).lower(carry, np.int32(2**31 - 1),
                                np.int32(capacity // 2),
                                np.int32(0)).compile().as_text()
    return {tuple(int(d) for d in m.group(1).split(","))
            for m in _SHAPE.finditer(text)}


def _dense_shapes(model, fmax):
    """The successor tensor over every lane of each step size, before and
    after its flat reshape (the chunk runs a 256-row step beside the
    ``fmax`` one)."""
    a, w = model.max_actions, model.packed_width
    return {s for f in (fmax, 256) for s in ((f, a, w), (f * a, w))}


def test_ordered_chunk_program_holds_no_successor_tensor():
    model = PackedAbd(2, server_count=2, ordered=True)
    fmax = 512
    # a candidate buffer narrower than either step's lanes, so its own
    # [kraw, W] rows cannot be mistaken for the dense tensor
    shapes = _shapes(model, fmax, kraw=1024, kmax=512)
    assert (1024, model.packed_width) in shapes
    assert not shapes & _dense_shapes(model, fmax)


@pytest.mark.parametrize("make", [lambda: PackedPaxos(2),
                                  lambda: TwoPhaseSys(4)],
                         ids=["paxos2", "2pc4"])
def test_models_without_the_lane_step_keep_the_dense_step(make):
    model = make()
    assert not getattr(model, "offers_lane_step", False)
    fmax = 512
    shapes = _shapes(model, fmax, kraw=1024, kmax=512)
    assert _dense_shapes(model, fmax) <= shapes
