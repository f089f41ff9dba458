"""The packed-model protocol: the keystone of the TPU engine.

A :class:`PackedModel` is an ordinary :class:`~stateright_tpu.core.Model`
that additionally defines a canonical fixed-width ``uint32``-word encoding of
its states and batched (vmappable) JAX implementations of its transition
relation and properties. The TPU engine (`checker/tpu.py`) runs entirely on
the packed representation; the inherited host methods remain the oracle for
differential testing and for trace replay of device-discovered
counterexamples.

The host/device contract (checked by :func:`validate_packed_model`):
  * ``fingerprint(state) == fp64_words(encode(state))`` — host and device
    fingerprints agree bit-for-bit;
  * the multiset of valid successors of ``packed_step(encode(s))`` equals
    ``{encode(t) for t in next_states(s)}``;
  * ``packed_properties(encode(s))[i] == properties()[i].condition(self, s)``;
  * states outside ``within_boundary`` are masked invalid by ``packed_step``.

This plays the role of the reference's ``Hash``-derived state encoding
(`/root/reference/src/lib.rs:303-311`) — but as an explicit, device-resident
struct-of-words layout rather than a hasher side effect.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import numpy as np

from ..core import Model
from ..fingerprint import fp64_words


class PackedModel(Model):
    """Mixin adding the packed/TPU interface to a model."""

    #: number of uint32 words per packed state
    packed_width: int
    #: static upper bound on actions per state
    max_actions: int
    #: True where the model also gives ``packed_lane_valid(words) ->
    #: (valid, overflow)`` over every action, with no successor built,
    #: and ``packed_step_lane(words, a) -> (row, valid, overflow)``, one
    #: lane of ``packed_step``: the device loop then builds only the rows
    #: of the lanes it keeps
    offers_lane_step: bool = False
    #: (offset, width) of the packed columns host-evaluated properties
    #: depend on (None = the whole row). Lets the device engine dedup
    #: states by host-property key before the host evaluates them.
    host_property_cols = None

    def cache_key(self):
        """Hashable identity of this model's *compiled program* — two
        models with the same key must trace identically (same config, same
        packed layout). Lets the engines reuse jitted step functions across
        checker runs (compilation dwarfs execution for small state spaces).
        Return ``None`` (the default) to disable cross-run reuse."""
        return None

    def encode(self, state: Any) -> np.ndarray:
        """Canonical ``uint32[packed_width]`` encoding of ``state``."""
        raise NotImplementedError

    def decode(self, words) -> Any:
        """Inverse of :meth:`encode` (used for debugging/witness dumps)."""
        raise NotImplementedError

    def packed_step(self, words):
        """JAX transition relation for one packed state.

        Args:
          words: uint32[packed_width] traced array.
        Returns:
          (successors uint32[max_actions, packed_width],
           valid bool[max_actions]) — row ``a`` is the result of action
          ``a``; invalid rows cover disabled actions, no-op transitions
          (the reference's ``next_state -> None``), and out-of-boundary
          successors. Models whose encoding can overflow (e.g. a fixed
          number of network slots) may return a third array
          ``overflow bool[max_actions]``: any set bit aborts the engines
          with a hard error rather than silently under-exploring.
        """
        raise NotImplementedError

    def packed_properties(self, words):
        """JAX evaluation of all properties for one packed state.

        For indices in ``host_property_indices`` (irregular predicates the
        device cannot express, e.g. the linearizability search) return a
        neutral placeholder (True for ALWAYS, False for SOMETIMES); the
        TPU engine evaluates those host-side per level on new states.

        Returns bool[P] in ``self.properties()`` order.
        """
        raise NotImplementedError

    #: property indices evaluated host-side by the TPU engine
    host_property_indices: Tuple[int, ...] = ()

    def host_property_key(self, row) -> bytes:
        """Memoization key for host-property evaluation of a packed row.

        Must discriminate at least as finely as every host property's
        dependence on the state; defaults to the whole row. Models whose
        host properties depend only on a state slice (e.g. the history
        words) override this so the expensive predicate runs once per
        distinct slice.
        """
        return np.asarray(row, dtype=np.uint32).tobytes()

    def fingerprint(self, state: Any) -> int:
        return fp64_words(self.encode(state).tolist())


def validate_packed_model(model: PackedModel, max_states: int = 2000,
                          batch: int = 256) -> int:
    """BFS-walk the host model, checking the host/device contract for
    every reachable state (up to ``max_states``). Device calls are
    BATCHED — one vmapped dispatch per ``batch`` states — so full
    reachable-space checks stay fast. Returns the number of states
    validated. Test helper."""
    import jax
    import jax.numpy as jnp

    from ..ops.hash_kernel import fp64_device

    step = jax.jit(jax.vmap(model.packed_step))
    props = jax.jit(jax.vmap(model.packed_properties))
    properties = model.properties()

    # host-side reachable walk first
    seen = set()
    states = []
    queue = list(model.init_states())
    while queue and len(states) < max_states:
        state = queue.pop()
        fp = model.fingerprint(state)
        if fp in seen:
            continue
        seen.add(fp)
        states.append((state, fp))
        queue.extend(t for t in model.next_states(state)
                     if model.within_boundary(t))

    for start in range(0, len(states), batch):
        chunk = states[start:start + batch]
        encs = []
        for state, fp in chunk:
            enc = model.encode(state)
            assert enc.dtype == np.uint32 \
                and enc.shape == (model.packed_width,), \
                f"encode() must return uint32[{model.packed_width}], " \
                f"got {enc.dtype}[{enc.shape}]"
            redec = model.decode(enc)
            assert np.array_equal(model.encode(redec), enc), \
                f"decode(encode(s)) != s for {state!r}"
            encs.append(enc)
        # pad the final partial chunk so every dispatch shares one
        # compiled shape (pad rows replicate row 0 and are never checked)
        pad = batch - len(encs)
        if pad and start:
            encs = encs + [encs[0]] * pad
        rows = jnp.asarray(np.stack(encs))
        dhi, dlo = fp64_device(rows)
        dhi, dlo = np.asarray(dhi), np.asarray(dlo)
        out = step(rows)
        succ, valid = np.asarray(out[0]), np.asarray(out[1])
        if len(out) == 3:
            ovf = np.asarray(out[2])
        else:
            ovf = np.zeros_like(valid)
        _validate_batch(model, chunk, dhi, dlo, succ, valid, ovf)
        _validate_props_batch(model, chunk, np.asarray(props(rows)),
                              properties)
    return len(states)


def _validate_batch(model, chunk, dhi, dlo, succ, valid, ovf) -> None:
    for k, (state, fp) in enumerate(chunk):
        dev_fp = (int(dhi[k]) << 32) | int(dlo[k])
        assert dev_fp == fp, \
            f"device fp {dev_fp:#x} != host fp {fp:#x} for {state!r}"
        assert not ovf[k].any(), \
            f"packed_step reports encoding overflow for {state!r}"
        packed_succ = sorted(tuple(succ[k, a].tolist())
                             for a in range(model.max_actions)
                             if valid[k, a])
        host_succ = sorted(tuple(model.encode(t).tolist())
                           for t in model.next_states(state)
                           if model.within_boundary(t))
        assert packed_succ == host_succ, \
            "packed successors disagree with host successors for " \
            f"{state!r}:\n packed={packed_succ}\n host={host_succ}"


def _validate_props_batch(model, chunk, pb, properties) -> None:
    # packed properties match host property conditions (host-evaluated
    # properties return a neutral placeholder on device — skip them)
    host_props = set(getattr(model, "host_property_indices", ()))
    for k, (state, _fp) in enumerate(chunk):
        for i, prop in enumerate(properties):
            if i in host_props:
                continue
            want = bool(prop.condition(model, state))
            assert bool(pb[k, i]) == want, \
                f"packed property {prop.name!r} = {bool(pb[k, i])} != " \
                f"host {want} for {state!r}"


class PackedLinearEquation(PackedModel):
    """Packed version of the LinearEquation fixture
    (`/root/reference/src/test_util.rs:141-188`): state (x, y) in u8 x u8,
    two increment actions. The minimal differential workload for the TPU
    engine (full enumeration = 65,536 unique states, `bfs.rs:371`)."""

    packed_width = 2
    max_actions = 2

    def cache_key(self):
        return ("lineq", self.a, self.b, self.c)

    def __init__(self, a: int, b: int, c: int):
        self.a, self.b, self.c = a, b, c

    # --- host side (mirrors models.fixtures.LinearEquation) -------------
    def init_states(self):
        return [(0, 0)]

    def actions(self, state, actions):
        actions.extend(["IncreaseX", "IncreaseY"])

    def next_state(self, state, action):
        x, y = state
        return ((x + 1) & 0xFF, y) if action == "IncreaseX" \
            else (x, (y + 1) & 0xFF)

    def properties(self):
        from ..core import Property

        def solvable(model, solution):
            x, y = solution
            return (model.a * x + model.b * y) & 0xFF == model.c
        return [Property.sometimes("solvable", solvable)]

    # --- packed side -----------------------------------------------------
    def encode(self, state):
        return np.array(state, dtype=np.uint32)

    def decode(self, words):
        return (int(words[0]), int(words[1]))

    def packed_step(self, words):
        import jax.numpy as jnp
        x, y = words[0], words[1]
        succ = jnp.stack([
            jnp.stack([(x + 1) & 0xFF, y]),
            jnp.stack([x, (y + 1) & 0xFF]),
        ]).astype(jnp.uint32)
        valid = jnp.ones((2,), dtype=bool)
        return succ, valid

    def packed_properties(self, words):
        import jax.numpy as jnp
        x, y = words[0], words[1]
        sat = ((jnp.uint32(self.a) * x + jnp.uint32(self.b) * y) & 0xFF) \
            == self.c
        return jnp.stack([sat])
