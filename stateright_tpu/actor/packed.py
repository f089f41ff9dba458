"""Packed ``ActorModel`` encoding: actor systems on the TPU engine.

The reference's whole value is that ``ActorModel`` is just another ``Model``
(`/root/reference/src/actor/model.rs:187-494`); this module carries that
bridge onto the device. A :class:`PackedActorModel` *is* an ``ActorModel``
(the host side reuses the exact behavioral semantics) that additionally
implements the :class:`~stateright_tpu.models.packed.PackedModel` protocol
with a canonical struct-of-words state layout:

    [ actor states | E network slots | timer bits | history words ]

* **Actor states** are fixed-width per actor index (ragged widths allowed);
  the subclass supplies ``encode_actor``/``decode_actor`` and a single JAX
  ``packed_deliver`` kernel that dispatches on the destination internally
  (under ``vmap`` every branch is computed and masked anyway, so explicit
  masks beat ``lax.switch``).
* **The network multiset** is the hard part (SURVEY hard-part #3): each
  distinct in-flight envelope occupies one slot ``[hdr, count, msg...]``
  with ``hdr = occupied<<16 | src<<8 | dst``; slots are kept sorted by
  ``(hdr, msg)`` (empties last), which makes the encoding — and thus the
  fingerprint — order-insensitive, the device analog of the reference's
  sorted-element-hash ``HashableHashSet`` recipe (`src/util.rs:124-145`).
  The count column is deliberately **not** part of the sort key (distinct
  envelopes make ``(hdr, msg)`` already unique), so delivering or re-sending
  an existing envelope only touches its count in place; the sorted invariant
  is maintained incrementally with one suffix shift per insert/remove
  instead of a full ``lax.sort`` per (state, action) lane — measured ~5
  ms/iteration cheaper inside the engine's device loop.
  Both unordered semantics are implemented (`network.rs:44-64`):
  ``UnorderedNonDuplicating`` (the default for every register-protocol
  example and the paxos north star) keeps per-envelope counts;
  ``UnorderedDuplicating`` is a set — delivery leaves the envelope in
  flight (redelivery is always possible) and a re-send of a present
  envelope is a network no-op.
* **Lossy networks** extend the action axis: action ``E + e`` drops one
  copy of slot ``e`` (`model.rs:217-220`; for duplicating networks a drop
  removes the envelope outright — "never deliver again",
  `network.rs:238-275`), so message-loss interleavings are explored
  exhaustively on device exactly like on the host.
* **History** (e.g. a linearizability tester) rides as packed words with
  JAX record hooks mirroring ``record_msg_out``/``record_msg_in``
  (`model.rs:157-184`, `:261-264`), so history distinctions stay part of
  device state identity. Properties that need the *decoded* history (the
  exponential linearizability search) are declared in
  ``host_property_indices`` and evaluated host-side per level on newly
  inserted states only — see ``checker/tpu.py``.

Delivery nondeterminism is the action axis: action ``e`` delivers slot
``e``; disabled slots, missing recipients, and no-op handler results
(``next_state -> None``, `model.rs:259-260`) are mask bits.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from ..models.packed import PackedModel
from .core import Down, Envelope, Id, Out
from .model import ActorModel, ActorModelState
from .network import (Ordered, UnorderedDuplicating,
                      UnorderedNonDuplicating)

_OCC = 1 << 16  # slot-occupied flag in the hdr word
_EMPTY_SORT_KEY = 0xFFFFFFFF  # empties sort last

_LOSSY_ORDERED_MESSAGE = (
    "lossy ordered networks are not supported on the device engine (no "
    "Drop lanes for FIFO channels yet). Check this model on the host "
    "engines instead — checker().spawn_bfs() or .spawn_dfs() explore "
    "the identical Drop interleavings and reach identical discoveries.")

_CRASH_ORDERED_MESSAGE = (
    "crash_restart() on an ordered network is not supported on the "
    "device engine yet. Check this model on the host engines instead — "
    "checker().spawn_bfs() or .spawn_dfs() explore the identical "
    "Crash/Restart interleavings and reach identical discoveries.")


class PackedActorModel(ActorModel, PackedModel):
    """An ``ActorModel`` with a packed device encoding.

    Subclasses configure (before calling :meth:`finalize_layout`):
      * ``actor_widths``: words per actor state, by actor index;
      * ``msg_width``: words per message;
      * ``net_capacity``: max distinct in-flight envelopes (E);
      * ``history_width``: words of packed history (0 = no history);
      * ``max_sends``: max Sends one handler emits;
      * ``host_property_indices``: property indices evaluated host-side.

    And implement: ``encode_actor``/``decode_actor``, ``encode_msg``/
    ``decode_msg``, ``encode_history``/``decode_history`` (if any),
    ``packed_deliver``, ``packed_record_out``/``packed_record_in`` (if
    history), and ``packed_properties``.
    """

    msg_width: int
    net_capacity: int
    history_width: int = 0
    max_sends: int = 1
    host_property_indices: Tuple[int, ...] = ()

    #: per-(src, dst) FIFO depth for ordered networks
    channel_depth: int = 4

    #: ordered networks: the (src, dst) pairs the protocol actually uses
    #: (None = the dense actor x actor grid). Register protocols never
    #: use client<->client channels, so declaring the real flows shrinks
    #: the packed row ~30% — width the expansion pays for every lane.
    ordered_channels: Optional[List[Tuple[int, int]]] = None

    def finalize_layout(self) -> None:
        """Compute offsets once the config fields are set."""
        self.actor_widths: List[int] = list(self.actor_widths)
        self._actor_off = np.cumsum([0] + self.actor_widths).tolist()
        self._aw = self._actor_off[-1]
        self._net_off = self._aw
        self._net_dup = isinstance(self.init_network_,
                                   UnorderedDuplicating)
        self._net_ordered = isinstance(self.init_network_, Ordered)
        if self._net_ordered:
            # ordered layout: one FIFO per declared (src, dst) channel
            # at a FIXED position — no sorting needed for canonicality,
            # the channel index and queue order are the identity
            a = len(self.actor_widths)
            if self.ordered_channels is None:
                chans = [(s, d) for s in range(a) for d in range(a)]
            else:
                chans = [(int(s), int(d))
                         for s, d in self.ordered_channels]
                if len(set(chans)) != len(chans):
                    raise ValueError("ordered_channels has duplicates")
                for s, d in chans:
                    if not (0 <= s < a and 0 <= d < a):
                        raise ValueError(
                            f"ordered_channels pair ({s}, {d}) is out "
                            f"of range for {a} actors")
            self._n_chan = len(chans)
            self._chan_src = np.asarray([s for s, _ in chans], np.int32)
            self._chan_dst = np.asarray([d for _, d in chans], np.int32)
            self._chan_lut = np.full((a * a,), -1, np.int32)
            for c, (s, d) in enumerate(chans):
                self._chan_lut[s * a + d] = c
            self._msgs_off = self._net_off + self._n_chan
            self._timer_off = self._msgs_off \
                + self._n_chan * self.channel_depth * self.msg_width
        else:
            self._sw = 2 + self.msg_width  # hdr, count, msg words
            self._timer_off = self._net_off \
                + self.net_capacity * self._sw
        # crash–restart: one extra word of per-actor nibbles right after
        # the timer word — bits [0..2] = crash count, bit 3 = down. Only
        # present when injection is configured, so the packed layout (and
        # every fingerprint) of existing models is untouched.
        a = len(self.actor_widths)
        if self.max_crashes_:
            if a > 8:
                raise NotImplementedError(
                    "crash_restart() on the device engine packs per-actor "
                    "crash nibbles into one word: at most 8 actors")
            if self.max_crashes_ > 7:
                raise NotImplementedError(
                    "crash_restart(max_crashes=k) supports k <= 7 on the "
                    "device engine (3-bit crash counters)")
            self._crash_off = self._timer_off + 1
            self._crash_idx = np.asarray(self._crashable_indices(),
                                         np.int32)
            owner = np.zeros((self._aw,), np.int32)
            durable = np.zeros((self._aw,), np.uint32)
            for i in range(a):
                lo, hi = self._actor_off[i], self._actor_off[i + 1]
                owner[lo:hi] = i
                mask = list(self.durable_word_mask(i))
                if len(mask) != self.actor_widths[i]:
                    raise ValueError(
                        f"durable_word_mask({i}) returned {len(mask)} "
                        f"entries; the declared actor width is "
                        f"{self.actor_widths[i]}")
                durable[lo:hi] = [1 if m else 0 for m in mask]
            self._word_owner = owner
            self._word_durable = durable
            self._hist_off = self._crash_off + 1
        else:
            self._hist_off = self._timer_off + 1
        self.packed_width = self._hist_off + self.history_width
        if self.history_width:
            # host properties (e.g. consistency testers) read the history
            self.host_property_cols = (self._hist_off, self.history_width)

    def crash_restart(self, max_crashes, actors=None):
        """See :meth:`ActorModel.crash_restart`. Unlike ``lossy_network``
        this changes the packed layout (the crash-nibble word), so the
        layout is recomputed if already finalized."""
        super().crash_restart(max_crashes, actors)
        if hasattr(self, "_actor_off"):
            self.finalize_layout()
        return self

    @property
    def max_actions(self) -> int:
        # a lossy network doubles the axis: action E + e drops slot e;
        # ``device_timers`` appends one Timeout lane per actor and
        # ``crash_restart`` one Crash + one Restart lane per crashable
        # actor. Computed on demand because ``lossy_network(...)`` may be
        # set after construction (the compiled-program caches key on it).
        if self._net_ordered:
            if self.lossy_network_:
                raise NotImplementedError(_LOSSY_ORDERED_MESSAGE)
            if self.max_crashes_:
                raise NotImplementedError(_CRASH_ORDERED_MESSAGE)
            n = self._n_chan
        else:
            n = self.net_capacity * (2 if self.lossy_network_ else 1)
        if self.device_timers:
            n += len(self.actor_widths)
        if self.max_crashes_:
            n += 2 * len(self._crash_idx)
        return n

    # --- subclass interface ----------------------------------------------
    def encode_actor(self, index: int, state: Any) -> List[int]:
        raise NotImplementedError

    def decode_actor(self, index: int, words: List[int]) -> Any:
        raise NotImplementedError

    def encode_msg(self, msg: Any) -> List[int]:
        raise NotImplementedError

    def decode_msg(self, words: List[int]) -> Any:
        raise NotImplementedError

    def encode_history(self, history: Any) -> List[int]:
        raise NotImplementedError

    def decode_history(self, words: List[int]) -> Any:
        raise NotImplementedError

    def packed_deliver(self, actors, src, dst, msg):
        """JAX delivery kernel.

        Args:
          actors: uint32[AW] concatenated actor states;
          src, dst: traced uint32 scalars; msg: uint32[msg_width].
        Returns:
          (new_actors uint32[AW], changed bool,
           sends: list of (dst scalar, msg uint32[msg_width], valid bool)
           of length ``max_sends``, in emission order).
        """
        raise NotImplementedError

    #: opt-in Timeout lanes: models whose actors use timers set this True
    #: and implement :meth:`packed_on_timeout`
    device_timers: bool = False

    def packed_on_timeout(self, actors, aidx):
        """JAX timeout kernel (``on_timeout``, `model.rs:288-306`).

        Args:
          actors: uint32[AW] concatenated actor states;
          aidx: traced uint32 actor index whose timer fired.
        Returns:
          (new_actors uint32[AW], changed bool,
           sends like :meth:`packed_deliver`,
           keep_timer bool — True iff the handler re-set its timer).
        """
        raise NotImplementedError

    # --- crash–restart (``crash_restart``) --------------------------------
    def durable_word_mask(self, index: int) -> List[int]:
        """Per-word 0/1 mask of actor ``index``'s crash-surviving words.

        A device Crash wipes the non-durable words to zero with
        ``jnp.where``; the host projection (:meth:`_crash_durable`)
        applies the identical mask through the codec, so both engines
        agree bit-for-bit. Default: all zeros — nothing survives, the
        fail-stop model."""
        return [0] * self.actor_widths[index]

    def packed_on_restart(self, actors, aidx):
        """JAX restart kernel (the device ``Actor.on_restart``).

        Args:
          actors: uint32[AW] concatenated actor states — actor ``aidx``'s
            words already hold only its durable content (the crash wiped
            the rest);
          aidx: traced uint32 index of the restarting actor.
        Returns:
          (new_actors uint32[AW],
           sends like :meth:`packed_deliver`,
           set_timer bool — True to arm the restarted actor's timer).

        Default: adopt the durable words as the new state, emit nothing —
        the mirror of the host default :meth:`_restart_state`. Override
        BOTH together for richer recovery (e.g. announce-rejoin sends).
        """
        import jax.numpy as jnp
        zmsg = jnp.zeros((self.msg_width,), jnp.uint32)
        sends = [(jnp.uint32(0), zmsg, jnp.bool_(False))
                 for _ in range(self.max_sends)]
        return actors, sends, jnp.bool_(False)

    def _crash_durable(self, index: int, state: Any) -> Any:
        """Host-side crash projection, bit-identical to the device wipe:
        encode, zero the volatile words, decode. (The actor-level
        ``durable()`` hook is bypassed — the word mask IS the durable
        contract for packed models.)"""
        words = self.encode_actor(index, state)
        mask = self.durable_word_mask(index)
        return self.decode_actor(
            index, [int(w) if m else 0 for w, m in zip(words, mask)])

    def _restart_state(self, index: int, durable: Any, out: Out) -> Any:
        """Host-side restart, mirroring the default
        :meth:`packed_on_restart`: adopt the durable projection, emit
        nothing. Override together with ``packed_on_restart``."""
        return durable

    def packed_record_out(self, history, src, dst, msg):
        """JAX analog of ``record_msg_out`` (applied per valid Send)."""
        return history

    def packed_record_in(self, history, src, dst, msg):
        """JAX analog of ``record_msg_in`` (applied per delivery)."""
        return history

    def packed_boundary(self, words) -> Any:
        """JAX analog of ``within_boundary``; True = keep."""
        import jax.numpy as jnp
        return jnp.bool_(True)

    # --- canonical encode/decode (host side) ------------------------------
    def _slot_sort_key(self, slot_words: Tuple[int, ...]) -> Tuple[int, ...]:
        # (hdr, msg) — the count column (index 1) is not part of the
        # canonical order; (src, dst, msg) is unique per distinct envelope
        if slot_words[0] == 0:  # empty
            return (_EMPTY_SORT_KEY,) + slot_words[2:]
        return (slot_words[0],) + slot_words[2:]

    def encode(self, state: ActorModelState) -> np.ndarray:
        out = np.zeros((self.packed_width,), dtype=np.uint32)
        for i, actor_state in enumerate(state.actor_states):
            off = self._actor_off[i]
            if isinstance(actor_state, Down):
                # a crashed actor's row holds exactly its durable words
                # (the device wipe leaves the masked words in place)
                words = self.encode_actor(i, actor_state.durable)
            else:
                words = self.encode_actor(i, actor_state)
            if len(words) != self.actor_widths[i]:
                raise ValueError(
                    f"encode_actor({i}) returned {len(words)} words; the "
                    f"declared actor width is {self.actor_widths[i]}")
            out[off:off + len(words)] = words
        network = state.network
        if self._net_ordered:
            if not isinstance(network, Ordered):
                raise TypeError(
                    "model was configured with an ordered init network; "
                    f"got {type(network).__name__}")
            a = len(self.actor_widths)
            d, mw = self.channel_depth, self.msg_width
            for (src, dst), msgs in network._channels:
                if int(src) >= a or int(dst) >= a:
                    raise ValueError(
                        f"ordered channel ({src}, {dst}) references an "
                        f"actor index >= {a}; out-of-range recipients "
                        "are not encodable on the device")
                c = int(self._chan_lut[int(src) * a + int(dst)])
                if c < 0:
                    raise ValueError(
                        f"ordered channel ({src}, {dst}) is not in the "
                        "model's declared ordered_channels; declare it "
                        "or drop the declaration for the dense grid")
                if len(msgs) > d:
                    raise ValueError(
                        f"channel ({src}, {dst}) holds {len(msgs)} "
                        f"messages, exceeding channel_depth={d}; raise "
                        "channel_depth to encode this state")
                out[self._net_off + c] = len(msgs)
                for j, msg in enumerate(msgs):
                    off = self._msgs_off + (c * d + j) * mw
                    out[off:off + mw] = self.encode_msg(msg)
        else:
            slots = []
            if isinstance(network, UnorderedNonDuplicating):
                assert not self._net_dup, \
                    "model was configured with a duplicating init network"
                entries = [(env, count) for env, count in network._counts]
            else:
                assert isinstance(network, UnorderedDuplicating) \
                    and self._net_dup, \
                    "PackedActorModel packs the two unordered network " \
                    f"semantics; got {type(network).__name__}"
                entries = [(env, 1) for env in network._set]
            for env, count in entries:
                if int(env.src) >= 256 or int(env.dst) >= 256:
                    raise ValueError(
                        f"envelope ({env.src} -> {env.dst}) does not fit "
                        "the 8-bit src/dst header fields; actor ids >= "
                        "256 are not encodable on the device")
                hdr = _OCC | (int(env.src) << 8) | int(env.dst)
                slots.append(tuple([hdr, count]
                                   + self.encode_msg(env.msg)))
            if len(slots) > self.net_capacity:
                raise ValueError(
                    f"network exceeds net_capacity={self.net_capacity}: "
                    f"{len(slots)} distinct envelopes; raise net_capacity "
                    "to encode this state")
            slots.sort(key=self._slot_sort_key)
            for e, slot in enumerate(slots):
                off = self._net_off + e * self._sw
                out[off:off + self._sw] = slot
        timer = 0
        for i, set_ in enumerate(state.is_timer_set):
            timer |= int(bool(set_)) << i
        out[self._timer_off] = timer
        if self.max_crashes_:
            crashes = state.crashes \
                or (0,) * len(state.actor_states)
            cw = 0
            for i, actor_state in enumerate(state.actor_states):
                cw |= (int(crashes[i]) & 7) << (4 * i)
                if isinstance(actor_state, Down):
                    cw |= 1 << (4 * i + 3)
            out[self._crash_off] = cw
        if self.history_width:
            hwords = self.encode_history(state.history)
            assert len(hwords) == self.history_width
            out[self._hist_off:] = hwords
        return out

    def decode(self, words) -> ActorModelState:
        words = [int(w) for w in words]
        actor_states = tuple(
            self.decode_actor(i, words[self._actor_off[i]:
                                       self._actor_off[i + 1]])
            for i in range(len(self.actor_widths)))
        if self._net_ordered:
            a = len(self.actor_widths)
            d, mw = self.channel_depth, self.msg_width
            channels = {}
            for c in range(self._n_chan):
                ln = words[self._net_off + c]
                if not ln:
                    continue
                msgs = []
                for j in range(ln):
                    off = self._msgs_off + (c * d + j) * mw
                    msgs.append(self.decode_msg(words[off:off + mw]))
                channels[(Id(int(self._chan_src[c])),
                          Id(int(self._chan_dst[c])))] = msgs
            network = Ordered._freeze(channels)
        else:
            counts = {}
            for e in range(self.net_capacity):
                off = self._net_off + e * self._sw
                hdr = words[off]
                if not hdr & _OCC:
                    continue
                env = Envelope(
                    src=Id((hdr >> 8) & 0xFF), dst=Id(hdr & 0xFF),
                    msg=self.decode_msg(words[off + 2:off + self._sw]))
                counts[env] = words[off + 1]
            if self._net_dup:
                network = UnorderedDuplicating(frozenset(counts.keys()))
            else:
                network = UnorderedNonDuplicating(
                    frozenset(counts.items()))
        timer = words[self._timer_off]
        is_timer_set = tuple(bool((timer >> i) & 1)
                             for i in range(len(self.actor_widths)))
        crashes = None
        if self.max_crashes_:
            cw = words[self._crash_off]
            crashes = tuple((cw >> (4 * i)) & 7
                            for i in range(len(self.actor_widths)))
            actor_states = tuple(
                Down(st) if (cw >> (4 * i + 3)) & 1 else st
                for i, st in enumerate(actor_states))
        history = self.decode_history(words[self._hist_off:]) \
            if self.history_width else self.init_history
        return ActorModelState(actor_states=actor_states, network=network,
                               is_timer_set=is_timer_set, history=history,
                               crashes=crashes)

    # --- device step -------------------------------------------------------
    def _net_consume(self, slots, e):
        """Deliver slot ``e``: decrement its count, freeing it at zero.

        A decrement never moves the row (count is not part of the sort
        key); a removal shifts the suffix up one row, which preserves the
        sorted-by-(hdr, msg) invariant and pushes the freed (zeroed) row
        onto the empty tail. Mask arithmetic only — under ``vmap`` inside
        the engine's device loop, dynamic-index row updates are the
        expensive primitive."""
        import jax.numpy as jnp
        idx = jnp.arange(self.net_capacity)
        rowsel = idx == e
        count = jnp.where(rowsel, slots[:, 1], 0).sum()
        emptied = count <= 1
        col1 = jnp.where(rowsel, slots[:, 1] - 1, slots[:, 1])
        slots = slots.at[:, 1].set(col1)  # static column: cheap
        up = jnp.concatenate([slots[1:], jnp.zeros_like(slots[:1])],
                             axis=0)
        return jnp.where((emptied & (idx >= e))[:, None], up, slots)

    def _net_remove(self, slots, e):
        """Remove slot ``e`` outright (a drop on a duplicating network —
        "never deliver again", `network.rs:238-275`): shift the suffix up
        one row, pushing a zeroed row onto the empty tail."""
        import jax.numpy as jnp
        idx = jnp.arange(self.net_capacity)
        up = jnp.concatenate([slots[1:], jnp.zeros_like(slots[:1])],
                             axis=0)
        return jnp.where((idx >= e)[:, None], up, slots)

    def _net_send(self, slots, src, dst, msg, valid):
        """Send one envelope: bump the matching slot's count in place, or
        insert a fresh ``[hdr, 1, msg]`` row at its (hdr, msg)-sorted
        position by shifting the suffix down one row (the last row is
        empty whenever ``has_empty`` holds, since empties stay at the
        tail). Returns (slots, overflowed). Mask arithmetic only (see
        ``_net_consume``)."""
        import jax.numpy as jnp
        e_cap = self.net_capacity
        idx = jnp.arange(e_cap)
        hdr = jnp.uint32(_OCC) | (src.astype(jnp.uint32) << 8) \
            | dst.astype(jnp.uint32)
        msg = msg.astype(jnp.uint32)
        occupied = (slots[:, 0] & _OCC) != 0
        match = occupied & (slots[:, 0] == hdr) \
            & jnp.all(slots[:, 2:] == msg[None, :], axis=1)
        has_match = match.any()
        has_empty = (~occupied).any()
        if not self._net_dup:
            # matched: bump the count column in place (no reorder); a
            # duplicating network is a set — re-sending a present
            # envelope is a no-op
            col1 = jnp.where(match & valid, slots[:, 1] + 1, slots[:, 1])
            slots = slots.at[:, 1].set(col1)
        # fresh: lexicographic rank of (hdr, msg) among occupied rows
        lt = jnp.zeros((e_cap,), bool)
        eq = jnp.ones((e_cap,), bool)
        for w in (0,) + tuple(range(2, self._sw)):
            ref = hdr if w == 0 else msg[w - 2]
            col = slots[:, w]
            lt = lt | (eq & (col < ref))
            eq = eq & (col == ref)
        pos = (occupied & lt).sum()
        new_slot = jnp.concatenate(
            [jnp.stack([hdr, jnp.uint32(1)]), msg])
        down = jnp.concatenate([jnp.zeros_like(slots[:1]), slots[:-1]],
                               axis=0)
        # dst rides an 8-bit hdr field; a recipient >= 256 would bleed
        # into the src bits and alias a different envelope — report it as
        # encoding overflow instead (recipients in [n_actors, 256) are
        # fine: like the host network, the envelope sits undeliverable)
        oob = dst >= jnp.uint32(256)
        do_ins = valid & ~has_match & has_empty & ~oob
        slots = jnp.where((do_ins & (idx > pos))[:, None], down, slots)
        slots = jnp.where((do_ins & (idx == pos))[:, None],
                          new_slot[None, :], slots)
        overflowed = valid & ((~has_match & ~has_empty) | oob)
        return slots, overflowed

    def validate_device_state(self, state: ActorModelState) -> None:
        """Refuse configurations whose transitions the packed action axis
        cannot express (the device would silently under-explore what the
        host model checks exhaustively). Called by ``spawn_tpu`` on every
        init state. With ``device_timers`` the Timeout lanes cover
        timer-driven actors (``packed_on_timeout``); ``packed_deliver``
        still has no set-timer interface, so a model whose MESSAGE
        handlers set timers stays host-only (the packed contract
        validator catches the successor mismatch)."""
        if any(state.is_timer_set) and not self.device_timers:
            raise NotImplementedError(
                "PackedActorModel needs device_timers=True (and a "
                "packed_on_timeout kernel) to explore Timeout actions on "
                "the device engine; use the host engines otherwise")

    @property
    def offers_lane_step(self) -> bool:
        """Whether the engine may build successor rows lane by lane,
        after it has kept only the valid lanes (:meth:`packed_lane_valid`
        for every lane, then :meth:`packed_step_lane` for the kept ones).
        True on an ordered network, whose lane validity reads the channel
        lengths, the heads and the actor words alone — unless a subclass
        overrides ``packed_step``, or ``packed_boundary``, which reads the
        built row."""
        cls = type(self)
        return (self._net_ordered
                and cls.packed_step is PackedActorModel.packed_step
                and cls.packed_boundary is PackedActorModel.packed_boundary)

    def packed_step(self, words):
        if self._net_ordered:
            import jax
            import jax.numpy as jnp
            return jax.vmap(lambda a: self.packed_step_lane(words, a))(
                jnp.arange(self.max_actions))
        return self._packed_step_unordered(words)

    def packed_step_lane(self, words, a):
        """Action ``a`` of an ordered network's step alone: ``(row
        uint32[packed_width], valid bool, overflow bool)``, equal to lane
        ``a`` of :meth:`packed_step`. The action delivers the HEAD of
        channel ``c = src * A + dst`` (`network.rs:157-170` — ordered
        networks expose only channel heads); sends append at the
        destination channel's tail; a full channel reports encoding
        overflow. Lossy ordered checking stays host-only."""
        if not self._net_ordered:
            raise NotImplementedError(
                "packed_step_lane is the ordered network's step")
        import jax
        # the ordered step's ops carry this name in the compiled HLO
        # (op_name metadata), so a device trace maps its fusions back
        with jax.named_scope("net.ordered"):
            return self._ordered_lane(words, a)

    def packed_lane_valid(self, words):
        """``(valid bool[max_actions], overflow bool[max_actions])`` of
        :meth:`packed_step`, with no successor row kept: validity reads
        the channel lengths, the heads and the actor words alone, so the
        compiler drops the unused rows. Offered where
        :attr:`offers_lane_step` holds."""
        if not self.offers_lane_step:
            raise NotImplementedError(
                "lane validity without the row needs an ordered network "
                "and the default packed_step and packed_boundary")
        import jax
        import jax.numpy as jnp
        return jax.vmap(lambda a: self.packed_step_lane(words, a)[1:])(
            jnp.arange(self.max_actions))

    def _ordered_lane(self, words, a):
        import jax.numpy as jnp
        aw, mw = self._aw, self.msg_width
        d, n_chan = self.channel_depth, self._n_chan
        hw = self.history_width
        timers_on = self.device_timers
        n_actors = len(self.actor_widths)
        actors = words[:aw]
        lens = words[self._net_off:self._net_off + n_chan]
        msgs = words[self._msgs_off:self._timer_off] \
            .reshape(n_chan, d, mw)
        hist = words[self._hist_off:] if hw else None
        timer = words[self._timer_off:self._timer_off + 1]
        crash = words[self._timer_off + 1:self._hist_off]

        chan_src = jnp.asarray(self._chan_src)
        chan_dst = jnp.asarray(self._chan_dst)
        chan_lut = jnp.asarray(self._chan_lut)

        def append_send(lens, msgs, hist, overflow, sender, sdst, smsg,
                        svalid):
            smsg = smsg.astype(jnp.uint32)
            if hw:
                rec = self.packed_record_out(hist, sender, sdst, smsg)
                hist = jnp.where(svalid, rec, hist)
            flat = jnp.minimum(
                sender.astype(jnp.int32) * n_actors
                + sdst.astype(jnp.int32), n_actors * n_actors - 1)
            cd = chan_lut[flat]
            csel = jnp.arange(n_chan, dtype=jnp.int32) == cd
            pos = jnp.where(csel, lens, 0).sum()
            # a send to an out-of-range recipient — or on a channel the
            # model did not declare — has no FIFO: report it as encoding
            # overflow rather than silently dropping it. Guard on sdst
            # itself (a flat index could alias a real channel).
            ovf = svalid & ((pos >= d) | (sdst >= n_actors) | (cd < 0))
            esel = csel[:, None] & (jnp.arange(d, dtype=jnp.uint32)
                                    == jnp.minimum(pos, d - 1))[None, :]
            write = esel[:, :, None] & svalid & ~ovf
            msgs = jnp.where(write, smsg[None, None, :], msgs)
            lens = jnp.where(csel & svalid & ~ovf, lens + 1, lens)
            return lens, msgs, hist, overflow | ovf

        is_timeout = a >= n_chan  # lanes only exist with timers
        c = jnp.minimum(a, n_chan - 1)
        src = chan_src[c].astype(jnp.uint32)
        dst = chan_dst[c].astype(jnp.uint32)
        csel = jnp.arange(n_chan) == c
        ln = jnp.where(csel, lens, 0).sum()
        occupied = ln > 0
        head = (msgs[:, 0, :] * csel[:, None]).sum(axis=0) \
            .astype(jnp.uint32)
        new_actors, changed, sends = self.packed_deliver(
            actors, src, dst, head)
        assert len(sends) == self.max_sends
        any_send = jnp.bool_(False)
        for _d2, _m2, sv in sends:
            any_send = any_send | sv
        valid = occupied & (changed | any_send)

        # pop the head: shift the channel left, zero the tail entry
        shifted = jnp.concatenate(
            [msgs[:, 1:, :], jnp.zeros_like(msgs[:, :1, :])], axis=1)
        new_msgs = jnp.where(csel[:, None, None], shifted, msgs)
        new_lens = jnp.where(csel, lens - 1, lens)
        new_hist = None
        if hw:
            new_hist = self.packed_record_in(hist, src, dst, head)
        overflow = jnp.bool_(False)
        for sdst, smsg, svalid in sends:
            new_lens, new_msgs, new_hist, overflow = append_send(
                new_lens, new_msgs, new_hist, overflow,
                dst, sdst.astype(jnp.uint32), smsg, svalid)
        parts = [new_actors, new_lens, new_msgs.reshape(-1), timer, crash]
        if hw:
            parts.append(new_hist)
        row_out = jnp.concatenate(parts).astype(jnp.uint32)

        if timers_on:
            # same Timeout semantics as the unordered step (see
            # _packed_step_unordered): a fired timer always yields a
            # successor; sends append to ordered channels
            aidx = jnp.minimum(a - n_chan, n_actors - 1) \
                .astype(jnp.uint32)
            tw = timer[0]
            tbit = ((tw >> aidx) & 1).astype(bool)
            t_actors, t_changed, t_sends, keep = \
                self.packed_on_timeout(actors, aidx)
            t_lens, t_msgs, t_hist = lens, msgs, hist
            t_ovf = jnp.bool_(False)
            for sdst, smsg, svalid in t_sends:
                t_lens, t_msgs, t_hist, t_ovf = append_send(
                    t_lens, t_msgs, t_hist, t_ovf,
                    aidx, sdst.astype(jnp.uint32), smsg, svalid)
            new_tw = (tw & ~(jnp.uint32(1) << aidx)) \
                | (keep.astype(jnp.uint32) << aidx)
            t_parts = [t_actors, t_lens, t_msgs.reshape(-1),
                       new_tw[None], crash]
            if hw:
                t_parts.append(t_hist)
            t_row = jnp.concatenate(t_parts).astype(jnp.uint32)
            row_out = jnp.where(is_timeout, t_row, row_out)
            valid = jnp.where(is_timeout, tbit, valid)
            overflow = jnp.where(is_timeout, t_ovf, overflow)

        overflow = valid & overflow
        row_out = jnp.where(overflow, jnp.full_like(row_out, 0xDEADBEEF),
                            row_out)
        valid = valid & ~overflow & self.packed_boundary(row_out)
        return row_out, valid, overflow

    def _packed_step_unordered(self, words):
        import jax
        import jax.numpy as jnp
        aw, sw, e_cap = self._aw, self._sw, self.net_capacity
        hw = self.history_width
        lossy = self.lossy_network_
        dup = self._net_dup
        timers_on = self.device_timers
        crashes_on = bool(self.max_crashes_)
        base = e_cap * (2 if lossy else 1)
        actors = words[:aw]
        slots = words[self._net_off:self._timer_off].reshape(e_cap, sw)
        hist = words[self._hist_off:] if hw else None
        n_actors = len(self.actor_widths)
        timer = words[self._timer_off:self._timer_off + 1]
        # the crash-nibble word rides between timer and history; the
        # slice is empty when injection is off, so appending it to every
        # successor row is a no-op there
        crash = words[self._timer_off + 1:self._hist_off]
        if crashes_on:
            n_cr = len(self._crash_idx)
            cr_base = base + (n_actors if timers_on else 0)
            crash_idx = jnp.asarray(self._crash_idx)
            word_owner = jnp.asarray(self._word_owner)
            word_durable = jnp.asarray(self._word_durable).astype(bool)

        def one_action(a):
            # the action axis is vmapped (not unrolled): one traced copy
            # of the delivery body serves all E slots (plus E drop lanes
            # when lossy), which keeps the XLA graph - and compile time -
            # independent of net_capacity. The slot row is read by masked
            # sum, not dynamic gather.
            is_drop = (a >= e_cap) & (a < 2 * e_cap)  # lossy lanes
            e = jnp.minimum(jnp.where(is_drop, a - e_cap, a),
                            e_cap - 1)
            rowsel = (jnp.arange(e_cap) == e).astype(jnp.uint32)
            row = (slots * rowsel[:, None]).sum(axis=0)
            hdr = row[0]
            occupied = (hdr & _OCC) != 0
            src = (hdr >> 8) & 0xFF
            dst = hdr & 0xFF
            msg = row[2:]
            new_actors, changed, sends = self.packed_deliver(
                actors, src, dst, msg)
            assert len(sends) == self.max_sends
            any_send = jnp.bool_(False)
            for _sdst, _smsg, svalid in sends:
                any_send = any_send | svalid
            # no-op pruning (model.rs:259-260) + recipient existence
            valid = occupied & (dst < n_actors) & (changed | any_send)
            if crashes_on:
                # a down recipient takes no deliveries (its messages
                # wait in the network until Restart)
                dst_nib = jnp.minimum(dst, n_actors - 1) * 4
                dst_down = ((crash[0] >> (dst_nib + 3)) & 1).astype(bool)
                valid = valid & ~dst_down

            # a duplicating delivery leaves the envelope in flight
            # (redelivery stays possible, `network.rs:199-236`)
            new_slots = slots if dup else self._net_consume(slots, e)
            new_hist = None
            if hw:
                new_hist = self.packed_record_in(hist, src, dst, msg)
            overflow = jnp.bool_(False)
            for sdst, smsg, svalid in sends:
                smsg = smsg.astype(jnp.uint32)
                if hw:
                    recorded = self.packed_record_out(
                        new_hist, dst, sdst, smsg)
                    new_hist = jnp.where(svalid, recorded, new_hist)
                new_slots, ovf = self._net_send(
                    new_slots, dst.astype(jnp.uint32),
                    sdst.astype(jnp.uint32), smsg, svalid)
                overflow = overflow | ovf

            parts = [new_actors, new_slots.reshape(-1), timer, crash]
            if hw:
                parts.append(new_hist)
            row_out = jnp.concatenate(parts).astype(jnp.uint32)

            if lossy:
                # Drop action (`model.rs:217-220`): remove one copy (the
                # whole envelope for duplicating networks); actors and
                # history are untouched, and the network always changes,
                # so validity is just occupancy
                drop_slots = (self._net_remove(slots, e) if dup
                              else self._net_consume(slots, e))
                drop_parts = [actors, drop_slots.reshape(-1), timer,
                              crash]
                if hw:
                    drop_parts.append(hist)
                drop_row = jnp.concatenate(drop_parts).astype(jnp.uint32)
                row_out = jnp.where(is_drop, drop_row, row_out)
                valid = jnp.where(is_drop, occupied, valid)
                overflow = overflow & ~is_drop

            if timers_on:
                # Timeout lane (`model.rs:288-306`): the timer must be
                # set; the fired timer clears unless the handler re-set
                # it. NOTE the host (like the reference, `model.rs:295`)
                # never actually prunes a Timeout: its no-op check needs
                # an empty command list while keep-timer needs a SetTimer
                # command, which is unsatisfiable — so a no-op handler
                # that re-sets its timer yields a self-loop successor
                # (harmless: dedup eats it), and validity here is just
                # the timer bit (a crash clears it, so down actors never
                # fire)
                is_timeout = (a >= base) & (a < base + n_actors)
                aidx = jnp.minimum(jnp.maximum(a - base, 0),
                                   n_actors - 1).astype(jnp.uint32)
                tw = timer[0]
                tbit = ((tw >> aidx) & 1).astype(bool)
                t_actors, t_changed, t_sends, keep = \
                    self.packed_on_timeout(actors, aidx)
                t_slots = slots
                t_hist = hist
                t_ovf = jnp.bool_(False)
                for sdst, smsg, svalid in t_sends:
                    smsg = smsg.astype(jnp.uint32)
                    if hw:
                        rec = self.packed_record_out(
                            t_hist, aidx, sdst, smsg)
                        t_hist = jnp.where(svalid, rec, t_hist)
                    t_slots, ovf2 = self._net_send(
                        t_slots, aidx, sdst.astype(jnp.uint32), smsg,
                        svalid)
                    t_ovf = t_ovf | ovf2
                new_tw = (tw & ~(jnp.uint32(1) << aidx)) \
                    | (keep.astype(jnp.uint32) << aidx)
                t_parts = [t_actors, t_slots.reshape(-1), new_tw[None],
                           crash]
                if hw:
                    t_parts.append(t_hist)
                t_row = jnp.concatenate(t_parts).astype(jnp.uint32)
                t_valid = tbit
                row_out = jnp.where(is_timeout, t_row, row_out)
                valid = jnp.where(is_timeout, t_valid, valid)
                overflow = jnp.where(is_timeout, t_ovf, overflow)

            if crashes_on:
                # Crash/Restart lanes: lane cr_base + c crashes the c-th
                # crashable actor, lane cr_base + n_cr + c restarts it.
                # Crash wipes the actor's volatile words (jnp.where over
                # the static durable mask), clears its timer bit, and
                # bumps its crash nibble; Restart clears the down bit and
                # runs the packed_on_restart kernel over the surviving
                # durable words. Both always yield a successor (the
                # nibble word changes), mirroring the host semantics.
                is_crash = (a >= cr_base) & (a < cr_base + n_cr)
                is_restart = a >= cr_base + n_cr
                ci = jnp.clip(
                    jnp.where(is_restart, a - cr_base - n_cr,
                              a - cr_base), 0, n_cr - 1)
                aidx = crash_idx[ci].astype(jnp.uint32)
                nib = aidx * 4
                cw = crash[0]
                cnt = (cw >> nib) & 7
                dbit = ((cw >> (nib + 3)) & 1).astype(bool)

                wipe = (word_owner == aidx.astype(jnp.int32)) \
                    & ~word_durable
                c_actors = jnp.where(wipe, jnp.uint32(0), actors)
                c_timer = timer[0] & ~(jnp.uint32(1) << aidx)
                # cnt < max_crashes when valid, so +1 never carries into
                # the down bit
                c_cw = (cw + (jnp.uint32(1) << nib)) \
                    | (jnp.uint32(1) << (nib + 3))
                c_parts = [c_actors, slots.reshape(-1), c_timer[None],
                           c_cw[None]]
                if hw:
                    c_parts.append(hist)
                c_row = jnp.concatenate(c_parts).astype(jnp.uint32)
                c_valid = ~dbit & (cnt < self.max_crashes_)

                r_actors, r_sends, r_set_timer = \
                    self.packed_on_restart(actors, aidx)
                r_slots = slots
                r_hist = hist
                r_ovf = jnp.bool_(False)
                for sdst, smsg, svalid in r_sends:
                    smsg = smsg.astype(jnp.uint32)
                    if hw:
                        rec = self.packed_record_out(
                            r_hist, aidx, sdst, smsg)
                        r_hist = jnp.where(svalid, rec, r_hist)
                    r_slots, ovf3 = self._net_send(
                        r_slots, aidx, sdst.astype(jnp.uint32), smsg,
                        svalid)
                    r_ovf = r_ovf | ovf3
                r_timer = timer[0] \
                    | (r_set_timer.astype(jnp.uint32) << aidx)
                r_cw = cw & ~(jnp.uint32(1) << (nib + 3))
                r_parts = [r_actors, r_slots.reshape(-1), r_timer[None],
                           r_cw[None]]
                if hw:
                    r_parts.append(r_hist)
                r_row = jnp.concatenate(r_parts).astype(jnp.uint32)

                row_out = jnp.where(is_crash, c_row, row_out)
                valid = jnp.where(is_crash, c_valid, valid)
                overflow = overflow & ~is_crash
                row_out = jnp.where(is_restart, r_row, row_out)
                valid = jnp.where(is_restart, dbit, valid)
                overflow = jnp.where(is_restart, r_ovf, overflow)

            # an overflowing successor would silently drop a message and
            # under-explore the state graph: poison + invalidate the row
            # AND report the overflow, which every engine surfaces as a
            # hard error (a mis-sized net_capacity must never read as
            # "checked clean")
            overflow = valid & overflow
            row_out = jnp.where(overflow,
                                jnp.full_like(row_out, 0xDEADBEEF),
                                row_out)
            valid = valid & ~overflow & self.packed_boundary(row_out)
            return row_out, valid, overflow

        return jax.vmap(one_action)(jnp.arange(self.max_actions))

    # --- fingerprint ------------------------------------------------------
    def fingerprint(self, state: ActorModelState) -> int:
        from ..fingerprint import fp64_words
        return fp64_words(self.encode(state).tolist())
