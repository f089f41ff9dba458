"""ABD, a linearizable register replicated over a quorum of servers, on
the ordered network: the reference's ``examples/linearizable-register.rs``
run as ``check N ordered``, in numpy over packed rows.

S servers run ABD (Attiya, Bar-Noy, Dolev): a Put or Get starts phase 1,
a Query to every peer; at a quorum of (seq, value) answers the server
records the newest (a write records its own value under the next clock)
with a Record to every peer, and at a quorum of AckRecords it answers
the client (PutOk, or GetOk with the value read). C clients each put
one value, then get. A seq is (clock, server id), the id breaking ties.

The network is ordered: one FIFO channel per (src, dst) pair. Only a
channel's head can be delivered; a send is appended at the tail of its
channel. A delivery that changes no actor state and sends nothing is no
transition, so its message stays at the head. A linearizability tester
records the clients' operations; ``linearizable`` holds where that
history has a legal sequential order, and ``value chosen`` where a
``GetOk`` carrying a value is at a channel's head.

A state is one row of uint32 words, in the layout that
``stateright_tpu``'s packed ABD documents, so that its fingerprints
(``fp64.py``) can be compared with the program's:

* per server, ``2 + S`` words: ``w0`` = ``seq | value << 12 | phase
  << 16 | request << 18 | requester << 26`` (a seq is ``clock << 4 |
  id``; phase 0 idle, 1 querying, 2 recording); ``w1`` = ``write? |
  write value << 1 | read? << 5 | read value << 6 | acks << 10`` (a bit
  per server that acknowledged the Record); one word per server j,
  ``1 << 16 | seq << 4 | value`` once j answered the Query;
* per client, one word: ``ops`` when awaiting nothing, else
  ``1 << 31 | awaited request << 8 | ops``;
* per channel, its length. The channels are client -> server (client
  major), then server -> client (server major), then server -> server
  (source major, no channel to itself);
* per channel, ``depth`` messages of two words, the head first, unused
  ones 0. A message is ``kind << 24 | request << 12 | low`` and a
  second word: ``low`` is the value of a Put or GetOk, the seq of an
  AckQuery or Record, whose second word is its value;
* one word of timers (always 0);
* the history, as ``paxos.py`` packs it: a valid flag, then per client
  two completed entries and one in flight.

Values are codes: 0 for none, k for the k-th letter. Client k (actor
S + k) puts letter k + 1: A for the first.

Departures from the source: a channel holds at most ``channel_depth``
messages, where the source's channels are unbounded, and a send to a
full channel is an error here, never a drop; clients never talk to each
other, so client -> client channels are not in the row.
"""

from __future__ import annotations

import numpy as np

from .paxos import Paxos

PUT, GET, PUTOK, GETOK, QUERY, ACKQUERY, RECORD, ACKRECORD = range(1, 9)
U = np.uint32
BLOCK = 1 << 14  # frontier rows expanded at once


class AbdOrdered:
    # the history and the linearizability tester: the register history
    # of paxos.py, which reads self.c, self.h0 and self._lin
    _words = Paxos._words
    _invoke = Paxos._invoke
    _return = Paxos._return
    linearizable = Paxos.linearizable
    _threads = Paxos._threads
    _serializable = Paxos._serializable

    def __init__(self, client_count: int, server_count: int = 3,
                 ordered: bool = True, channel_depth: int = 8):
        assert ordered, "AbdOrdered is ABD on the ordered network"
        assert server_count <= 4 and client_count <= 4
        self.c = client_count
        self.s = server_count
        self.depth = channel_depth
        self.quorum = server_count // 2 + 1
        self.sw = 2 + server_count                    # words per server
        self.cl0 = self.s * self.sw                   # first client word
        servers = range(self.s)
        clients = range(self.s, self.s + self.c)
        chans = ([(c, s) for c in clients for s in servers]
                 + [(s, c) for s in servers for c in clients]
                 + [(s, t) for s in servers for t in servers if s != t])
        self.n = len(chans)
        self.chan_src = np.array([a for a, _ in chans], np.int64)
        self.chan_dst = np.array([b for _, b in chans], np.int64)
        actors = self.s + self.c
        self.lut = np.full((actors, actors), -1, np.int64)
        for i, (a, b) in enumerate(chans):
            self.lut[a, b] = i
        self.len0 = self.cl0 + self.c                 # channel lengths
        self.msg0 = self.len0 + self.n                # channel messages
        self.h0 = self.msg0 + 2 * self.depth * self.n + 1  # history
        self.width = self.h0 + 1 + 3 * self.c
        self._lin = {}

    # --- the interface of bfs.py ---------------------------------------
    def init(self):
        row = np.zeros((1, self.width), U)
        for sid in range(self.s):
            row[0, sid * self.sw] = sid               # seq (0, sid)
        row[0, self.h0] = 1  # the history is valid
        one = np.zeros(1, np.int64)
        for k in range(self.c):
            index = self.s + k
            row[0, self.cl0 + k] = (1 << 31) | (index << 8) | 1
            self._append(row, one, np.full(1, self.lut[index, index
                                                       % self.s]),
                         np.full(1, (PUT << 24) | (index << 12) | (k + 1),
                                 U), np.zeros(1, U))
            self._invoke(row, k, 0, k + 1)
        return row, row

    @staticmethod
    def take(items, index):
        return items[index]

    def expand(self, rows):
        out = [self._expand_block(rows[i:i + BLOCK])
               for i in range(0, len(rows), BLOCK)]
        succ = np.concatenate(out) if out else \
            np.zeros((0, self.width), U)
        return succ, succ

    def conditions(self, rows, _rows=None):
        rows = np.asarray(rows, U)
        hist = np.ascontiguousarray(rows[:, self.h0:])
        keys = hist.view(np.dtype((np.void, 4 * hist.shape[1]))).ravel()
        _, first, inv = np.unique(keys, return_index=True,
                                  return_inverse=True)
        vals = np.array([self.linearizable(hist[i]) for i in first], bool)
        lens = rows[:, self.len0:self.len0 + self.n]
        heads = rows[:, self.msg0 + 2 * self.depth * np.arange(self.n)]
        chosen = ((lens > 0) & ((heads >> U(24)) == GETOK)
                  & ((heads & U(0xF)) != 0)).any(axis=1)
        return {"linearizable": vals[inv.ravel()], "value chosen": chosen}

    # --- one block of deliveries -----------------------------------------
    def _expand_block(self, rows):
        rows = np.asarray(rows, U)
        s = self.s
        # a lane per non-empty channel: deliver its head
        r_idx, ch = np.nonzero(rows[:, self.len0:self.len0 + self.n])
        m = len(r_idx)
        at = self.msg0 + 2 * self.depth * ch
        m0, m1 = rows[r_idx, at], rows[r_idx, at + 1]
        src, dst = self.chan_src[ch], self.chan_dst[ch]
        kind = m0 >> U(24)
        req = (m0 >> U(12)) & U(0xFFF)
        low = m0 & U(0xFFF)
        mval = m1 & U(0xF)
        # up to S - 1 sends per delivery: (valid, to, m0, m1); a
        # broadcast to the peers, else one send in slot 0
        sends = [[np.zeros(m, bool), np.zeros(m, np.int64),
                  np.zeros(m, U), np.zeros(m, U)]
                 for _ in range(max(s - 1, 1))]

        def send(i, cond, to, w0, w1=0):
            sl = sends[i]
            sl[0] = sl[0] | cond
            sl[1] = np.where(cond, to, sl[1])
            sl[2] = np.where(cond, w0, sl[2])
            sl[3] = np.where(cond, w1, sl[3])

        def peers(cond, me, w0, w1=0):
            # to each server but me, in server order
            for i in range(s - 1):
                send(i, cond, np.where(i < me, i, i + 1), w0, w1)

        # --- servers ---------------------------------------------------
        is_srv = dst < s
        sidx = np.where(is_srv, dst, 0)
        col = (sidx * self.sw)[:, None] + np.arange(self.sw)[None, :]
        sv = rows[r_idx[:, None], col]   # the receiving server's words
        w0, w1, resp = sv[:, 0], sv[:, 1], sv[:, 2:]
        seq = w0 & U(0xFFF)
        val = (w0 >> U(12)) & U(0xF)
        phase = (w0 >> U(16)) & U(3)
        rid = (w0 >> U(18)) & U(0xFF)
        requester = ((w0 >> U(26)) & U(0xF)).astype(np.int64)
        writes = (w1 & U(1)) == 1
        wval = (w1 >> U(1)) & U(0xF)
        reads = ((w1 >> U(5)) & U(1)) == 1
        rval = (w1 >> U(6)) & U(0xF)
        acks = (w1 >> U(10)) & U(0xF)
        me = sidx.astype(U)
        nw0, nw1, nresp = w0, w1, resp
        # Put or Get while idle: phase 1, a Query to every peer, my own
        # (seq, value) as the first answer
        c = is_srv & ((kind == PUT) | (kind == GET)) & (phase == 0)
        peers(c, sidx, (QUERY << 24) | (req << U(12)))
        nw0 = np.where(c, seq | (val << U(12)) | U(1 << 16)
                       | (req << U(18)) | (src.astype(U) << U(26)), nw0)
        nw1 = np.where(c, np.where(kind == PUT, U(1) | ((low & U(0xF))
                                                        << U(1)), U(0)),
                       nw1)
        own = np.arange(s)[None, :] == sidx[:, None]
        nresp = np.where(c[:, None],
                         np.where(own, (U(1 << 16) | (seq << U(4))
                                        | val)[:, None], U(0)), nresp)
        # Query: answer with my (seq, value)
        c = is_srv & (kind == QUERY)
        send(0, c, src, (ACKQUERY << 24) | (req << U(12)) | seq, val)
        # AckQuery for my request in phase 1: note it; at a quorum,
        # record the newest (seq, value), or a write's value under the
        # next clock, with a Record to every peer; phase 2
        c = is_srv & (kind == ACKQUERY) & (phase == 1) & (req == rid)
        at_src = np.arange(s)[None, :] == src[:, None]
        resp2 = np.where(c[:, None] & at_src,
                         (U(1 << 16) | (low << U(4)) | mval)[:, None],
                         resp)
        held = ((resp2 >> U(16)) & U(1)) == 1
        quorum = c & (held.sum(axis=1) == self.quorum)
        best = np.where(held, resp2 & U(0xFFFF), U(0)).max(axis=1)
        b_seq, b_val = best >> U(4), best & U(0xF)
        n_seq = np.where(writes, (((b_seq >> U(4)) + U(1)) << U(4)) | me,
                         b_seq)
        n_val = np.where(writes, wval, b_val)
        peers(quorum, sidx, (RECORD << 24) | (rid << U(12)) | n_seq, n_val)
        kept = np.where(n_seq > seq, n_seq | (n_val << U(12)),
                        seq | (val << U(12)))
        nw0 = np.where(quorum, kept | U(2 << 16) | (rid << U(18))
                       | (requester.astype(U) << U(26)), nw0)
        nw1 = np.where(quorum, np.where(writes, U(0),
                                        U(1 << 5) | (b_val << U(6)))
                       | (U(1) << (me + U(10))), nw1)
        nresp = np.where(quorum[:, None], U(0),
                         np.where(c[:, None], resp2, nresp))
        # Record: acknowledge; adopt a newer (seq, value)
        c = is_srv & (kind == RECORD)
        send(0, c, src, (ACKRECORD << 24) | (req << U(12)))
        c = c & (low > seq)
        nw0 = np.where(c, (w0 & ~U(0xFFFF)) | low | (mval << U(12)), nw0)
        # AckRecord for my request in phase 2, from a new server: at a
        # quorum, answer the requester and go idle
        bit = U(1) << np.where(src < s, src, 0).astype(U)
        c = is_srv & (kind == ACKRECORD) & (phase == 2) & (req == rid) \
            & ((acks & bit) == 0)
        acks2 = acks | bit
        done = c & (((acks2 & U(1)) + ((acks2 >> U(1)) & U(1))
                     + ((acks2 >> U(2)) & U(1)) + ((acks2 >> U(3)) & U(1)))
                    == self.quorum)
        send(0, done & reads, requester,
             (GETOK << 24) | (rid << U(12)) | rval)
        send(0, done & ~reads, requester, (PUTOK << 24) | (rid << U(12)))
        nw1 = np.where(c, (w1 & ~U(0xF << 10)) | (acks2 << U(10)), nw1)
        nw0 = np.where(done, w0 & U(0xFFFF), nw0)
        nw1 = np.where(done, U(0), nw1)
        srv_moved = is_srv & ((nw0 != w0) | (nw1 != w1)
                              | (nresp != resp).any(axis=1))
        # --- clients ---------------------------------------------------
        k = np.where(is_srv, 0, dst - s)
        cw = rows[r_idx, self.cl0 + k]
        waiting = ~is_srv & ((cw >> U(31)) == 1)
        ops = cw & U(0xF)
        awaited = (cw >> U(8)) & U(0xFF)
        put_ok = waiting & (kind == PUTOK) & (req == awaited)
        get_ok = waiting & (kind == GETOK) & (req == awaited)
        # after its one Put, a client gets from the next server
        req2 = (ops + U(1)) * dst.astype(U)
        send(0, put_ok, (dst + ops.astype(np.int64)) % s,
             (GET << 24) | (req2 << U(12)))
        ncw = np.where(put_ok, U(1 << 31) | (req2 << U(8)) | (ops + U(1)),
                       ops + U(1))
        # --- the successors: only deliveries that are transitions ------
        moved = srv_moved | put_ok | get_ok
        for sl in sends:
            moved |= sl[0]
        keep = np.nonzero(moved)[0]
        succ = rows[r_idx[keep]]
        n = len(succ)
        ar = np.arange(n)
        srv = np.nonzero(is_srv[keep])[0]
        ks = keep[srv]
        succ[srv[:, None], col[ks]] = np.concatenate(
            [nw0[ks, None], nw1[ks, None], nresp[ks]], 1)
        cli = np.nonzero(~is_srv[keep])[0]
        kc = keep[cli]
        succ[cli, self.cl0 + k[kc]] = ncw[kc]
        for t in range(self.c):
            for ok, ret in ((put_ok, None), (get_ok, low & U(0xF))):
                sel = np.nonzero(ok[kc] & (k[kc] == t))[0]
                if not len(sel):
                    continue
                sub = succ[cli[sel]]
                self._return(sub, t, None if ret is None
                             else ret[kc[sel]])
                if ret is None:
                    self._invoke(sub, t, 1, 0)  # then the Get
                succ[cli[sel]] = sub
        # pop the delivered head, then append each send at its tail
        ch, dst = ch[keep], dst[keep]
        span = (self.msg0 + 2 * self.depth * ch)[:, None] \
            + np.arange(2 * self.depth)[None, :]
        fifo = succ[ar[:, None], span]
        succ[ar[:, None], span] = np.concatenate(
            [fifo[:, 2:], np.zeros((n, 2), U)], 1)
        succ[ar, self.len0 + ch] -= U(1)
        for ok, to, w0_, w1_ in sends:
            ok = ok[keep]
            sel = np.nonzero(ok)[0]
            self._append(succ, sel, self.lut[dst[sel], to[keep][sel]],
                         w0_[keep][sel], w1_[keep][sel])
        return succ

    def _append(self, rows, sel, ch, w0, w1):
        """Append message ``(w0, w1)`` at the tail of channel ``ch`` in
        rows ``sel``; a full or undeclared channel is an error."""
        if (ch < 0).any():
            raise ValueError("a send on a channel the row does not hold")
        pos = rows[sel, self.len0 + ch].astype(np.int64)
        if (pos >= self.depth).any():
            raise ValueError(
                f"channel over depth: a send to a channel that holds "
                f"{self.depth} messages")
        at = self.msg0 + 2 * (self.depth * ch + pos)
        rows[sel, at] = w0
        rows[sel, at + 1] = w1
        rows[sel, self.len0 + ch] += U(1)
