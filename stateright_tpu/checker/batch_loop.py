"""Lane-batched chunk loop: many small same-shape jobs per kernel launch.

The service (``stateright_tpu/service``) scales in device *width* but
not in job *count*: every submitted model pays its own trace/compile
and its own per-chunk dispatch, which caps throughput at a few jobs
per minute no matter how small the state spaces are. This module is
the job-count analog of the frontier batching the engines already do:
``jax.vmap`` maps the existing chunk program (`device_loop.py
build_chunk_core`) over a LANE axis, so ONE compiled program advances
up to L independent jobs at once — each lane carries its own queue,
visited table, log and discovery registers, stacked along the leading
axis of one :class:`~stateright_tpu.checker.device_loop.ChunkCarry`.

Lane semantics (all inherited from the solo chunk program — the body
is literally the same traced code):

* the vmapped ``lax.while_loop`` runs while ANY lane's condition
  holds; finished/dead lanes are masked out (their body results are
  discarded by the batching rule's per-lane select), so a lane that
  exhausts its queue or completes its discoveries simply goes inert;
* a retired lane can be RE-SEEDED mid-flight with a fresh job
  (:meth:`BatchLoop.activate` grafts the shared seed carry into that
  lane's slices) — the backfill that keeps all lanes busy while a
  bucket queue drains;
* anything the solo engine would handle with a host intervention
  (table growth, kovf resize, capacity overflow) instead RETIRES the
  lane with a reason (``BatchLoop.step`` reports it); the service
  layer re-runs such jobs through the solo engine, which has the full
  growth/retry machinery. Batched jobs are meant to be small — the
  normalizer (``service/batch.py``) sizes the bucket so retirement is
  the exception.

Correctness: a lane explores the identical state graph as a solo run
of the same model — dedup is set-semantics and the chunk body is the
same program — so the per-job reached fingerprint set (and its sha256
digest) is bit-identical to the solo engine's, regardless of lane
position or mid-flight backfill (pinned in tests/test_batch.py).

Support matrix: packed models without host-evaluated properties, no
symmetry reduction, no sound_eventually, no memory tiering, single
device. Everything else runs solo.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .device_loop import LruCache, build_chunk_core, model_cache_key, \
    seed_carry

#: compiled lane-batched chunk programs, keyed like the solo chunk
#: cache plus the lane count (the vmapped leading axis is part of the
#: traced shape)
_BATCH_CHUNK_CACHE = LruCache()
_STACK_CACHE = LruCache(limit=16)
_GRAFT_CACHE = LruCache(limit=16)

#: lane-retirement reasons reported by :meth:`BatchLoop.step`
DONE = "done"
GROW = "grow"            # visited table / queue outgrew the bucket
KOVF = "kovf"            # candidate-buffer overflow (bucket too tight)
XOVF = "xovf"            # packed-state capacity overflow (model error)
OVF = "ovf"              # table probe overflow below the growth limit
STALL = "stall"          # no progress and not done: wedged lane
_ABNORMAL = (GROW, KOVF, XOVF, OVF, STALL)


def batch_supports(model) -> Optional[str]:
    """``None`` when ``model`` can run on the batch loop, else the
    human-readable reason it must run solo."""
    for attr in ("packed_width", "max_actions", "encode", "packed_step",
                 "packed_properties"):
        if not hasattr(model, attr):
            return f"not a packed model (missing {attr!r})"
    if getattr(model, "host_property_indices", ()):
        return "host-evaluated properties need the solo engine's " \
               "representative windows"
    if model_cache_key(model) is None:
        return "model declares no cache_key (compile keys cannot " \
               "bucket)"
    return None


class _Lane:
    """Host bookkeeping for one lane: the (fp -> parent fp) mirror,
    counts, discoveries, and progress markers."""

    __slots__ = ("active", "mirror", "state_count", "log_n", "disc",
                 "stalls", "started_at")

    def __init__(self):
        self.active = False
        self.mirror: Dict[int, Optional[int]] = {}
        self.state_count = 0
        self.log_n = 0
        self.disc: Dict[str, int] = {}
        self.stalls = 0
        self.started_at = 0.0


class BatchLoop:
    """Drive up to ``lanes`` independent jobs of ONE model config
    through a single vmapped chunk program."""

    def __init__(self, model, lanes: int, capacity: int, fmax: int,
                 chunk_steps: int = 32, grow_at: float = 0.55,
                 metrics=None, trace=None):
        reason = batch_supports(model)
        if reason is not None:
            raise ValueError(f"model unsupported by the batch loop: "
                             f"{reason}")
        assert capacity & (capacity - 1) == 0, \
            "batch capacity must be a power of two"
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        from .tpu import _enable_compile_cache
        _enable_compile_cache()
        self.model = model
        self.lanes = int(lanes)
        self.capacity = int(capacity)
        self.fmax = int(fmax)
        self._steps = int(chunk_steps)
        self._metrics = metrics
        self._trace = trace
        # span profiler (obs/spans.py SpanRecorder): the batch's
        # seed/dispatch/sync/pull/decode phases (and the after-the-fact
        # device interval) for stall attribution, timed under span_s.*
        from ..obs import SpanRecorder
        self._spans = SpanRecorder(trace, metrics=metrics)
        self._properties = model.properties()
        self._prop_count = len(self._properties)
        fa = self.fmax * model.max_actions
        # kraw = kmax = fa: the candidate buffers cover the widest
        # possible iteration, so the solo engine's kovf resize protocol
        # can never fire from undersizing — only the thin-frontier
        # small loop keeps its (narrower) default, and a small-loop
        # kovf retires the lane to the solo engine like any other
        # intervention
        self._headroom = fa
        self.grow_limit = int(min(grow_at * capacity, capacity - fa))
        self.qcap = self._seed_count_bound() + self.grow_limit + 2 * fa
        self._lanes: List[_Lane] = [_Lane() for _ in range(self.lanes)]
        self._proto = None
        self._carry = None
        self._chunk = None
        self._last_stats = None

    # --- seeds ---------------------------------------------------------
    def _seed_count_bound(self) -> int:
        return max(1, len(self.model.init_states()))

    def _seed_inits(self):
        model = self.model
        init_states = [s for s in model.init_states()
                       if model.within_boundary(s)]
        validate = getattr(model, "validate_device_state", None)
        rows, fps, seen = [], [], set()
        for s in init_states:
            if validate is not None:
                validate(s)
            fp = model.fingerprint(s)
            if fp not in seen:
                seen.add(fp)
                rows.append(model.encode(s))
                fps.append(fp)
        return init_states, rows, fps

    @property
    def compile_key(self) -> tuple:
        """What makes two configs share this compiled program: the
        model's chunk cache key plus the bucket shapes and lane count
        (the same composition ``device_loop.build_chunk_fn`` memoizes
        on, with the vmapped lane axis appended)."""
        return (model_cache_key(self.model), self.qcap, self.capacity,
                self.fmax, self.lanes)

    def start(self) -> None:
        """Seed the shared lane prototype, stack it to ``lanes`` dead
        lanes, and build (or reuse) the vmapped chunk program."""
        jax, jnp = self._jax, self._jnp
        model = self.model
        init_states, rows, fps = self._seed_inits()
        self._init_states_n = len(init_states)
        self._init_rows = rows
        self._init_fps = fps
        self._n_init = len(rows)
        from ..ops.hashtable import plan_insert_host
        with self._spans.span("seed"):
            plan = plan_insert_host(fps, self.capacity)
            self._proto = seed_carry(model, self.qcap, self.capacity,
                                     rows, np.uint32(0), init_fps=fps,
                                     table_plan=(plan, fps))
        key = self.compile_key
        fn = _BATCH_CHUNK_CACHE.get(key)
        if fn is None:
            fa = self.fmax * model.max_actions
            core = build_chunk_core(model, self.qcap, self.capacity,
                                    self.fmax, fa, symmetry=False,
                                    n_init=self._n_init, kraw=fa)
            fn = jax.jit(jax.vmap(core, in_axes=(0, None, None, None)),
                         donate_argnums=(0,))
            _BATCH_CHUNK_CACHE[key] = fn
            # only a genuine build counts: a bucket whose program is
            # already resident re-forms batches compile-free — the
            # number the storm pin compares against the solo engines'
            # per-job mk_chunk count
            if self._metrics is not None:
                self._metrics.inc("compiles")
            if self._trace:
                self._trace.emit("compile", reason="batch",
                                 lanes=self.lanes)
        self._chunk = fn
        L = self.lanes
        skey = ("stack", L) + key
        stack = _STACK_CACHE.get(skey)
        if stack is None:
            stack = jax.jit(lambda c: jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    x[None], (L,) + x.shape), c))
            _STACK_CACHE[skey] = stack
        carry = stack(self._proto)
        # every lane starts DEAD: q_head == q_tail == n_init, so the
        # vmapped cond is immediately false for it until activate()
        # grafts a fresh seed (q_head=0) into its slices
        carry = carry._replace(
            q_head=jnp.full((L,), self._n_init, jnp.int32))
        self._carry = carry
        gkey = ("graft",) + key
        graft = _GRAFT_CACHE.get(gkey)
        if graft is None:
            def _graft(c, proto, lane):
                return jax.tree_util.tree_map(
                    lambda b, s: b.at[lane].set(s), c, proto)
            graft = jax.jit(_graft, donate_argnums=(0,))
            _GRAFT_CACHE[gkey] = graft
        self._graft = graft

    # --- lane lifecycle ------------------------------------------------
    def activate(self, lane: int) -> None:
        """Graft a fresh job seed into ``lane`` (initial fill AND
        mid-flight backfill take this path)."""
        st = self._lanes[lane]
        assert not st.active, f"lane {lane} is already live"
        self._carry = self._graft(self._carry, self._proto,
                                  np.int32(lane))
        st.active = True
        st.mirror = {fp: None for fp in self._init_fps}
        st.state_count = self._init_states_n
        st.log_n = 0
        st.disc = {}
        st.stalls = 0
        st.started_at = time.monotonic()

    def deactivate(self, lane: int) -> None:
        self._lanes[lane].active = False

    def active_lanes(self) -> List[int]:
        return [i for i, st in enumerate(self._lanes) if st.active]

    # --- the batched chunk step ----------------------------------------
    def step(self) -> List[Tuple[int, str]]:
        """Dispatch ONE batched chunk and consume its per-lane stats.
        Returns the lanes that just retired as ``(lane, reason)`` with
        reason ``'done'`` or an abnormal cause (the lane is already
        deactivated; abnormal lanes should re-run solo). Lanes with a
        completed run keep their mirror/discoveries readable until the
        next ``activate`` on that lane."""
        jax, jnp = self._jax, self._jnp
        L = self.lanes
        carry = self._carry._replace(
            gen=jnp.zeros((L,), jnp.int32),
            steps=jnp.full((L,), self._steps, jnp.int32),
            vmax=jnp.zeros((L,), jnp.int32),
            pdh=jnp.zeros((L,), jnp.int32),
            prb=jnp.zeros((L,), jnp.int32))
        spans = self._spans
        with spans.span("dispatch"):
            carry, stats_d = self._chunk(carry, np.int32(2**31 - 1),
                                         np.int32(self.grow_limit),
                                         np.int32(0))
        self._carry = carry
        t_disp = time.perf_counter()
        if self._metrics is not None:
            self._metrics.inc("chunks")
        # readiness split (the solo engines' _materialize_stats idiom):
        # dispatch->ready is the batched kernel executing (the device
        # span), the pull the stats transfer
        with spans.span("sync"):
            try:
                stats_d.block_until_ready()
            except AttributeError:
                pass  # already host-side (host fallbacks, tests)
        spans.record("device", t_disp, time.perf_counter())
        with spans.span("pull"):
            stats = np.asarray(jax.device_get(stats_d))
        self._last_stats = stats
        # the per-lane consume loop is this engine's host phase
        with spans.span("decode"):
            return self._consume(stats, carry)

    def _consume(self, stats, carry) -> List[Tuple[int, str]]:
        jax = self._jax
        # ONE pull covers every lane's fresh log rows (the batch is
        # sized for small jobs, so the whole log matrix is cheap)
        log = None
        exits: List[Tuple[int, str]] = []
        P = self._prop_count
        for lane in self.active_lanes():
            st = self._lanes[lane]
            row = stats[lane]
            q_head, q_tail, log_n, gen = (int(row[0]), int(row[1]),
                                          int(row[2]), int(row[3]))
            ovf, xovf, kovf = bool(row[4]), bool(row[5]), bool(row[6])
            st.state_count += gen
            if log_n > st.log_n:
                if log is None:
                    log = np.asarray(jax.device_get(carry.log))
                new = log[lane, st.log_n:log_n]
                child = ((new[:, 0].astype(np.uint64) << np.uint64(32))
                         | new[:, 1].astype(np.uint64))
                parent = ((new[:, 2].astype(np.uint64) << np.uint64(32))
                          | new[:, 3].astype(np.uint64))
                st.mirror.update(zip(child.tolist(), parent.tolist()))
            # a lane that ran any iteration this chunk generated
            # children (gen resets at dispatch), so gen>0 is the
            # progress signal even when every child was a duplicate
            progressed = gen > 0 or log_n > st.log_n
            st.log_n = log_n
            if P:
                hit = row[16:16 + P].astype(bool)
                hi = row[16 + P:16 + 2 * P].astype(np.uint64)
                lo = row[16 + 2 * P:16 + 3 * P].astype(np.uint64)
                for i, prop in enumerate(self._properties):
                    if hit[i] and prop.name not in st.disc:
                        st.disc[prop.name] = int(
                            (hi[i] << np.uint64(32)) | lo[i])
            # retirement decisions mirror the solo engine's
            # intervention points; anything needing a host fixup
            # retires to the solo path instead
            reason = None
            if xovf:
                reason = XOVF
            elif ovf:
                reason = OVF
            elif kovf:
                reason = KOVF
            elif (log_n >= self.grow_limit
                  or q_tail > self.qcap - self._headroom):
                reason = GROW
            elif (q_tail - q_head == 0
                  or (P and len(st.disc) == P)):
                reason = DONE
            elif not progressed:
                st.stalls += 1
                if st.stalls >= 2:
                    reason = STALL
            else:
                st.stalls = 0
            if reason is not None:
                st.active = False
                exits.append((lane, reason))
        return exits

    # --- per-lane reads ------------------------------------------------
    def lane_unique(self, lane: int) -> int:
        return len(self._lanes[lane].mirror)

    def lane_state_count(self, lane: int) -> int:
        return self._lanes[lane].state_count

    def lane_mirror(self, lane: int) -> Dict[int, Optional[int]]:
        return self._lanes[lane].mirror

    def lane_discoveries(self, lane: int) -> Dict[str, int]:
        return dict(self._lanes[lane].disc)

    def lane_chunk_stats(self, lane: int) -> Dict[str, int]:
        """The lane's most recent chunk scalars (per-job ``chunk``
        trace events are built from these)."""
        assert self._last_stats is not None
        row = self._last_stats[lane]
        return {"gen": int(row[3]),
                "q_size": int(row[1]) - int(row[0]),
                "log_n": int(row[2])}

    def lane_progress(self, lane: int) -> Dict[str, int]:
        """Live per-lane counters for the trace/console (valid after
        at least one ``step``)."""
        st = self._lanes[lane]
        out = {"gen": st.state_count, "unique": len(st.mirror),
               "q_size": 0}
        if self._last_stats is not None:
            row = self._last_stats[lane]
            out["q_size"] = int(row[1]) - int(row[0])
        return out

    def lane_pending(self, lane: int):
        """The lane's pending frontier ``(rows, ebits, fps)`` — what a
        pause checkpoint needs beyond the mirror. Must be called after
        the ``step`` that observed the lane (the stats anchor the
        queue span)."""
        assert self._last_stats is not None
        row = self._last_stats[lane]
        head, tail = int(row[0]), int(row[1])
        jax = self._jax
        width = self.model.packed_width
        q = np.asarray(jax.device_get(self._carry.q[lane]))
        pend = q[head:tail]
        fps = ((pend[:, width + 1].astype(np.uint64) << np.uint64(32))
               | pend[:, width + 2].astype(np.uint64))
        return pend[:, :width].copy(), pend[:, width].copy(), fps
