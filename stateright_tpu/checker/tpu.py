"""The TPU-native checking engine: ``CheckerBuilder.spawn_tpu()``.

Re-design of the reference's BFS hot loop (`/root/reference/src/checker/bfs.rs:165-274`)
for the XLA compilation model. Instead of threads popping one state at a time
from a shared deque with DashMap dedup, the frontier is a device-resident
batch of packed states and one jitted *level step* fuses everything the
reference does per state:

  * property evaluation (always/sometimes masks + eventually-bit clearing)
    via the model's vmapped ``packed_properties`` — fused into the step, no
    host round-trip per state;
  * expansion via vmapped ``packed_step`` (the action axis is the
    nondeterminism axis; disabled actions, no-op transitions and boundary
    violations are mask bits, mirroring ``next_state -> None`` pruning);
  * fingerprinting via the device hash kernel (`ops/hash_kernel.py`);
  * visited-set dedup via batched parallel insert into an HBM-resident
    open-addressed table (`ops/hashtable.py`);
  * **compaction**: newly inserted children are scatter-compacted into a
    dense buffer that directly becomes the next frontier — packed states
    never round-trip to the host, and the host pulls only 16 bytes per new
    state (its fingerprint and its parent's) plus a handful of scalars.
    Discovery selection (which frontier row violated/satisfied each
    property) is likewise reduced on device to one fingerprint per property.

The host orchestrates: it keeps the (fingerprint -> parent-fingerprint)
mirror used for trace reconstruction by replay (the TLC technique,
`bfs.rs:314-342`), records discoveries, and slices frontier segments out of
the device-resident compact buffers.

Semantic differences vs the host engines (both documented and benign):
  * work granularity is a frontier segment, not a single state, so
    ``state_count``/``unique_state_count`` may exceed the host engines'
    values on early-exit runs (the reference's own multithreaded runs are
    similarly nondeterministic); full-enumeration unique counts match
    exactly;
  * which duplicate within a batch wins a slot (and thus which parent a
    state records) is unspecified — the reference tolerates the same benign
    DashMap race (`bfs.rs:198,206,268`).

The ``eventually`` semantics replicate the reference's documented caveats
(`bfs.rs:239-256`): ebits ride per-frontier-row (bit i = property i not yet
satisfied on this path), are not part of the fingerprint, and joins/cycles
are not treated as terminal.
"""

from __future__ import annotations

import os
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, Optional

import numpy as np

from ..core import Expectation
from .builder import CheckerBuilder
from .host import HostChecker
from .path import Path

_MIN_BUCKET = 16

_XOVF_MESSAGE = (
    "packed-state capacity overflow: a successor state could not be "
    "encoded (e.g. more distinct in-flight envelopes than net_capacity "
    "slots). Raise the model's capacity bounds — continuing would "
    "silently under-explore the state graph.")


def _next_pow2(n: int) -> int:
    return 1 << max((n - 1).bit_length(), 0)


def model_tag(model) -> str:
    """Checkpoint identity check, shared by the solo engines and the
    batch loop's per-lane pause checkpoints: a checkpoint only makes
    sense for the same model config (same packed layout, same
    transitions) AND the same fingerprint algorithm — resuming
    old-scheme fingerprints would silently fail to dedup against newly
    computed ones."""
    from ..fingerprint import FP_VERSION

    return (f"{type(model).__module__}.{type(model).__qualname__}"
            f"|{model.cache_key()!r}|w={model.packed_width}"
            f"|fpv={FP_VERSION}")


def _bucket(n: int) -> int:
    return max(_MIN_BUCKET, _next_pow2(n))


def _combine64(hi, lo) -> np.ndarray:
    """Host-side (hi, lo) uint32 pair -> uint64 fingerprint array."""
    return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)


def _word_key(fp: int) -> np.uint64:
    """``fp`` as a log row stores it: its (hi, lo) word pair read as one
    native uint64, the form of the walk's child-key column (a view of
    the pulled rows, so no column has to be combined)."""
    return np.array([fp >> 32, fp & 0xFFFFFFFF],
                    np.uint32).view(np.uint64)[0]


def _compact(mask, *columns):
    """Scatter-compact ``columns`` rows where ``mask`` holds to the front.

    Returns (count, *compacted) with compacted columns the same shape as the
    inputs; rows past ``count`` are zero.
    """
    import jax.numpy as jnp

    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    idx = jnp.where(mask, pos, n)
    out = tuple(jnp.zeros_like(c).at[idx].set(c, mode="drop")
                for c in columns)
    return (mask.sum(dtype=jnp.int32),) + out


from .device_loop import LruCache as _LruCache

_LEVEL_CACHE = _LruCache()
#: observed per-iteration maxima by engine config — (vmax raw, dmax
#: post-dedup), keyed like the chunk-program cache. Later runs of the
#: same model config start with candidate buffers sized to what the
#: config actually branches (+17%), instead of the static defaults
#: (2pc's fa/2 default is ~65% wider than its observed raw maximum —
#: every dedup/compaction pass scales with that width). An unlucky
#: shallow first run that under-observes costs at most one kovf
#: abort-and-rebuild, the same protocol that covers undersized defaults.
_SIZE_MEMO = _LruCache(limit=256)
_INSERT_JIT = None


def _size_fit(observed: int) -> int:
    """Quantize an observed maximum (+1/6 margin) to 1/8-power-of-two
    buckets: run-to-run drift in the maxima (batch boundaries move) must
    not move the compiled shapes, or every run would recompile."""
    want = observed + observed // 6
    step = max(256, 1 << (max(want.bit_length(), 9) - 3))
    return -(-want // step) * step


def candidate_sizes(model, fmax: int, sound: bool, opts: dict,
                    size_key) -> "tuple":
    """The kraw/kmax candidate-buffer sizing shared by the single-chip
    and sharded engines: static defaults (ops/expand.py), tightened by
    the observed-size memo — which only tightens the DEFAULTS (a
    user-tuned kraw/kmax is an explicit instruction and must not be
    clamped by what a possibly-shallow earlier run happened to
    observe)."""
    from ..ops.expand import kfinal_default, kmax_default
    fa = fmax * model.max_actions
    kraw = kmax_default(model, fmax, sound)
    kmax = kfinal_default(model, fmax, sound)
    if "kraw" not in opts and "kmax" not in opts and size_key is not None:
        seen = _SIZE_MEMO.get(size_key)
        if seen is not None:
            kraw = min(kraw, max(1 << 12, _size_fit(seen[0])))
            kmax = min(kmax, max(1 << 12, _size_fit(seen[1])))
    kraw = min(int(opts.get("kraw", kraw)), fa)
    kmax = min(int(opts.get("kmax", kmax)), kraw)
    return kraw, kmax


def _insert_jit():
    """Process-wide jitted ``table_insert`` (shapes retrace within one
    wrapper; a fresh ``jax.jit`` per run would recompile every time)."""
    global _INSERT_JIT
    if _INSERT_JIT is None:
        import jax

        from ..ops.hashtable import table_insert
        _INSERT_JIT = jax.jit(table_insert)
    return _INSERT_JIT


_EVICT_JIT = None


def _evict_jit():
    """Process-wide jitted ``table_evict_prefix`` (the spill path's
    in-place range eviction; shapes retrace within one wrapper)."""
    global _EVICT_JIT
    if _EVICT_JIT is None:
        import jax

        from ..ops.hashtable import table_evict_prefix
        _EVICT_JIT = jax.jit(table_evict_prefix)
    return _EVICT_JIT


def build_level_fn(model, symmetry: bool = False):
    """Build the jitted single-chip BFS level step for a packed model.

    One launch fuses everything the reference does per state in
    ``check_block`` (`bfs.rs:165-274`) — the shared expansion core
    (`ops/expand.py`) plus visited-set insert and child compaction. Outputs
    are device-resident; everything the host must inspect is either a
    scalar or a compacted array whose prefix length is one of those
    scalars. Memoized on ``model.cache_key()``.
    """
    from .device_loop import model_cache_key

    mkey = model_cache_key(model)
    if mkey is not None:
        mkey = (mkey, symmetry)
        cached = _LEVEL_CACHE.get(mkey)
        if cached is not None:
            return cached
    fn = _build_level_fn(model, symmetry)
    if mkey is not None:
        _LEVEL_CACHE[mkey] = fn
    return fn


def _build_level_fn(model, symmetry: bool):
    import jax
    import jax.numpy as jnp

    from ..ops.expand import (discovery_candidates, eventually_indices,
                              expand_frontier)
    from ..ops.hashtable import table_insert

    properties = model.properties()
    n_actions = model.max_actions
    eventually_idx = eventually_indices(properties)

    def level_fn(frontier, fvalid, ebits, key_hi, key_lo):
        exp = expand_frontier(model, frontier, fvalid, ebits,
                              eventually_idx, symmetry=symmetry)
        inserted, key_hi, key_lo, overflow = table_insert(
            key_hi, key_lo, exp.chi, exp.clo, exp.cvalid)

        # compact the new states: this dense prefix IS the next frontier
        par_hi = jnp.repeat(exp.phi, n_actions)
        par_lo = jnp.repeat(exp.plo, n_actions)
        ceb = jnp.repeat(exp.ebits, n_actions)
        (count, comp_rows, comp_chi, comp_clo, comp_phi, comp_plo,
         comp_eb, comp_ohi, comp_olo) = _compact(
            inserted, exp.flat, exp.chi, exp.clo, par_hi, par_lo, ceb,
            exp.ohi, exp.olo)

        disc_hit, disc_hi, disc_lo = discovery_candidates(
            properties, exp, fvalid)
        gen_count = exp.cvalid.sum(dtype=jnp.int32)
        return (key_hi, key_lo, comp_rows, comp_chi, comp_clo, comp_phi,
                comp_plo, comp_eb, count, disc_hit, disc_hi, disc_lo,
                gen_count, overflow, exp.phi, exp.plo, exp.xovf,
                comp_ohi, comp_olo)

    return jax.jit(level_fn)


_LEVEL_HELPERS = None


def _level_helpers():
    """Process-wide jitted helpers for the per-level engine (shapes retrace
    within each wrapper)."""
    global _LEVEL_HELPERS
    if _LEVEL_HELPERS is None:
        import jax
        import jax.numpy as jnp

        def slice_fn(rows, ebs, start, size):
            # clipped gather: out-of-range rows are garbage but always land
            # in the fvalid-masked tail, so no state is shifted or dropped
            idx = jnp.minimum(start + jnp.arange(size),
                              rows.shape[0] - 1)
            return rows[idx], ebs[idx]

        def take_fn(chi, clo, phi, plo, size):
            return chi[:size], clo[:size], phi[:size], plo[:size]

        def take2_fn(a, b, size):
            return a[:size], b[:size]

        def take_rows_fn(rows, size):
            return rows[:size]

        _LEVEL_HELPERS = (jax.jit(slice_fn, static_argnums=(3,)),
                          jax.jit(take_fn, static_argnums=(4,)),
                          jax.jit(take_rows_fn, static_argnums=(1,)),
                          jax.jit(take2_fn, static_argnums=(2,)))
    return _LEVEL_HELPERS


def auto_fmax(model, shards: int = 1) -> int:
    """Default expansion width: ~12.5M child lane-words per iteration
    (divided across shards) — empirically the knee of the lane-cost curve
    across model shapes (paxos at 8192 rows measures ~8% faster per
    unique state than at 10922; narrow high-action models like 2pc keep
    the 12288-row cap). VERY wide rows (packed actor
    states, width >= 256) have a much lower knee (~6M lane-words —
    ABD-ordered measured best near fmax=1024 at width 331, round 4): the
    dense successor materialization is bandwidth-bound there, not
    op-latency-bound. That knee was tuned on dense materialization: the
    single-chip loop now builds an ordered actor model's rows only for
    the lanes it keeps (``offers_lane_step``), so its knee may lie
    elsewhere. Shared by the single-chip and sharded engines so
    the knee is tuned in one place. The floor (1024 rows on a single
    chip, divided across shards down to 256) keeps enough frontier rows
    per iteration to amortize the fixed per-iteration cost."""
    target = (3 << 21) if model.packed_width >= 256 else (3 << 22)
    return max(max(256, (1 << 10) // shards), min(
        3 << 12,
        target // (model.max_actions * model.packed_width * shards)))


def _enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache. Engine shapes recur
    across processes — without it every checker run repays its XLA
    compiles. Where ``JAX_COMPILATION_CACHE_DIR`` (or the config) names
    a directory, JAX already uses it and no other is set here;
    otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
    (git-ignored) — a fixed path, because the path is part of the
    cache's key."""
    import os

    import jax

    if not jax.config.jax_compilation_cache_dir:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, ".jax_cache")
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            return  # unwritable checkout: compile uncached
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


class TpuChecker(HostChecker):
    """Level-synchronous device BFS over a packed model."""

    def __init__(self, builder: CheckerBuilder,
                 spawn_t0: Optional[float] = None):
        #: where first_dispatch_s counts from: spawn_tpu()'s entry when
        #: the builder spawned the engine, else its construction
        self._spawn_t0 = (time.perf_counter() if spawn_t0 is None
                          else spawn_t0)
        model = builder.model
        for attr in ("packed_width", "max_actions", "encode", "packed_step",
                     "packed_properties"):
            if not hasattr(model, attr):
                raise TypeError(
                    f"spawn_tpu() requires a PackedModel (missing {attr!r}); "
                    "see stateright_tpu.models.packed.PackedModel. Host-only "
                    "models can use spawn_bfs()/spawn_dfs().")
        super().__init__(builder)
        opts = builder.tpu_options_
        self._tpu_options = opts
        self._capacity = int(opts.get("capacity", 1 << 20))
        assert self._capacity & (self._capacity - 1) == 0, \
            "capacity must be a power of two"
        self._max_segment = int(opts.get("max_segment", 1 << 15))
        self._grow_at = float(opts.get("grow_at", 0.55))
        # host-evaluated properties (e.g. the linearizability search):
        # declared by the model, evaluated per level on newly inserted
        # states, memoized by model.host_property_key(row)
        self._host_props = [
            (i, self._properties[i])
            for i in getattr(model, "host_property_indices", ())]
        # packed fast-path evaluators, resolved ONCE into _host_props
        # order. The canonical form is a dict keyed by PROPERTY NAME —
        # a renamed/reordered subclass property binds the right lambda
        # or fails loudly, where the legacy positional list could
        # silently bind the wrong one (it survives only with the
        # length guard)
        self._host_fns = self._resolve_host_fns(
            getattr(model, "host_property_fns", None))
        # --- resilience knobs (checker/resilience.py) ------------------
        from .resilience import (AuditPolicy, DegradePolicy, RetryPolicy,
                                 SpillPolicy)
        self._retry_policy = RetryPolicy.from_options(opts)
        self._degrade_policy = DegradePolicy.from_options(opts)
        # silent-corruption audit (README § Silent corruption defense):
        # sampled chunks re-execute their frontier fingerprints on a
        # different device (host oracle on single-chip) and a mismatch
        # quarantines the lying chip. Off by default — the unaudited
        # path is the pre-existing engine bit for bit.
        self._audit_policy = AuditPolicy.from_options(opts)
        # injected lying-chip hook (tests/bench): (ordinal, shards) ->
        # the mesh position whose reported fingerprints get one bit
        # flipped this chunk, or None — the corruption analog of
        # fault_hook
        self._corrupt_hook = opts.get("corrupt_hook")
        #: mesh positions the auditor caught lying this run — the
        #: scheduler maps them onto the lease's devices and withholds
        #: them from future grants (service/scheduler.py)
        self._quarantined: set = set()
        #: the shadow's running chunk-digest head at the last fold —
        #: what checkpoint/result artifacts chain their integrity to
        self._shadow_chain_head = None
        # memory tiering (README § Memory tiering): growth past the HBM
        # budget — or a spill-eligible capacity fault in the retry
        # envelope — evicts cold fingerprint-prefix ranges to the host
        # tier instead of dying
        self._spill_policy = SpillPolicy.from_options(opts)
        if self._spill_policy.max_capacity is not None \
                and self._spill_policy.max_capacity < self._capacity:
            raise ValueError(
                f"tpu_options(max_capacity={self._spill_policy.max_capacity}) "
                f"is below capacity={self._capacity}; the budget caps "
                "GROWTH, so it must be >= the starting capacity")
        self._fault_hook = opts.get("fault_hook")
        # legacy hooks take (chunk); two-parameter hooks also receive
        # the current mesh width, so an injected "permanent" device
        # fault can stop firing once the ladder drops the dead chip
        self._fault_hook_arity = 1
        if self._fault_hook is not None:
            import inspect
            try:
                self._fault_hook_arity = len(
                    inspect.signature(self._fault_hook).parameters)
            except (TypeError, ValueError):
                pass
        #: mesh width the fault hooks/watchdog report (the sharded
        #: engine maintains it down the ladder; 1 on the plain loop)
        self._fault_shards = 1
        # degraded-mesh handoff (parallel/engine.py ladder -> the
        # single-chip rung): pending frontier + discoveries, and the
        # run-spanning shadow _make_shadow re-adopts
        self._handoff = None
        self._handoff_shadow = None
        self._handoff_device = None
        self._chunk_deadline = opts.get("chunk_deadline")
        if self._chunk_deadline is not None \
                and float(self._chunk_deadline) <= 0:
            raise ValueError(
                "tpu_options(chunk_deadline=...) must be positive "
                "seconds (omit it to disable the watchdog)")
        self._autosave_path = opts.get("autosave")
        self._autosave_every = int(opts.get("autosave_interval", 32))
        # host-evaluated EVENTUALLY properties run on the per-level
        # engine: the device never clears their ebits (the packed
        # placeholder bit must be False); the host evaluates each new
        # state's condition (memoized by host_property_key) and corrects
        # its ebits before it is enqueued, so terminal flushes report
        # faithful counterexamples
        self._host_ev = [(i, p) for i, p in self._host_props
                         if p.expectation == Expectation.EVENTUALLY]
        self._host_prop_cache: Dict[bytes, List[bool]] = {}
        # sound-eventually mode: dedup on (state, pending-ebits) NODE keys
        # (`fingerprint.fp64_node`), fixing the reference's documented
        # DAG-rejoin miss (`bfs.rs:239-244`)
        self._sound = builder.sound_eventually_ and any(
            p.expectation == Expectation.EVENTUALLY
            for p in self._properties)
        if self._sound:
            if any(i > 31 for i, p in enumerate(self._properties)
                   if p.expectation == Expectation.EVENTUALLY):
                raise NotImplementedError(
                    "sound_eventually() supports eventually-property "
                    "indices 0..31")
            if self._host_props:
                raise NotImplementedError(
                    "sound_eventually() with host-evaluated properties "
                    "is not supported on the TPU engine")
            if self._spill_policy.max_capacity is not None:
                raise NotImplementedError(
                    "tpu_options(max_capacity=...) memory tiering is "
                    "not supported with sound_eventually(): rediscovered"
                    " node keys would re-enter the cross-edge records "
                    "the lasso sweep treats as a faithful node graph. "
                    "Raise tpu_options(capacity=...) instead.")
        # host-property history dedup (device engine): the history-key
        # table rides IN the chunk carry (device_loop.ChunkCarry.hkey_*);
        # hcap is its capacity, grown on occupancy pressure or hovf.
        # (The 'hmax' option is read by the sharded engine only.)
        self._posthoc_cap = int(opts.get("hcap", 1 << 16))
        if self._posthoc_cap & (self._posthoc_cap - 1) \
                or self._posthoc_cap < 4:
            raise ValueError(
                "tpu_options(hcap=...) must be a power of two >= 4 "
                "(the open-addressing probe ring masks by bucket count)")
        self._h_pulled = 0  # representatives already host-evaluated
        self._hscan_tail = 0  # queue rows known fully history-deduped
        # phase timers/counters ride the shared obs registry
        # (HostChecker._metrics); keys are the obs.GLOSSARY canon
        # device-resident search record, pulled lazily by _pull_log
        self._mirror_carry = None
        # the pulled log's live rows and their child-key column, walked
        # by _reconstruct_path until _ensure_mirror folds them into the
        # dict; _log_ordered holds where a parent's row precedes its
        # child's (one insertion-ordered log), _walks memoises chains
        self._log_rows: Optional[np.ndarray] = None
        self._log_keys: Optional[np.ndarray] = None
        self._log_ordered = False
        self._walks: Dict[int, List[int]] = {}
        # most recently enqueued queue row (rides each chunk sync) —
        # the Explorer's live-progress sample for the device engine
        self._recent_row = None
        self._resume_path = builder.resume_path_
        self._resume_frontier = None
        self._base_fps: List[int] = []
        _enable_compile_cache()
        # fingerprint -> parent fingerprint mirror (host side; the
        # checkpointable search record, also used for path reconstruction).
        self._generated: Dict[int, Optional[int]] = {}
        # under symmetry: canonical fp -> the ORIGINAL explored state's fp,
        # so witness paths replay through concrete states
        self._orig_of: Dict[int, int] = {}
        self._symmetry_fn = builder.symmetry_fn_
        self._symmetry = builder.symmetry_fn_ is not None
        if self._symmetry:
            if not hasattr(model, "packed_representative"):
                raise NotImplementedError(
                    "symmetry reduction on the TPU engine requires the "
                    "model to implement packed_representative (the device "
                    "canonicalization); use spawn_dfs() otherwise")
            if self._audit_policy.enabled:
                raise NotImplementedError(
                    "tpu_options(audit=...) is not supported with "
                    "symmetry reduction: the queue rows are ORIGINAL "
                    "states while their cached fingerprints are the "
                    "canonical representatives', so the oracle cannot "
                    "re-execute them independently. Audit unreduced "
                    "runs, or rely on the artifact integrity chain.")

    # profile() and the span recorder come from HostChecker: ONE
    # metrics registry per run, keys documented once in
    # stateright_tpu.obs.GLOSSARY (every phase span as span_s.<phase>).

    def _resolve_host_fns(self, fns) -> "Optional[list]":
        """Normalize ``model.host_property_fns`` into ``_host_props``
        order: a dict binds by property name (unknown/missing names
        fail loudly); a legacy sequence binds positionally behind the
        length guard."""
        if fns is None:
            return None
        if isinstance(fns, dict):
            names = [p.name for _i, p in self._host_props]
            unknown = sorted(set(fns) - set(names))
            missing = [n for n in names if n not in fns]
            if unknown or missing:
                raise ValueError(
                    "host_property_fns keys must match the model's "
                    "host-evaluated property names exactly "
                    f"(host_property_indices -> {names}); "
                    f"unknown={unknown}, missing={missing}. A subclass "
                    "that renames or reorders properties must keep the "
                    "packed fast-path evaluators in lockstep (or drop "
                    "host_property_fns to fall back to decode())")
            return [fns[n] for n in names]
        if len(fns) != len(self._host_props):
            raise ValueError(
                f"model declares {len(self._host_props)} host-evaluated "
                f"properties (host_property_indices) but {len(fns)} "
                "host_property_fns; a subclass that changes properties "
                "must keep the packed fast-path evaluators in lockstep "
                "(or drop host_property_fns to fall back to decode())")
        return list(fns)

    # --- resilience plumbing (checker/resilience.py) -------------------
    def _make_shadow(self, shards: int):
        """The host-side authoritative state, maintained per chunk when
        retry or autosave is on (``None`` otherwise — zero cost). A
        degraded-mesh handoff re-adopts the run-spanning shadow (its
        cumulative insert/edge records feed the sound-mode lasso sweep
        across every epoch and rung) instead of starting a fresh one.
        An HBM budget (``max_capacity``) also turns the shadow on — the
        host tier IS the shadow, so tiering cannot run without it; so
        does the chunk auditor (``audit=``), whose rollback boundary
        and replay frontier live in the shadow."""
        if not (self._retry_policy.enabled
                or self._autosave_path is not None
                or self._audit_policy.enabled
                or (self._spill_policy.enabled
                    and self._spill_policy.max_capacity is not None)):
            return None
        adopted = self._handoff_shadow
        if adopted is not None:
            self._handoff_shadow = None
            adopted.reshard(shards)
            adopted.audit_enabled = self._audit_policy.enabled
            return adopted
        from .resilience import HostShadow
        shadow = HostShadow(shards, self._model.packed_width,
                            self._generated, self._orig_of,
                            translate=self._symmetry or self._sound,
                            sound=self._sound)
        shadow.audit_enabled = self._audit_policy.enabled
        return shadow

    def _materialize_stats(self, stats_d, ordinal: int,
                           t_disp: "Optional[float]" = None) -> np.ndarray:
        """Pull one chunk's stats vector through the resilience hooks:
        the injected fault hook fires first (the tests' transient-fault
        injection point), then the optional watchdog deadline bounds
        the device round trip (a hang becomes a classified fault).

        The pull is two spans: ``sync`` (blocked until the stats future
        is ready) and ``pull`` (the transfer). Dispatch to readiness is
        recorded after the fact as the chunk's ``device`` span (the
        chunk program executing; an upper bound under pipelining,
        where host work overlaps it)."""
        import jax

        hook = self._fault_hook
        shards = int(self._fault_shards)
        spans = self._spans

        def pull():
            if hook is not None:
                if self._fault_hook_arity >= 2:
                    hook(ordinal, shards)
                else:
                    hook(ordinal)
            t0 = time.perf_counter()
            with spans.span("sync", chunk=ordinal):
                try:
                    stats_d.block_until_ready()
                except AttributeError:
                    pass  # already host-side (host fallbacks, tests)
            t_ready = time.perf_counter()
            with spans.span("pull", chunk=ordinal):
                out = np.asarray(jax.device_get(stats_d))
            spans.record("device", t_disp if t_disp is not None else t0,
                         t_ready, chunk=ordinal)
            return out

        deadline = self._chunk_deadline
        if not deadline:
            return pull()
        from .resilience import ChunkDeadlineError, call_with_deadline
        try:
            return call_with_deadline(pull, float(deadline),
                                      what=f"chunk {ordinal} sync")
        except ChunkDeadlineError:
            if self._trace:
                # the hung transfer cannot name its chip; the mesh
                # width at least scopes the postmortem
                self._trace.emit("watchdog", deadline=float(deadline),
                                 chunk=ordinal, shards=shards)
            # a hung sync is exactly the crash the flight recorder
            # exists for: land the postmortem before the retry envelope
            # decides what happens next
            self._flight_dump("watchdog")
            raise

    def _checkpoint_save(self, path, rows, ebits, ffps,
                         discoveries: Dict[str, object]) -> None:
        """Write a ``resume_from``-loadable checkpoint (the complete
        mirror + the given pending frontier) through the crash-safe
        atomic write. Shared by ``save()`` and the autosave path. The
        metadata carries the artifact integrity chain: a sha256 over
        the payload arrays chained to the run's chunk-digest head,
        which ``_load_checkpoint`` verifies before seeding anything."""
        import json

        from .resilience import (atomic_savez, chain_integrity,
                                 payload_digest)

        child = np.fromiter(self._generated.keys(), np.uint64,
                            len(self._generated))
        parent = np.fromiter(
            (p if p is not None else 0
             for p in self._generated.values()),
            np.uint64, len(self._generated))
        okeys = np.fromiter(self._orig_of.keys(), np.uint64,
                            len(self._orig_of))
        ovals = np.fromiter(self._orig_of.values(), np.uint64,
                            len(self._orig_of))
        arrays = dict(child=child, parent=parent,
                      rows=np.asarray(rows, np.uint32),
                      ebits=np.asarray(ebits, np.uint32),
                      ffps=np.asarray(ffps, np.uint64),
                      okeys=okeys, ovals=ovals,
                      state_count=np.int64(self._state_count))
        chain_head = self._shadow_chain_head or ""
        meta = json.dumps({
            "model": self._model_tag(),
            "discoveries": {n: ([int(f) for f in fp]
                                if isinstance(fp, (list, tuple))
                                else int(fp))
                            for n, fp in discoveries.items()},
            "symmetry": bool(self._symmetry),
            "sound": bool(self._sound),
            "chain_head": chain_head,
            "integrity": chain_integrity(payload_digest(arrays),
                                         chain_head),
        })
        atomic_savez(path, meta=np.asarray(meta), **arrays)

    def _write_autosave(self, shadow,
                        discoveries: Dict[str, object]) -> None:
        """Checkpoint the shadow (periodic, and on exhausted retries):
        purely host-side, so it works even with a dead backend.

        Generation rotation: the previous checkpoint survives as
        ``<path>.g1`` before the new one lands at ``<path>`` (always
        the newest loadable generation), so a corrupt or truncated
        newest file rolls the resume back ONE generation instead of
        losing the run (``_load_checkpoint``)."""
        from .resilience import AUTOSAVE_PREV_SUFFIX
        path = os.fspath(self._autosave_path)
        if os.path.exists(path):
            os.replace(path, path + AUTOSAVE_PREV_SUFFIX)
        rows, ebits, fps = shadow.pending()
        self._shadow_chain_head = shadow.chain_head
        self._checkpoint_save(self._autosave_path, rows, ebits, fps,
                              discoveries)
        self._metrics.inc("autosaves")
        if self._trace:
            self._trace.emit("autosave",
                             path=path,
                             unique=len(self._generated))

    def _resilience_degrade(self, exc: BaseException, shadow,
                            discoveries: Dict[str, object]) -> None:
        """Retries exhausted below the ladder's ``min_mesh`` (or with
        ``degrade=False``): land an artifact instead of just dying —
        write the autosave checkpoint (when configured) and raise ONE
        actionable error naming the resume command."""
        # exhausted retries are a flight-recorder trigger in their own
        # right: the ring at this point holds the whole retry burst
        self._flight_dump("retries_exhausted")
        if self._autosave_path is not None:
            self._write_autosave(shadow, discoveries)
            path = os.fspath(self._autosave_path)
            raise RuntimeError(
                "transient device fault persisted after "
                f"{self._retry_policy.retries} retries "
                f"({type(exc).__name__}: {exc}); progress checkpointed "
                f"to {path!r} — resume with "
                f"model.checker().resume_from({path!r}).spawn_tpu() "
                "once the backend recovers") from exc
        raise RuntimeError(
            "transient device fault persisted after "
            f"{self._retry_policy.retries} retries "
            f"({type(exc).__name__}: {exc}); set "
            "tpu_options(autosave=path) to checkpoint progress on "
            "exhausted retries") from exc

    def _capacity_terminal(self, exc: BaseException, shadow,
                           discoveries: Dict[str, object]) -> None:
        """Capacity-class termination — spill disabled, ineligible, or
        the host tier exhausted too: land the postmortem artifacts a
        watchdog/retry exhaustion already gets (flight-recorder dump,
        and an autosave checkpoint when configured) before raising ONE
        actionable error naming both outs (a bigger bound, or resume)."""
        self._flight_dump("capacity")
        detail = f"{type(exc).__name__}: {exc}"
        if self._autosave_path is not None and shadow is not None:
            self._write_autosave(shadow, discoveries)
            path = os.fspath(self._autosave_path)
            raise RuntimeError(
                f"capacity exhausted and not recoverable by spill "
                f"({detail}); progress checkpointed to {path!r} — raise "
                "tpu_options(capacity=...) (or max_capacity=...) and "
                f"resume with model.checker().resume_from({path!r})"
                ".spawn_tpu()") from exc
        raise RuntimeError(
            f"capacity exhausted and not recoverable by spill "
            f"({detail}); raise tpu_options(capacity=...) (or "
            "max_capacity=...), or set tpu_options(autosave=path) to "
            "checkpoint progress at this point next time") from exc

    def _shadow_lasso_sweep(self, shadow, full_mask: int,
                            discoveries: Dict[str, object]) -> None:
        """The sound-mode SCC sweep rebuilt from the shadow's insert and
        cross-edge records instead of the device logs — after a
        mid-run recovery the device logs only cover the last epoch,
        while the shadow spans the whole run."""
        from .lasso import add_log_block, add_seed_nodes, lasso_sweep

        node_fp: Dict[int, int] = {}
        node_parent: Dict[int, tuple] = {}
        node_mask: Dict[int, int] = {}
        node_edges: Dict[int, list] = {}
        add_seed_nodes(node_fp, node_parent, node_mask,
                       shadow.root_keys(), self._orig_of, full_mask)
        empty_edges = np.zeros((0, 4), np.uint32)
        for s in range(shadow.shards):
            block = shadow.insert_block(s)
            edges = shadow.edge_block(s)
            if block is None and not len(edges):
                continue
            log_rows, eb_rows = block if block is not None else (
                np.zeros((0, 6), np.uint32), np.zeros((0,), np.uint32))
            add_log_block(node_fp, node_parent, node_mask, node_edges,
                          log_rows, eb_rows,
                          edges if len(edges) else empty_edges)
        lasso_sweep(self._properties, discoveries, node_edges,
                    node_mask, node_parent, node_fp)
        if self._trace:
            self._trace.emit(
                "lasso", nodes=len(node_mask),
                edges=sum(len(v) for v in node_edges.values()))

    # --- pausable runs (the step-driver/job-service boundary) ----------
    def request_pause(self, path=None) -> None:
        """Pause the device run at the next chunk boundary: the chunk
        loop drains its pipeline and writes a ``resume_from``-loadable
        checkpoint (complete mirror + pending frontier) to ``path``
        (default: the ``tpu_options(autosave=...)`` destination — which
        ``tpu_options(artifact_dir=...)`` always provides) before
        exiting; ``paused()`` then reports True and the checkpoint
        resumes on ANY mesh width (the scheduler's preemption-to-a-
        smaller-subset primitive). The per-level engine mode has no
        checkpointable loop and stops without a checkpoint."""
        if path is not None:
            self._pause_path = os.fspath(path)
        if self.pause_path() is None:
            raise ValueError(
                "request_pause() needs a checkpoint destination: pass "
                "request_pause(path=...) or configure "
                "tpu_options(autosave=...) / tpu_options(artifact_dir"
                "=...)")
        self._pause_event.set()

    def request_promote(self, devices) -> None:
        """Widen a sharded run D -> 2D at the next chunk boundary: the
        chunk loop drains its pipeline, extends the mesh with (up to D
        of) the granted ``devices``, re-routes the shadow's mirror and
        pending frontier by ``owner_of(fp, 2D)`` with preload-aware
        growth limits recomputed at the new width, recompiles, and
        resumes — the exact mirror of one degradation-ladder rung, so
        a job that degraded around a transient fault can climb back up
        once the blamed chip is released healthy. Requires the host
        shadow (``retries``/``autosave``/``max_capacity``); runs
        without one — and non-sharded engines — quietly decline, and
        a grant that cannot double the mesh (too few distinct new
        devices, or 2D past the shard limit) is dropped at the
        boundary rather than raising mid-run."""
        grant = list(devices)
        if not grant:
            raise ValueError(
                "request_promote() needs at least one device to widen "
                "onto (pass the freed jax.Device objects, their global "
                "ids, or jax.devices() positions)")
        self._promote_request = grant
        self._promote_event.set()

    def promote_pending(self) -> bool:
        """True while a ``request_promote`` grant awaits its
        chunk-boundary decision (the flex controller steps the driver
        until this clears, then reads the ``promotes`` counter to
        learn whether the engine took or declined the grant)."""
        return self._promote_event.is_set()

    # ------------------------------------------------------------------
    def _run(self) -> None:
        for _ in self._run_steps():
            pass

    def _run_steps(self):
        mode = str(self._tpu_options.get("mode", "auto"))
        if mode not in ("auto", "device", "level"):
            raise ValueError(
                f"unknown tpu_options mode {mode!r}; expected 'auto', "
                "'device', or 'level'")
        # a CheckerVisitor rides the DEVICE engine since round 5: the
        # append-only log is the insertion-ordered visitation record, so
        # visits replay from the mirror after the run (one path
        # reconstruction per unique state, like the per-level engine's
        # in-loop visits). The per-level engine still visits in-loop when
        # selected for other reasons (host eventually properties).
        # host-evaluated properties run on either engine: the per-level
        # engine evaluates them on each level's new states; the device
        # engine evaluates them via the in-carry history dedup. Host
        # EVENTUALLY properties need their per-row ebits corrected before
        # each state is enqueued, which only the per-level orchestration
        # provides.
        if self._host_ev:
            if mode == "device":
                raise NotImplementedError(
                    "host-evaluated eventually properties need the "
                    "per-level engine; drop tpu_options(mode='device')")
            mode = "level"
        if self._resume_path is not None and mode == "level":
            raise NotImplementedError(
                "resume_from() requires the device engine; drop "
                "tpu_options(mode='level')")
        if self._sound and mode == "level":
            raise NotImplementedError(
                "sound_eventually() requires the device engine; drop "
                "tpu_options(mode='level')")
        if mode in ("auto", "device"):
            yield from self._drive_device()
            if self._visitor is not None and not self._paused:
                with self._spans.span("visit"):
                    self._visit_reached()
        else:
            self._run_levels()

    def _write_pause_checkpoint(self, rows, ebits, ffps,
                                discoveries: Dict[str, object]) -> None:
        """Land the pause checkpoint (complete mirror + the pending
        frontier the caller gathered) and mark the run paused. Shared
        by the single-chip and sharded chunk loops."""
        path = self.pause_path()
        with self._spans.span("pause"):
            self._checkpoint_save(path, rows, ebits, ffps, discoveries)
        self._paused = True
        self._metrics.inc("pauses")
        if self._trace:
            self._trace.emit("pause", path=os.fspath(path),
                             unique=len(self._generated))

    def _seed_inits(self) -> "List[np.ndarray]":
        """Filter/fingerprint/encode the initial states into the mirror and
        return their packed rows (both engine modes seed identically)."""
        model = self._model
        init_states = [s for s in model.init_states()
                       if model.within_boundary(s)]
        self._state_count = len(init_states)
        validate = getattr(model, "validate_device_state", None)
        if self._symmetry:
            # the host symmetry_fn and the device packed_representative
            # must agree bit-for-bit, or dedup silently corrupts; check
            # the init states up front (the builder API accepts any fn)
            for s in init_states[:4]:
                host = model.encode(self._symmetry_fn(s))
                dev = np.asarray(model.packed_representative(
                    model.encode(s)))
                if not np.array_equal(host, dev):
                    raise ValueError(
                        "symmetry_fn disagrees with the model's "
                        "packed_representative on an init state: host "
                        f"canonical {host.tolist()} vs device "
                        f"{dev.tolist()}. The device engines require the "
                        "two canonicalizations to be bit-identical.")
        init_rows: List[np.ndarray] = []
        full_mask = 0
        if self._sound:
            from ..fingerprint import fp64_node
            from ..ops.expand import eventually_indices
            full_mask = sum(1 << i
                            for i in eventually_indices(self._properties))
        self._seed_cache_fps: List[int] = []
        for s in init_states:
            if validate is not None:
                validate(s)
            fp = self._canon_fp(s)
            key = fp64_node(fp, full_mask) if self._sound else fp
            if key not in self._generated:
                self._generated[key] = None
                if self._symmetry or self._sound:
                    # replay translation: node/canonical key -> the
                    # ORIGINAL explored state's fingerprint
                    self._orig_of[key] = model.fingerprint(s)
                init_rows.append(model.encode(s))
                # the queue fingerprint cache wants the CANONICAL state
                # fp (node keys are re-derived from it + the row ebits)
                self._seed_cache_fps.append(fp)
        self._unique_state_count = len(self._generated)
        return init_rows

    # ------------------------------------------------------------------
    def _run_device(self) -> None:
        """Blocking form of :meth:`_drive_device` (the degradation
        ladder's single-chip handoff rung still calls it directly)."""
        for _ in self._drive_device():
            pass

    def _drive_device(self):
        """Device-resident search: the whole multi-level loop is one XLA
        ``while_loop`` (see `device_loop.py`); the host syncs once per
        K-level chunk and pulls the (child fp, parent fp) log at the end.

        A GENERATOR since round 10: each ``yield`` is one chunk-loop
        quantum (a processed chunk or a handled intervention), so the
        run can be driven step-by-step by the job service's
        ``StepDriver`` (start → step(budget) → … → finish) instead of
        only as a blocking call; a pending ``request_pause()`` drains
        the pipeline, writes the resume_from-loadable pause checkpoint
        and exits the loop cleanly."""
        import jax
        import jax.numpy as jnp

        from .device_loop import build_chunk_fn, seed_carry
        from ..ops.hashtable import table_insert

        model = self._model
        properties = self._properties
        prop_count = len(properties)
        from ..ops.expand import eventually_indices
        full_ebits = np.uint32(sum(1 << i
                                   for i in eventually_indices(properties)))
        generated = self._generated
        # discoveries are buffered locally and published only after the
        # mirror is finalized: publishing early flips is_done() (all
        # properties discovered) while reconstruction data is still
        # device-resident, racing report()/assert_* with an empty mirror
        discoveries: Dict[str, int] = {}
        host_prop_idx = {i for i, _p in self._host_props}
        target = self._target_state_count
        opts = self._tpu_options
        fmax = int(opts.get("fmax", auto_fmax(model)))
        fa = fmax * model.max_actions
        # two-stage candidate-buffer widths (ops/expand.py): kraw holds
        # the raw-valid lanes (hash + in-batch dedup width), kmax the
        # dedup survivors (probe/append width) — every gather/probe in
        # the loop body scales with one of them, so models that know
        # their branching (max valid children per state) shrink both via
        # ``branching_hint``; an iteration that spikes past either
        # triggers the cheap kovf resize
        from .device_loop import model_cache_key

        size_key = model_cache_key(model)
        if size_key is not None:
            size_key = (size_key, fmax, self._sound, self._symmetry)
        kraw, kmax = candidate_sizes(model, fmax, self._sound, opts,
                                     size_key)
        # OPT-IN per-row stage-one compaction (device_loop.py): kraw
        # becomes the static fmax*hint; a row outgrowing it triggers the
        # same kovf rebuild protocol. Off by default: ``branching_hint``
        # is a batch-average heuristic, not a per-row bound (paxos
        # declares 4 but rows reach 10 — measured via profile()['rmax']),
        # so the global cross-row compaction usually packs tighter. Only
        # worth trying on models whose TRUE per-row branching is small
        # and uniform.
        hint_eff = int(opts.get("hint", 0))
        if hint_eff < 0 or hint_eff >= model.max_actions:
            # mirror the device-side degenerate fallback
            # (device_loop.py): the host must agree it is running the
            # global path, or the kovf resize logic would never grow
            # kraw and the chunk loop would rebuild forever
            hint_eff = 0
        k_steps = int(opts.get("chunk_steps", 64))
        insert_fn = _insert_jit()

        # --- seed -------------------------------------------------------
        self._fault_shards = 1
        handoff = self._handoff
        if self._resume_path is not None:
            init_rows, seed_ebits, seed_fps = self._load_checkpoint(
                discoveries)
        elif handoff is not None:
            # degraded-mesh handoff (the ladder's single-chip rung):
            # the shadow's pending frontier becomes the seed; the
            # mirrored reached set is already in self._generated, and
            # the prior rungs' discoveries carry over
            self._handoff = None
            init_rows, seed_ebits, seed_fps, prior = handoff
            discoveries.update(prior)
        else:
            init_rows = self._seed_inits()
            seed_ebits = full_ebits
            seed_fps = list(generated.keys())
        n_init = len(init_rows)
        self._hscan_tail = n_init
        base_unique = len(generated)
        # everything known at seed time must be re-inserted on growth (the
        # device log only records states found since)
        self._base_fps = list(generated.keys())
        if self._host_props and self._resume_path is None:
            # seed rows never enter the in-loop history log (only fresh
            # inserts do); evaluate them host-side once, like the
            # reference evaluates properties on every popped state. A
            # resumed frontier needs no pass: every pre-checkpoint state
            # was already evaluated and its discoveries ride the
            # checkpoint metadata.
            self._eval_host_props_block(
                [np.asarray(row) for row in init_rows], seed_fps,
                discoveries)
        if prop_count == 0:
            # nothing to search for: mirror the reference's immediate stop
            # once discoveries (vacuously) cover all properties
            # (bfs.rs:121-128)
            return

        # --- resilience plumbing (checker/resilience.py), created
        # BEFORE the seed: with memory tiering the shadow decides which
        # keys are device-resident at all (a degraded-mesh handoff may
        # arrive with ranges already evicted down the ladder)
        from .resilience import (SPILL_PREFIX_BITS, CorruptionError,
                                 FaultKind, audit_chunk_rows,
                                 blamed_device, classify_error,
                                 find_candidate_overflow, gather_rows,
                                 pack_qrows, spill_eligible)

        policy = self._retry_policy
        audit_pol = self._audit_policy
        corrupt_hook = self._corrupt_hook
        spill_pol = self._spill_policy
        spill_on = spill_pol.enabled and not self._sound
        shadow = self._make_shadow(1)

        # one while_loop iteration inserts at most kmax new states (and at
        # most fa once kmax has grown to its bound); capacity must keep
        # that headroom below the growth exit. ``preload`` is the table
        # occupancy seeded before the first chunk (just the inits on a
        # fresh run, the WHOLE mirrored reached set on a resume or a
        # post-fault re-seed — minus the host tier once ranges have been
        # evicted) — the growth trigger compares the epoch-local device
        # log count against the limit, so the limit must leave room for
        # the preloaded keys
        headroom = fa
        seed_keys = (shadow.hot_keys() if shadow is not None
                     else list(generated.keys()))
        preload = len(seed_keys)
        while self._grow_at * self._capacity <= headroom + preload \
                and spill_pol.can_grow(self._capacity):
            self._capacity *= 4
        if self._grow_at * self._capacity <= headroom + preload:
            # the preloaded set alone exceeds the HBM budget (a resumed
            # mirror, or a handoff after heavy spilling): evict at seed
            plan = (shadow.spill_plan(
                int(self._grow_at * self._capacity) - headroom - 1)
                if spill_on and shadow is not None else None)
            if plan is None:
                self._capacity_terminal(RuntimeError(
                    f"device hash table budget (max_capacity="
                    f"{spill_pol.max_capacity}) cannot hold the seeded "
                    f"reached set ({preload} keys) with spill "
                    "unavailable"), shadow, discoveries)
            seed_keys = shadow.hot_keys()
            preload = len(seed_keys)
            self._metrics.inc("spills")
            if plan[2]:
                self._metrics.inc("evicted_keys", plan[2])
            self._metrics.set("host_tier_keys", shadow.host_tier_keys)
            if self._trace:
                self._trace.emit("evict", prefixes=len(plan[0]),
                                 keys=plan[2])
                self._trace.emit("spill", capacity=self._capacity,
                                 hot=preload, reason="seed",
                                 host_tier_keys=shadow.host_tier_keys)

        # growth re-inserts the seed-time keys the device log lacks: the
        # HOT set only — re-promoting evicted ranges would undo a spill
        self._base_fps = seed_keys

        # append-only queue: must hold every state enqueued before the next
        # growth point (n_init + grow_limit) plus one iteration of appends
        qcap = self._device_qcap(n_init, headroom)
        hcap = self._posthoc_cap if self._host_props else 0
        # sound mode logs cross edges (dedup hits with pending bits) for
        # the post-exhaustion lasso sweep; grows independently on demand
        ecap = self._capacity if self._sound else 0
        with self._spans.span("seed"):
            # the block before the first chunk launch is deliberate:
            # launching the chunk (which donates the carry) while the
            # seed/insert programs are still in flight was measured to
            # slow the whole chunk loop ~2.5x (round 2/3)
            # the queue's cached fingerprints are canonical STATE fps
            # (sound mode dedups on node keys but re-derives them from
            # these); on resume (and on a degraded-mesh handoff) the
            # frontier rows carry their own recomputed fps
            cache_fps = (self._seed_cache_fps
                         if self._resume_path is None and handoff is None
                         else seed_fps)
            # the table is empty, so small seeds (the fresh-run case) are
            # placed by a host plan scattered INSIDE the seed program —
            # zero extra dispatches (a standalone table_insert dispatch,
            # a data-dependent while_loop program, is one more launch
            # even for a handful of keys). Large seeds
            # (checkpoint resume mirrors the whole reached set) keep the
            # chunked device insert: the host plan's per-fingerprint
            # Python loop would be the slow path there. seed_keys is
            # the device-resident HOT set (== the whole mirror until
            # ranges have been evicted to the host tier).
            table_plan = None
            if len(seed_keys) <= (1 << 15):
                from ..ops.hashtable import plan_insert_host
                plan = plan_insert_host(seed_keys, self._capacity)
                table_plan = (plan, seed_keys)
            carry = seed_carry(
                model, qcap, self._capacity, init_rows, seed_ebits,
                symmetry=self._symmetry or self._sound, hcap=hcap,
                init_fps=cache_fps, table_plan=table_plan, ecap=ecap)
            if table_plan is None:
                key_hi, key_lo, seed_ovf = self._bulk_insert_async(
                    insert_fn, carry.key_hi, carry.key_lo, seed_keys)
                carry = carry._replace(key_hi=key_hi, key_lo=key_lo)
            else:
                seed_ovf = None  # plan_insert_host raises on overflow
            # No readiness wait: a block_until_ready here costs one
            # more sync. The
            # round-2/3 finding that launching the chunk over an
            # in-flight seed slowed the loop ~2.5x no longer reproduces
            # with the consolidated carry (q/log matrices, 2-D table);
            # PJRT orders the dependent programs itself.
        self._metrics.set("action_lanes", model.max_actions)
        self._metrics.set("row_words", model.packed_width)

        # True until a freshly built chunk program's first call, which
        # traces and compiles it (or loads it from the compile cache)
        first_call = [False]

        def mk_chunk(reason: str = "initial"):
            # every rebuild implies an XLA retrace (unless the shapes
            # hit the compile cache) — count it and leave a trace event
            self._metrics.inc("compiles")
            if self._trace:
                self._trace.emit("compile", reason=reason)
            with self._spans.span("program"):
                fn = build_chunk_fn(model, qcap, self._capacity, fmax,
                                    kmax, symmetry=self._symmetry,
                                    sound=self._sound, hcap=hcap,
                                    n_init=n_init, kraw=kraw,
                                    hint_eff=hint_eff, ecap=ecap)
            first_call[0] = True
            return fn

        chunk_fn = mk_chunk()
        pipeline = bool(opts.get("pipeline", True))

        # with retry, autosave or tiering on, the host keeps the
        # authoritative shadow (mirror + pending frontier + sound-mode
        # edge records + the host tier), updated per chunk; a transient
        # backend fault re-seeds a fresh device incarnation from it and
        # resumes, and a capacity fault spills before re-seeding
        if shadow is not None:
            shadow.seed_epoch([pack_qrows(init_rows, seed_ebits,
                                          cache_fps,
                                          model.packed_width)])

        # --- chunk loop -------------------------------------------------
        # Double-buffered pipeline (``tpu_options(pipeline=False)`` forces
        # the synchronous path): chunk N+1 is launched on the carry — a
        # device future, donated straight back in — BEFORE chunk N's stats
        # are materialized, so the host work (stats decode, batched
        # host-property evaluation, discovery bookkeeping) hides under
        # the accelerator instead of serializing with it. Speculation is
        # safe because every host-intervention condition (kovf / hovf /
        # ovf / xovf, the growth limits, an empty queue, device-property
        # completion) also gates the device loop's own cond
        # (device_loop.make_cond), so a chunk launched past one of them
        # runs zero iterations and replaying its stats is idempotent.
        # The one sanctioned divergence: an exit only the HOST can see (a
        # host-property discovery, a reached generation target) lands one
        # chunk late, so generated/unique counts may include one extra
        # chunk of real exploration — the same overshoot the chunk
        # granularity already implies (module docstring); discoveries and
        # witness paths are unaffected (sticky registers; the window
        # evaluation order is anchored per chunk).
        from .device_loop import HIST_WINDOW

        inflight: deque = deque()
        # latest unpacked per-chunk scalars, read by the post-loop phases
        cur = {"q_head": 0, "q_size": 0, "q_tail": 0, "log_n": 0,
               "e_n": 0}
        hgrow_pend = {"on": False, "hovf": False, "h_n": 0}
        kovf_pend = [0, 0, 0]  # observed vmax/dmax/rmax of kovf chunks

        def want_reps_now() -> bool:
            return bool(self._host_props) and any(
                p.name not in discoveries for _i, p in self._host_props)

        def dispatch() -> None:
            nonlocal carry, chunk_fn, hcap
            if hcap and not want_reps_now():
                # every host property has its discovery: the in-loop
                # history dedup is dead work now (and, saturated, would
                # stall the loop via hovf) — rebuild without it
                hcap = 0
                chunk_fn = mk_chunk("hdrop")
            # the growth limit bounds the EPOCH-LOCAL device log; the
            # preloaded table keys (inits / resumed mirror / post-fault
            # re-seed) are subtracted so total occupancy still trips
            # growth at ~grow_at
            grow_limit = np.int32(min(
                self._grow_at * self._capacity,
                self._capacity - headroom) - preload)
            remaining = np.int32(
                min(max(target - self._state_count, 0), 2**31 - 1)
                if target is not None else 2**31 - 1)
            carry = carry._replace(gen=jnp.int32(0),
                                   steps=jnp.int32(k_steps),
                                   vmax=jnp.int32(0),
                                   pdh=jnp.int32(0), prb=jnp.int32(0))
            ordinal = int(self._metrics.get("chunks", 0)) + 1
            with self._spans.span("dispatch", chunk=ordinal), \
                    (self._spans.span("program", chunk=ordinal)
                     if first_call[0] else nullcontext()):
                carry, stats_d = chunk_fn(carry, remaining, grow_limit,
                                          np.int32(self._h_pulled))
            first_call[0] = False
            t_disp = time.perf_counter()
            self._metrics.inc("chunks")
            if self._metrics.get("first_dispatch_s") is None:
                self._metrics.set("first_dispatch_s",
                                  t_disp - self._spawn_t0)
            inflight.append((ordinal, stats_d, self._h_pulled,
                             int(grow_limit), hcap, t_disp))

        def process(ordinal: int, stats_d, h_base: int, grow_limit: int,
                    hcap_d: int, t_disp: float) -> set:
            """Consume one chunk's stats vector; returns the host
            actions it demands (handled once the pipeline is drained)."""
            nonlocal fault_attempt, spill_attempt
            # ONE transfer for everything the host reads per chunk
            # (scalars + the representative window when host props are
            # on): each transfer is a sync — routed through the fault
            # hook + watchdog deadline
            stats = self._materialize_stats(stats_d, ordinal,
                                            t_disp=t_disp)
            # a successful sync proves the backend is alive: the retry
            # budget bounds CONSECUTIVE faults, not lifetime hiccups
            # (and the spill budget CONSECUTIVE unproductive spills)
            fault_attempt = 0
            spill_attempt = 0
            # the host's side of the chunk: overlapped when chunk N+1
            # is in flight, the pipeline bubble when nothing is
            with self._spans.span("decode", chunk=ordinal):
                return decode(ordinal, stats, h_base, grow_limit, hcap_d)

        def decode(ordinal: int, stats, h_base: int, grow_limit: int,
                   hcap_d: int) -> set:
            nonlocal seed_ovf, corruption_attempt
            acts: set = set()
            (q_head, q_tail, log_n, gen, ovf, xovf, kovf, h_n, hovf,
             vmax, dmax, rmax, e_n, pdh, prb, mat) = (
                int(stats[0]), int(stats[1]), int(stats[2]),
                int(stats[3]), bool(stats[4]), bool(stats[5]),
                bool(stats[6]), int(stats[7]), bool(stats[8]),
                int(stats[9]), int(stats[10]), int(stats[11]),
                int(stats[12]), int(stats[13]), int(stats[14]),
                int(stats[15]))
            disc_hit = stats[16:16 + prop_count].astype(bool)
            disc_hi = stats[16 + prop_count:16 + 2 * prop_count]
            disc_lo = stats[16 + 2 * prop_count:16 + 3 * prop_count]
            tail0 = 16 + 3 * prop_count
            width3 = model.packed_width + 3
            if q_tail > 0:
                # most recently enqueued state (live Explorer progress)
                self._recent_row = stats[tail0:tail0 + width3].copy()
            if shadow is not None:
                # fold this chunk's appends into the host shadow (the
                # queue/log suffixes are append-only, so gathering them
                # from the LIVE carry — possibly a later in-flight
                # chunk's future — reads exactly the committed rows)
                with self._spans.span("shadow", chunk=ordinal):
                    prev = shadow.log_n[0]
                    q_new = gather_rows(carry.q, np.arange(
                        n_init + prev, n_init + log_n, dtype=np.int32))
                    log_new = gather_rows(carry.log, np.arange(
                        prev, log_n, dtype=np.int32))
                    e_new = None
                    if ecap:
                        e_new = gather_rows(carry.elog, np.arange(
                            shadow.e_n[0], e_n, dtype=np.int32))
                    if corrupt_hook is not None and len(q_new) \
                            and corrupt_hook(ordinal, 1) == 0:
                        # injected lying chip (tests/bench): flip one
                        # bit in the fingerprints the device reported —
                        # consistently in the queue's fp column and the
                        # insert log's child key, like a chip whose
                        # hash unit miscomputed
                        q_new = q_new.copy()
                        log_new = log_new.copy()
                        q_new[:, model.packed_width + 1] ^= np.uint32(1)
                        log_new[:, 0] ^= np.uint32(1)
                    audited = audit_pol.should_audit(ordinal)
                    if audited:
                        self._metrics.inc("audits")
                        bad = audit_chunk_rows(
                            q_new, log_new, model.packed_width,
                            sound=self._sound)
                        if self._trace:
                            self._trace.emit("audit", chunk=ordinal,
                                             rows=int(len(q_new)),
                                             mismatches=bad, device=0)
                        if bad:
                            self._metrics.inc("audit_mismatches")
                            raise CorruptionError(
                                f"chunk {ordinal} audit: {bad} of "
                                f"{len(q_new)} frontier fingerprints "
                                "disagree with the host oracle's "
                                "re-execution — the chip is returning "
                                "wrong results",
                                device_index=0, mismatches=bad)
                    hits = shadow.note_chunk(0, q_new, log_new, e_new,
                                             q_head)
                    if audited:
                        # the oracle vouched for everything up to and
                        # including this fold: pin the replay boundary
                        # (and only a PASSED audit clears the
                        # consecutive-corruption counter — a lying chip
                        # syncs just fine)
                        shadow.audit_mark()
                        corruption_attempt = 0
                    self._shadow_chain_head = shadow.chain_head
                    if hits:
                        # host-tier re-probe: device-"fresh" keys the
                        # mirror already held (rediscoveries of evicted
                        # ranges); excluded from the unique counts
                        self._metrics.inc("host_probe_hits", hits)
                        self._metrics.set("host_tier_keys",
                                          shadow.host_tier_keys)
                if (self._autosave_path is not None
                        and self._autosave_every > 0
                        and ordinal % self._autosave_every == 0):
                    self._write_autosave(shadow, discoveries)
            new = log_n - cur["log_n"]  # this chunk's fresh inserts
            metrics = self._metrics
            # rows this chunk expanded: the head only advances (a
            # re-seed restarts it at 0, and cur with it)
            metrics.inc("expanded", q_head - cur["q_head"])
            # full-width successor rows the chunk built (chunk-local)
            metrics.inc("materialized", mat)
            cur.update(q_head=q_head, q_size=q_tail - q_head,
                       q_tail=q_tail, log_n=log_n, e_n=e_n)
            # observed branching (raw / post-dedup), for tuning
            # model.branching_hint and the kraw/kmax buffer sizes
            metrics.observe_max("vmax", vmax)
            metrics.observe_max("dmax", dmax)
            metrics.observe_max("rmax", rmax)
            # dedup telemetry: chunk-local counters (reset at dispatch,
            # so a zero-iteration speculative chunk contributes 0)
            if pdh:
                metrics.inc("predup_hits", pdh)
            if prb:
                metrics.inc("probe_rounds", prb)
            if size_key is not None:
                _SIZE_MEMO.merge_max(size_key, (vmax, dmax))
            self._state_count += gen
            # with the shadow on, len(generated) is authoritative — and
            # past a spill it is the ONLY correct count (the device log
            # includes host-filtered rediscoveries)
            self._unique_state_count = (len(generated)
                                        if shadow is not None
                                        else base_unique + log_n)
            trace = self._trace
            if trace:
                trace.emit(
                    "chunk", chunk=ordinal,
                    gen=gen, unique=self._unique_state_count,
                    q_size=q_tail - q_head, new=new,
                    # dedup hit-rate: generated children this chunk
                    # that were already in the visited table
                    dedup_hit=(round(1.0 - new / gen, 4) if gen else 0.0),
                    # hash-table load factor (growth trips near grow_at)
                    load=round(log_n / self._capacity, 4),
                    vmax=vmax, dmax=dmax)
            disc_fps = _combine64(disc_hi, disc_lo)
            for i, prop in enumerate(properties):
                if i in host_prop_idx:
                    continue  # host-evaluated: device bits are placeholders
                if disc_hit[i] and prop.name not in discoveries:
                    discoveries[prop.name] = int(disc_fps[i])
                    self._note_discovery(prop.name, int(disc_fps[i]))
            if seed_ovf is not None:
                if bool(jax.device_get(seed_ovf)):
                    raise RuntimeError(
                        "device hash table overflow while seeding; raise "
                        "tpu_options(capacity=...)")
                seed_ovf = None
            if xovf:
                raise RuntimeError(_XOVF_MESSAGE)
            if ovf:
                raise RuntimeError(
                    "device hash table probe overflow below the growth "
                    f"limit (capacity {self._capacity}); raise via "
                    "checker_builder.tpu_options(capacity=...)")
            if hcap_d and want_reps_now():
                # host properties are evaluated on the distinct-history
                # representatives the chunk loop logged (memoized per
                # key), so a shallow host counterexample still exits
                # early instead of waiting for full exhaustion. The
                # inline window is anchored at the chunk's DISPATCH-time
                # pulled count (h_base); under pipelining the rows
                # consumed since then are skipped by offset, so each
                # representative is evaluated exactly once and in the
                # same order as the synchronous path.
                fresh = h_n - self._h_pulled
                if fresh > 0:
                    with self._spans.span("props", chunk=ordinal):
                        win = stats[tail0 + width3:].reshape(
                            (HIST_WINDOW, -1))
                        offset = self._h_pulled - h_base
                        take = max(0, min(fresh, HIST_WINDOW - offset))
                        if take:
                            wfp = _combine64(
                                win[offset:offset + take, -2],
                                win[offset:offset + take, -1])
                            self._eval_host_props_block(
                                win[offset:offset + take, :-2], wfp,
                                discoveries)
                            self._h_pulled += take
                        if fresh > take:
                            # more fresh keys than the inline window:
                            # pull the remainder standalone
                            self._pull_host_reps(carry, h_n, n_init,
                                                 discoveries)
                if hovf or h_n >= self._grow_at * hcap_d:
                    acts.add("hgrow")
                    hgrow_pend.update(
                        on=True, hovf=hgrow_pend["hovf"] or hovf,
                        h_n=max(hgrow_pend["h_n"], h_n))
                else:
                    self._hscan_tail = q_tail
            if kovf:
                # resize data for the drained handler; skip the exit
                # checks exactly like the synchronous retry `continue`
                kovf_pend[0] = max(kovf_pend[0], vmax)
                kovf_pend[1] = max(kovf_pend[1], dmax)
                kovf_pend[2] = max(kovf_pend[2], rmax)
                acts.add("kovf")
                return acts
            if (q_tail - q_head == 0
                    or len(discoveries) == prop_count
                    or (target is not None
                        and self._state_count >= target)
                    or self._cancel_event.is_set()
                    or self._pause_event.is_set()):
                acts.add("done")
            elif ecap and e_n >= ecap - max(kmax, fmax):
                acts.add("egrow")
            elif log_n >= grow_limit or q_tail > qcap - headroom:
                acts.add("grow")
            return acts

        def handle_hgrow() -> None:
            # grow the history-key table: proactively at the same
            # occupancy threshold as the fingerprint table (a near-full
            # open table crawls through thousands of probe rounds per
            # insert), or reactively on hovf. Re-seed from the logged
            # representatives; after an hovf the overflowing iteration
            # still committed, so rescan its queue span for the keys
            # that went unlogged (growing further if even the bigger
            # table overflows on that span). Runs only with the
            # pipeline drained: the reseed is sized by h_n, and an
            # in-flight chunk could log representatives past it.
            nonlocal carry, hcap, chunk_fn
            h_n = hgrow_pend["h_n"]
            q_tail = cur["q_tail"]
            with self._spans.span("hgrow"):
                while True:
                    new_hcap = self._posthoc_cap
                    while new_hcap * self._grow_at <= h_n:
                        new_hcap *= 4
                    if new_hcap == self._posthoc_cap:
                        new_hcap *= 4  # hovf w/o occupancy
                    hcap = self._posthoc_cap = new_hcap
                    carry = self._regrow_history_table(carry, h_n, hcap)
                    if not hgrow_pend["hovf"]:
                        break
                    carry, rescan_ovf = self._rescan_history(
                        carry, self._hscan_tail, q_tail, qcap, n_init,
                        discoveries)
                    if not rescan_ovf:
                        break
            self._hscan_tail = q_tail
            self._metrics.inc("hgrows")
            if self._trace:
                self._trace.emit("hgrow", hcap=hcap,
                                 hovf=hgrow_pend["hovf"], h_n=h_n)
            hgrow_pend.update(on=False, hovf=False, h_n=0)
            chunk_fn = mk_chunk("hgrow")

        def handle_kovf() -> None:
            # a batch overflowed one of the candidate buffers; nothing
            # was committed — resize the overflowed stage(s) to the
            # observed branching (at least doubling) and resume. rmax =
            # per-row max (sizes hint_eff), vmax = raw-valid max (sizes
            # kraw), dmax = post-dedup max (sizes kmax).
            nonlocal carry, chunk_fn, kraw, kmax, hint_eff
            vmax, dmax, rmax = kovf_pend
            before = (kraw, kmax, hint_eff)
            grew = False
            if hint_eff and rmax > hint_eff:
                hint_eff = max(hint_eff + 1, rmax + rmax // 4)
                if hint_eff >= model.max_actions:
                    hint_eff = 0  # degenerate: fall back to global
                grew = True
            if not hint_eff and vmax > kraw:
                kraw = min(max(kraw * 2,
                               -(-(vmax + vmax // 4) // 256) * 256),
                           fa)
                grew = True
            if dmax > kmax or not grew:
                kmax = min(max(kmax * 2,
                               -(-(dmax + dmax // 4) // 256) * 256),
                           kraw if not hint_eff
                           else fmax * hint_eff)
            kmax = min(kmax, kraw if not hint_eff
                       else fmax * hint_eff)
            if (kraw, kmax, hint_eff) == before:
                # wedged: rebuilding the identical program would abort
                # forever — reclassify as a capacity fault; the retry
                # envelope recovers with a k-buffer grown to its bound
                # (a pre-mutation abort lost no data)
                from .resilience import CandidateOverflowError
                raise CandidateOverflowError(
                    "candidate-buffer capacity overflow (kovf) wedged "
                    f"at kraw={kraw} kmax={kmax} hint={hint_eff} "
                    f"(observed vmax={vmax} dmax={dmax} rmax={rmax})",
                    vmax=vmax, dmax=dmax)
            self._metrics.inc("kovfs")
            if self._trace:
                self._trace.emit("kovf", kraw=kraw, kmax=kmax,
                                 vmax=kovf_pend[0], dmax=kovf_pend[1],
                                 rmax=kovf_pend[2])
            kovf_pend[:] = [0, 0, 0]
            chunk_fn = mk_chunk("kovf")
            carry = carry._replace(kovf=jnp.bool_(False))

        def handle_egrow() -> None:
            # cross-edge log full: quadruple it and resume
            nonlocal carry, chunk_fn, ecap
            with self._spans.span("grow"):
                new_elog = jnp.zeros((ecap * 4, 4), jnp.uint32)
                new_elog = jax.lax.dynamic_update_slice(
                    new_elog, carry.elog, (0, 0))
                ecap *= 4
                carry = carry._replace(elog=new_elog)
            if self._trace:
                self._trace.emit("egrow", ecap=ecap)
            chunk_fn = mk_chunk("egrow")

        def handle_grow() -> None:
            nonlocal carry, chunk_fn, qcap
            with self._spans.span("grow"):
                carry, qcap = self._grow_device(carry, qcap, n_init,
                                                headroom, insert_fn)
            self._metrics.inc("grows")
            if self._trace:
                self._trace.emit("grow", capacity=self._capacity,
                                 qcap=qcap)
            chunk_fn = mk_chunk("grow")

        spill_warned = [False]

        def warn_spill_eventually() -> None:
            # unsound EVENTUALLY + spill: a rediscovered duplicate
            # re-enqueues with its rediscovery path's pending bits, so
            # eventually verdicts may differ from an uncapped run — the
            # same path-dependence the unsound engine already documents,
            # but worth a one-time flag. sound_eventually() rejects
            # tiering up front instead.
            if spill_warned[0] or self._sound:
                return
            if any(p.expectation == Expectation.EVENTUALLY
                   for p in properties):
                import warnings
                warnings.warn(
                    "memory tiering with (unsound) eventually "
                    "properties: rediscovered duplicates re-enter the "
                    "frontier with rediscovery-path pending bits, so "
                    "eventually verdicts may differ from an uncapped "
                    "run (safety properties and fingerprint sets are "
                    "unaffected)", RuntimeWarning, stacklevel=2)
            spill_warned[0] = True

        def handle_spill(reason: str = "budget") -> None:
            # the memory wall, survived: growth would exceed the HBM
            # budget, so drain (the caller already did), evict the
            # coldest fingerprint-prefix ranges from the device table
            # IN PLACE (ops/hashtable.py table_evict_prefix — the host
            # tier already holds every key), and re-seed a fresh epoch
            # around the evicted table: the queue/log reset bounds the
            # epoch buffers, and the growth limit's preload term drops
            # by the evicted occupancy, making room to keep checking.
            nonlocal carry, chunk_fn, qcap, hcap, n_init, base_unique, \
                preload
            if int(min(self._grow_at * self._capacity,
                       self._capacity - headroom)) <= 0:
                # even an empty table cannot fit one iteration's
                # headroom under this budget: spilling again would spin
                # forever at zero progress
                self._capacity_terminal(RuntimeError(
                    f"device table budget (capacity {self._capacity}) "
                    f"cannot fit one iteration's headroom ({headroom}) "
                    "— raise tpu_options(max_capacity=...) or shrink "
                    "fmax/kmax"), shadow, discoveries)
            occupancy = preload + cur["log_n"]
            hot_budget = max(0, min(
                int((1.0 - spill_pol.frac) * occupancy),
                int(self._grow_at * self._capacity) - headroom - 1))
            plan = shadow.spill_plan(hot_budget)
            if plan is None:
                self._capacity_terminal(RuntimeError(
                    "host tier exhausted: range eviction cannot bring "
                    f"the device table (capacity {self._capacity}) "
                    "under its growth budget"), shadow, discoveries)
            warn_spill_eventually()
            with self._spans.span("spill"):
                mask = np.zeros((1 << SPILL_PREFIX_BITS,), bool)
                mask[sorted(shadow.evicted_prefixes)] = True
                khi, klo, ecount_d = _evict_jit()(
                    carry.key_hi, carry.key_lo, jnp.asarray(mask))
                ecount = int(jax.device_get(ecount_d))
                rows, ebs, fps = shadow.pending()
                init_rows2 = [rows[i] for i in range(rows.shape[0])]
                n_init = len(init_rows2)
                self._h_pulled = 0
                self._hscan_tail = n_init
                self._base_fps = shadow.hot_keys()
                base_unique = len(generated)
                preload = max(occupancy - ecount, 0)
                qcap = self._device_qcap(n_init, headroom)
                hcap = (self._posthoc_cap
                        if self._host_props and want_reps_now() else 0)
                with self._spans.span("seed"):
                    carry = seed_carry(
                        model, qcap, self._capacity, init_rows2,
                        np.asarray(ebs, np.uint32),
                        symmetry=self._symmetry or self._sound,
                        hcap=hcap, init_fps=[int(f) for f in fps],
                        ecap=ecap, table=(khi, klo))
                shadow.seed_epoch([pack_qrows(init_rows2, ebs, fps,
                                              model.packed_width)])
            cur.update(q_head=0, q_size=n_init, q_tail=n_init, log_n=0,
                       e_n=0)
            hgrow_pend.update(on=False, hovf=False, h_n=0)
            kovf_pend[:] = [0, 0, 0]
            self._metrics.inc("spills")
            if ecount:
                self._metrics.inc("evicted_keys", ecount)
            self._metrics.set("host_tier_keys", shadow.host_tier_keys)
            if self._trace:
                self._trace.emit("evict", prefixes=len(plan[0]),
                                 keys=ecount)
                self._trace.emit("spill", capacity=self._capacity,
                                 hot=preload, reason=reason,
                                 host_tier_keys=shadow.host_tier_keys)
            chunk_fn = mk_chunk("spill")

        def reseed() -> None:
            # post-fault recovery: rebuild the device state from the
            # shadow — a fresh carry seeded with the pending frontier,
            # the visited table re-inserted from the complete host
            # mirror, the chunk program recompiled for the new n_init.
            # Dedup is set-semantics, so the rebuilt run explores
            # exactly the remaining graph: discoveries and fingerprint
            # sets match an uninterrupted run (tests/test_resilience.py)
            nonlocal carry, chunk_fn, qcap, hcap, ecap, n_init, \
                base_unique, seed_ovf, preload
            rows, ebs, fps = shadow.pending()
            init_rows2 = [rows[i] for i in range(rows.shape[0])]
            n_init = len(init_rows2)
            self._h_pulled = 0
            self._hscan_tail = n_init
            # the device table re-seeds with the HOT set only (== the
            # whole mirror until ranges have been evicted): a recovery
            # must not re-promote what a spill just moved host-side
            hot = shadow.hot_keys()
            self._base_fps = hot
            base_unique = len(generated)
            preload = len(hot)
            while self._grow_at * self._capacity <= headroom + preload \
                    and spill_pol.can_grow(self._capacity):
                self._capacity *= 4
            if self._grow_at * self._capacity <= headroom + preload:
                plan = (shadow.spill_plan(
                    int(self._grow_at * self._capacity) - headroom - 1)
                    if spill_on else None)
                if plan is None:
                    self._capacity_terminal(RuntimeError(
                        "device hash table budget (max_capacity="
                        f"{spill_pol.max_capacity}) cannot hold the "
                        f"re-seeded hot set ({preload} keys)"),
                        shadow, discoveries)
                hot = shadow.hot_keys()
                self._base_fps = hot
                preload = len(hot)
                self._metrics.inc("spills")
                if plan[2]:
                    self._metrics.inc("evicted_keys", plan[2])
                self._metrics.set("host_tier_keys",
                                  shadow.host_tier_keys)
                if self._trace:
                    self._trace.emit("evict", prefixes=len(plan[0]),
                                     keys=plan[2])
                    self._trace.emit("spill", capacity=self._capacity,
                                     hot=preload, reason="reseed",
                                     host_tier_keys=shadow.host_tier_keys)
            qcap = self._device_qcap(n_init, headroom)
            hcap = (self._posthoc_cap
                    if self._host_props and want_reps_now() else 0)
            if self._sound:
                ecap = max(ecap, self._capacity)
            with self._spans.span("seed"):
                carry = seed_carry(
                    model, qcap, self._capacity, init_rows2,
                    np.asarray(ebs, np.uint32),
                    symmetry=self._symmetry or self._sound, hcap=hcap,
                    init_fps=[int(f) for f in fps], ecap=ecap)
                key_hi, key_lo, seed_ovf = self._bulk_insert_async(
                    insert_fn, carry.key_hi, carry.key_lo, hot)
                carry = carry._replace(key_hi=key_hi, key_lo=key_lo)
            shadow.seed_epoch([pack_qrows(init_rows2, ebs, fps,
                                          model.packed_width)])
            cur.update(q_head=0, q_size=n_init, q_tail=n_init, log_n=0,
                       e_n=0)
            hgrow_pend.update(on=False, hovf=False, h_n=0)
            kovf_pend[:] = [0, 0, 0]
            chunk_fn = mk_chunk("retry")

        fault_attempt = 0
        spill_attempt = 0
        corruption_attempt = 0
        recover_delay: "Optional[float]" = None
        while True:
            try:
                if recover_delay is not None:
                    # back off BEFORE touching the device again (give a
                    # restarting backend time to come up); the
                    # reseed itself runs inside the retry envelope, so
                    # a still-dead backend just burns another attempt
                    if recover_delay > 0:
                        time.sleep(recover_delay)
                    recover_delay = None
                    reseed()
                dispatch()
                while True:
                    if pipeline and len(inflight) == 1:
                        dispatch()
                    acts = process(*inflight.popleft())
                    if not acts:
                        if not inflight:
                            dispatch()
                        yield  # step boundary: one chunk consumed
                        continue
                    # a host intervention (or an exit) is due: drain the
                    # one speculative chunk first — under any
                    # device-visible stop condition it ran zero
                    # iterations and its stats replay idempotently; past
                    # a host-only exit it is one extra chunk of real
                    # (merged) exploration
                    while inflight:
                        acts |= process(*inflight.popleft())
                    if hgrow_pend["on"]:
                        handle_hgrow()
                        acts.discard("hgrow")
                    if "kovf" in acts:
                        handle_kovf()
                    elif "done" in acts:
                        break
                    elif "egrow" in acts:
                        handle_egrow()
                    elif "grow" in acts:
                        # budget-aware growth: quadruple while the HBM
                        # budget allows, spill to the host tier once it
                        # does not (capacity-terminal only when tiering
                        # is off)
                        if spill_pol.can_grow(self._capacity):
                            handle_grow()
                        elif spill_on and shadow is not None:
                            handle_spill("budget")
                        else:
                            self._capacity_terminal(RuntimeError(
                                "device table growth past tpu_options("
                                f"max_capacity={spill_pol.max_capacity})"
                                " needed and spill is disabled"),
                                shadow, discoveries)
                    dispatch()
                    yield  # step boundary: intervention handled
                break
            except BaseException as exc:
                if shadow is None:
                    raise
                kind = classify_error(exc)
                if kind is FaultKind.CAPACITY:
                    # a capacity fault inside the retry envelope: a
                    # spill-eligible one (RESOURCE_EXHAUSTED, table
                    # pressure, a wedged kovf) recovers by shrinking the
                    # device-resident set (or growing the k-buffer) and
                    # re-seeding; everything else — and an exhausted
                    # spill budget — takes the capacity-terminal ending
                    # (checkpoint + flight dump + actionable raise)
                    if not (spill_on and spill_eligible(exc)):
                        self._capacity_terminal(exc, shadow, discoveries)
                    inflight.clear()
                    spill_attempt += 1
                    if spill_attempt > spill_pol.max_spills:
                        self._capacity_terminal(exc, shadow, discoveries)
                    cand = find_candidate_overflow(exc)
                    if cand is not None:
                        # a kovf abort (the iteration committed
                        # nothing) re-routes through the envelope with a
                        # GROWN k-buffer instead of raising to the user
                        kraw = fa
                        hint_eff = 0
                        kmax = min(max(kmax * 2, cand.dmax
                                       + cand.dmax // 4), fa)
                        self._metrics.inc("kovfs")
                        if self._trace:
                            self._trace.emit("kovf", kraw=kraw,
                                             kmax=kmax, recovered=True)
                    else:
                        # a real allocation/table fault names the HBM
                        # budget better than any option could: clamp
                        # growth at the current capacity and spill
                        if spill_pol.max_capacity is None \
                                or spill_pol.max_capacity > self._capacity:
                            spill_pol.max_capacity = self._capacity
                        plan = shadow.spill_plan(max(0, min(
                            int((1.0 - spill_pol.frac)
                                * self._grow_at * self._capacity),
                            int(self._grow_at * self._capacity)
                            - headroom - 1)))
                        if plan is None:
                            self._capacity_terminal(exc, shadow,
                                                    discoveries)
                        warn_spill_eventually()
                        self._metrics.inc("spills")
                        if plan[2]:
                            self._metrics.inc("evicted_keys", plan[2])
                        self._metrics.set("host_tier_keys",
                                          shadow.host_tier_keys)
                        if self._trace:
                            self._trace.emit("evict",
                                             prefixes=len(plan[0]),
                                             keys=plan[2])
                            self._trace.emit(
                                "spill", capacity=self._capacity,
                                hot=plan[1], reason="fault",
                                host_tier_keys=shadow.host_tier_keys,
                                error=f"{type(exc).__name__}: {exc}")
                    recover_delay = 0.0
                    continue
                if kind is FaultKind.CORRUPTION:
                    # the auditor caught the chip lying: every fold
                    # since the last audited boundary is suspect — roll
                    # the shadow back to it (corrupt mirror entries are
                    # undone, so the final digest matches an
                    # uncorrupted oracle run) and replay from there. On
                    # a single chip there is nothing to quarantine
                    # AROUND, so the replay re-executes under audit
                    # with a bounded consecutive-corruption budget; the
                    # sharded engine degrades around the liar instead
                    # (parallel/engine.py).
                    inflight.clear()
                    blamed = blamed_device(exc)
                    self._quarantined.add(blamed if blamed is not None
                                          else 0)
                    self._metrics.set("fault_device",
                                      blamed if blamed is not None
                                      else 0)
                    self._metrics.set("quarantined",
                                      len(self._quarantined))
                    shadow.rollback_to_mark()
                    self._unique_state_count = len(generated)
                    if self._trace:
                        self._trace.emit(
                            "corruption", device=blamed,
                            error=f"{type(exc).__name__}: {exc}")
                        self._trace.emit(
                            "quarantine",
                            device=blamed if blamed is not None else 0,
                            quarantined=len(self._quarantined))
                    if corruption_attempt >= max(1, policy.retries):
                        self._flight_dump("corruption")
                        raise RuntimeError(
                            "chunk audit failed "
                            f"{corruption_attempt + 1} consecutive "
                            "times on the only device — the chip is "
                            "persistently returning wrong results and "
                            "there is no healthy silicon to replay on "
                            f"({exc})") from exc
                    corruption_attempt += 1
                    recover_delay = 0.0
                    continue
                if kind is not FaultKind.TRANSIENT:
                    raise
                # transient backend fault: the in-flight futures are
                # poisoned (or superseded — their un-consumed work
                # replays from the shadow); drop them, back off,
                # re-seed, resume. Programming errors re-raise:
                # retrying reproduces them.
                inflight.clear()
                blamed = blamed_device(exc)
                if blamed is not None:
                    self._metrics.set("fault_device", blamed)
                if fault_attempt >= policy.retries:
                    self._resilience_degrade(exc, shadow, discoveries)
                fault_attempt += 1
                recover_delay = policy.delay(fault_attempt)
                self._metrics.inc("retries")
                if self._trace:
                    self._trace.emit(
                        "retry", attempt=fault_attempt,
                        delay=round(recover_delay, 3),
                        error=f"{type(exc).__name__}: {exc}",
                        device=blamed)
        # the engine's tail after its last chunk, up to done
        with self._spans.span("finish"):
            q_size = cur["q_size"]
            q_tail, log_n, e_n = cur["q_tail"], cur["log_n"], cur["e_n"]

            if (self._pause_event.is_set()
                    and not self._cancel_event.is_set()
                    and q_size > 0
                    and len(discoveries) < prop_count
                    and not (target is not None
                             and self._state_count >= target)):
                # pause exit (the run did NOT finish): the pipeline drained
                # above; gather the pending frontier — the shadow holds it
                # when resilience is on, otherwise pull it from the live
                # carry exactly like the resumable-frontier path — and land
                # the resume_from-loadable pause checkpoint
                if shadow is not None:
                    p_rows, p_ebs, p_fps = shadow.pending()
                else:
                    # complete the host mirror from the device log first:
                    # the checkpoint needs the full (fp -> parent) record
                    self._mirror_carry = (carry.log, carry.log_n)
                    self._ensure_mirror()
                    head = int(jax.device_get(carry.q_head))
                    tail = int(jax.device_get(carry.q_tail))
                    width = model.packed_width
                    pend = np.asarray(jax.device_get(carry.q[head:tail]))
                    p_rows = pend[:, :width]
                    p_ebs = pend[:, width]
                    p_fps = _combine64(pend[:, width + 1],
                                       pend[:, width + 2])
                self._write_pause_checkpoint(p_rows, p_ebs, p_fps,
                                             discoveries)
                self._discovery_fps.update(discoveries)
                return

            if self._sound and q_size == 0 and self._resume_path is not None:
                import warnings
                warnings.warn(
                    "resume_from() + sound_eventually(): the post-exhaustion "
                    "lasso sweep is SKIPPED on resumed runs (the "
                    "pre-checkpoint subgraph's edges are not in this run's "
                    "device logs), so liveness cycles entered through "
                    "pre-checkpoint states go unreported. Re-run without "
                    "resume_from() for a cycle-complete liveness verdict.",
                    RuntimeWarning, stacklevel=2)
            if (self._sound and q_size == 0 and self._resume_path is None
                    and not self._symmetry
                    and not self._cancel_event.is_set()):
                # (not under symmetry: a cross-branch cycle witness cannot
                # be replayed through concrete orbit members — the host DFS
                # disables its sweep the same way, dfs.py)
                # full exhaustion under sound mode: run the shared lasso
                # sweep (checker/lasso.py) over the node graph rebuilt from
                # the device logs — insert edges from the main log, cross
                # edges (dedup hits with pending bits) from the round-5 edge
                # log. Cycles entered via cross edges into explored branches
                # are liveness counterexamples neither the per-row flush nor
                # the reference can see. Skipped on resume: the
                # pre-checkpoint subgraph's edges are not in this run's logs.
                with self._spans.span("lasso"):
                    if shadow is not None:
                        # after a mid-run recovery the device logs cover
                        # only the last epoch; the shadow spans the run
                        self._shadow_lasso_sweep(shadow, int(full_ebits),
                                                 discoveries)
                    else:
                        self._device_lasso_sweep(carry, int(q_tail),
                                                 int(log_n), int(e_n),
                                                 n_init, int(full_ebits),
                                                 discoveries)

            if self._tpu_options.get("resumable"):
                # pull the pending frontier eagerly so save() needs no pinned
                # device buffers; the queue's cached fps (canonical under
                # symmetry) ride along so resume never recomputes them
                head = int(jax.device_get(carry.q_head))
                tail = int(jax.device_get(carry.q_tail))
                width = model.packed_width
                pend = np.asarray(jax.device_get(carry.q[head:tail]))
                self._resume_frontier = (
                    pend[:, :width].copy(), pend[:, width].copy(),
                    _combine64(pend[:, width + 1], pend[:, width + 2]))
            # the mirror (fp -> parent fp) stays device-resident until someone
            # needs it (path reconstruction, checkpointing): the log pull is
            # pure host-link cost, pointless for count-only runs. Keep only
            # the log fields so the table/queue HBM is freed promptly. With
            # the shadow on, the host mirror is already complete — no pull.
            self._mirror_carry = (None if shadow is not None
                                  else (carry.log, carry.log_n))
            self._discovery_fps.update(discoveries)

    def _device_lasso_sweep(self, carry, q_tail: int, log_n: int,
                            e_n: int, n_init: int, full_mask: int,
                            discoveries: Dict[str, object]) -> None:
        """Rebuild the (state, pending-ebits) node graph from the device
        logs and run the shared SCC sweep. Node masks come from the
        queue's at-enqueue ebits column (queue row ``n_init + i`` aligns
        with log row ``i``); witnesses land in ``discoveries`` as
        explicit fingerprint paths (stem + one cycle lap)."""
        import jax

        from .lasso import lasso_sweep

        from .lasso import add_log_block, add_seed_nodes

        model = self._model
        width = model.packed_width
        node_fp: Dict[int, int] = {}
        node_parent: Dict[int, tuple] = {}
        node_mask: Dict[int, int] = {}
        node_edges: Dict[int, list] = {}
        add_seed_nodes(node_fp, node_parent, node_mask, self._base_fps,
                       self._orig_of, full_mask)
        log_h = np.asarray(jax.device_get(carry.log[:max(log_n, 1)]))
        eb_h = np.asarray(jax.device_get(
            carry.q[n_init:n_init + max(log_n, 1), width]))
        edges_h = np.asarray(jax.device_get(carry.elog[:max(e_n, 1)]))
        add_log_block(node_fp, node_parent, node_mask, node_edges,
                      log_h[:log_n], eb_h[:log_n], edges_h[:e_n])
        lasso_sweep(self._properties, discoveries, node_edges,
                    node_mask, node_parent, node_fp)
        if self._trace:
            self._trace.emit(
                "lasso", nodes=len(node_mask),
                edges=sum(len(v) for v in node_edges.values()))

    def _visit_reached(self) -> None:
        """Drive the CheckerVisitor over every reached state — the device
        log IS the visitation record, so the visits replay post-hoc from
        the host mirror. The previous design forced visitors onto the
        per-level engine, which pays the ~0.15 s standalone-dispatch
        floor PLUS a sync per BFS level.

        Replay walks the parent FOREST depth-first from a
        children-by-parent index, with an explicit spine of
        (state, action) steps. Each state's transition is matched ONCE
        against its parent's decoded state — O(states) model-replay
        steps — and a node's decoded state is DROPPED at backtrack, when
        its last pending child has been matched (the per-parent
        refcount is the exhausted child iterator), so resident decoded
        states are bounded by the live path depth, not the reached-set
        size. The children index also replaces the old wave-based
        deferral for cross-shard mirrors (a child preceding its parent
        in the concatenated per-shard logs simply waits in the index) —
        the waves rescanned every still-pending key per round, O(states
        squared) on adversarial orders. Each visit still materializes
        its own O(depth) Path from the spine — that is the visitor API.
        Visit order is the DFS order of the parent forest (parents
        before children); the log's sibling interleaving is not
        preserved, matching the reference's unordered multithreaded
        visitors."""
        from .path import NondeterministicModelError, Path

        self._ensure_mirror()
        model = self._model
        translate = self._symmetry or self._sound
        children: Dict[int, list] = {}
        roots: list = []
        for key, parent_key in self._generated.items():
            if parent_key is not None and parent_key in self._generated:
                children.setdefault(parent_key, []).append(key)
            else:
                # an init state (or a resumed root whose chain is
                # outside the mirror): full reconstruction, once
                roots.append(key)
        visited = 0
        peak = 0
        for root in roots:
            base = self._reconstruct_path(root)._steps
            # spine[i] = [state_i, action taken from state_i]; the last
            # entry's action is None (the path ends there)
            spine = [[base[-1][0], None]]
            base = base[:-1]
            self._visitor.visit(
                model, Path(base + [(spine[0][0], None)]))
            visited += 1
            iters = [iter(children.get(root, ()))]
            while iters:
                if self._cancel_event.is_set():
                    return
                key = next(iters[-1], None)
                if key is None:
                    # refcount exhausted: this node's decoded state is
                    # no longer needed by any pending child — drop it
                    iters.pop()
                    spine.pop()
                    continue
                fp = self._orig_of.get(key, key) if translate else key
                parent_state = spine[-1][0]
                found = None
                for action, state in model.next_steps(parent_state):
                    if model.fingerprint(state) == fp:
                        found = (action, state)
                        break
                if found is None:
                    raise NondeterministicModelError(
                        "Unable to extend a visitation path: no "
                        f"successor of the parent state has fingerprint "
                        f"{fp}. This usually means Model.actions or "
                        "Model.next_state vary across calls.")
                spine[-1][1] = found[0]
                spine.append([found[1], None])
                iters.append(iter(children.get(key, ())))
                peak = max(peak, len(base) + len(spine))
                self._visitor.visit(
                    model,
                    Path(base + [(s, a) for s, a in spine]))
                visited += 1
        # observability for the refcounted drop: the maximum number of
        # decoded states resident at once during the replay
        self._metrics.observe_max("visit_peak_resident", peak)
        if self._trace:
            self._trace.emit("visit", visited=visited,
                             peak_resident=peak)
        if visited != len(self._generated):  # pragma: no cover
            raise NondeterministicModelError(
                "visitation replay stalled: a parent chain in the "
                "mirror is cyclic or incomplete "
                f"({len(self._generated) - visited} unreached keys)")

    def _device_qcap(self, n_init: int, headroom: int) -> int:
        """Queue rows needed between growths: every enqueued state is
        unique, so the tail never exceeds n_init + grow_limit + one
        iteration's appends. A ``target_state_count`` additionally bounds
        total appends (generated >= inserted), which keeps the queue — by
        far the biggest device buffer, and its memset is real seed-time —
        proportional to the requested work."""
        grow_limit = int(min(self._grow_at * self._capacity,
                             self._capacity - headroom))
        if self._target_state_count is not None:
            grow_limit = min(grow_limit,
                             self._target_state_count + headroom)
        return n_init + grow_limit + 2 * headroom

    # ------------------------------------------------------------------
    def _grow_device(self, carry, qcap: int, n_init: int, headroom: int,
                     insert_fn):
        """Quadruple table+log capacity, relocate the live queue region to
        the front of a correspondingly larger queue, and re-insert all
        known fingerprints from the device-resident log. No host round trip
        for the fingerprints themselves."""
        import jax
        import jax.numpy as jnp

        from ..ops.hashtable import table_insert as table_insert_local

        old_capacity = self._capacity
        self._capacity = old_capacity * 4
        new_qcap = self._device_qcap(n_init, headroom)

        hist_on = carry.hidx.shape[0] > 1

        def rebuild(q, q_head, q_tail, log, log_n, hidx):
            # copy the whole queue prefix into the larger buffer at the
            # same positions: the [0, tail) region doubles as the list of
            # every unique state's packed row (post-hoc property eval,
            # checkpointing), so consumed rows are retained
            nq = jnp.zeros((new_qcap, q.shape[1]), jnp.uint32)
            nq = jax.lax.dynamic_update_slice(nq, q, (0, 0))
            nlog = jnp.zeros((self._capacity, log.shape[1]), jnp.uint32)
            nlog = jax.lax.dynamic_update_slice(nlog, log, (0, 0))
            if hist_on:
                nh_idx = jnp.zeros((self._capacity,), jnp.int32)
                nh_idx = jax.lax.dynamic_update_slice(nh_idx, hidx, (0,))
            else:
                nh_idx = hidx
            # fresh table (2-D bucket-major, like the chunk carry);
            # re-insert every logged fingerprint
            from ..ops.hashtable import _BUCKET
            key_hi = jnp.zeros(
                (self._capacity // _BUCKET, _BUCKET), jnp.uint32)
            key_lo = jnp.zeros(
                (self._capacity // _BUCKET, _BUCKET), jnp.uint32)
            valid = jnp.arange(old_capacity, dtype=jnp.int32) < log_n
            _, key_hi, key_lo, ovf = table_insert_local(
                key_hi, key_lo, log[:, 0], log[:, 1], valid)
            return (nq, key_hi, key_lo, nlog, nh_idx, ovf)

        rebuild = jax.jit(rebuild)
        nq, key_hi, key_lo, nlog, nh_idx, ovf = rebuild(
            carry.q, carry.q_head, carry.q_tail, carry.log, carry.log_n,
            carry.hidx)
        if bool(jax.device_get(ovf)):
            raise RuntimeError("overflow while re-inserting during growth")
        # fingerprints known at seed time (inits, or a resumed snapshot)
        # are not in the device log; re-insert them from the host
        key_hi, key_lo = self._bulk_insert(insert_fn, key_hi, key_lo,
                                           self._base_fps)
        carry = carry._replace(
            q=nq, key_hi=key_hi, key_lo=key_lo, log=nlog, hidx=nh_idx)
        return carry, new_qcap

    # ------------------------------------------------------------------
    _HPULL_JIT = None

    @classmethod
    def _hpull_jit(cls):
        """Process-wide jitted gather of fresh history representatives:
        rows + witness fingerprints for ``hidx[start : start + bucket)``.
        A pure gather program — it avoids the standalone-dispatch floor a
        while_loop program (the old post-hoc reduction) paid per chunk."""
        if cls._HPULL_JIT is None:
            import jax
            import jax.numpy as jnp

            def fn(q, hidx, log, start, n_init, bucket):
                sel = hidx[jnp.minimum(start + jnp.arange(bucket),
                                       hidx.shape[0] - 1)]
                # the queue matrix carries 3 bookkeeping columns past the
                # packed row (ebits + cached fp)
                rows = q[jnp.minimum(sel, q.shape[0] - 1)][:,
                                                           :q.shape[1] - 3]
                # queue row i >= n_init is log entry i - n_init (queue
                # and log append in lockstep); seed rows never appear in
                # hidx (they are evaluated host-side at seed time).
                # ONE output array: each transferred leaf is its own
                # sync, so the witness-fp columns
                # ride the row matrix
                li = jnp.clip(sel - n_init, 0, log.shape[0] - 1)
                return jnp.concatenate(
                    [rows, log[li, 0:1], log[li, 1:2]], axis=1)

            cls._HPULL_JIT = jax.jit(fn, static_argnums=(5,))
        return cls._HPULL_JIT

    def _pull_host_reps(self, carry, h_n: int, n_init: int,
                        discoveries: Dict[str, int]) -> None:
        """Host-evaluate the distinct-history representatives the chunk
        loop logged since the last pull (memoized per key)."""
        import jax
        import jax.numpy as jnp

        if all(p.name in discoveries for _i, p in self._host_props):
            return
        start = self._h_pulled
        if h_n <= start:
            return
        count = h_n - start
        bucket = _bucket(count)
        out_d = self._hpull_jit()(
            carry.q, carry.hidx, carry.log,
            jnp.int32(start), jnp.int32(n_init), bucket)
        out_h = np.asarray(jax.device_get(out_d))
        wfp = _combine64(out_h[:count, -2], out_h[:count, -1])
        self._eval_host_props_block(out_h[:count, :-2], wfp, discoveries)
        self._h_pulled = h_n

    def _regrow_history_table(self, carry, h_n: int, hcap: int):
        """Re-seed a larger history-key table from the logged
        representatives' queue rows (one rare standalone dispatch)."""
        import jax
        import jax.numpy as jnp

        from ..ops.hash_kernel import fp64_device
        from ..ops.hashtable import table_insert

        model = self._model
        cols = getattr(model, "host_property_cols", None)
        off, hw = cols if cols is not None else (0, model.packed_width)

        def reseed(q, hidx, n):
            khi = jnp.zeros((hcap,), jnp.uint32)
            klo = jnp.zeros((hcap,), jnp.uint32)
            sel = jnp.minimum(hidx, q.shape[0] - 1)
            hhi, hlo = fp64_device(q[sel][:, off:off + hw])
            valid = jnp.arange(hidx.shape[0], dtype=jnp.int32) < n
            _, khi, klo, ovf = table_insert(khi, klo, hhi, hlo, valid)
            return khi, klo, ovf

        bucket = min(_bucket(max(h_n, 1)), carry.hidx.shape[0])
        khi, klo, ovf = jax.jit(reseed)(carry.q,
                                        carry.hidx[:bucket],
                                        jnp.int32(h_n))
        if bool(jax.device_get(ovf)):
            raise RuntimeError(
                "history-key table overflow while re-seeding after "
                "growth; raise tpu_options(hcap=...)")
        return carry._replace(hkey_hi=khi, hkey_lo=klo,
                              hovf=jnp.bool_(False))

    def _rescan_history(self, carry, start: int, end: int, qcap: int,
                        n_init: int, discoveries: Dict[str, int]):
        """Recovery after an in-chunk history-table overflow: the
        overflowing iteration committed its rows, but its unresolved
        keys were neither inserted nor logged. Re-dedup the queue span
        ``[start, end)`` against the (re-grown) table, insert the
        missing keys, and host-evaluate their representatives (rare
        standalone dispatch; duplicate evaluations are memoized).
        Returns ``(carry, overflowed)`` — on overflow the caller grows
        the table again and retries."""
        import jax
        import jax.numpy as jnp

        from .device_loop import shrink_indices
        from ..ops.hash_kernel import fp64_device
        from ..ops.hashtable import table_insert

        if end <= start:
            return carry, False
        model = self._model
        width = model.packed_width
        cols = getattr(model, "host_property_cols", None)
        off, hw = cols if cols is not None else (0, width)
        rmax = min(_bucket(end - start), qcap)
        s0 = min(start, qcap - rmax)

        # recovered representatives are logged into hidx too, so later
        # table growths (which re-seed from hidx) keep their keys; when
        # the log lacks room for the write window, skip logging — later
        # duplicates just re-log and re-evaluate (memoized), benign
        log_reps = (int(jax.device_get(carry.h_n)) + rmax
                    <= carry.hidx.shape[0])

        def fn(q, log, khi, klo, hidx, h_n, s0_, q_off, q_len):
            region = jax.lax.dynamic_slice(q, (s0_, 0),
                                           (rmax, width + 3))
            hhi, hlo = fp64_device(region[:, off:off + hw])
            idx = jnp.arange(rmax, dtype=jnp.int32)
            valid = (idx >= q_off) & (idx < q_off + q_len)
            ins, khi, klo, ovf = table_insert(khi, klo, hhi, hlo, valid)
            src = shrink_indices(ins, rmax)
            rows = region[src][:, :width]
            hcnt = ins.sum(dtype=jnp.int32)
            if log_reps:
                hidx = jax.lax.dynamic_update_slice(
                    hidx, (src + s0_).astype(jnp.int32), (h_n,))
                h_n = h_n + hcnt
            li = jnp.clip(src + s0_ - n_init, 0, log.shape[0] - 1)
            return (rows, log[li, 0], log[li, 1], hcnt, ovf, khi, klo,
                    hidx, h_n)

        (rows_d, whi_d, wlo_d, hcnt_d, ovf_d, khi, klo, hidx_d,
         h_n_d) = jax.jit(fn)(
            carry.q, carry.log,
            carry.hkey_hi, carry.hkey_lo, carry.hidx, carry.h_n,
            jnp.int32(s0), jnp.int32(start - s0), jnp.int32(end - start))
        hcnt, ovf = jax.device_get((hcnt_d, ovf_d))
        if bool(ovf):
            return carry, True
        hcnt = int(hcnt)
        if log_reps:
            carry = carry._replace(hidx=hidx_d, h_n=h_n_d)
            self._h_pulled += hcnt  # evaluated below, stay in lockstep
        if hcnt:
            n = min(_bucket(hcnt), rmax)
            rows_h, whi_h, wlo_h = jax.device_get(
                (rows_d[:n], whi_d[:n], wlo_d[:n]))
            wfp = _combine64(whi_h[:hcnt], wlo_h[:hcnt])
            self._eval_host_props_block(rows_h[:hcnt], wfp, discoveries)
        return carry._replace(hkey_hi=khi, hkey_lo=klo), False

    def _pull_log_blocks(self, mirror) -> List[np.ndarray]:
        """The device-resident search log's live rows, in ONE transfer
        (engine-specific; the sharded engine returns one block per
        shard). Each block is in insertion order."""
        log_d, log_n_d = mirror
        import jax

        log_n = int(jax.device_get(log_n_d))
        if self._trace:
            self._trace.emit("mirror_pull", n=log_n)
        if not log_n:
            return []
        # pull only the live prefix (pow2-padded slice jitted on
        # device); the log matrix rides ONE transfer
        n = min(_bucket(log_n), log_d.shape[0])
        _slice, _take, take_rows_fn, _take2 = _level_helpers()
        return [np.asarray(jax.device_get(take_rows_fn(log_d, n)))[:log_n]]

    def _pull_log(self) -> None:
        """Pull the search log — lazily, on first use — and index its
        child keys for path walks (``_walk_log``). The fp -> parent dict
        is not built here: only the callers that need the whole record
        build it (``_ensure_mirror``)."""
        mirror = self._mirror_carry
        if mirror is None:
            return
        self._mirror_carry = None
        self._metrics.inc("log_walks", 0)
        self._metrics.inc("mirror_builds", 0)
        with self._spans.span("mirror.pull"):
            blocks = self._pull_log_blocks(mirror)
        if not blocks:
            return
        with self._spans.span("mirror.index"):
            log = np.ascontiguousarray(
                blocks[0] if len(blocks) == 1 else np.concatenate(blocks))
            self._log_rows = log
            # child (hi, lo) words read as one uint64 each: one strided
            # copy, no combine (queried in the same form, _word_key)
            self._log_keys = np.ascontiguousarray(
                log.view(np.uint64)[:, 0])
            # a parent is inserted (and expanded) before its children,
            # so within one block its row comes first
            self._log_ordered = len(blocks) == 1

    def _walk_log(self, fp: int) -> List[int]:
        """The chain from an init state to ``fp``, as the fingerprints
        replay takes, walked over the pulled log: one vectorised key
        scan a step, the parent read from the found row. A key in
        ``_generated`` (init states, a resumed checkpoint's records, the
        whole record once built) is walked there; the walk stops where
        neither holds the key. Under symmetry/sound each canonical or
        node key is translated to the ORIGINAL explored state's
        fingerprint (recorded device-side), so the replayed trace is a
        concrete path — the DFS engine's enqueue-original rule
        (`dfs.rs:260-285`)."""
        keys, rows = self._log_keys, self._log_rows
        generated, orig_of = self._generated, self._orig_of
        translate = self._symmetry or self._sound
        end = 0 if keys is None else len(keys)
        chain: List[int] = []
        nxt = fp
        while True:
            if nxt in generated:
                chain.append(orig_of.get(nxt, nxt) if translate else nxt)
                parent = generated[nxt]
                if parent is None:
                    break
                nxt = parent
                continue
            if keys is None:
                break
            hit = np.flatnonzero(keys[:end] == _word_key(nxt))
            if not hit.size:
                break
            r = int(hit[0])
            row = rows[r]
            chain.append((int(row[4]) << 32 | int(row[5])) if translate
                         else nxt)
            nxt = int(row[2]) << 32 | int(row[3])
            if self._log_ordered:
                end = r
        chain.reverse()
        return chain

    def _ensure_mirror(self) -> None:
        """Complete the host mirror — the fp -> parent dict, and the
        original-fp map under symmetry/sound — from the pulled log, for
        the callers that need the whole record (checkpoints, visitors,
        ``generated_fingerprints()``). Built once, from the one pull."""
        self._pull_log()
        log = self._log_rows
        if log is None:
            return
        self._log_rows = self._log_keys = None
        with self._spans.span("mirror.dict"):
            child = _combine64(log[:, 0], log[:, 1]).tolist()
            self._generated.update(
                zip(child, _combine64(log[:, 2], log[:, 3]).tolist()))
            if self._symmetry or self._sound:
                self._orig_of.update(
                    zip(child, _combine64(log[:, 4], log[:, 5]).tolist()))
            self._unique_state_count = len(self._generated)
        self._metrics.inc("mirror_builds")

    # ------------------------------------------------------------------
    def _run_levels(self) -> None:
        import jax
        import jax.numpy as jnp

        from ..ops.hashtable import make_table, table_insert

        model = self._model
        properties = self._properties
        prop_count = len(properties)
        width = model.packed_width
        from ..ops.expand import eventually_indices
        full_ebits = np.uint32(sum(1 << i
                                   for i in eventually_indices(properties)))
        generated = self._generated
        discoveries = self._discovery_fps
        # host ALWAYS/SOMETIMES bits are placeholders on device; host
        # EVENTUALLY discoveries come from the device's terminal flush
        # over host-corrected ebits, so their device bits are authoritative
        host_prop_idx = {i for i, p in self._host_props
                         if p.expectation != Expectation.EVENTUALLY}
        host_ev = self._host_ev
        target = self._target_state_count
        visitor = self._visitor

        level_fn = build_level_fn(model, symmetry=self._symmetry)
        insert_fn = _insert_jit()
        slice_fn, take_fn, take_rows_fn, take2_fn = _level_helpers()

        # --- init -------------------------------------------------------
        init_rows = self._seed_inits()
        if self._host_props:
            # the reference evaluates properties on every popped unique
            # state; our per-level insertion pass covers everything except
            # the seeds, handled here on the host states directly
            for s in model.init_states():
                if model.within_boundary(s):
                    self._eval_host_props_state(s, self._canon_fp(s),
                                                discoveries)

        key_hi, key_lo = make_table(self._capacity)
        key_hi, key_lo = self._bulk_insert(
            insert_fn, key_hi, key_lo, list(generated.keys()))

        # segments reference (rows, ebits, start, length) on device
        segments: deque = deque()
        for start in range(0, len(init_rows), self._max_segment):
            chunk = init_rows[start:start + self._max_segment]
            fcount = len(chunk)
            bucket = _bucket(fcount)
            rows = np.zeros((bucket, width), dtype=np.uint32)
            rows[:fcount] = np.stack(chunk)
            ebs = np.full((bucket,), full_ebits, dtype=np.uint32)
            if host_ev:
                for j in range(fcount):
                    ebs[j] &= ~np.uint32(
                        self._host_ev_clear_bits(chunk[j]))
            segments.append((jnp.asarray(rows), jnp.asarray(ebs), 0, fcount))

        # --- search loop ------------------------------------------------
        while segments:
            if len(discoveries) == prop_count:
                return
            if self._cancel_event.is_set() or self._pause_event.is_set():
                # raced loser (checker/race.py) or a pause request:
                # stop promptly (the per-level engine has no
                # checkpointable loop, so a pause here is a plain stop)
                return
            rows, ebs, start, length = segments.popleft()
            bucket = _bucket(length)
            if rows.shape[0] == bucket and start == 0:
                frontier, ebits = rows, ebs
            else:
                frontier, ebits = slice_fn(rows, ebs, start, bucket)
            fvalid = jnp.arange(bucket) < length

            while True:
                (key_hi, key_lo, comp_rows, comp_chi, comp_clo, comp_phi,
                 comp_plo, comp_eb, count_d, disc_hit_d, disc_hi_d,
                 disc_lo_d, gen_d, ovf_d, fp_hi_d, fp_lo_d, xovf_d,
                 comp_ohi, comp_olo) = \
                    level_fn(frontier, fvalid, ebits, key_hi, key_lo)

                # small pull: scalars + per-property discovery candidates
                (count, disc_hit, disc_hi, disc_lo, gen_count, overflow,
                 xovf) = jax.device_get((count_d, disc_hit_d, disc_hi_d,
                                         disc_lo_d, gen_d, ovf_d, xovf_d))
                if bool(xovf):
                    raise RuntimeError(_XOVF_MESSAGE)
                if not overflow:
                    break
                # a single level's batch outran the table headroom: grow,
                # rebuild from the host mirror (which excludes this level's
                # partial inserts), and retry the level cleanly
                self._capacity *= 4
                key_hi, key_lo = make_table(self._capacity)
                key_hi, key_lo = self._bulk_insert(
                    insert_fn, key_hi, key_lo, list(generated.keys()))
            count = int(count)
            self._state_count += int(gen_count)

            if visitor is not None:
                # host-fallback feature: materialize each frontier state's
                # path (requires the frontier fingerprints — pull them)
                phi, plo = jax.device_get((fp_hi_d, fp_lo_d))
                fps = _combine64(phi, plo)
                for k in range(length):
                    visitor.visit(
                        model, self._reconstruct_path(int(fps[k])))

            disc_fps = _combine64(disc_hi, disc_lo)
            for i, prop in enumerate(properties):
                if i in host_prop_idx:
                    continue  # host-evaluated: device bits are placeholders
                if disc_hit[i] and prop.name not in discoveries:
                    discoveries[prop.name] = int(disc_fps[i])
                    self._note_discovery(prop.name, int(disc_fps[i]))

            # mirror the newly inserted (fingerprint, parent) pairs:
            # 16 bytes per new state over the host link
            if count:
                chi_h, clo_h, phi_h, plo_h = jax.device_get(take_fn(
                    comp_chi, comp_clo, comp_phi, comp_plo, _bucket(count)))
                fp_c = _combine64(chi_h[:count], clo_h[:count])
                fp_p = _combine64(phi_h[:count], plo_h[:count])
                generated.update(zip(fp_c.tolist(), fp_p.tolist()))
                if self._symmetry:
                    ohi_h, olo_h = jax.device_get(take2_fn(
                        comp_ohi, comp_olo, _bucket(count)))
                    fp_o = _combine64(ohi_h[:count], olo_h[:count])
                    self._orig_of.update(zip(fp_c.tolist(),
                                             fp_o.tolist()))
                if self._host_props and any(
                        p.name not in discoveries
                        for _i, p in self._host_props):
                    # skip the row pull + decode once every host property
                    # already has its discovery
                    nb = _bucket(count)
                    rows_h = np.asarray(jax.device_get(take_rows_fn(
                        comp_rows, nb)))
                    ev_clear = (np.zeros((nb,), np.uint32)
                                if host_ev else None)
                    for k in range(count):
                        self._eval_host_props_row(
                            rows_h[k], int(fp_c[k]), discoveries)
                        if host_ev:
                            ev_clear[k] = self._host_ev_clear_bits(
                                rows_h[k])
                    if host_ev and ev_clear.any():
                        # correct the new states' ebits BEFORE they are
                        # enqueued: the device cannot evaluate these
                        # conditions, so their bits only clear here
                        comp_eb = self._clear_ebits_jit(nb)(
                            comp_eb, jnp.asarray(ev_clear))
            self._unique_state_count = len(generated)
            # one "level" event per frontier segment (a level splits
            # into segments of at most max_segment rows)
            self._metrics.inc("levels")
            if self._trace:
                self._trace.emit(
                    "level", level=int(self._metrics.get("levels")),
                    frontier=length, gen=self._state_count,
                    unique=self._unique_state_count)

            if len(discoveries) == prop_count:
                return
            if target is not None and self._state_count >= target:
                return

            # grow the table before it saturates
            if len(generated) > self._grow_at * self._capacity:
                self._capacity *= 4
                key_hi, key_lo = make_table(self._capacity)
                key_hi, key_lo = self._bulk_insert(
                    insert_fn, key_hi, key_lo, list(generated.keys()))

            # next frontier: the compacted child buffer, segmented lazily
            for seg_start in range(0, count, self._max_segment):
                seg_len = min(self._max_segment, count - seg_start)
                segments.append((comp_rows, comp_eb, seg_start, seg_len))

    # ------------------------------------------------------------------
    def _eval_host_props_state(self, state, fp: int,
                               discoveries: Dict[str, int]) -> None:
        for i, prop in self._host_props:
            if prop.name in discoveries:
                continue
            res = bool(prop.condition(self._model, state))
            if prop.expectation == Expectation.ALWAYS and not res:
                discoveries[prop.name] = fp
                self._note_discovery(prop.name, fp)
            elif prop.expectation == Expectation.SOMETIMES and res:
                discoveries[prop.name] = fp
                self._note_discovery(prop.name, fp)

    _CLEAR_JITS: dict = {}

    @classmethod
    def _clear_ebits_jit(cls, n: int):
        """Jitted per-bucket helper: clear host-corrected eventually bits
        on the first ``n`` rows of a compacted child-ebits buffer."""
        fn = cls._CLEAR_JITS.get(n)
        if fn is None:
            import jax
            import jax.numpy as jnp

            def clear(eb, mask):
                return eb.at[:n].set(eb[:n] & ~mask)

            fn = cls._CLEAR_JITS[n] = jax.jit(clear)
        return fn

    def _host_ev_clear_bits(self, row) -> int:
        """Bitmask of host-evaluated EVENTUALLY properties whose condition
        holds on this packed state (memoized with the other host props)."""
        results = self._host_props_results(row)
        bits = 0
        for (i, prop), res in zip(self._host_props, results):
            if prop.expectation == Expectation.EVENTUALLY and res:
                bits |= 1 << i
        return bits

    def _host_props_results(self, row) -> List[bool]:
        model = self._model
        key = model.host_property_key(row)
        results = self._host_prop_cache.get(key)
        if results is None:
            fns = self._host_fns
            if fns is not None:
                # packed fast path: the model evaluates each host
                # property straight off the packed row (e.g. ABD's
                # linearizability needs only the history columns) —
                # the full decode() built the whole actor/network state
                # per representative, ~4x the cost of the history walk
                results = [bool(fn(row)) for fn in fns]
            else:
                state = model.decode(row)
                results = [bool(prop.condition(model, state))
                           for _i, prop in self._host_props]
            self._host_prop_cache[key] = results
        return results

    def _eval_host_props_row(self, row, fp: int,
                             discoveries: Dict[str, int]) -> None:
        """Evaluate host properties on one newly inserted packed state,
        memoized by ``model.host_property_key`` (e.g. distinct histories
        recur across thousands of states)."""
        results = self._host_props_results(row)
        for (i, prop), res in zip(self._host_props, results):
            if prop.name in discoveries:
                continue
            if prop.expectation == Expectation.ALWAYS and not res:
                discoveries[prop.name] = fp
                self._note_discovery(prop.name, fp)
            elif prop.expectation == Expectation.SOMETIMES and res:
                discoveries[prop.name] = fp
                self._note_discovery(prop.name, fp)

    def _eval_host_props_block(self, rows, fps,
                               discoveries: Dict[str, int]) -> None:
        """Evaluate host properties over a whole pulled block of packed
        states at once: one vectorized key pass
        (``model.host_property_key_block`` when the model provides it),
        then one in-order scan that decodes/evaluates only cache-missing
        keys — the per-row slice+hash overhead of the old
        ``_eval_host_props_row`` loop was the dominant host cost per
        representative. Scan order is block order and stops at the first
        point every host property has a discovery, so the witnessing
        fingerprints are identical to the per-row path's."""
        host_props = self._host_props
        n = len(rows)
        if not n or not host_props or all(
                p.name in discoveries for _i, p in host_props):
            return
        model = self._model
        block_fn = getattr(model, "host_property_key_block", None)
        keys = (block_fn(rows) if block_fn is not None
                else [model.host_property_key(row) for row in rows])
        cache = self._host_prop_cache
        fns = self._host_fns
        for j in range(n):
            results = cache.get(keys[j])
            if results is None:
                row = rows[j]
                if fns is not None:
                    results = [bool(fn(row)) for fn in fns]
                else:
                    state = model.decode(row)
                    results = [bool(prop.condition(model, state))
                               for _i, prop in host_props]
                cache[keys[j]] = results
            fp = int(fps[j])
            for (i, prop), res in zip(host_props, results):
                if prop.name in discoveries:
                    continue
                if prop.expectation == Expectation.ALWAYS and not res:
                    discoveries[prop.name] = fp
                    self._note_discovery(prop.name, fp)
                elif prop.expectation == Expectation.SOMETIMES and res:
                    discoveries[prop.name] = fp
                    self._note_discovery(prop.name, fp)
            if all(p.name in discoveries for _i, p in host_props):
                return

    def _bulk_insert_async(self, insert_fn, key_hi, key_lo,
                           fps: List[int]):
        """(Re)insert known fingerprints without syncing; returns
        ``(key_hi, key_lo, overflow)`` with ``overflow`` a device bool
        scalar the caller must eventually check."""
        import jax.numpy as jnp
        overflow = None  # stays None when fps is empty
        chunk_size = 1 << 16
        for start in range(0, len(fps), chunk_size):
            chunk = fps[start:start + chunk_size]
            n = _bucket(len(chunk))
            arr = np.zeros((n,), dtype=np.uint64)
            arr[:len(chunk)] = np.asarray(chunk, dtype=np.uint64)
            valid = np.arange(n) < len(chunk)
            _, key_hi, key_lo, ovf = insert_fn(
                key_hi, key_lo,
                jnp.asarray((arr >> np.uint64(32)).astype(np.uint32)),
                jnp.asarray(arr.astype(np.uint32)),
                jnp.asarray(valid))
            overflow = ovf if overflow is None else (overflow | ovf)
        return key_hi, key_lo, overflow

    def _bulk_insert(self, insert_fn, key_hi, key_lo, fps: List[int]):
        """(Re)insert known fingerprints, e.g. after growth (synced)."""
        key_hi, key_lo, overflow = self._bulk_insert_async(
            insert_fn, key_hi, key_lo, fps)
        if overflow is not None and bool(overflow):
            raise RuntimeError(
                "device hash table overflow during bulk insert")
        return key_hi, key_lo

    def _canon_fp(self, state) -> int:
        """The fingerprint dedup works in canonical-orbit space under
        symmetry reduction, plain state space otherwise."""
        if self._symmetry:
            return self._model.fingerprint(self._symmetry_fn(state))
        return self._model.fingerprint(state)

    def generated_fingerprints(self):
        """All visited STATE fingerprints (pulls the device log if
        pending; under ``sound_eventually`` the node-keyed dedup record is
        translated back to state fingerprints)."""
        self._ensure_mirror()
        if self._sound:
            return {self._orig_of.get(k, k)
                    for k in self._generated.keys()}
        return set(self._generated.keys())

    # ------------------------------------------------------------------
    def save(self, path) -> None:
        """Checkpoint a finished (typically ``target_state_count``-bounded)
        run: the complete (fingerprint -> parent) search record plus the
        pending frontier rows, from which ``CheckerBuilder.resume_from``
        continues the search (SURVEY.md §5; the record is the TLC
        technique, `bfs.rs:314-342`)."""
        if not self.is_done():
            raise RuntimeError(
                "save() requires a finished run; bound it with "
                "target_state_count(...) to checkpoint mid-search")
        if self._resume_frontier is None:
            raise RuntimeError(
                "save() needs the pending frontier: run with "
                "tpu_options(resumable=True) on the device engine")
        self._ensure_mirror()
        rows, ebits, ffps = self._resume_frontier
        # the shared crash-safe writer (resilience.atomic_savez under
        # _checkpoint_save): mirror + pending frontier, with the
        # canonical/node-key -> original-fp translation and the
        # dedup-key semantics (symmetry/sound) in the metadata;
        # list-valued discoveries are explicit fingerprint paths
        # (lasso witnesses) and round-trip as lists
        self._checkpoint_save(path, rows, ebits, ffps,
                              self._discovery_fps)

    def _model_tag(self) -> str:
        return model_tag(self._model)

    def _read_checkpoint(self, path):
        """Open and verify ONE checkpoint file. Structural load errors
        (truncated archive, missing entries, bad JSON) and integrity-
        chain mismatches both raise one actionable RuntimeError, so the
        caller's generation-rollback logic has a single failure
        surface."""
        import json

        from .resilience import chain_integrity, payload_digest

        try:
            data = np.load(path)
            meta = json.loads(str(data["meta"]))
            arrays = {}
            for key in ("child", "parent", "rows", "ebits",
                        "state_count"):
                arrays[key] = data[key]
            for key in data.files:
                if key != "meta":
                    arrays[key] = data[key]
        except Exception as e:
            # anything the load raises — zipfile.BadZipFile for a
            # truncated archive, KeyError for missing entries, OSError,
            # json decode errors — means the file is not a usable
            # checkpoint; surface ONE actionable error instead of a
            # numpy/zipfile traceback
            raise RuntimeError(
                f"cannot resume from {path!r}: the "
                "checkpoint file is corrupt, truncated, or not a "
                f"Checker.save() file ({type(e).__name__}: {e}). "
                "Re-create it with save() on a finished resumable "
                "run.") from e
        want = meta.get("integrity")
        if want is not None and chain_integrity(
                payload_digest(arrays),
                meta.get("chain_head") or "") != want:
            raise RuntimeError(
                f"cannot resume from {path!r}: integrity chain "
                "mismatch — the payload no longer matches the sha256 "
                "it was written under (bit rot, tampering, or a "
                "partial write)")
        return data, meta

    def _load_checkpoint(self, discoveries: Dict[str, int]):
        """Seed state from a ``save()`` file: the mirror (and its
        canonical/node-key -> original-fp translation), the saved
        discoveries, and the pending frontier (whose rows become the seed
        'inits' — their parents are already in the mirror). Returns
        ``(rows, ebits, cache_fps)`` with ``cache_fps`` the frontier's
        queue-cached state fingerprints (canonical under symmetry).

        Every checkpoint is verified against its integrity chain
        (payload sha256 chained to the writing run's chunk-digest head
        — ``_checkpoint_save``) BEFORE anything is seeded; a corrupt,
        truncated, or tampered newest file rolls back to the previous
        autosave generation (``<path>.g1``) when one exists instead of
        resuming from garbage."""
        from .resilience import AUTOSAVE_PREV_SUFFIX

        try:
            data, meta = self._read_checkpoint(self._resume_path)
        except RuntimeError as first:
            prev = os.fspath(self._resume_path) + AUTOSAVE_PREV_SUFFIX
            if not os.path.exists(prev):
                raise
            # generation rollback: the newest autosave is unusable but
            # the one before it survived rotation — resume from that
            # (strictly older progress; the run re-explores the gap)
            data, meta = self._read_checkpoint(prev)
            if self._trace:
                self._trace.emit(
                    "corruption", device=None,
                    error=f"autosave rollback to {prev!r}: {first}")
        if meta["model"] != self._model_tag():
            raise RuntimeError(
                "checkpoint was written by a different model config: "
                f"saved {meta['model']!r}, resuming {self._model_tag()!r}")
        if (bool(meta.get("symmetry")) != self._symmetry
                or bool(meta.get("sound")) != self._sound):
            raise RuntimeError(
                "checkpoint dedup-key semantics do not match this run: "
                f"saved symmetry={meta.get('symmetry')} "
                f"sound={meta.get('sound')}, resuming "
                f"symmetry={self._symmetry} sound={self._sound}")
        child = data["child"].tolist()
        parent = [None if p == 0 else int(p)
                  for p in data["parent"].tolist()]
        self._generated.update(zip(child, parent))
        if "okeys" in data:
            self._orig_of.update(zip(data["okeys"].tolist(),
                                     data["ovals"].tolist()))
        self._state_count = int(data["state_count"])
        self._unique_state_count = len(self._generated)
        for name, fp in meta["discoveries"].items():
            discoveries[name] = ([int(f) for f in fp]
                                 if isinstance(fp, list) else int(fp))
        rows = [np.asarray(r, np.uint32) for r in data["rows"]]
        if "ffps" in data:
            fps = [int(f) for f in data["ffps"]]
        else:  # pre-round-4 checkpoint: plain mode only, recompute
            from ..fingerprint import fp64_words
            fps = [fp64_words(r.tolist()) for r in rows]
        return rows, np.asarray(data["ebits"], np.uint32), fps

    def _reconstruct_path(self, fp: int) -> Path:
        self._pull_log()
        with self._spans.span("paths"):
            if self._log_keys is None:  # the whole record is the dict
                chain = self._walk_log(fp)
            else:
                chain = self._walks.get(fp)
                if chain is None:
                    chain = self._walks[fp] = self._walk_log(fp)
                    self._metrics.inc("log_walks")
            return Path.from_fingerprints(self._model, chain)
