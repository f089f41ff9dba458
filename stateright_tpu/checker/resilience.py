"""Resilience layer for the device checking engines.

Round 5's primary bench artifact was empty because the TPU backend died
mid-run (``UNAVAILABLE: TPU backend setup/compile error``) *after* the
initial probe succeeded, and nothing between the chunk loop and
``bench.py`` could survive it. Long device runs on remote/preemptible
chips fail in ways a single-process host search never does, and each
way deserves a different response:

* **transient backend faults** (``UNAVAILABLE``, ``DEADLINE_EXCEEDED``,
  connection resets, a watchdog-expired chunk sync) — the chip or its
  host link hiccupped; the productive response is bounded
  retry-with-backoff: re-seed the device buffers from the host-side
  authoritative state (:class:`HostShadow`) and resume;
* **capacity/model errors** (``RESOURCE_EXHAUSTED``, the engines' own
  table/probe/packed-capacity overflows) — retrying reproduces them;
  the user must raise a bound;
* **programming errors** — everything else; surface immediately.

The engines wire this module around chunk dispatch
(``TpuChecker._run_device``, ``ShardedTpuChecker._run``):
``tpu_options(retries=N, backoff=s)`` bounds the retry loop,
``tpu_options(chunk_deadline=s)`` turns a hung device sync into a
classified fault via :func:`call_with_deadline`, and
``tpu_options(autosave=path, autosave_interval=chunks)`` checkpoints
the shadow periodically (and on exhausted retries) through the same
atomic tmp+``os.replace`` write as ``Checker.save``
(:func:`atomic_savez`). Every retry/failover/autosave/watchdog event
flows through the obs layer (``retries``/``failovers``/``autosaves``
metric keys, matching trace events).

Past the retry budget, a sharded run does not die: the **degradation
ladder** (:class:`DegradePolicy`, ``tpu_options(degrade=True,
min_mesh=1)``) halves the mesh onto the surviving power-of-two device
subset — excluding the chip :func:`blamed_device` names, and jumping
the rest of the budget when :class:`FaultAttributor` pins consecutive
faults on one chip — re-routes the shadow's pending frontier by
``owner_of(fp, D/2)``, and resumes; the final rung hands the shadow to
the single-chip device loop. Host BFS (a raced run's failover) and the
checkpoint-and-raise ending are only reached below ``min_mesh``.

:class:`HostShadow` is the piece that makes retry *possible*: with
resilience enabled the host keeps an authoritative copy of everything
needed to rebuild the device state — the (fingerprint -> parent)
mirror, the pending frontier rows (with their at-enqueue ebits and
cached fingerprints), and under ``sound_eventually`` the insert/cross
edge records the post-exhaustion lasso sweep reads. Maintenance costs
one small device gather per chunk (the ``shadow`` phase timer), which
also forfeits most of the double-buffered pipeline's overlap — the
documented price of a run that can outlive its backend.
"""

from __future__ import annotations

import enum
import hashlib
import os
import random
import re
import tempfile
import threading
from typing import Dict, List, Optional

import numpy as np


def _combine64(hi, lo) -> np.ndarray:
    return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)


# ----------------------------------------------------------------------
# error classes
# ----------------------------------------------------------------------
class FaultKind(enum.Enum):
    """What a runtime error means for the run (README § Resilience)."""

    TRANSIENT = "transient"      # backend/link hiccup: retry
    CAPACITY = "capacity"        # a bound is too small: raise it, rerun
    CORRUPTION = "corruption"    # a chip returned wrong results: quarantine
    PROGRAMMING = "programming"  # a bug: surface immediately


#: lowercase substrings marking a transient backend fault. The PJRT
#: status codes (UNAVAILABLE/DEADLINE_EXCEEDED/ABORTED) cover the
#: round-5 failure mode; the connection phrases cover a dropped
#: connection surfacing as a raw socket error.
TRANSIENT_MARKERS = (
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "aborted:",
    "connection reset",
    "connection refused",
    "connection aborted",
    "connection closed",
    "broken pipe",
    "socket closed",
    "heartbeat",
)

#: lowercase substrings marking a capacity/model error — retrying
#: reproduces these; the fix is a bigger bound (tpu_options(capacity=),
#: hcap=, net_capacity, ...) — or, for the table/allocation subset
#: (SPILLABLE_MARKERS), a spill of the visited set into the host tier.
CAPACITY_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "hash table overflow",
    "probe overflow",
    "capacity overflow",
    "table overflow",
)

#: the capacity subset a visited-set spill can actually relieve: table
#: and allocation pressure. "capacity overflow" is deliberately absent —
#: that is the PACKED-STATE encoding bound (net_capacity and friends;
#: `checker/tpu.py` ``_XOVF_MESSAGE``), which no amount of host-tiering
#: fixes, so it stays terminal.
SPILLABLE_MARKERS = tuple(m for m in CAPACITY_MARKERS
                          if m != "capacity overflow")


class ChunkDeadlineError(RuntimeError):
    """A chunk sync outran ``tpu_options(chunk_deadline=s)`` — a hung
    dispatch reclassified as a transient fault instead of an eternal
    hang (the watchdog; classified TRANSIENT by construction)."""


class CorruptionError(RuntimeError):
    """A chunk audit caught a device lying: the fingerprints it reported
    do not match a deterministic re-execution of the same frontier slice
    (host oracle or a different chip). Silent data corruption never
    *raises* on its own — this error is synthesized by the auditor
    (:class:`AuditPolicy`, ``tpu_options(audit=...)``) so the fault can
    route through the ordinary classification/attribution machinery.
    ``device_index`` names the lying chip (mesh position) for
    :func:`blamed_device`; the message deliberately matches no
    TRANSIENT/CAPACITY marker so :func:`classify_error` reports
    CORRUPTION by type, never by substring accident."""

    def __init__(self, msg: str, device_index: int = 0,
                 mismatches: int = 0):
        super().__init__(msg)
        self.device_index = int(device_index)
        self.mismatches = int(mismatches)


class CandidateOverflowError(RuntimeError):
    """A wedged ``kovf`` protocol: the candidate-buffer resize made no
    progress (the pre-mutation abort would rebuild the identical
    program and abort forever). The message carries a
    :data:`CAPACITY_MARKERS` phrase so :func:`classify_error` reports
    CAPACITY, and the retry envelope recovers by growing the k-buffer
    to its bound and re-seeding, instead of surfacing to the user."""

    def __init__(self, msg: str, vmax: int = 0, dmax: int = 0,
                 bmax: int = 0):
        super().__init__(msg)
        self.vmax, self.dmax, self.bmax = vmax, dmax, bmax


def classify_error(exc: BaseException) -> FaultKind:
    """Classify a runtime error, walking the ``__cause__`` chain so a
    wrapped fault (e.g. the exhausted-retries RuntimeError raised
    ``from`` the original backend error) keeps its classification."""
    seen: set = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, CorruptionError):
            return FaultKind.CORRUPTION
        if isinstance(e, (ChunkDeadlineError, ConnectionError,
                          TimeoutError)):
            return FaultKind.TRANSIENT
        msg = f"{type(e).__name__}: {e}".lower()
        if any(m in msg for m in TRANSIENT_MARKERS):
            return FaultKind.TRANSIENT
        if any(m in msg for m in CAPACITY_MARKERS):
            return FaultKind.CAPACITY
        e = e.__cause__ if e.__cause__ is not None else e.__context__
    return FaultKind.PROGRAMMING


def find_candidate_overflow(
        exc: BaseException) -> "Optional[CandidateOverflowError]":
    """The :class:`CandidateOverflowError` in ``exc``'s cause chain, if
    any — the retry envelope's capacity branch recovers from one by
    growing the k-buffer instead of evicting table ranges."""
    seen: set = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, CandidateOverflowError):
            return e
        e = e.__cause__ if e.__cause__ is not None else e.__context__
    return None


def spill_eligible(exc: BaseException) -> bool:
    """Whether a capacity-classified fault is one a visited-set spill
    (HBM -> host tier) can relieve: table/allocation pressure
    (:data:`SPILLABLE_MARKERS`) or a wedged candidate-buffer protocol
    (:class:`CandidateOverflowError`). Packed-state encoding overflows
    (``xovf``) are capacity faults too, but tiering cannot fix a model
    bound — they stay terminal. Walks the cause chain like
    :func:`classify_error`."""
    seen: set = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, CandidateOverflowError):
            return True
        msg = f"{type(e).__name__}: {e}".lower()
        if "packed-state capacity overflow" in msg:
            return False
        if any(m in msg for m in SPILLABLE_MARKERS):
            return True
        e = e.__cause__ if e.__cause__ is not None else e.__context__
    return False


# ----------------------------------------------------------------------
# retry policy
# ----------------------------------------------------------------------
class RetryPolicy:
    """Bounded retry with jittered exponential backoff.

    ``retries`` is the number of recoveries allowed per consecutive
    fault burst (the attempt counter resets after any successful chunk
    sync, so a long run that hiccups every few minutes keeps going;
    ``retries`` bounds how long the engine beats on a *dead* backend
    before degrading). ``backoff`` is the first delay in seconds; each
    further consecutive attempt doubles it (capped) with +/-25% jitter
    so a fleet of runs sharing one recovering backend does not
    stampede it. ``seed`` (``tpu_options(retry_seed=...)``) pins the
    jitter to a private ``random.Random`` stream so fault-injection
    tests are deterministic across ``PYTHONHASHSEED`` and reruns; the
    default draws from the global RNG (fleet-level decorrelation).
    """

    __slots__ = ("retries", "backoff", "cap", "jitter", "_rng")

    def __init__(self, retries: int = 0, backoff: float = 1.0,
                 cap: float = 30.0, jitter: float = 0.25,
                 seed: Optional[int] = None):
        if retries < 0:
            raise ValueError("tpu_options(retries=...) must be >= 0")
        if backoff < 0:
            raise ValueError("tpu_options(backoff=...) must be >= 0")
        self.retries = int(retries)
        self.backoff = float(backoff)
        self.cap = float(cap)
        self.jitter = float(jitter)
        self._rng = random if seed is None else random.Random(seed)

    @classmethod
    def from_options(cls, opts: dict) -> "RetryPolicy":
        seed = opts.get("retry_seed")
        return cls(retries=int(opts.get("retries", 0)),
                   backoff=float(opts.get("backoff", 1.0)),
                   seed=None if seed is None else int(seed))

    @property
    def enabled(self) -> bool:
        return self.retries > 0

    def delay(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (1-based), jittered."""
        if self.backoff <= 0:
            return 0.0
        base = min(self.backoff * (2.0 ** (attempt - 1)), self.cap)
        return base * (1.0 + self.jitter * (2.0 * self._rng.random() - 1.0))


# ----------------------------------------------------------------------
# per-device fault attribution + the mesh degradation ladder
# ----------------------------------------------------------------------
#: message patterns naming the chip a backend error came from. PJRT
#: status strings usually carry one ("device 3", "TPU_2 heartbeat
#: lost", ...); the injected test faults use the same phrasing.
_DEVICE_PATTERNS = tuple(re.compile(p) for p in (
    r"\bdevice[ _#:]+(\d+)",
    r"\btpu[_ :](\d+)\b",
    r"\bchip[ _#:]+(\d+)",
    r"\bshard[ _#:]+(\d+)",
))


def blamed_device(exc: BaseException) -> Optional[int]:
    """The device index a fault names, or ``None`` when the error is
    not attributable to one chip. Walks the cause chain like
    :func:`classify_error`; an explicit integer ``device_index``
    attribute on any link wins over message parsing."""
    seen: set = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        idx = getattr(e, "device_index", None)
        if isinstance(idx, int) and idx >= 0:
            return idx
        msg = f"{type(e).__name__}: {e}".lower()
        for pat in _DEVICE_PATTERNS:
            m = pat.search(msg)
            if m:
                return int(m.group(1))
        e = e.__cause__ if e.__cause__ is not None else e.__context__
    return None


class FaultAttributor:
    """Consecutive per-device fault attribution across a run.

    ``note(device)`` records one classified transient fault; it returns
    ``True`` when the same chip has been blamed ``blame_after`` times
    in a row — a repeat offender the ladder drops *without* burning the
    rest of the retry budget on it (re-seeding a mesh whose one bad
    chip raises every attempt is pure waste). A successful chunk sync
    or a taken rung calls :meth:`clear` (the streak is consecutive,
    like the retry budget); lifetime per-device totals survive for
    postmortems."""

    __slots__ = ("blame_after", "totals", "_last", "_streak")

    def __init__(self, blame_after: int = 2):
        self.blame_after = max(1, int(blame_after))
        self.totals: Dict[int, int] = {}
        self._last: Optional[int] = None
        self._streak = 0

    def note(self, device: Optional[int]) -> bool:
        if device is None:
            self._last, self._streak = None, 0
            return False
        self.totals[device] = self.totals.get(device, 0) + 1
        if device == self._last:
            self._streak += 1
        else:
            self._last, self._streak = device, 1
        return self._streak >= self.blame_after

    def clear(self) -> None:
        self._last, self._streak = None, 0


def match_device(devices, ref) -> Optional[int]:
    """Resolve one device reference onto a mesh position, or ``None``.

    Shared by ``degrade_step`` and ``promote_step`` (parallel/
    engine.py) so the two directions of the elastic ladder cannot
    drift: a real PJRT fault names the GLOBAL device id; an injected
    one may name the mesh position; a promote grant hands whole
    ``jax.Device`` objects. Matching order — object identity, then the
    global ``.id``, then the bare position fallback."""
    if ref is None:
        return None
    devs = list(devices)
    if not isinstance(ref, int):
        for i, dv in enumerate(devs):
            if dv is ref or dv == ref:
                return i
        ref = getattr(ref, "id", None)
        if not isinstance(ref, int):
            return None
    ids = [getattr(dv, "id", None) for dv in devs]
    if ref in ids:
        return ids.index(ref)
    if 0 <= ref < len(devs):
        return ref
    return None


def select_survivors(devices, new_d: int, *, blamed_pos=None,
                     labels=None) -> list:
    """The width-``new_d`` survivor subset for one ladder rung.

    On a multi-host mesh (``labels`` has more than one distinct value)
    a blamed position takes its whole HOST out first — DCN partitions
    and host deaths fault every chip behind that NIC — so the halved
    mesh stays host-aligned; on one host only the blamed chip is
    dropped. The kept prefix preserves host-major order, which is what
    keeps the ``owner_of(fp, D/2)`` re-route identical to a cross-mesh
    checkpoint resume."""
    devs = list(devices)
    if blamed_pos is not None:
        if labels is not None and len(set(labels)) > 1:
            bad = labels[blamed_pos]
            devs = [dv for dv, h in zip(devs, labels) if h != bad]
        else:
            devs.pop(blamed_pos)
    return devs[:new_d]


def resolve_grant(universe, refs, exclude=()) -> list:
    """Map a promote grant (``jax.Device`` objects, global ids, or
    positions into ``universe``) onto concrete devices, dropping
    duplicates, unresolvable refs, and anything in ``exclude`` (the
    devices the mesh already holds)."""
    universe = list(universe)
    out: list = []
    taken = {id(dv) for dv in exclude}
    for ref in refs:
        pos = match_device(universe, ref)
        if pos is None:
            continue
        dv = universe[pos]
        if id(dv) in taken:
            continue
        taken.add(id(dv))
        out.append(dv)
    return out


class DegradePolicy:
    """The mesh degradation ladder (README § Resilience).

    When the sharded engine exhausts its :class:`RetryPolicy` on a
    transient fault — or :class:`FaultAttributor` pins repeated faults
    on one chip — it re-routes the shadow's pending frontier by
    ``owner_of(fp, D/2)`` onto the surviving power-of-two device
    subset (excluding the blamed chip when known), rebuilds the
    sharded carry, recompiles for the smaller mesh, and resumes:
    D -> D/2 -> ... -> ``min_mesh``. The final single-chip rung runs
    the plain device loop (``TpuChecker._run_device``) seeded from the
    shadow handoff. Only below ``min_mesh`` does the run take the old
    endings (checkpoint-and-raise, or a raced run's host-BFS
    failover). ``tpu_options(degrade=False)`` opts out; ``min_mesh``
    must be a power of two >= 1."""

    __slots__ = ("enabled", "min_mesh", "blame_after")

    def __init__(self, enabled: bool = True, min_mesh: int = 1,
                 blame_after: int = 2):
        min_mesh = int(min_mesh)
        if min_mesh < 1 or (min_mesh & (min_mesh - 1)):
            raise ValueError(
                "tpu_options(min_mesh=...) must be a power of two >= 1 "
                "(the mesh halves rung by rung)")
        self.enabled = bool(enabled)
        self.min_mesh = min_mesh
        self.blame_after = max(1, int(blame_after))

    @classmethod
    def from_options(cls, opts: dict) -> "DegradePolicy":
        return cls(enabled=bool(opts.get("degrade", True)),
                   min_mesh=int(opts.get("min_mesh", 1)),
                   blame_after=int(opts.get("blame_after", 2)))


class SpillPolicy:
    """Visited-set tiering HBM -> host RAM (README § Memory tiering).

    The device table growth protocol quadruples capacity until the
    state space fits; ``tpu_options(max_capacity=N)`` caps that at the
    HBM budget. Once growth would exceed the cap — or an allocation
    raises a spill-eligible capacity fault inside the retry envelope —
    the engines drain the pipeline, evict the coldest
    fingerprint-prefix ranges from the device table into the host tier
    (:class:`HostShadow` already holds every key; eviction just shrinks
    the device-resident hot set), re-seed and resume. Rediscoveries of
    evicted keys are filtered against the host tier during the
    pipeline's host-side process stage, so a capped run enumerates the
    same fingerprint set as an uncapped one.

    ``spill`` (default True) gates eligibility; ``spill_frac`` is the
    fraction of resident keys each spill targets for eviction;
    ``max_spills`` bounds CONSECUTIVE fault-driven spills (reset by any
    successful chunk sync) before the run takes the capacity-terminal
    ending (checkpoint + flight dump + actionable raise)."""

    __slots__ = ("enabled", "max_capacity", "frac", "max_spills")

    def __init__(self, enabled: bool = True,
                 max_capacity: Optional[int] = None, frac: float = 0.5,
                 max_spills: int = 8):
        if max_capacity is not None:
            max_capacity = int(max_capacity)
            if max_capacity < 4 or (max_capacity & (max_capacity - 1)):
                raise ValueError(
                    "tpu_options(max_capacity=...) must be a power of "
                    "two >= 4 (the table quadruples up to it)")
        frac = float(frac)
        if not 0.0 < frac <= 1.0:
            raise ValueError(
                "tpu_options(spill_frac=...) must be in (0, 1]")
        self.enabled = bool(enabled)
        self.max_capacity = max_capacity
        self.frac = frac
        self.max_spills = max(1, int(max_spills))

    @classmethod
    def from_options(cls, opts: dict) -> "SpillPolicy":
        return cls(enabled=bool(opts.get("spill", True)),
                   max_capacity=opts.get("max_capacity"),
                   frac=float(opts.get("spill_frac", 0.5)),
                   max_spills=int(opts.get("max_spills", 8)))

    def can_grow(self, capacity: int) -> bool:
        """Whether quadrupling ``capacity`` stays inside the budget."""
        return self.max_capacity is None \
            or capacity * 4 <= self.max_capacity


# ----------------------------------------------------------------------
# silent-corruption audit (README § Silent corruption defense)
# ----------------------------------------------------------------------
class AuditPolicy:
    """Sampled redundant re-execution of chunk results.

    Every robustness layer above defends against faults that *raise*;
    a chip that silently returns wrong fingerprints completes "green"
    with states unexplored ("Cores that don't count", HotOS'21).
    ``tpu_options(audit=N)`` re-executes every Nth chunk's frontier
    slice — the fingerprints of the freshly appended queue rows — on a
    *different* device (host oracle on single-chip) and compares them
    word-for-word against what the chip claimed; ``audit=frac`` with a
    float in (0, 1] samples that fraction of chunks deterministically;
    ``audit=True`` means every chunk; ``audit=False`` (the default) is
    the unaudited pre-existing engine, bit for bit.

    A mismatch becomes a :class:`CorruptionError` blaming the lying
    chip, the shadow rolls back to the last audited boundary
    (:meth:`HostShadow.audit_mark` / :meth:`HostShadow.rollback_to_mark`
    — corrupt folds since the boundary are undone, so the final digest
    matches an uncorrupted oracle run), and the fault routes down the
    existing ladder: quarantine + degrade on a mesh, re-seed + replay
    on a single chip.

    Sampling caveat (documented, inherent): a chip that lies ONCE
    between two sampled audits goes uncaught — the audit verifies the
    sampled chunk's own rows. A *persistently* lying chip is caught at
    the next sampled chunk and every fold since the last clean audit is
    rolled back. ``audit=1`` closes the gap entirely.
    """

    __slots__ = ("every",)

    def __init__(self, every: int = 0):
        every = int(every)
        if every < 0:
            raise ValueError("tpu_options(audit=...) must be >= 0")
        self.every = every

    @classmethod
    def from_options(cls, opts: dict) -> "AuditPolicy":
        raw = opts.get("audit", False)
        if raw is False or raw is None:
            return cls(0)
        if raw is True:
            return cls(1)
        if isinstance(raw, float):
            if not 0.0 < raw <= 1.0:
                raise ValueError(
                    "tpu_options(audit=...) as a float is a sampling "
                    "fraction and must be in (0, 1]")
            return cls(max(1, round(1.0 / raw)))
        return cls(int(raw))

    @property
    def enabled(self) -> bool:
        return self.every > 0

    def should_audit(self, ordinal: int) -> bool:
        """Whether chunk ``ordinal`` (0-based) is a sampled audit point
        (deterministic — every run audits the same chunks)."""
        return self.every > 0 and ordinal % self.every == 0


_AUDIT_JIT = None


def oracle_fps(rows: np.ndarray, device=None) -> np.ndarray:
    """Independently re-execute the fingerprint of each packed state
    row. With ``device`` the computation runs on THAT chip (the
    cross-device redundant-execution path: a different device re-hashes
    the rows the audited chip produced); without, the host oracle
    (`fingerprint.fp64_rows`, the native C reference) answers. Both are
    bit-identical to the device kernel by the differential-test
    contract, so any disagreement with the claimed fingerprints is the
    audited chip lying, not an oracle artifact."""
    rows = np.ascontiguousarray(rows, np.uint32)
    if device is None:
        from ..fingerprint import fp64_rows
        return np.asarray(fp64_rows(rows), np.uint64)
    global _AUDIT_JIT
    if _AUDIT_JIT is None:
        import jax

        from ..ops.hash_kernel import fp64_device
        _AUDIT_JIT = jax.jit(fp64_device)
    import jax
    hi, lo = _AUDIT_JIT(jax.device_put(rows, device))
    return _combine64(np.asarray(hi), np.asarray(lo))


def audit_chunk_rows(q_new: np.ndarray, log_new: np.ndarray,
                     width: int, *, sound: bool = False,
                     device=None) -> int:
    """Audit one chunk's fresh appends for shard ``s``: re-execute the
    frontier slice's fingerprints (on ``device`` when given, else the
    host oracle) and compare against the two places the chip claimed
    them — the queue rows' fingerprint columns and the insert log's
    child-key columns. Returns the number of mismatching rows (0 =
    clean). Uses only host-resident arrays the shadow fold already
    gathered — auditing adds no extra device pulls."""
    n = len(q_new)
    if n == 0:
        return 0
    q_new = np.asarray(q_new, np.uint32)
    log_new = np.asarray(log_new, np.uint32)
    claimed = _combine64(q_new[:, width + 1], q_new[:, width + 2])
    expect = oracle_fps(q_new[:, :width], device=device)
    bad = claimed != expect
    logged = _combine64(log_new[:, 0], log_new[:, 1])
    if sound:
        # the insert log keys on (state, pending-ebits) NODE identity;
        # re-derive it from the re-executed state fp + at-enqueue ebits
        node_rows = np.stack(
            [expect.astype(np.uint32),
             (expect >> np.uint64(32)).astype(np.uint32),
             q_new[:, width]], axis=1)
        bad |= logged != oracle_fps(node_rows, device=device)
    else:
        bad |= logged != expect
    return int(np.count_nonzero(bad))


# ----------------------------------------------------------------------
# watchdog
# ----------------------------------------------------------------------
def call_with_deadline(fn, deadline: float, what: str = "device sync"):
    """Run ``fn()`` on a watchdog thread; raise :class:`ChunkDeadlineError`
    if it has not returned within ``deadline`` seconds.

    The abandoned call cannot be cancelled (there is no portable way to
    interrupt a blocked PJRT transfer) — the daemon thread is left to
    finish or die with the process; the RUN, however, gets a classified
    transient fault instead of hanging forever."""
    if not deadline or deadline <= 0:
        return fn()
    box: list = []

    def run():
        try:
            box.append(("ok", fn()))
        except BaseException as exc:  # delivered to the caller below
            box.append(("err", exc))

    t = threading.Thread(target=run, daemon=True,
                         name="stateright-tpu-watchdog")
    t.start()
    t.join(deadline)
    if not box:
        raise ChunkDeadlineError(
            f"{what} exceeded tpu_options(chunk_deadline={deadline}) — "
            "treating the hung dispatch as a transient backend fault")
    tag, val = box[0]
    if tag == "err":
        raise val
    return val


# ----------------------------------------------------------------------
# host-side authoritative state
# ----------------------------------------------------------------------
def pack_qrows(rows, ebits, fps, width: int) -> np.ndarray:
    """Host-side queue-row packing: ``[packed row | ebits | fp hi | fp
    lo]`` — the exact layout ``seed_carry``/``seed_sharded_carry`` put
    on device, so the shadow's seed rows match the device's bit for
    bit."""
    k = len(rows)
    out = np.zeros((k, width + 3), np.uint32)
    if not k:
        return out
    out[:, :width] = np.stack([np.asarray(r, np.uint32) for r in rows])
    out[:, width] = np.broadcast_to(np.asarray(ebits, np.uint32), (k,))
    fp_arr = np.asarray([int(f) for f in fps], np.uint64)
    out[:, width + 1] = (fp_arr >> np.uint64(32)).astype(np.uint32)
    out[:, width + 2] = fp_arr.astype(np.uint32)
    return out


#: fingerprint-prefix granularity of the host tier: eviction ranges are
#: buckets of the dedup key's TOP 8 bits. Top bits compose with
#: ``owner_of(fp, D)`` (also top-bit) routing: with D <= 256 every
#: prefix bucket lies entirely inside one shard, so per-shard eviction
#: ranges are owner-consistent by construction and survive mesh
#: halving (adjacent shards merge, adjacent prefix sets merge).
SPILL_PREFIX_BITS = 8


def fp_prefix(fps) -> np.ndarray:
    """The host-tier prefix bucket of each 64-bit dedup key."""
    return (np.asarray(fps, np.uint64)
            >> np.uint64(64 - SPILL_PREFIX_BITS)).astype(np.int64)


_GATHER_JIT = None


def gather_rows(mat, idx: np.ndarray) -> np.ndarray:
    """Pull ``mat[idx]`` to the host through one process-wide jitted
    gather (indices padded to power-of-two buckets so the shape set —
    and thus the retrace count — stays logarithmic)."""
    global _GATHER_JIT
    n = len(idx)
    if n == 0:
        return np.zeros((0,) + tuple(mat.shape[1:]), np.uint32)
    if _GATHER_JIT is None:
        import jax
        import jax.numpy as jnp

        def g(m, i):
            return m[jnp.minimum(i, m.shape[0] - 1)]

        _GATHER_JIT = jax.jit(g)
    bucket = max(16, 1 << (n - 1).bit_length())
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = np.asarray(idx, np.int32)
    return np.asarray(_GATHER_JIT(mat, padded))[:n]


class HostShadow:
    """The host-side authoritative copy of a device run's search state.

    Maintained per chunk while resilience is enabled
    (``tpu_options(retries=..., autosave=...)``); everything a recovery
    or an autosave needs lives here, so a dead backend can never take
    the run's progress with it:

    * the (dedup key -> parent key) mirror is updated incrementally
      (the engine's ``_generated``/``_orig_of`` dicts are shared by
      reference, so path reconstruction and checkpointing see a
      complete mirror without the end-of-run device log pull);
    * the current epoch's queue rows (packed row + at-enqueue ebits +
      cached fingerprint), from which :meth:`pending` rebuilds the
      frontier after a fault — an *epoch* is one device incarnation;
      re-seeding starts a new one from the pending rows;
    * per-shard insert records (log rows + at-enqueue ebits) and cross
      edges, from which the ``sound_eventually`` lasso sweep rebuilds
      the node graph without touching the device.

    Layout invariants leaned on: both engines' queues and logs are
    append-only and append in lockstep (queue row ``n_init_s + i`` is
    log row ``i`` of its shard), and growth passes preserve every
    shard-relative position — so per-chunk gathers of the new suffixes
    reconstruct the device state exactly.

    With :class:`SpillPolicy` tiering active the shadow additionally IS
    the host tier: it tracks which fingerprint-prefix ranges have been
    evicted from the device table (``evicted_prefixes`` — top
    :data:`SPILL_PREFIX_BITS` bits of the dedup key, so ranges compose
    with ``owner_of``'s top-bit shard routing and survive
    :meth:`reshard` down the degradation ladder), a per-prefix
    last-touch clock that :meth:`spill_plan` uses to pick the COLDEST
    ranges, and :meth:`probe_host` — the batched membership check the
    engines run over each chunk's device-"fresh" keys so rediscoveries
    of evicted keys are filtered (and never corrupt the parent mirror)
    before their successors are counted.
    """

    def __init__(self, shards: int, width: int, generated: Dict,
                 orig_of: Dict, translate: bool, sound: bool):
        self.shards = shards
        self.width = width
        self._generated = generated
        self._orig_of = orig_of
        self._translate = translate
        self._sound = sound
        # --- memory tiering (SpillPolicy) -----------------------------
        #: device-evicted fingerprint-prefix buckets (monotone: a prefix
        #: stays evicted once spilled — re-promotion would need the
        #: device table to re-absorb keys the budget just rejected)
        self.evicted_prefixes: set = set()
        #: keys resident ONLY in the host tier at the last spill
        self.host_tier_keys = 0
        #: cumulative rediscoveries filtered against the host tier
        self.host_probe_hits = 0
        self._heat = np.zeros((1 << SPILL_PREFIX_BITS,), np.int64)
        self._clock = 0
        self._roots: List[int] = []   # first-epoch dedup keys (lasso)
        self._first_epoch = True
        # --- silent-corruption defense (AuditPolicy) ------------------
        #: running chunk-digest head: sha256 folded over each chunk's
        #: reported child keys in fold order — the provenance anchor the
        #: artifact integrity chain binds checkpoints/results to
        self.chain_head = hashlib.sha256(b"stateright-tpu").hexdigest()
        #: set by the engines when ``tpu_options(audit=...)`` is on;
        #: gates the mark/rollback bookkeeping so the unaudited default
        #: path stays zero-cost
        self.audit_enabled = False
        self._mark: Optional[tuple] = None
        self._mark_keys: List[int] = []
        # cumulative across epochs (the lasso sweep's inputs)
        self._inserts: List[List[tuple]] = [[] for _ in range(shards)]
        self._edges: List[List[np.ndarray]] = [[] for _ in range(shards)]
        # current-epoch queue state
        self._epoch_q: List[List[np.ndarray]] = [[] for _ in range(shards)]
        self._heads = [0] * shards
        self._tails = [0] * shards
        self.log_n = [0] * shards  # epoch-local committed log counts
        self.e_n = [0] * shards    # epoch-local committed edge counts

    # ------------------------------------------------------------------
    def seed_epoch(self, per_shard_rows: List[np.ndarray]) -> None:
        """Start a device epoch: ``per_shard_rows[s]`` are shard ``s``'s
        seed queue rows (``pack_qrows`` layout) in device queue order."""
        assert len(per_shard_rows) == self.shards
        self._epoch_q = [[np.asarray(r, np.uint32)] if len(r) else []
                         for r in per_shard_rows]
        self._heads = [0] * self.shards
        self._tails = [len(r) for r in per_shard_rows]
        self.log_n = [0] * self.shards
        self.e_n = [0] * self.shards
        if self._first_epoch:
            self._first_epoch = False
            if self._sound:
                from ..fingerprint import fp64_node
            for r in per_shard_rows:
                for j in range(len(r)):
                    fp = int(_combine64(r[j, self.width + 1],
                                        r[j, self.width + 2]))
                    self._roots.append(
                        fp64_node(fp, int(r[j, self.width]))
                        if self._sound else fp)
        if self.audit_enabled:
            self.audit_mark()

    def note_chunk(self, s: int, q_new: np.ndarray, log_new: np.ndarray,
                   elog_new: Optional[np.ndarray], q_head: int) -> int:
        """Fold one chunk's per-shard appends in (queue rows and log
        rows are the lockstep suffixes; counts must match). Returns the
        number of device-"fresh" keys the host tier recognized as
        rediscoveries (0 while no ranges are evicted) — those keys'
        mirror entries are left untouched, so a rediscovery can never
        rewrite a parent chain into a cycle."""
        n = len(log_new)
        assert len(q_new) == n, (len(q_new), n)
        hits = 0
        if n:
            q_new = np.asarray(q_new, np.uint32)
            log_new = np.asarray(log_new, np.uint32)
            self._epoch_q[s].append(q_new)
            self._tails[s] += n
            self.log_n[s] += n
            self._inserts[s].append((log_new, q_new[:, self.width]))
            child = _combine64(log_new[:, 0], log_new[:, 1])
            parent = _combine64(log_new[:, 2], log_new[:, 3])
            self.chain_head = hashlib.sha256(
                self.chain_head.encode() + child.tobytes()).hexdigest()
            # per-prefix last-touch clock: newly inserted children mark
            # their ranges hot, and so do the parents being expanded —
            # the ranges dedup is currently hitting are the ones NOT to
            # evict
            self._clock += 1
            self._heat[np.unique(np.concatenate(
                (fp_prefix(child), fp_prefix(parent))))] = self._clock
            pairs = zip(child.tolist(), parent.tolist())
            if self.evicted_prefixes:
                # host-tier re-probe: with eviction active a device-
                # "fresh" key may be a rediscovery (its range was
                # evicted, or bucket compaction opened an earlier slot);
                # only genuinely fresh keys enter the mirror
                g = self._generated
                fresh = [(c, p) for c, p in pairs if c not in g]
                hits = n - len(fresh)
                self.host_probe_hits += hits
                self.host_tier_keys = max(0, self.host_tier_keys - hits)
                pairs = fresh
                if self._mark is not None:
                    self._mark_keys.extend(c for c, _p in fresh)
                g.update(pairs)
            else:
                if self._mark is not None:
                    pairs = list(pairs)
                    self._mark_keys.extend(c for c, _p in pairs)
                self._generated.update(pairs)
            if self._translate:
                orig = _combine64(log_new[:, 4], log_new[:, 5])
                if self.evicted_prefixes:
                    keep = {c for c, _p in pairs}
                    self._orig_of.update(
                        (c, o) for c, o in zip(child.tolist(),
                                               orig.tolist())
                        if c in keep)
                else:
                    self._orig_of.update(zip(child.tolist(),
                                             orig.tolist()))
        if elog_new is not None and len(elog_new):
            self._edges[s].append(np.asarray(elog_new, np.uint32))
            self.e_n[s] += len(elog_new)
        self._heads[s] = int(q_head)
        return hits

    # --- silent-corruption defense (AuditPolicy) ----------------------
    def audit_mark(self) -> None:
        """Pin the current fold position as the last audited boundary.
        Called after every PASSED audit (and at each epoch seed), so
        :meth:`rollback_to_mark` can undo everything a lying chip
        folded in since the last point the oracle vouched for."""
        self._mark = (list(self._heads), list(self._tails),
                      list(self.log_n), list(self.e_n),
                      [len(p) for p in self._inserts],
                      [len(p) for p in self._edges],
                      self.chain_head, self.host_probe_hits,
                      self.host_tier_keys)
        self._mark_keys = []

    def rollback_to_mark(self) -> int:
        """Undo every fold since :meth:`audit_mark`: mirror entries,
        queue appends, insert/edge records, head positions and the
        chain head all return to the audited boundary, so the replay
        re-expands the same frontier on trustworthy silicon and the
        final digest matches an uncorrupted run. Returns the number of
        mirror keys undone (0 when no mark is pinned)."""
        if self._mark is None:
            return 0
        (heads, tails, log_n, e_n, ins_len, edg_len,
         chain, probe_hits, tier_keys) = self._mark
        for k in self._mark_keys:
            self._generated.pop(k, None)
            self._orig_of.pop(k, None)
        undone = len(self._mark_keys)
        self._mark_keys = []
        for s in range(self.shards):
            rows = self._epoch_rows(s)
            self._epoch_q[s] = [rows[:tails[s]]] if tails[s] else []
            del self._inserts[s][ins_len[s]:]
            del self._edges[s][edg_len[s]:]
        self._heads = list(heads)
        self._tails = list(tails)
        self.log_n = list(log_n)
        self.e_n = list(e_n)
        self.chain_head = chain
        self.host_probe_hits = probe_hits
        self.host_tier_keys = tier_keys
        return undone

    # --- memory tiering (SpillPolicy) ---------------------------------
    @property
    def spill_active(self) -> bool:
        return bool(self.evicted_prefixes)

    def is_evicted(self, key: int) -> bool:
        return (int(key) >> (64 - SPILL_PREFIX_BITS)) \
            in self.evicted_prefixes

    def hot_keys(self) -> List[int]:
        """The device-resident hot set: every mirrored dedup key whose
        prefix range has not been evicted — what a post-fault re-seed
        (or a degradation rung) re-inserts into the device table."""
        if not self.evicted_prefixes:
            return list(self._generated.keys())
        shift = 64 - SPILL_PREFIX_BITS
        ev = self.evicted_prefixes
        return [k for k in self._generated if (k >> shift) not in ev]

    def probe_host(self, fps) -> np.ndarray:
        """Batched host-tier membership: ``mask[i]`` is True when
        ``fps[i]`` is already in the authoritative mirror (a duplicate
        the device table could no longer see)."""
        g = self._generated
        return np.fromiter((int(f) in g for f in np.asarray(fps)),
                           bool, len(fps))

    def spill_plan(self, hot_budget: int):
        """Pick the coldest not-yet-evicted prefix ranges until the
        projected device-resident key count fits ``hot_budget``.

        Returns ``(new_prefixes, hot_count, evicted_now)`` — the ranges
        to evict now (possibly empty when everything over budget is
        already evicted), the resulting hot-set size, and the number of
        mirrored keys those new ranges move to the host tier — or
        ``None`` when no plan can shrink the hot set below the budget
        (host tier exhausted in the only sense that matters: eviction
        cannot make more room)."""
        keys = np.fromiter((int(k) for k in self._generated), np.uint64,
                           len(self._generated))
        counts = np.bincount(fp_prefix(keys),
                             minlength=1 << SPILL_PREFIX_BITS)
        resident = [p for p in range(1 << SPILL_PREFIX_BITS)
                    if counts[p] and p not in self.evicted_prefixes]
        hot = int(sum(counts[p] for p in resident))
        new: List[int] = []
        evicted_now = 0
        # coldest first: oldest last-touch clock, prefix as tiebreak
        for p in sorted(resident, key=lambda p: (self._heat[p], p)):
            if hot <= hot_budget:
                break
            new.append(p)
            hot -= int(counts[p])
            evicted_now += int(counts[p])
        if hot > hot_budget:
            return None
        self.evicted_prefixes.update(new)
        self.host_tier_keys = int(
            sum(int(counts[p]) for p in self.evicted_prefixes
                if p < len(counts)))
        return new, hot, evicted_now

    def reshard(self, shards: int) -> None:
        """Re-partition for a new mesh width (the degradation ladder).

        The live pending frontier is preserved — concatenated into the
        first slot so :meth:`pending` keeps answering until the caller
        re-routes it and starts the next epoch with :meth:`seed_epoch`.
        The cumulative insert/edge records just re-bucket (the lasso
        sweep merges across shards anyway); roots and the shared
        mirror dicts are untouched."""
        live = [self._epoch_rows(s)[self._heads[s]:self._tails[s]]
                for s in range(self.shards)]
        live_rows = (np.concatenate(live) if live
                     else np.zeros((0, self.width + 3), np.uint32))
        old_inserts, old_edges = self._inserts, self._edges
        self.shards = shards
        self._inserts = [[] for _ in range(shards)]
        self._edges = [[] for _ in range(shards)]
        for s, parts in enumerate(old_inserts):
            self._inserts[s % shards].extend(parts)
        for s, parts in enumerate(old_edges):
            self._edges[s % shards].extend(parts)
        self._epoch_q = [[] for _ in range(shards)]
        self._heads = [0] * shards
        self._tails = [0] * shards
        if len(live_rows):
            self._epoch_q[0] = [live_rows]
            self._tails[0] = len(live_rows)
        self.log_n = [0] * shards
        self.e_n = [0] * shards

    # ------------------------------------------------------------------
    def _epoch_rows(self, s: int) -> np.ndarray:
        parts = self._epoch_q[s]
        if not parts:
            return np.zeros((0, self.width + 3), np.uint32)
        if len(parts) > 1:
            self._epoch_q[s] = parts = [np.concatenate(parts)]
        return parts[0]

    def pending(self):
        """The live frontier — ``(rows, ebits, fps)`` concatenated in
        shard order — from which a recovery (or an autosave checkpoint)
        re-seeds a fresh device incarnation."""
        rows_l, eb_l, fp_l = [], [], []
        for s in range(self.shards):
            allq = self._epoch_rows(s)
            live = allq[self._heads[s]:self._tails[s]]
            rows_l.append(live[:, :self.width])
            eb_l.append(live[:, self.width])
            fp_l.append(_combine64(live[:, self.width + 1],
                                   live[:, self.width + 2]))
        return (np.concatenate(rows_l) if rows_l
                else np.zeros((0, self.width), np.uint32),
                np.concatenate(eb_l) if eb_l
                else np.zeros((0,), np.uint32),
                np.concatenate(fp_l) if fp_l
                else np.zeros((0,), np.uint64))

    def root_keys(self) -> List[int]:
        """First-epoch seed dedup keys (the lasso sweep's roots)."""
        return list(self._roots)

    def insert_block(self, s: int):
        """Shard ``s``'s cumulative insert records as ``(log_rows,
        ebits)`` arrays (the lasso sweep's ``add_log_block`` inputs)."""
        parts = self._inserts[s]
        if not parts:
            return None
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))

    def edge_block(self, s: int) -> np.ndarray:
        parts = self._edges[s]
        if not parts:
            return np.zeros((0, 4), np.uint32)
        return np.concatenate(parts)


# ----------------------------------------------------------------------
# artifact integrity chain (checkpoints, autosaves, result.json)
# ----------------------------------------------------------------------
#: the previous autosave generation's suffix: `<path>` is always the
#: NEWEST loadable checkpoint (g0 — what `resume_from(path)` reads and
#: every pre-existing test pins), `<path>.g1` the one before it. A
#: corrupt or truncated `<path>` rolls back one generation on resume.
AUTOSAVE_PREV_SUFFIX = ".g1"


def payload_digest(arrays: Dict[str, np.ndarray]) -> str:
    """Deterministic sha256 over a checkpoint payload: sorted array
    names with dtype, shape and raw bytes — what the integrity chain
    signs, independent of npz compression details."""
    h = hashlib.sha256()
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def chain_integrity(payload_sha: str, chain_head: str) -> str:
    """The integrity field an artifact carries: its payload sha256
    chained to the run's chunk-digest head at write time, so a
    tampered/corrupt payload AND a payload transplanted from a
    different run history both fail verification."""
    return hashlib.sha256(
        (payload_sha + ":" + chain_head).encode()).hexdigest()


# ----------------------------------------------------------------------
# crash-safe checkpoint write (shared by save() and autosave)
# ----------------------------------------------------------------------
def atomic_savez(path, **arrays) -> None:
    """``np.savez_compressed`` into a temp file in the target directory,
    fsync, then ``os.replace`` into place — an interrupted write
    (SIGKILL, full disk, a dying host) can never leave a truncated file
    where a good checkpoint stood. The file object (not a path) keeps
    numpy from appending its own ``.npz`` suffix."""
    path = os.fspath(path)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path) or ".",
        prefix=os.path.basename(path) + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
