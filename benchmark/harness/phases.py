"""Device idle by program phase, from the program's own spans.

The program annotates every host phase of its engines as
``sr.<phase>`` (``stateright_tpu/obs/spans.py``); a ``jax.profiler``
trace holds them on the host plane, on the device ops' clock. Here
each piece of the fullest device's idle time goes to a name, in this
order:

* the innermost open ``sr.*`` span other than ``sr.race.host``;
* else ``sr.race.host`` (the host racer, which runs beside the loop);
* else the innermost ``bench.*`` annotation;
* else ``none``.

``unattributed_ns`` is the idle time under no ``sr.*`` span. A trace
with no ``sr.*`` events attributes exactly as ``tracing.reduce`` does.
Checked on recorded v5e traces (``tests/test_phases.py``).
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Tuple

from . import tracing

PREFIX = "sr."
RACER = "sr.race.host"


def collect_spans(trace_dir: str) -> List[List]:
    """The ``sr.*`` host events of a trace as ``[name, start_ns,
    dur_ns]``."""
    from jax.profiler import ProfileData
    out: List[List] = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        out.append([ev.name, int(ev.start_ns),
                                    int(ev.duration_ns)])
    return out


def idle_gaps(trace: Dict[str, Any], fullest: str) -> List[Tuple[int, int]]:
    """The fullest device's idle intervals inside the traced window."""
    lo, hi = [(s, s + d) for n, s, d in trace["host"]
              if n == tracing.WINDOW][0]
    ops = [(s, s + d) for _n, s, d, _c in trace["devices"][fullest]]
    return tracing._gaps(tracing.union(tracing._clip(ops, lo, hi)), lo, hi)


def attribute(gaps: List[Tuple[int, int]], host: List[List],
              spans: List[List]) -> Dict[str, int]:
    """Idle ns per name over ``gaps``, in the order the module states.
    One sweep over every boundary, so long traces stay linear."""
    # (start, end, name, rank): rank 0 sr, 1 the racer, 2 bench; an
    # empty interval covers no idle time
    ivs = [(s, s + d, n, 1 if n == RACER else 0) for n, s, d in spans
           if d > 0]
    ivs += [(s, s + d, n, 2) for n, s, d in host if d > 0]
    starts: Dict[int, List[int]] = {}
    ends: Dict[int, List[int]] = {}
    for i, (s, e, _n, _r) in enumerate(ivs):
        starts.setdefault(s, []).append(i)
        ends.setdefault(e, []).append(i)
    cuts = sorted(set(starts) | set(ends)
                  | {t for ab in gaps for t in ab})
    out: Dict[str, int] = {}
    active: set = set()
    gi = 0
    for x, y in zip(cuts, cuts[1:]):
        active.difference_update(ends.get(x, ()))
        active.update(starts.get(x, ()))
        while gi < len(gaps) and gaps[gi][1] <= x:
            gi += 1
        if gi == len(gaps) or gaps[gi][0] > x:
            continue  # the device is busy over [x, y)
        name = "none"
        for rank in (0, 1, 2):
            best = [i for i in active if ivs[i][3] == rank]
            if best:
                # the innermost: the latest start (ties: the later one)
                name = ivs[max(best, key=lambda i: (ivs[i][0], i))][2]
                break
        out[name] = out.get(name, 0) + (y - x)
    return out


def breakdown(trace: Dict[str, Any], red: Dict[str, Any],
              spans: List[List]) -> Dict[str, Any]:
    """Idle by phase for a trace ``tracing.collect`` read and
    ``tracing.reduce`` reduced: every name's idle ns (largest first),
    the idle total and ``unattributed_ns``."""
    by_name = attribute(idle_gaps(trace, red["fullest"]), trace["host"],
                        spans)
    return {
        "idle_ns": sum(by_name.values()),
        "unattributed_ns": sum(v for k, v in by_name.items()
                               if not k.startswith(PREFIX)),
        "idle_gaps": sorted(by_name.items(), key=lambda kv: -kv[1]),
    }
