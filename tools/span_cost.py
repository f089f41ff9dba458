#!/usr/bin/env python3
"""What a phase span costs the host with no profiler session running.

    python tools/span_cost.py [--n 200000]

Times, best of five loops of ``--n`` each, an empty call, one
``jax.profiler.TraceAnnotation`` entered and left, ``obs.annotate``
(the same behind the import check), and a whole
``SpanRecorder.span()`` with its metrics timer. Prints one JSON line
of microseconds per call, with the host's CPU and JAX version.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _best_us(fn, n: int) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    n = ap.parse_args(argv).n

    import jax

    from stateright_tpu.obs import Metrics, SpanRecorder, annotate

    rec = SpanRecorder(None, metrics=Metrics())

    def empty():
        pass

    def trace_annotation():
        with jax.profiler.TraceAnnotation("sr.dispatch"):
            pass

    def obs_annotate():
        with annotate("dispatch"):
            pass

    def span():
        with rec.span("dispatch"):
            pass

    cpu = platform.processor() or platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            cpu = next(ln.split(":", 1)[1].strip() for ln in f
                       if ln.startswith("model name"))
    except (OSError, StopIteration):
        pass
    print(json.dumps({
        "cpu": cpu, "cores": os.cpu_count(), "jax": jax.__version__,
        "n": n, "us": {name: _best_us(fn, n) for name, fn in (
            ("empty", empty), ("trace_annotation", trace_annotation),
            ("annotate", obs_annotate), ("span", span))}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
