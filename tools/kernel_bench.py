"""Microbenchmark: the staged expand→hash→dedup→probe pipeline, per stage.

Usage:
    python tools/kernel_bench.py [--model 2pc7|2pc4|paxos3] [--fmax N]
                                 [--iters N] [--capacity 2^k] [--out F]

Times ONE device iteration's dedup pipeline on a synthetic frontier
drawn from the model's real reachable states (BFS prefix), per stage —
``expand`` (``ops.expand.expand_frontier``, child fingerprints
deferred), ``hash`` (``fp64_device`` over the compacted raw-valid
lanes), ``pre_dedup`` (scatter-min claim arena), ``probe``
(``ops.hashtable.table_insert``) — each stage jitted standalone so the
per-stage costs are visible, plus the composed pipeline in one jit
(what the engines run).

JSON fields (one line on stdout):
  ``stages.expand_ms/hash_ms/pre_dedup_ms/probe_ms`` — the stages;
  ``staged_ms`` — the composed pipeline;
  ``dup_lane_frac`` — the workload's duplicate-lane fraction (the
  share of lanes the in-batch dedup kills).

The line names its ``backend``: a time from a non-TPU backend is not a
TPU result.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _median(xs):
    s = sorted(xs)
    return s[len(s) // 2]


def _make_model(name: str):
    if name.startswith("2pc"):
        from stateright_tpu.models.twopc import TwoPhaseSys
        return TwoPhaseSys(int(name[3:]))
    if name.startswith("paxos"):
        from stateright_tpu.examples.paxos_packed import PackedPaxos
        return PackedPaxos(int(name[5:]))
    raise SystemExit(f"unknown --model {name!r} (want 2pcN or paxosN)")


def _frontier(model, fmax: int):
    """A real frontier slab: BFS from the inits until fmax rows exist
    (duplicate structure matters — a random frontier would understate
    the dedup stages)."""
    import numpy as np

    seen = set()
    rows = []
    queue = [s for s in model.init_states() if model.within_boundary(s)]
    while queue and len(rows) < fmax:
        state = queue.pop(0)
        fp = model.fingerprint(state)
        if fp in seen:
            continue
        seen.add(fp)
        rows.append(np.asarray(model.encode(state), np.uint32))
        for _a, nxt in model.next_steps(state):
            queue.append(nxt)
    while len(rows) < fmax:  # tiny models: tile the reached set
        rows.append(rows[len(rows) % max(len(seen), 1)])
    return np.stack(rows[:fmax])


def _timed(fn, args, iters: int):
    import jax

    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e3)
    return round(_median(samples), 3)


def main(argv) -> int:
    args = {"--model": "2pc4", "--fmax": "256", "--iters": "5",
            "--capacity": "16", "--out": None}
    it = iter(argv)
    for a in it:
        if a in ("-h", "--help"):
            print(__doc__)
            return 0
        if a not in args:
            raise SystemExit(f"unknown flag {a!r}")
        args[a] = next(it)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from stateright_tpu.checker.device_loop import shrink_indices
    from stateright_tpu.ops.expand import (eventually_indices,
                                           expand_frontier, pre_dedup)
    from stateright_tpu.ops.hash_kernel import fp64_device
    from stateright_tpu.ops.hashtable import _BUCKET, table_insert

    model = _make_model(args["--model"])
    fmax = int(args["--fmax"])
    iters = int(args["--iters"])
    capacity = 1 << int(args["--capacity"])
    backend = jax.default_backend()

    width = model.packed_width
    n_actions = model.max_actions
    fa = fmax * n_actions
    ev_idx = eventually_indices(model.properties())

    frontier = jnp.asarray(_frontier(model, fmax))
    ebits = jnp.zeros((fmax,), jnp.uint32)
    fvalid = jnp.ones((fmax,), bool)
    khi0 = jnp.zeros((capacity // _BUCKET, _BUCKET), jnp.uint32)
    klo0 = jnp.zeros((capacity // _BUCKET, _BUCKET), jnp.uint32)

    # --- staged stages, each standalone ------------------------------
    def stage_expand(rows):
        exp = expand_frontier(model, rows, fvalid, ebits, ev_idx,
                              child_fp=False)
        return exp.flat, exp.cvalid, exp.ebits

    def stage_hash(flat, cvalid):
        src = shrink_indices(cvalid, fa)
        rows_k = flat[src]
        return fp64_device(rows_k)

    def stage_dedup(chi, clo, cvalid):
        return pre_dedup(chi, clo, cvalid)

    def stage_probe(khi, klo, chi, clo, dvalid):
        return table_insert(khi, klo, chi, clo, dvalid)

    def staged_all(rows, khi, klo):
        flat, cvalid, _eb = stage_expand(rows)
        chi, clo = stage_hash(flat, cvalid)
        dvalid = stage_dedup(chi, clo, cvalid)
        return stage_probe(khi, klo, chi, clo, dvalid)

    j_expand = jax.jit(stage_expand)
    flat, cvalid, _ = j_expand(frontier)
    j_hash = jax.jit(stage_hash)
    chi, clo = j_hash(flat, cvalid)
    j_dedup = jax.jit(stage_dedup)
    dvalid = j_dedup(chi, clo, cvalid)
    j_probe = jax.jit(stage_probe)
    j_staged = jax.jit(staged_all)

    stages = {
        "expand_ms": _timed(j_expand, (frontier,), iters),
        "hash_ms": _timed(j_hash, (flat, cvalid), iters),
        "pre_dedup_ms": _timed(j_dedup, (chi, clo, cvalid), iters),
        "probe_ms": _timed(j_probe, (khi0, klo0, chi, clo, dvalid),
                           iters),
    }
    staged_ms = _timed(j_staged, (frontier, khi0, klo0), iters)

    n_valid = int(np.asarray(cvalid).sum())
    n_dedup = int(np.asarray(dvalid).sum())
    line = {
        "model": args["--model"], "backend": backend, "fmax": fmax,
        "lanes": fa, "valid_lanes": n_valid,
        "dup_lane_frac": round(1.0 - n_dedup / max(n_valid, 1), 4),
        "stages": stages,
        "staged_ms": staged_ms,
    }
    out = json.dumps(line)
    print(out)
    if args["--out"]:
        with open(args["--out"], "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
