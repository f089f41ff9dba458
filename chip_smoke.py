"""Prove the device checker runs on the chip: ``python chip_smoke.py``.

One process drives the normal entry (``model.checker().spawn_tpu()``)
and checks what comes out against the host BFS oracle and the counts
the reference pins. It never goes on on the CPU: with no TPU it exits
non-zero before any check runs.

Default run (one chip):

* device check — platform, kind, count, versions, compile-cache dir;
* oracle parity — ``TwoPhaseSys(5)`` (8,832 unique, reference
  ``examples/2pc.rs:133``) and ``PackedPaxos(2)`` (16,668,
  ``examples/paxos.rs:291``) against ``spawn_bfs()`` with one thread:
  unique count, fingerprint set, discovery names;
* main path at full width — ``PackedPaxos(3)`` capped at 500,000 with
  capacity 2^21 (the BASELINE.json north star), properties asserted;
  ``TwoPhaseSys(7)`` full at capacity 2^22 (296,448 unique), compared
  with the host BFS too.

``--chips 4`` runs only the mesh phase: ``TwoPhaseSys(7)`` and
``PackedPaxos(2)`` sharded over four chips (``degrade=False``,
``retries=0``) against the same models on one chip, with every shard
owning states.

Every run fails on a non-zero ``failovers``/``retries``/``degrades``/
``spills`` or ``chunks == 0`` (the host racer answered). Speeds printed
here are informational, not metrics. The
last line of stdout is the JSON verdict; everything else comes first.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

#: unique-state oracles pinned by the reference (and, for 2pc7, by the
#: earlier chip rounds)
UNIQUE = {"2pc5": 8_832, "paxos2": 16_668, "2pc7": 296_448}

#: counters that must stay zero: each one means the run did not take the
#: device path it reports
MUST_BE_ZERO = ("failovers", "retries", "degrades", "spills")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(**row) -> None:
    print(json.dumps(row, default=str), flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self):
        return (self.compile_s, self.hits, self.misses)


def device_check(chips: int):
    import importlib.metadata as md

    import jax
    import jaxlib

    devices = jax.devices()
    d0 = devices[0]
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        versions["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        versions["libtpu"] = None
    say(phase="device", platform=d0.platform, kind=d0.device_kind,
        count=len(devices), **versions)
    check(d0.platform == "tpu",
          f"no TPU found: JAX reports platform {d0.platform!r} "
          f"({len(devices)} device(s)); this smoke never runs on the CPU")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found "
          f"{len(devices)}")
    from stateright_tpu.checker.tpu import _enable_compile_cache
    _enable_compile_cache()
    say(phase="compile-cache", dir=jax.config.jax_compilation_cache_dir)
    return devices


def run_device(label: str, builder, clock: CompileClock):
    """One ``spawn_tpu()`` run through the normal entry, its numbers
    printed on one line; fails on any counter that means the device
    path did not carry the run."""
    import jax

    c0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    ck = builder.spawn_tpu().join()
    wall = time.perf_counter() - t0
    c1, h1, m1 = clock.snapshot()
    check(ck.error() is None, f"{label}: engine error {ck.error()!r}")
    prof = ck.profile()
    unique = ck.unique_state_count()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    say(phase=label, wall_s=wall, compiles=int(prof.get("compiles", 0)),
        compile_s=c1 - c0, cache_hits=h1 - h0, cache_misses=m1 - m0,
        unique=unique, uniq_per_s=unique / wall if wall else None,
        chunks=int(prof.get("chunks", 0)),
        engine=prof.get("engine", "device"),
        mesh_shards=prof.get("mesh_shards"),
        peak_bytes_in_use=peaks[0] if len(peaks) == 1 else peaks)
    for key in MUST_BE_ZERO:
        check(not prof.get(key), f"{label}: {key}={prof.get(key)}")
    check(int(prof.get("chunks", 0)) > 0,
          f"{label}: chunks == 0 — the host racer answered, the device "
          "never ran a chunk")
    return ck, prof


def host_oracle(label: str, model):
    t0 = time.perf_counter()
    host = model.checker().threads(1).spawn_bfs().join()
    say(phase=f"{label}-host", wall_s=time.perf_counter() - t0,
        unique=host.unique_state_count())
    return host


def same_result(label: str, got, want) -> None:
    check(got.unique_state_count() == want.unique_state_count(),
          f"{label}: unique {got.unique_state_count()} != "
          f"{want.unique_state_count()}")
    check(got.generated_fingerprints() == want.generated_fingerprints(),
          f"{label}: fingerprint sets differ")
    check(set(got.discoveries()) == set(want.discoveries()),
          f"{label}: discoveries {sorted(got.discoveries())} != "
          f"{sorted(want.discoveries())}")


def phase_parity(clock) -> None:
    from stateright_tpu.examples.paxos_packed import PackedPaxos
    from stateright_tpu.models.twopc import TwoPhaseSys

    # race=False: the host racer would answer models this small before
    # the device compiles, and then nothing on the chip was compared
    for label, mk in (("2pc5", lambda: TwoPhaseSys(5)),
                      ("paxos2", lambda: PackedPaxos(2))):
        dev, _ = run_device(label, mk().checker().tpu_options(race=False),
                            clock)
        check(dev.unique_state_count() == UNIQUE[label],
              f"{label}: unique {dev.unique_state_count()} != "
              f"{UNIQUE[label]} (reference)")
        same_result(label, dev, host_oracle(label, mk()))


def phase_main(clock) -> None:
    from stateright_tpu.examples.paxos_packed import PackedPaxos
    from stateright_tpu.models.twopc import TwoPhaseSys

    # the north-star workload, as bench.py runs it
    ck, _ = run_device(
        "paxos3-capped",
        PackedPaxos(3).checker().tpu_options(capacity=1 << 21)
        .target_state_count(500_000), clock)
    check(ck.state_count() >= 500_000,
          f"paxos3: stopped at {ck.state_count()} generated states")
    ck.assert_properties()
    check(ck.discovery("value chosen") is not None,
          "paxos3: 'value chosen' was not discovered")

    ck, _ = run_device(
        "2pc7-full", TwoPhaseSys(7).checker().tpu_options(capacity=1 << 22),
        clock)
    check(ck.unique_state_count() == UNIQUE["2pc7"],
          f"2pc7: unique {ck.unique_state_count()} != {UNIQUE['2pc7']}")
    same_result("2pc7", ck, host_oracle("2pc7", TwoPhaseSys(7)))


def phase_mesh(clock, devices) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from stateright_tpu.examples.paxos_packed import PackedPaxos
    from stateright_tpu.models.twopc import TwoPhaseSys
    from stateright_tpu.parallel.sharded import owner_of

    mesh = Mesh(np.array(devices[:4]), ("shards",))
    for label, mk, cap in (("2pc7", lambda: TwoPhaseSys(7), 1 << 22),
                           ("paxos2", lambda: PackedPaxos(2), 1 << 20)):
        one, _ = run_device(
            f"{label}-1chip",
            mk().checker().tpu_options(capacity=cap, race=False), clock)
        four, prof = run_device(
            f"{label}-mesh4",
            mk().checker().tpu_options(mesh=mesh, capacity=cap,
                                       degrade=False, retries=0), clock)
        check(one.unique_state_count() == UNIQUE[label],
              f"{label}: unique {one.unique_state_count()} != "
              f"{UNIQUE[label]}")
        same_result(f"{label}-mesh4", four, one)
        check(int(prof.get("mesh_shards", 0)) == 4,
              f"{label}: mesh_shards={prof.get('mesh_shards')}")
        share = np.bincount([owner_of(fp, 4)
                             for fp in four.generated_fingerprints()],
                            minlength=4)
        say(phase=f"{label}-shares", owner_share=share.tolist(),
            shard_balance=prof.get("shard_balance"))
        # shard_balance is min/max of the per-device inserted counts the
        # chips themselves report: zero means a shard's device held none
        check(share.min() > 0 and (prof.get("shard_balance") or 0) > 0,
              f"{label}: a shard owns no states ({share.tolist()}, "
              f"shard_balance={prof.get('shard_balance')})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh phase")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        devices = device_check(args.chips)
        clock = CompileClock()
        if args.chips == 4:
            phase_mesh(clock, devices)
        else:
            phase_parity(clock)
            phase_main(clock)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    d0 = devices[0]
    say(phase="total", wall_s=time.perf_counter() - t0,
        compile_s=clock.compile_s, cache_hits=clock.hits,
        cache_misses=clock.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
