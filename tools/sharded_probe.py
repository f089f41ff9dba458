"""Sharded-engine measurements: plain vs sharded D=1 on the chip, and a
D sweep on the virtual CPU mesh."""
import time
import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_plain(runs=3):
    from stateright_tpu.models.twopc import TwoPhaseSys
    def mk():
        t0 = time.perf_counter()
        ck = (TwoPhaseSys(7).checker()
              .tpu_options(capacity=1 << 22, race=False)
              .spawn_tpu().join())
        return time.perf_counter() - t0, ck.unique_state_count()
    mk()
    rates = []
    for _ in range(runs):
        dt, uq = mk()
        assert uq == 296448
        rates.append(uq / dt)
    print(f"plain device 2pc7: best={max(rates):,.0f} "
          f"samples={[f'{r:,.0f}' for r in rates]}")
    return max(rates)


def run_sharded(d=1, runs=3, n=7, expect=296448):
    import jax
    from jax.sharding import Mesh
    from stateright_tpu.models.twopc import TwoPhaseSys
    devices = jax.devices()
    if len(devices) < d:
        print(f"SKIP d={d}: only {len(devices)} devices")
        return None
    mesh = Mesh(np.array(devices[:d]), ("shards",))
    def mk():
        t0 = time.perf_counter()
        ck = (TwoPhaseSys(n).checker()
              .tpu_options(mesh=mesh, capacity=1 << 22)
              .spawn_tpu().join())
        return time.perf_counter() - t0, ck.unique_state_count()
    mk()
    rates = []
    for _ in range(runs):
        dt, uq = mk()
        assert uq == expect, uq
        rates.append(uq / dt)
    print(f"sharded D={d} 2pc{n}: best={max(rates):,.0f} "
          f"samples={[f'{r:,.0f}' for r in rates]}")
    return max(rates)


if __name__ == "__main__":
    which = sys.argv[1]
    if which == "cpu":
        # the virtual 8-device CPU mesh, set BEFORE backend init
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    if which == "tpu":
        p = run_plain()
        s = run_sharded(1)
        if s:
            print(f"D=1 shard_map overhead: {100 * (1 - s / p):.1f}%")
    elif which == "cpu":
        for d in (1, 2, 4, 8):
            run_sharded(d)
