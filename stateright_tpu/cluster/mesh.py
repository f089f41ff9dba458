"""Fleet mesh: multi-host ``jax.distributed`` checking over DCN.

One host tops out at its own chips; the routing machinery never did.
``owner_of(fp, D)`` top-bit sharding, ``HostShadow.reshard``, and the
shard-agnostic checkpoint format are all mesh-WIDTH-agnostic, so the
gap between "8 devices" and "a fleet" is exactly the multi-pod
decomposition every distributed training stack uses:

* every participating process calls :func:`init_process` (or
  :func:`init_from_env`, the launcher contract) — a
  ``jax.distributed.initialize`` bootstrap that also forces the
  virtual-CPU backend for dry runs (``gloo`` cross-process collectives,
  per-process ``jax_num_cpu_devices`` device forcing,
  exactly like ``__graft_entry__.dryrun_multichip``);
* :func:`fleet_mesh` builds the host×device ``Mesh`` over the GLOBAL
  device list in host-major order, trimmed so every host contributes
  the same power-of-two device count and the host count is a power of
  two — host-major order is what makes mesh halving host-aligned, so
  the degradation ladder's new top rung can drop a whole HOST and the
  ``owner_of(fp, D/2)`` re-route stays the chip rung's exact math;
* the sharded chunk program (``parallel/sharded.py``) runs under
  ``shard_map`` across the global axis unchanged — the bucketed
  ``all_to_all`` exchange simply spans DCN between hosts instead of
  ICI between chips;
* :func:`pull_global` is the one new primitive the host loop needs:
  ``jax.device_get`` of a process-spanning sharded array raises, so
  every host pull replicates through a jitted identity (an all-gather
  over DCN) first. It is a COLLECTIVE — every process must execute the
  same pulls in the same order, which the engine's host loop
  guarantees by deciding everything from the replicated stats vector.

Multi-controller discipline: every process runs the same host loop and
must take the same dispatch/growth/retry decisions. Everything the
loop branches on is replicated (the stats vector, psum-reduced flags),
so the only per-rank asymmetry allowed is host-side artifact OWNERSHIP
(rank 0 writes the canonical result/trace; other ranks write rank-local
paths or nothing) — never device work.
"""

from __future__ import annotations

import os
import time
from typing import List, NamedTuple, Optional

import numpy as np

#: launcher <-> worker environment contract (tools/mesh_launch.py)
ENV_COORDINATOR = "STPU_COORDINATOR"
ENV_NUM_PROCS = "STPU_NUM_PROCS"
ENV_RANK = "STPU_RANK"
ENV_LOCAL_DEVICES = "STPU_LOCAL_DEVICES"
ENV_CPU = "STPU_CPU"


class FleetContext(NamedTuple):
    """What one bootstrapped process knows about the fleet."""

    rank: int
    num_processes: int
    coordinator: Optional[str]
    local_devices: int

    @property
    def is_coordinator(self) -> bool:
        return self.rank == 0


def force_cpu_devices(n: int) -> None:
    """Pin this process to ``n`` virtual CPU devices, BEFORE backend
    init. ``jax_num_cpu_devices`` takes precedence over an inherited
    ``XLA_FLAGS`` device count (the test suite exports 8), so every
    launched worker sees its own count, not its parent's."""
    import jax
    jax.config.update("jax_num_cpu_devices", int(n))


def init_process(coordinator: Optional[str] = None,
                 num_processes: int = 1, process_id: int = 0, *,
                 cpu: bool = False,
                 local_devices: Optional[int] = None) -> FleetContext:
    """Bootstrap ONE process of the fleet.

    With ``cpu=True`` (the dry-run/test path) the backend is forced to
    the virtual CPU mesh with ``local_devices`` devices and the
    ``gloo`` cross-process collective implementation, all before any
    backend initialization. ``num_processes > 1`` then runs
    ``jax.distributed.initialize`` against the coordinator — rank 0
    hosts the coordination service, so it must be launched (not
    necessarily finished initializing) before the others time out.
    """
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if local_devices:
            force_cpu_devices(local_devices)
    import jax
    if cpu:
        jax.config.update("jax_platforms", "cpu")
        try:
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass  # single-process CPU runs need no collectives impl
    if num_processes > 1:
        if not coordinator:
            raise ValueError(
                "multi-process init needs a coordinator address "
                "(host:port)")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_processes),
            process_id=int(process_id))
    return FleetContext(int(process_id), int(num_processes),
                        coordinator,
                        int(local_devices or 0)
                        or len(jax.local_devices()))


def init_from_env() -> Optional[FleetContext]:
    """The worker half of the launcher contract: bootstrap from the
    ``STPU_*`` environment (None when not launched by the launcher)."""
    rank = os.environ.get(ENV_RANK)
    if rank is None:
        return None
    return init_process(
        coordinator=os.environ.get(ENV_COORDINATOR),
        num_processes=int(os.environ.get(ENV_NUM_PROCS, "1")),
        process_id=int(rank),
        cpu=os.environ.get(ENV_CPU, "1") == "1",
        local_devices=int(os.environ.get(ENV_LOCAL_DEVICES, "0")) or None)


# ----------------------------------------------------------------------
# host identity
# ----------------------------------------------------------------------
def process_identity() -> tuple:
    """``(rank, host)`` of THIS process — the fields every trace
    stream's correlation header carries (``obs/trace.py``), so a rank's
    artifact names its own position in the fleet.

    Rank resolution order: the launcher's ``STPU_RANK`` env contract
    (set before any backend exists, so it is authoritative for
    launcher-spawned workers), else ``jax.process_index()`` — but ONLY
    when a backend is already live: a trace header must never be the
    thing that initializes JAX (host engines run backend-free). Host is
    the OS hostname (ranks of a real pod land on distinct machines; CPU
    dry-run ranks share one, which is why the rank rides alongside)."""
    import socket
    import sys
    host = socket.gethostname()
    rank = os.environ.get(ENV_RANK)
    if rank is not None:
        return int(rank), host
    try:
        jaxmod = sys.modules.get("jax")
        if jaxmod is not None:
            from jax._src import xla_bridge
            if getattr(xla_bridge, "_backends", None):
                return int(jaxmod.process_index()), host
    except Exception:
        pass
    return 0, host


def device_host(device, host_map=None):
    """The host label of a device: the injected ``host_map`` (a
    ``{device_id: label}`` dict — the simulated-fleet knob
    ``tpu_options(host_map=...)`` and the service's simulated pools
    use) wins; real devices fall back to their ``process_index``."""
    if host_map is not None:
        did = getattr(device, "id", device)
        try:
            return host_map[did]
        except (KeyError, IndexError, TypeError):
            pass
    return getattr(device, "process_index", 0)


def mesh_hosts(mesh, host_map=None) -> list:
    """Per-position host labels of a mesh's device list."""
    return [device_host(d, host_map) for d in mesh.devices.flat]


def mesh_spans_processes(mesh) -> bool:
    """True when the mesh holds devices this process cannot address
    (every host pull must then replicate first — :func:`pull_global`)."""
    return len({getattr(d, "process_index", 0)
                for d in mesh.devices.flat}) > 1


def _pow2_floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1) if n else 0


def fleet_mesh(axis: str = "shards", devices=None, host_map=None):
    """The host×device mesh over the GLOBAL device list.

    Devices are ordered host-major (all of host 0, then host 1, ...)
    and trimmed so every host contributes the same power-of-two count
    and the host count is a power of two — the order that makes any
    naturally-aligned power-of-two sub-block either nest inside one
    host or span whole hosts, which both the degradation ladder's host
    rung and the service's two-level :class:`~stateright_tpu.service.
    scheduler.DevicePool` lean on."""
    import jax
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    if not devices:
        raise ValueError("fleet_mesh needs at least one device")
    order: List = []
    groups: dict = {}
    for d in devices:
        h = device_host(d, host_map)
        if h not in groups:
            groups[h] = []
            order.append(h)
        groups[h].append(d)
    per_host = min(_pow2_floor(len(g)) for g in groups.values())
    n_hosts = _pow2_floor(len(order))
    picked = [d for h in order[:n_hosts] for d in groups[h][:per_host]]
    return Mesh(np.asarray(picked), (axis,))


def host_major(devices, host_map=None) -> list:
    """Reorder a device list host-major (stable: hosts keep their
    first-appearance order, devices keep their order within a host).
    ``promote_step`` (parallel/engine.py) runs the widened mesh
    through this so a mid-run host join lands host-aligned — the
    degradation ladder's host rung can then drop a later-failing host
    as a contiguous block, exactly as if the fleet had started wide."""
    devices = list(devices)
    order: List = []
    groups: dict = {}
    for d in devices:
        h = device_host(d, host_map)
        if h not in groups:
            groups[h] = []
            order.append(h)
        groups[h].append(d)
    return [d for h in order for d in groups[h]]


# ----------------------------------------------------------------------
# process-spanning host pulls
# ----------------------------------------------------------------------
def pull_global(arrays, mesh):
    """``jax.device_get`` that survives process-spanning meshes.

    A sharded global array has non-addressable shards on every other
    host; fetching it raises. The fix is one jitted identity with a
    replicated out-sharding — an all-gather over DCN — after which the
    value is host-local everywhere. On a single-process mesh this is a
    plain ``device_get`` (no extra dispatch, no behavior change).

    COLLECTIVE: on a multi-process mesh every process must execute the
    same ``pull_global`` calls in the same order (the engines guarantee
    this by deriving all control flow from replicated stats).
    """
    import jax

    if not mesh_spans_processes(mesh):
        return jax.device_get(arrays)
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    flat, tree = jax.tree_util.tree_flatten(arrays)
    pulled = jax.jit(lambda *xs: tuple(xs),
                     out_shardings=(rep,) * len(flat))(*flat)
    return jax.tree_util.tree_unflatten(
        tree, [np.asarray(x) for x in pulled])


def dcn_probe(mesh, axis: str) -> float:
    """One warm cross-host round trip: the wall seconds of a replicated
    psum over the global mesh (compiled and warmed first, then timed) —
    the latency floor every fingerprint exchange pays once it spans
    DCN. Rides the ``dcn_exchange_s`` metric / ``mesh_init`` event."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    d = mesh.shape[axis]

    def local(x):
        return lax.psum(jnp.sum(x), axis)

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                               out_specs=P(), check_vma=False))
    x = jax.device_put(np.ones((d,), np.float32),
                       NamedSharding(mesh, P(axis)))
    np.asarray(fn(x))  # compile + warm
    t0 = time.perf_counter()
    np.asarray(fn(x))
    return time.perf_counter() - t0
