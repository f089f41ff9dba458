"""AOT compiles of the device engine's chunk programs for a described
v5e chip (on-chip-measurement guide, section 2.3): what the TPU
compiler refuses shows here, on the CPU, before any chip time is spent.

Each test drives the NORMAL entry (``spawn_tpu()``) on the CPU far
enough to learn the exact program the engine would build — its build
arguments and the chunk's argument shapes — then rebuilds that program
for the described chip and compiles it. Nothing runs on a device.

The topology is described inside a module fixture: only one process may
load the TPU library, and the driver runs the suite under several xdist
workers, so nothing here may touch it at import or collection time.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from stateright_tpu.checker import device_loop
from stateright_tpu.examples.paxos_packed import PackedPaxos
from stateright_tpu.models.twopc import TwoPhaseSys
from stateright_tpu.parallel import sharded


class _Captured(Exception):
    """Raised by the spy at the first chunk dispatch: the engine's
    build arguments and the chunk's call arguments are recorded."""


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spy(monkeypatch, module, builder_name):
    """Swap ``module.<builder_name>`` (the un-memoized program builder)
    for one that records its arguments and returns a callable raising
    :class:`_Captured` with the chunk's call arguments. The memo caches
    are swapped too, so no spy outlives the test. ``seen["real"]`` keeps
    the real builder."""
    seen = {"real": getattr(module, builder_name)}

    def fake_build(*args):
        seen["build"] = args

        def call(*call_args):
            seen["call"] = call_args
            raise _Captured()
        return call

    monkeypatch.setattr(module, builder_name, fake_build)
    monkeypatch.setattr(device_loop, "_CHUNK_CACHE", device_loop.LruCache())
    monkeypatch.setattr(sharded, "_SHARDED_CACHE", device_loop.LruCache())
    return seen


def _capture(builder, seen):
    with pytest.raises(Exception) as info:
        builder.spawn_tpu().join()
    exc = info.value
    while exc is not None and not isinstance(exc, _Captured):
        exc = exc.__cause__ or exc.__context__
    assert exc is not None, \
        f"engine failed before its first chunk: {info.value!r}"
    return seen["build"], seen["call"]


def _shape(x, sharding):
    x = np.asarray(x) if not hasattr(x, "shape") else x
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@pytest.mark.parametrize("name,model,capacity", [
    ("paxos3", lambda: PackedPaxos(3), 1 << 21),
    ("2pc7", lambda: TwoPhaseSys(7), 1 << 22),
])
def test_single_chip_chunk_compiles_for_v5e(topo, no_persistent_cache,
                                           monkeypatch, name, model,
                                           capacity):
    seen = _spy(monkeypatch, device_loop, "_build_chunk_fn")
    build_args, call_args = _capture(
        model().checker().tpu_options(capacity=capacity, race=False), seen)
    assert build_args[2] == capacity
    fn = device_loop.build_chunk_core(*build_args)
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(lambda x: _shape(x, one_chip), call_args)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    # the carry (queue, table, log) must fit one chip's 16 GB of HBM
    assert mem.argument_size_in_bytes < 16 << 30
    assert mem.argument_size_in_bytes > capacity * 8  # the table halves


def test_sharded_chunk_compiles_for_v5e_2x2(topo, no_persistent_cache,
                                            monkeypatch):
    seen = _spy(monkeypatch, sharded, "_build_sharded_chunk_fn")
    cpu_mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    build_args, call_args = _capture(
        TwoPhaseSys(7).checker().tpu_options(
            mesh=cpu_mesh, capacity=1 << 22, degrade=False, retries=0),
        seen)
    model, mesh, axis, *rest = build_args
    assert mesh.shape[axis] == 4
    tpu_mesh = Mesh(np.array(topo.devices[:4]), (axis,))
    fn = seen["real"](model, tpu_mesh, axis, *rest)
    carry, *scalars = call_args
    specs = sharded.carry_specs(axis)
    carry_shapes = jax.tree.map(
        lambda x, s: _shape(x, NamedSharding(tpu_mesh, s)), carry, specs)
    rep = NamedSharding(tpu_mesh, jax.sharding.PartitionSpec())
    scalar_shapes = [_shape(x, rep) for x in scalars]
    compiled = fn.lower(carry_shapes, *scalar_shapes).compile()
    text = compiled.as_text()
    # fingerprint-prefix routing is a real cross-chip exchange
    assert "all-to-all" in text or "collective-permute" in text
