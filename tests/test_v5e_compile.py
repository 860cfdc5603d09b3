"""Window programs compiled for a described TPU v5e, without the chip.

The TPU's compiler is installed beside JAX and compiles for a chip that is
described and not attached. What it decides that a CPU run cannot show is
kept here, all in this one file (the topology is described inside a
fixture, so only the worker given this file loads the TPU's library):

* the value plane of every program that hands one to the host comes back
  in the host's row order (``DeviceKVTable._host_rows``). Left 3-D, the
  chip lays ``u32[G, S, VW4]`` out with the shard on the lanes, major to
  minor ``(0, 2, 1)``, and the host's copy of it was a transpose.

Compiled at the north-star deployment's widths (4096 shards, 256 records a
shard, a window of 64 waves), one chip.
"""

from __future__ import annotations

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rabia_tpu.apps.device_kv import DeviceKVTable, DeviceWindowOps
from rabia_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS, MeshPhaseKernel

S, RECORDS, W, R = 4096, 256, 64, 5
PROGRAMS = ("mixed", "mixnet", "get", "read")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _table(topo, loss: float) -> DeviceKVTable:
    """A table on one described chip. Nothing can be placed on a chip that
    is not attached, so the table's planes are shapes with their
    shardings, which is all a compile reads."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (SHARD_AXIS, REPLICA_AXIS))
    kernel = MeshPhaseKernel(S, R, mesh, seed=3, net_loss=loss)

    def shape_of(a, sharding):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding)

    with mock.patch.object(jax, "device_put", shape_of):
        return DeviceKVTable(S, kernel, per_shard_capacity=RECORDS)


def _lowered(tab: DeviceKVTable, program: str):
    mesh = tab.kernel.mesh

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    wave = P(None, SHARD_AXIS)
    alive = arg((S, R), jnp.bool_, P(SHARD_AXIS, REPLICA_AXIS))
    base = arg((S,), jnp.int32, P(SHARD_AXIS))
    klen = arg((W, S), jnp.int16, wave)
    kwin = arg((W, S, tab.K4), jnp.uint32, wave)
    depth = np.int32(W)
    if program == "read":
        return tab._build_lookup_only(tab.K4).lower(tab.state, klen, kwin, W=W)
    if program == "get":
        fn = tab._build_lookup(tab.K4)
        return fn.lower(tab.state, alive, base, depth, klen, kwin, W=W, max_phases=4)
    ops = DeviceWindowOps(
        klen, arg((W, S), jnp.int16, wave), kwin, arg((W, S, tab.VW4), jnp.uint32, wave)
    )
    rest = (depth, arg((W, S), jnp.int8, wave), np.arange(W, dtype=np.int32), ops)
    if program == "mixnet":
        fn = tab._build_mixed(tab.K4, tab.VW4, W, net=True)
        return fn.lower(tab.state, alive, base, *rest, W=W)
    fn = tab._build_mixed(tab.K4, tab.VW4, W)
    return fn.lower(tab.state, alive, base, *rest, W=W, max_phases=4)


@pytest.mark.parametrize("program", PROGRAMS)
def test_value_plane_comes_back_in_the_hosts_row_order(topo, program):
    tab = _table(topo, 0.05 if program == "mixnet" else 0.0)
    compiled = _lowered(tab, program).compile()
    plane = compiled.output_formats[-1]
    assert compiled.out_info[-1].shape == (W, S * tab.VW4)
    assert plane.layout.major_to_minor == (0, 1)
