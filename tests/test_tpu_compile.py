"""Compile the served path for a described TPU v5e, without the chip.

The TPU compiler ships with JAX and compiles for a topology that is
described, not attached: it refuses a program that does not fit the chip's
memory, and its HLO shows the collectives the four-chip step uses.  The
topology is described inside a fixture, so only the worker that runs this
file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (ChainConfig, ChainDist, ChainSim, ClusterConfig, Msg,
                        make_loadgen)

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back here; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_openloop_scan_fits_one_v5e(topo):
    """The fused open-loop scan (generator + tick), the one-chip served
    path, compiles for one v5e and fits its memory."""
    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=4, num_keys=1024, num_versions=4,
                          value_words=4),
        n_chains=2,
    )
    sim = ChainSim(cluster, inject_capacity=8, route_capacity=32,
                   reply_capacity=512)
    one = SingleDeviceSharding(topo.devices[0])
    state = _on(jax.eval_shape(sim.init_state), one)
    gen = _on(jax.eval_shape(lambda: make_loadgen(
        cluster, qps=32.0, write_fraction=0.25, txn_fraction=0.05,
        backlog_capacity=64)), one)
    lanes = sim.C * sim.n * sim.c_in
    compiled = ChainSim._openloop_scan.lower(
        sim, state, gen, 4, lanes, 0).compile()
    used = _device_bytes(compiled)
    assert 0 < used < V5E_HBM_BYTES, used


def test_dist_step_collectives_on_2x2(topo):
    """``ChainDist.make_step`` with one chain node per chip of a 2x2 v5e:
    next-hop forwarding compiles to a collective-permute, the fabric and
    the lock stage to all-gathers."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("chain",),
                axis_types=(AxisType.Auto,))
    dist = ChainDist(ChainConfig(n_nodes=4, num_keys=1024, num_versions=4),
                     mesh, axis="chain")
    shard, rep = NamedSharding(mesh, P("chain")), NamedSharding(mesh, P())
    B = 16
    inbox = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.tile(x[None], (4,) + (1,) * x.ndim), Msg.empty(B)))
    compiled = dist.make_step(B).lower(
        _on(jax.eval_shape(dist.init_state), shard),
        _on(inbox, shard),
        _on(jax.eval_shape(dist.full_roles), shard),
        _on(jax.eval_shape(dist.default_pmap), rep),
        _on(jax.eval_shape(dist.init_locks), rep),
    ).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo
    assert "all-gather" in hlo
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
