"""Compile the served path for a described TPU v5e, without the chip.

The TPU compiler ships with JAX and compiles for a topology that is
described, not attached: it refuses a program that does not fit the chip's
memory, and its HLO shows the collectives the four-chip step uses.  The
topology is described inside a fixture, so only the worker that runs this
file loads the TPU library.
"""
import hashlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (ChainConfig, ChainDist, ChainSim, ClusterConfig, Msg,
                        make_loadgen)
from tests.helpers import REPO, hlo_instruction_lines, stages_off

V5E_HBM_BYTES = 16 * 10**9
SCAN_KEYS = 1024  # keys per chain of the scan compiled below


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


def _compile_scan(topo):
    """The fused open-loop scan (generator + tick), the one-chip served
    path, compiled for one v5e; a fresh engine, so each call traces anew."""
    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=4, num_keys=SCAN_KEYS, num_versions=4,
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
    return ChainSim._openloop_scan.lower(sim, state, gen, 4, lanes, 0).compile()


@pytest.fixture(scope="module")
def scan_v5e(topo):
    return _compile_scan(topo)


def test_openloop_scan_fits_one_v5e(scan_v5e):
    """The open-loop scan compiles for one v5e and fits its memory."""
    used = _device_bytes(scan_v5e)
    assert 0 < used < V5E_HBM_BYTES, used


def test_scopes_leave_the_v5e_scan_unchanged(topo, scan_v5e):
    """The tick's stage scopes change only metadata: with them replaced by
    a null context the v5e program has the same instructions."""
    with stages_off():
        plain = _compile_scan(topo).as_text()
    text = scan_v5e.as_text()
    assert "vmap(store)" in text and "vmap(store)" not in plain
    assert hlo_instruction_lines(text) == hlo_instruction_lines(plain)


def test_store_sorts_no_row_of_the_whole_store(scan_v5e):
    """The store's commit compacts only the rows its batch names: no sort
    of the ``store`` stage has an operand with the store's key axis."""
    from repro.core.stages import op_stages

    text = scan_v5e.as_text()
    smap = op_stages(text)
    assert "store" in smap.values()
    sorts = [line for line in text.splitlines()
             if re.search(r" sort\(", line)
             and smap.get(line.split("=")[0].split()[-1].lstrip("%")) == "store"]
    for line in sorts:
        dims = [d for shape in re.findall(r"\[([\d,]*)\]", line)
                for d in shape.split(",")]
        assert str(SCAN_KEYS) not in dims, line


def test_dist_step_collectives_on_2x2(topo):
    """``ChainDist.make_step`` with one chain node per chip of a 2x2 v5e:
    the exchange compiles to an all-to-all (each chip's block for each
    position), the lock stage's candidates and the role rows to
    all-gathers."""
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
    assert "all-to-all" in hlo
    assert "all-gather" in hlo
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


# the one-chip scan above, its optimized v5e text with metadata and names
# taken out (``hlo_instruction_lines``): the program ``ChainDist`` must
# leave as it is.  A change to the one-chip tick changes it on purpose.
ONE_CHIP_SCAN = (15981, "774678f7f948fef98c201a596f5c85db"
                        "1815703334a52dd5190151ed717e6a7f")


def test_one_chip_scan_program_is_pinned(scan_v5e):
    lines = hlo_instruction_lines(scan_v5e.as_text())
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert (len(lines), digest) == ONE_CHIP_SCAN


COLLECTIVE = re.compile(r" (all-gather|all-to-all|collective-permute|all-reduce"
                        r"|reduce-scatter)(-start)?\(")


def test_dist_scan_on_2x2_at_cell_size(topo):
    """``ChainDist.run_openloop`` as the four-chip cell runs it (its
    configuration file, one replica position per chip of a 2x2 v5e, laid
    out as a ring): it fits a chip, and every collective is the
    ``exchange`` stage's."""
    from repro.core import ring_order
    from repro.core.stages import op_stages

    with open(os.path.join(REPO, "bench", "configs",
                           "netcraq_dist4_16x4_1m.json")) as f:
        cfg = json.load(f)
    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=cfg["replicas"],
                          num_keys=cfg["keys"] // cfg["chains"],
                          num_versions=cfg["num_versions"],
                          value_words=cfg["value_words"],
                          protocol=cfg["protocol"]),
        n_chains=cfg["chains"],
    )
    ring = ring_order(topo.devices)
    assert [tuple(d.coords[:2]) for d in ring] == [(0, 0), (1, 0), (1, 1), (0, 1)]
    mesh = Mesh(np.asarray(ring), ("chain",), axis_types=(AxisType.Auto,))
    seg, n, lanes = 8, cluster.n_nodes, cfg["lanes_per_node"]
    dist = ChainDist(cluster, mesh, inject_capacity=lanes,
                     route_capacity=cfg["route_capacity"],
                     reply_capacity=seg * n * lanes + n * cfg["route_capacity"])
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(dist.init_scan_state), dist.scan_shardings())
    gen = _on(jax.eval_shape(lambda: make_loadgen(
        cluster, qps=1.0, backlog_capacity=dist.C * n * lanes)),
        NamedSharding(mesh, P()))
    compiled = ChainDist._dist_openloop_scan.lower(
        dist, state, gen, seg, dist.C * n * lanes, 0).compile()
    used = _device_bytes(compiled)
    print(f"four-chip scan: {used} bytes per chip")
    assert 0 < used < V5E_HBM_BYTES
    text = compiled.as_text()
    smap = op_stages(text)
    found = [line.split("=")[0].split()[-1].lstrip("%")
             for line in text.splitlines() if COLLECTIVE.search(line)]
    assert found and all(smap[name] == "exchange" for name in found), found
