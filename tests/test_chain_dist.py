"""Distributed (shard_map) chain engine + replicated-KV-cache collectives.
Run in subprocesses with emulated devices (jax pins device count at init).
"""
import pytest

from helpers import run_with_devices


@pytest.mark.slow
def test_chain_dist_write_read_roundtrip():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import ChainConfig, ChainDist, CLIENT_BASE
from repro.core.types import Msg, OP_READ, OP_WRITE

mesh = jax.make_mesh((4,), ("chain",), axis_types=(AxisType.Auto,))
cfg = ChainConfig(n_nodes=4, num_keys=16, num_versions=4, protocol="netcraq")
dist = ChainDist(cfg, mesh, axis="chain")
stores = dist.init_state()
roles = dist.full_roles()
pmap = dist.default_pmap()
B = 8
step = dist.make_step(B)
locks = dist.init_locks()

def inject(op, key, val, node):
    m = Msg.empty(B)
    m = jax.tree.map(lambda x: jnp.tile(x[None], (4,) + (1,)*x.ndim), m)
    return m._replace(
        op=m.op.at[node, 0].set(op), key=m.key.at[node, 0].set(key),
        value=m.value.at[node, 0, 0].set(val),
        src=m.src.at[node, 0].set(CLIENT_BASE+7),
        client=m.client.at[node, 0].set(CLIENT_BASE+7),
        qid=m.qid.at[node, 0].set(42), dst=m.dst.at[node, 0].set(node))

inbox = inject(OP_WRITE, 3, 99, 0)
for _ in range(8):
    stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
assert stores.values[:, 3, 0, 0].tolist() == [99]*4, stores.values[:, 3, 0, 0]
assert stores.pending[:, 3].tolist() == [0]*4

inbox = inject(OP_READ, 3, 0, 2)
stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
r = jax.device_get(replies)
live = r.op != 0
assert live.sum() == 1 and r.value[live][0, 0] == 99, r.value[live]
print("DIST_OK")
""")
    assert "DIST_OK" in out


@pytest.mark.slow
def test_chain_dist_serves_with_dead_node():
    """make_step consumes the CP's live role table: with node 1 spliced out
    the write path runs head 0 -> 2 -> tail 3 (the skip rides the fabric
    collective), the dead device neither stores nor ACKs, and reads keep
    serving - all without re-making the step function."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import ChainConfig, ChainDist, Coordinator, CLIENT_BASE
from repro.core.types import Msg, OP_READ, OP_WRITE

mesh = jax.make_mesh((4,), ("chain",), axis_types=(AxisType.Auto,))
cfg = ChainConfig(n_nodes=4, num_keys=16, num_versions=4, protocol="netcraq")
dist = ChainDist(cfg, mesh, axis="chain")
stores = dist.init_state()
pmap = dist.default_pmap()
B = 8
step = dist.make_step(B)
locks = dist.init_locks()

def inject(op, key, val, node):
    m = Msg.empty(B)
    m = jax.tree.map(lambda x: jnp.tile(x[None], (4,) + (1,)*x.ndim), m)
    return m._replace(
        op=m.op.at[node, 0].set(op), key=m.key.at[node, 0].set(key),
        value=m.value.at[node, 0, 0].set(val),
        src=m.src.at[node, 0].set(CLIENT_BASE+7),
        client=m.client.at[node, 0].set(CLIENT_BASE+7),
        qid=m.qid.at[node, 0].set(42), dst=m.dst.at[node, 0].set(node))

co = Coordinator(cfg)
co.fail_node(0, 1)
roles = jax.tree.map(lambda x: x[0], co.roles_table())  # [n] leaves

inbox = inject(OP_WRITE, 3, 99, 0)
for _ in range(8):
    stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
assert stores.values[:, 3, 0, 0].tolist() == [99, 0, 99, 99], \\
    stores.values[:, 3, 0, 0]
assert stores.pending[:, 3].tolist() == [0]*4

inbox = inject(OP_READ, 3, 0, 2)
stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
r = jax.device_get(replies)
live = r.op != 0
assert live.sum() == 1 and r.value[live][0, 0] == 99, r.value[live]
print("DEAD_NODE_OK")
""")
    assert "DEAD_NODE_OK" in out


@pytest.mark.slow
def test_chain_dist_multichain_groups():
    """Two chains side by side on a (cgroup, chain) mesh: collectives stay
    scoped to each chain, writes/reads never leak across groups."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import ChainConfig, ClusterConfig, ChainDist, CLIENT_BASE
from repro.core.types import Msg, OP_READ, OP_WRITE

mesh = jax.make_mesh((2, 4), ("cgroup", "chain"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = ChainConfig(n_nodes=4, num_keys=16, num_versions=4, protocol="netcraq")
dist = ChainDist(ClusterConfig(chain=cfg, n_chains=2), mesh,
                 axis="chain", group_axis="cgroup")
stores = dist.init_state()
roles = dist.full_roles()
pmap = dist.default_pmap()
B = 8
step = dist.make_step(B)
locks = dist.init_locks()

def inject(op, key, val, node, chain):
    m = Msg.empty(B)
    m = jax.tree.map(lambda x: jnp.tile(x[None, None], (2, 4) + (1,)*x.ndim), m)
    return m._replace(
        op=m.op.at[chain, node, 0].set(op),
        key=m.key.at[chain, node, 0].set(key),
        value=m.value.at[chain, node, 0, 0].set(val),
        src=m.src.at[chain, node, 0].set(CLIENT_BASE+7),
        client=m.client.at[chain, node, 0].set(CLIENT_BASE+7),
        qid=m.qid.at[chain, node, 0].set(42),
        dst=m.dst.at[chain, node, 0].set(node))

inbox = inject(OP_WRITE, 5, 123, 0, 1)
for _ in range(8):
    stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
assert stores.values[1, :, 5, 0, 0].tolist() == [123]*4, stores.values[1, :, 5, 0, 0]
assert stores.values[0, :, 5, 0, 0].tolist() == [0]*4   # chain 0 untouched
assert int(stores.pending.sum()) == 0

inbox = inject(OP_READ, 5, 0, 2, 1)
stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
r = jax.device_get(replies)
live = r.op != 0
assert live.sum() == 1 and r.value[live][0, 0] == 123, r.value[live]
print("GROUPS_OK")
""")
    assert "GROUPS_OK" in out


@pytest.mark.slow
def test_chain_dist_lock_stage():
    """The dist engine's replicated head lock stage: a PREPARE at the head
    acquires the lock and ACKs, a conflicting PREPARE NACKs, COMMIT lands
    the value and releases - the lock shard stays consistent (replicated)
    across devices without a collective write-back."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import ChainConfig, ChainDist, CLIENT_BASE
from repro.core.types import (Msg, OP_PREPARE, OP_PREPARE_ACK,
                              OP_PREPARE_NACK, OP_COMMIT, OP_TXN_REPLY)

mesh = jax.make_mesh((4,), ("chain",), axis_types=(AxisType.Auto,))
cfg = ChainConfig(n_nodes=4, num_keys=16, num_versions=4, protocol="netcraq")
dist = ChainDist(cfg, mesh, axis="chain")
stores = dist.init_state()
roles = dist.full_roles()
pmap = dist.default_pmap()
B = 8
step = dist.make_step(B)
locks = dist.init_locks()

def inject(op, key, val, seq, client, slot=0, node=0):
    m = Msg.empty(B)
    m = jax.tree.map(lambda x: jnp.tile(x[None], (4,) + (1,)*x.ndim), m)
    return m._replace(
        op=m.op.at[node, slot].set(op), key=m.key.at[node, slot].set(key),
        value=m.value.at[node, slot, 0].set(val),
        seq=m.seq.at[node, slot].set(seq),
        src=m.src.at[node, slot].set(CLIENT_BASE+client),
        client=m.client.at[node, slot].set(CLIENT_BASE+client),
        qid=m.qid.at[node, slot].set(40+slot),
        dst=m.dst.at[node, slot].set(node))

# two PREPAREs for the same key in one batch: first wins, second NACKs
m1 = inject(OP_PREPARE, 3, 0, 7, 1, slot=0)
m2 = inject(OP_PREPARE, 3, 0, 8, 2, slot=1)
live2 = m2.op != 0
inbox = jax.tree.map(lambda a, b: jnp.where(
    live2.reshape(live2.shape + (1,)*(a.ndim - live2.ndim)), b, a), m1, m2)
stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
r = jax.device_get(replies)
ops = r.op[r.op != 0].tolist()
assert sorted(ops) == sorted([OP_PREPARE_ACK, OP_PREPARE_NACK]), ops
assert locks.holder[0, 3].tolist() == 7, locks.holder
assert locks.client[0, 3].tolist() == CLIENT_BASE + 1

# COMMIT releases the lock and the write propagates to every live node
inbox = inject(OP_COMMIT, 3, 99, 7, 1)
for _ in range(8):
    stores, inbox, replies, locks = step(stores, inbox, roles, pmap, locks)
assert locks.holder[0, 3].tolist() == -1, locks.holder
assert locks.version[0, 3].tolist() == 1
assert stores.values[:, 3, 0, 0].tolist() == [99]*4, stores.values[:, 3, 0, 0]
print("LOCK_STAGE_OK")
""")
    assert "LOCK_STAGE_OK" in out


@pytest.mark.slow
def test_chain_dist_telemetry_hist():
    """The dist engine's opt-in telemetry: ``make_step(B, telemetry=True)``
    threads a Telemetry operand through the shard_map step and scatters
    each device's reply batch into its latency histogram shard, clocked by
    the per-device ``ring_cursor`` step counter (the dist engine has no
    shared SimState.t).  The histogram totals must match the replies the
    host actually saw, per op class."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.core import ChainConfig, ChainDist, CLIENT_BASE
from repro.core.types import (Msg, OP_READ, OP_READ_REPLY, OP_WRITE,
                              OP_WRITE_REPLY, OPCLASS_READ, OPCLASS_WRITE)

mesh = jax.make_mesh((4,), ("chain",), axis_types=(AxisType.Auto,))
cfg = ChainConfig(n_nodes=4, num_keys=16, num_versions=4, protocol="netcraq")
dist = ChainDist(cfg, mesh, axis="chain")
stores = dist.init_state()
roles = dist.full_roles()
pmap = dist.default_pmap()
B = 8
step = dist.make_step(B, telemetry=True)
locks = dist.init_locks()
tel = dist.init_telemetry()

def inject(op, key, val, node, t):
    m = Msg.empty(B)
    m = jax.tree.map(lambda x: jnp.tile(x[None], (4,) + (1,)*x.ndim), m)
    return m._replace(
        op=m.op.at[node, 0].set(op), key=m.key.at[node, 0].set(key),
        value=m.value.at[node, 0, 0].set(val),
        src=m.src.at[node, 0].set(CLIENT_BASE+7),
        client=m.client.at[node, 0].set(CLIENT_BASE+7),
        qid=m.qid.at[node, 0].set(42), dst=m.dst.at[node, 0].set(node),
        t_inject=m.t_inject.at[node, 0].set(t))

seen_r = seen_w = 0
inbox = inject(OP_WRITE, 3, 99, 0, 0)
for _ in range(8):
    stores, inbox, replies, locks, tel = step(
        stores, inbox, roles, pmap, locks, tel)
    r = jax.device_get(replies)
    seen_r += int((r.op == OP_READ_REPLY).sum())
    seen_w += int((r.op == OP_WRITE_REPLY).sum())
inbox = inject(OP_READ, 3, 0, 2, 8)  # injected at clock 8
stores, inbox, replies, locks, tel = step(
    stores, inbox, roles, pmap, locks, tel)
r = jax.device_get(replies)
seen_r += int((r.op == OP_READ_REPLY).sum())
seen_w += int((r.op == OP_WRITE_REPLY).sum())
assert seen_r == 1 and seen_w == 1, (seen_r, seen_w)

hist = np.asarray(jax.device_get(tel.lat_hist))
flat = hist.reshape((-1,) + hist.shape[-2:]).sum(axis=0)  # [OPCLASS, BKT]
assert int(flat[OPCLASS_READ].sum()) == seen_r, flat
assert int(flat[OPCLASS_WRITE].sum()) == seen_w, flat
assert int(flat.sum()) == seen_r + seen_w, flat
# per-device step clock: one row per step on every device
assert np.asarray(jax.device_get(tel.ring_cursor)).tolist() == [9]*4
# the read completed in one step -> bucket 0; the write propagated the
# whole 4-node chain -> strictly slower
read_b = int(np.nonzero(flat[OPCLASS_READ])[0][0])
write_b = int(np.nonzero(flat[OPCLASS_WRITE])[0][0])
assert read_b == 0 and write_b >= read_b, (read_b, write_b)
print("DIST_TEL_OK")
""")
    assert "DIST_TEL_OK" in out


@pytest.mark.slow
def test_replicated_kv_cache_protocols():
    out = run_with_devices("""
import jax, jax.numpy as jnp, functools
from jax.sharding import AxisType, PartitionSpec as P
from repro.serve import kv_cache as KV

n = 4
mesh = jax.make_mesh((n,), ("chain",), axis_types=(AxisType.Auto,))

def craq_body(kv_new, seq):
    own, replica, ack = KV.netcraq_append(kv_new, seq, axis="chain", n=n)
    return own, replica, ack

def cr_body(page, seq):
    fetched = KV.netchain_read(page, axis="chain", n=n)
    committed, ack = KV.netchain_append(page, seq, axis="chain", n=n)
    return fetched, committed, ack

kv = jnp.arange(n*8, dtype=jnp.float32).reshape(n, 8)   # distinct per node
seqs = jnp.arange(n, dtype=jnp.int32) + 10

craq = jax.jit(jax.shard_map(craq_body, mesh=mesh,
    in_specs=(P("chain"), P("chain")), out_specs=(P("chain"), P("chain"), P("chain"))))
own, replica, ack = craq(kv, seqs)
# node i>0 stores node i-1's page as the replica copy
assert jnp.allclose(replica[1:], kv[:-1]), replica
assert jnp.allclose(replica[0], kv[0])
# tail's seq broadcast to everyone
assert ack.tolist() == [13]*n, ack

cr = jax.jit(jax.shard_map(cr_body, mesh=mesh,
    in_specs=(P("chain"), P("chain")), out_specs=(P("chain"), P("chain"), P("chain"))))
fetched, committed, ack2 = cr(kv, seqs)
# CR read: every node receives the TAIL's page
assert jnp.allclose(fetched, jnp.tile(kv[-1], (n, 1))), fetched
# CR write: the tail ends holding the head's page after n-1 hops
assert jnp.allclose(committed[-1], kv[0]), committed[-1]
print("KV_OK")
""")
    assert "KV_OK" in out


@pytest.mark.slow
def test_failover_select():
    out = run_with_devices("""
import jax.numpy as jnp
from repro.serve.kv_cache import failover_select
local = jnp.zeros((4, 3))
replica = jnp.ones((4, 3))
failed = jnp.asarray([True, False, True, False])
out = failover_select(local, replica, failed)
assert out[:, 0].tolist() == [1., 0., 1., 0.]
print("FO_OK")
""", n_devices=1)
    assert "FO_OK" in out
