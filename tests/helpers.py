"""Shared test utilities."""
from __future__ import annotations

import contextlib
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 540) -> str:
    """Run a python snippet in a subprocess with emulated devices.

    Needed because jax locks the device count at first init; multi-device
    tests must not contaminate (or be contaminated by) the main process.
    """
    prelude = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n_devices}'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", prelude + code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


# ---------------------------------------------------------------------------
# Partition-map strategies (used by the round-trip property test in
# test_partition.py): arbitrary-but-legal epoch tables, not just the seed
# modulo map.  A legal placement assigns every bucket a distinct
# bucket-aligned register region on some chain.
# ---------------------------------------------------------------------------
def partition_regions(cluster):
    """Every legal (chain, base) landing region of the cluster: bucket-
    aligned, bucket-sized windows of each chain's physical register file
    (spare-tail regions included)."""
    bsz = cluster.bucket_slots
    K = cluster.chain.num_keys
    return [
        (c, b)
        for c in range(cluster.n_chains)
        for b in range(0, K - bsz + 1, bsz)
    ]


def build_partition_map(cluster, placement, epoch: int = 0):
    """``PartitionMap`` from an explicit bucket -> (chain, base) placement
    (one distinct region per bucket) - the example source for property
    tests over arbitrary epoch tables.

    ``slot_epoch`` is stamped ``epoch`` on every slot whose occupancy
    differs from the epoch-0 home map (the one-step history a real CP
    would have recorded), so the data plane's and the router's stale
    checks behave as if the placement were reached by live migrations.
    """
    import jax.numpy as jnp

    from repro.core import PartitionMap

    assert len(placement) == cluster.num_buckets
    assert len(set(placement)) == len(placement), "regions must be distinct"
    pm = PartitionMap.build(
        owner=[c for c, _ in placement],
        base=[b for _, b in placement],
        epoch=epoch,
        n_chains=cluster.n_chains,
        num_keys=cluster.chain.num_keys,
        bucket_slots=cluster.bucket_slots,
    )
    moved = pm.slot_bucket != cluster.default_partition().slot_bucket
    return pm._replace(
        slot_epoch=jnp.where(moved, jnp.int32(epoch), jnp.int32(0))
    )


def check_partition_round_trip(cluster, placement):
    """The round-trip oracle shared by the seeded always-run test
    (test_partition.py) and the hypothesis twin
    (test_partition_properties.py): for a legal placement,
    ``global_key(key_to_slot(g), key_to_chain(g)) == g`` for every key,
    the occupancy table accounts for exactly the placed slots, and free
    slots invert to -1."""
    import numpy as np

    pm = build_partition_map(cluster, placement, epoch=1)
    g = np.arange(cluster.num_global_keys)
    owner = cluster.key_to_chain(g, pm)
    slot = cluster.key_to_slot(g, pm)
    rt = np.asarray(cluster.global_key(slot, owner, pm))
    np.testing.assert_array_equal(rt, g)
    sb = np.asarray(pm.slot_bucket)
    assert (sb >= 0).sum() == cluster.num_buckets * cluster.bucket_slots
    for c, s in np.argwhere(sb < 0)[:8]:  # free slots invert to "no key"
        assert int(cluster.global_key(int(s), int(c), pm)) == -1


# ---------------------------------------------------------------------------
# Routing-fabric equivalence harness (used by the seeded property test in
# test_fabric.py and its hypothesis twin in test_fabric_properties.py - one
# oracle + one checker, two example sources).
# ---------------------------------------------------------------------------
def reference_route_numpy(flat_fields: dict, alive, chain_pos, c_route: int):
    """Straight-line numpy re-statement of the ORIGINAL per-node-argsort
    router's delivery contract - the oracle both fabrics must match
    bit-for-bit.  Completely independent of the jax implementations: a
    python loop over nodes and flat-outbox slots.

    ``flat_fields`` maps Msg field name -> numpy array ([M] or [M, W]).
    Returns (inbox_fields [n, c_route, ...], dropped [n], mcast_copies,
    mcast_hop_sum) with the same empty-slot bit pattern as ``Msg.mask``.
    """
    import numpy as np

    from repro.core.types import MULTICAST, NOWHERE, OP_NOP, TO_CLIENT

    op, dst, src = (flat_fields[k] for k in ("op", "dst", "src"))
    alive = np.asarray(alive)
    chain_pos = np.asarray(chain_pos)
    n = alive.shape[0]
    M = op.shape[0]
    W = flat_fields["value"].shape[1]
    empty = {
        "op": OP_NOP, "key": 0, "value": 0, "seq": -1, "src": 0,
        "dst": NOWHERE, "client": 0, "entry": 0, "qid": -1, "t_inject": 0,
        "extra": 0, "ver": 0,
    }
    out = {
        k: np.full(
            (n, c_route) + flat_fields[k].shape[1:], v, np.int32
        )
        for k, v in empty.items()
    }
    dropped = np.zeros(n, np.int64)
    mcast_copies = 0
    mcast_hop_sum = 0
    cp = lambda i: chain_pos[min(max(int(i), 0), n - 1)]
    for i in range(n):
        slot = 0
        for f in range(M):
            if op[f] == OP_NOP or not alive[i]:
                continue
            unicast = (
                0 <= dst[f] < n and dst[f] == i and alive[dst[f]]
            )
            mcast = dst[f] == MULTICAST and src[f] != i
            if not (unicast or mcast):
                continue
            if mcast:
                mcast_copies += 1
                mcast_hop_sum += abs(cp(i) - cp(src[f]))
            if slot >= c_route:
                dropped[i] += 1
                continue
            for k in out:
                out[k][i, slot] = flat_fields[k][f]
            if mcast:
                out["extra"][i, slot] += abs(cp(i) - cp(src[f]))
            slot += 1
    return out, dropped, mcast_copies, mcast_hop_sum


def check_fabric_equivalence(flat_fields: dict, alive, chain_pos,
                             c_route: int, mcast_lane=None):
    """Route one flat outbox through the numpy oracle, the dense reference
    fabric and the segmented production fabric, and assert the three agree
    bit-for-bit on every inbox field, the per-node drop counts and the
    multicast copy/hop accounting."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.chain import dense_route, segmented_route
    from repro.core.types import Msg

    flat = Msg(**{k: jnp.asarray(v, jnp.int32) for k, v in flat_fields.items()})
    alive_j = jnp.asarray(np.asarray(alive))
    cp_j = jnp.asarray(np.asarray(chain_pos), jnp.int32)
    ref, ref_drop, ref_copies, ref_hops = reference_route_numpy(
        flat_fields, alive, chain_pos, c_route
    )
    for name, (routed, dropped, copies, hops) in (
        ("dense", dense_route(flat, alive_j, cp_j, c_route)),
        ("segmented",
         segmented_route(flat, alive_j, cp_j, c_route, mcast_lane=mcast_lane)),
    ):
        for k in Msg._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(routed, k)), ref[k],
                err_msg=f"{name} fabric diverges from the oracle on {k!r}",
            )
        np.testing.assert_array_equal(
            np.asarray(dropped), ref_drop,
            err_msg=f"{name} fabric drop counts diverge",
        )
        assert int(copies) == ref_copies, (
            f"{name} fabric multicast copy count {int(copies)} != "
            f"{ref_copies}"
        )
        assert int(hops) == ref_hops, (
            f"{name} fabric multicast hop total {int(hops)} != {ref_hops}"
        )


def random_outbox_fields(rng, n: int, width: int, *, value_words: int = 4,
                         num_keys: int = 8, mcast_heavy: bool = False,
                         adversarial_src: bool = False) -> dict:
    """A random masked [n * width] flat outbox in numpy field form.

    Realistic mode pins ``src`` to the emitting node (every engine outbox
    does - the segmented fabric's bounded multicast lane relies on it);
    ``adversarial_src`` frees it entirely (callers must then route with
    ``mcast_lane=M``).  ``mcast_heavy`` skews destinations toward
    MULTICAST to stress the fan-out path.
    """
    import numpy as np

    from repro.core.types import MULTICAST, NOWHERE, TO_CLIENT

    M = n * width
    dst_pool = [NOWHERE, MULTICAST, TO_CLIENT, n + 3, -7] + list(range(n))
    probs = None
    if mcast_heavy:
        probs = np.ones(len(dst_pool))
        probs[1] = 4 * len(dst_pool)
        probs /= probs.sum()
    fields = {
        "op": rng.integers(0, 7, M),
        "key": rng.integers(0, num_keys, M),
        "value": rng.integers(0, 1 << 16, (M, value_words)),
        "seq": rng.integers(-1, 64, M),
        "src": (rng.integers(-2, n + 2, M) if adversarial_src
                else np.repeat(np.arange(n), width)),
        "dst": rng.choice(dst_pool, M, p=probs),
        "client": rng.integers(0, 1 << 20, M),
        "entry": rng.integers(0, n, M),
        "qid": rng.integers(-1, 1 << 16, M),
        "t_inject": rng.integers(0, 64, M),
        "extra": rng.integers(0, 8, M),
        "ver": rng.integers(0, 4, M),
    }
    # NOP slots must be fully blank (the engines only ever hand the fabric
    # masked outboxes; Msg.mask pins the empty bit pattern)
    blank = {"op": 0, "key": 0, "value": 0, "seq": -1, "src": 0,
             "dst": NOWHERE, "client": 0, "entry": 0, "qid": -1,
             "t_inject": 0, "extra": 0, "ver": 0}
    nop = fields["op"] == 0
    for k, v in blank.items():
        arr = fields[k]
        arr[nop] = v
        fields[k] = arr.astype(np.int32)
    return fields


# ---------------------------------------------------------------------------
# Shared transactional-serializability harness (used by the seeded fuzz in
# test_txn.py and the hypothesis property test in
# test_txn_serializability.py - one checker, two example sources).
# ---------------------------------------------------------------------------
_PROP_ENGINE = None
_WAVE_PROP_ENGINE = None

# Workload shape bounds: constant sim shapes across examples (no recompiles)
# and waves that always fit the head injection lanes.
PROP_MAX_WAVES = 2
PROP_MAX_TXNS_PER_WAVE = 4
PROP_MAX_KEYS_PER_TXN = 3
PROP_NUM_GLOBAL_KEYS = 8


def prop_engine():
    """Lazy singleton (cluster, sim) for serializability fuzzing: jit
    caches key on the ChainSim instance, so every example must reuse it."""
    global _PROP_ENGINE
    if _PROP_ENGINE is None:
        from repro.core import ChainConfig, ChainSim, ClusterConfig

        cluster = ClusterConfig(
            chain=ChainConfig(n_nodes=3, num_keys=4, num_versions=8),
            n_chains=2,
        )
        sim = ChainSim(cluster, inject_capacity=16, route_capacity=96,
                       reply_capacity=512)
        _PROP_ENGINE = (cluster, sim)
    return _PROP_ENGINE


def wave_prop_engine():
    """Same cluster as ``prop_engine`` but with the in-network wave-table
    coordinator enabled - the engine behind ``driver="wave"`` runs of the
    serializability oracle (separate singleton: wave_depth changes the
    compiled tick, and jit caches key on the instance)."""
    global _WAVE_PROP_ENGINE
    if _WAVE_PROP_ENGINE is None:
        from repro.core import ChainConfig, ChainSim, ClusterConfig

        cluster = ClusterConfig(
            chain=ChainConfig(n_nodes=3, num_keys=4, num_versions=8),
            n_chains=2,
        )
        sim = ChainSim(cluster, inject_capacity=16, route_capacity=96,
                       reply_capacity=512,
                       wave_depth=PROP_MAX_TXNS_PER_WAVE,
                       wave_keys=PROP_MAX_KEYS_PER_TXN,
                       wave_log_capacity=64)
        _WAVE_PROP_ENGINE = (cluster, sim)
    return _WAVE_PROP_ENGINE


def txn_waves_from_spec(spec):
    """Build Txn waves from a plain spec: [[(k1, k2, ...), ...], ...] -
    nested tuples of distinct global keys, one inner tuple per txn.  Values
    are unique per (txn, key) so a partially-applied txn is detectable."""
    from repro.core import Txn

    waves, tid = [], 1
    for wave_spec in spec:
        wave = []
        for keys in wave_spec:
            wave.append(Txn(
                txn_id=tid,
                writes=tuple((int(k), (tid << 8) | (j + 1))
                             for j, k in enumerate(keys)),
            ))
            tid += 1
        waves.append(wave)
    return waves


def inject_abandoned_prepares(sim, cluster, state, abandon, tid_base=9001):
    """Phantom clients for the lock-lease tests: grab the head lock of
    each *distinct* global key in ``abandon`` with a bare PREPARE, then
    vanish - phase 2 never arrives, so the lock either leaks forever
    (``lease_ticks == LEASE_OFF``) or is reclaimed by
    ``lease_expiry_stage``.  Returns the post-injection state (one tick)."""
    from repro.core.types import CLIENT_BASE, OP_PREPARE

    assert len(set(abandon)) == len(abandon), "abandoned keys must be distinct"
    pm = cluster.default_partition()
    m = sim.empty_injection()
    lanes: dict[int, int] = {}
    for i, gk in enumerate(abandon):
        chain = int(cluster.key_to_chain(gk, pm))
        slot = int(cluster.key_to_slot(gk, pm))
        lane = lanes.get(chain, 0)
        lanes[chain] = lane + 1
        m = m._replace(
            op=m.op.at[chain, 0, lane].set(OP_PREPARE),
            key=m.key.at[chain, 0, lane].set(slot),
            seq=m.seq.at[chain, 0, lane].set(tid_base + i),
            src=m.src.at[chain, 0, lane].set(CLIENT_BASE + 7),
            client=m.client.at[chain, 0, lane].set(CLIENT_BASE + 7),
            dst=m.dst.at[chain, 0, lane].set(0),
            qid=m.qid.at[chain, 0, lane].set((1 << 20) + i),
        )
    return sim.tick(state, m)


def run_txn_waves_and_check(spec, driver="host", abandon=(), lease_ticks=None):
    """The serializability oracle: run the spec's waves through the shared
    engine, then assert (1) locks drained + chains converged, (2) committed
    txns are atomic, (3) the observed write precedence is acyclic, and (4)
    serially replaying it reproduces every chain's store bit-exactly.

    ``driver`` selects the coordinator under test: ``"host"`` drives each
    wave through the host-side ``TxnDriver`` (the correctness oracle of
    core/txn.py), ``"wave"`` admits the same waves into the in-network
    wave-table coordinator (``TxnWaveDriver``) - same checks, wave
    boundaries preserved (one run per wave, like the host driver).

    ``abandon`` names distinct global keys whose locks are grabbed by
    phantom clients *before* the waves and never released (see
    ``inject_abandoned_prepares``).  ``lease_ticks`` (when not ``None``)
    arms the lock-lease clock on the engine's lock table.  At a finite
    lease the oracle additionally asserts the abandoned locks were
    reclaimed (``lease_expiries`` counted, table drained); at
    ``None``/``LEASE_OFF`` it asserts the leak is exactly the abandoned
    lock count - the unbounded-growth arm of the lease sweep."""
    import numpy as np

    from repro.core import (Coordinator, TxnDriver, TxnPlanner,
                            TxnWaveDriver, committed_view, held_locks,
                            locks_all_free, reference_execute, serial_order,
                            set_lease)
    from repro.core.types import LEASE_OFF

    assert driver in ("host", "wave"), driver
    cluster, sim = prop_engine() if driver == "host" else wave_prop_engine()
    waves = txn_waves_from_spec(spec)
    state = sim.init_state()
    finite = lease_ticks is not None and lease_ticks != LEASE_OFF
    if lease_ticks is not None:
        state = state._replace(locks=set_lease(state.locks, lease_ticks))
    if abandon:
        state = inject_abandoned_prepares(sim, cluster, state, abandon)
    if driver == "host":
        drv = TxnDriver(sim, TxnPlanner(cluster))
    else:
        drv = TxnWaveDriver(sim, TxnPlanner(cluster))
    results = []
    for wave in waves:
        state, res = drv.run(state, wave)
        results += res
    empty = sim.empty_injection()
    drain_ticks = 4 * sim.n + 4
    if finite and abandon:
        # the phantom locks must age past the lease *during* the drain
        drain_ticks += int(lease_ticks)
    for _ in range(drain_ticks):
        state = sim.tick(state, empty)

    if abandon and not finite:
        # abandonment without a lease: the leak is permanent and exact
        assert held_locks(state.locks) == len(abandon)
        assert state.metrics.asdict()["lease_expiries"] == 0
    else:
        assert locks_all_free(state.locks)
        if abandon:
            assert state.metrics.asdict()["lease_expiries"] >= len(abandon)
    assert int(state.stores.pending.sum()) == 0
    if driver == "wave":
        assert Coordinator.waves_drained(state)

    by_id = {t.txn_id: t for wave in waves for t in wave}
    committed_ids = {r.txn_id for r in results if r.committed}
    for r in results:  # atomicity: all-or-nothing write acknowledgements
        if r.committed:
            assert set(r.write_seqs) == {k for k, _ in by_id[r.txn_id].writes}

    order = serial_order(results)  # raises on cyclic precedence
    assert set(order) <= committed_ids
    tail = [t for t in sorted(committed_ids) if t not in set(order)]
    expected = reference_execute([by_id[t] for t in order + tail])
    view = committed_view(cluster, state)
    for gk in range(cluster.num_global_keys):
        assert view[gk] == expected.get(gk, 0), (
            f"key {gk}: store={view[gk]} reference={expected.get(gk, 0)}"
        )
    vals = np.asarray(state.stores.values)[:, :, :, 0, 0]
    for c in range(cluster.n_chains):
        for node in range(sim.n):
            np.testing.assert_array_equal(vals[c, node], vals[c, -1])
    return results



# ---------------------------------------------------------------------------
# Store commit reference (used by the property tests in test_store.py)
# ---------------------------------------------------------------------------
def dense_commit(store, keys, values, seqs, active):
    """The store's commit as it was first written: every row of the store
    is rebuilt, O(K*V*W), and rows no batch entry touches are selected back
    unchanged.  The reference ``store.commit`` must match leaf by leaf
    (tests/test_store.py)."""
    import jax.numpy as jnp

    K, V, W = store.values.shape
    active = active.astype(bool)

    # Per-key max committed seq in this batch (acks are cumulative).
    neg = jnp.full((K,), -1, jnp.int32)
    ack_seq = neg.at[keys].max(jnp.where(active, seqs, -1))

    # Which batch entry supplies the value for each key: the one whose seq
    # equals the per-key max.  Non-winners scatter out of bounds and are
    # dropped - scattering a where()-writeback instead would race the
    # winner (XLA scatter order with duplicate indices is undefined).
    is_winner = active & (seqs == ack_seq[keys]) & (seqs > store.seqs[keys, 0])
    K_oob = store.num_keys  # out-of-bounds sentinel row
    safe_key = jnp.where(is_winner, keys, K_oob)
    cell0 = store.values[:, 0, :]
    new_cell0 = cell0.at[safe_key].set(values, mode="drop")
    seq0 = store.seqs[:, 0]
    new_seq0 = seq0.at[safe_key].set(seqs, mode="drop")

    # Monotone guard: never roll the committed seq backwards.
    effective = jnp.maximum(ack_seq, seq0)  # per-key commit floor after batch
    touched = ack_seq >= 0

    # Compact dirty region per key: keep dirty cells with seq > effective.
    cell_idx = jnp.arange(V)[None, :]
    dirty = (cell_idx >= 1) & (cell_idx <= store.pending[:, None])
    keep = dirty & (store.seqs > effective[:, None]) & touched[:, None]
    keep = jnp.where(touched[:, None], keep, dirty)  # untouched keys unchanged
    # Stable argsort: kept dirty cells first, in original (seq) order.
    order = jnp.argsort(~keep, axis=1, stable=True)  # [K, V]
    kept_vals = jnp.take_along_axis(store.values, order[:, :, None], axis=1)
    kept_seqs = jnp.take_along_axis(store.seqs, order[:, :, None].squeeze(-1), axis=1)
    n_keep = keep.sum(axis=1).astype(jnp.int32)

    # Rebuild rows only for touched keys; shift kept versions to cells 1..n.
    shifted_vals = jnp.concatenate([new_cell0[:, None, :], kept_vals[:, : V - 1]], axis=1)
    shifted_seqs = jnp.concatenate([new_seq0[:, None], kept_seqs[:, : V - 1]], axis=1)
    # Blank cells beyond the kept region.
    valid = cell_idx <= n_keep[:, None]
    shifted_seqs = jnp.where(valid, shifted_seqs, -1)

    out_values = jnp.where(touched[:, None, None], shifted_vals, store.values)
    out_seqs = jnp.where(touched[:, None], shifted_seqs, store.seqs)
    out_pending = jnp.where(touched, n_keep, store.pending)
    return store._replace(values=out_values, seqs=out_seqs, pending=out_pending)


_HLO_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_HLO_DEF = re.compile(r"^\s*(?:ROOT\s+|ENTRY\s+)?%?([\w.\-]+)(?: = | \()")
_HLO_TOKEN = re.compile(r"(?<![\w.\-])%?([A-Za-z_][\w.\-]*)")


def hlo_instruction_lines(hlo_text: str) -> list[str]:
    """An optimized HLO module's lines with what a named scope may change
    taken out: each instruction's ``metadata={...}``, the module's
    stack-frame and file-location tables, and the names of instructions,
    parameters and computations, which XLA derives in part from the ops'
    locations (each is replaced by its order of first definition)."""
    lines, skip = [], False
    for line in hlo_text.splitlines():
        if line in _HLO_TABLES:
            skip = True
            continue
        if skip and not line.startswith(("%", "ENTRY")):
            continue
        skip = False
        lines.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    names: dict[str, str] = {}
    for line in lines:
        m = _HLO_DEF.match(line)
        if m:
            names.setdefault(m.group(1), f"v{len(names)}")
        # parameters in a computation's signature: "(param_0.1: s32[], ..."
        for p in re.findall(r"[(,]\s*(?:/\*index=\d+\*/)?([\w.\-]+): ", line):
            names.setdefault(p, f"v{len(names)}")
    return [_HLO_TOKEN.sub(lambda t: names.get(t.group(1), t.group(0)), line)
            for line in lines]


@contextlib.contextmanager
def stages_off():
    """Every ``stage(...)`` scope of the program replaced by a null
    context, for programs traced inside the block."""
    from repro.core import stages

    real = stages.stage
    mods = [m for name, m in list(sys.modules.items())
            if name.startswith("repro.") and getattr(m, "stage", None) is real]
    for m in mods:
        m.stage = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        for m in mods:
            m.stage = real
