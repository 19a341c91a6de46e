"""Chain execution engines.

``ChainSim``  - tick-synchronous simulator over a *cluster* of C virtual
chains: state carries a leading chain axis ``[C, n, ...]`` and the per-chain
tick (node vmap + explicit routing fabric with exact packet/hop/byte
accounting) is vmapped over the chain axis - one jit, C independent chains
per tick.  Chains serve disjoint key partitions (``ClusterConfig``), so the
fabric only ever delivers within a chain; a single-chain cluster reproduces
the seed engine's counts bit-for-bit.  This is the engine behind the
paper-figure benchmarks and the consistency tests.

The routing fabric is a **single segmented stable sort** of the flat
per-chain outbox keyed by ``(destination, original index)``
(``segmented_route``): O(M log M) per tick instead of the original
delivery-matrix router's O(n * M log M), with bit-identical inboxes, drop
counts and hop/packet accounting (the original is kept as ``dense_route``,
the equivalence oracle and benchmark baseline - see
benchmarks/fig_tick_cost.py).  ``tick`` donates the state buffers and
``run`` drains through one fused ``lax.scan``, so a tick allocates no new
cluster state and the drain pays one dispatch, not sixteen.

``ChainDist`` - the production engine: one chain node per device along a
named mesh axis under ``shard_map``.  Write propagation uses
``jax.lax.ppermute`` (one ICI hop per chain hop, exactly the paper's
next-hop forwarding), dirty-read fetch and ACK multicast use a masked
``all_gather`` (the ICI ring acting as the multicast tree).  With a second
``group_axis`` on the mesh, C chains run side by side - the collectives are
scoped to the position axis, so each chain group exchanges only within
itself.  The multi-pod dry-run lowers this engine on the production meshes.

Both engines share the per-node control logic in ``craq.py``/``netchain.py``.

Live-membership contract
------------------------
The data plane reads its forwarding state from a per-chain ``Roles`` table
(``SimState.roles``, ``[C, n]`` leaves; ``ChainDist`` takes the same table
as a step argument).  The table is *owned by the control plane*: only the
``Coordinator`` (via ``fail_node``/``begin_recovery``/``complete_recovery``
followed by ``install_roles``) may rewrite it, and only **between ticks** -
the engines never mutate it, a tick observes one consistent snapshot, and
the paper's CP/DP split is preserved (role edits are tiny metadata writes,
never on the per-query path).  Because an edit keeps every leaf's shape and
dtype, ``fail_node``/``recover_node`` on a running state trigger **no
recompilation and no state reset**: the chain keeps serving while
membership changes (paper §III.C two-phase recovery).

Semantics under a partial-health table: a dead node neither receives nor
emits - injection into its lanes and in-flight unicast addressed to it
are dropped and counted in ``Metrics.drops``; multicast copies for it are
simply not generated (the CP pruned the multicast group, so they are not
lost traffic and not counted);
forwarding follows ``next_pos``/``prev_pos`` along the *live* chain; hop
accounting uses live-chain positions (``chain_pos``), so a spliced-out
node is not a link traversal; while ``frozen`` is set, client writes are
NACKed at the entry node (``OP_WRITE_NACK``, counted in ``write_nacks``).

Machine-checked by repro-lint: the role table stays a *traced leaf* of
the donated tick - RL002 rejects closure-captured role arrays, RL001
rejects callers that read a pre-tick state after donation, and RL004
rejects host-side branching on role values inside the jitted stages
(which is what "the engines never mutate it" compiles down to).

Lock-table rules (the transaction extension of the same contract)
-----------------------------------------------------------------
``SimState.locks`` is a per-chain ``LockTable`` ([C, K] leaves).  Unlike
the role table it is **data-plane-owned**: only the head's transaction
stage (``txn.head_txn_stage``, running inside the jitted tick) may write
it - a PREPARE acquires, COMMIT/ABORT release, nothing else touches it.
The CP never edits lock words directly; its one interaction is the freeze
flag: while ``frozen`` is set the stage NACKs every new PREPARE (frozen
writes must NACK prepares too - otherwise a lock granted during the copy
window would admit a commit write behind the CP's back), while COMMIT/
ABORT of *already-held* locks still proceed, since they only complete
transactions admitted before the freeze.  Consequently recovery must
treat the lock table like in-flight writes: after ``begin_recovery`` the
CP waits until the chain's locks drain (``txn.locks_all_free`` - bounded,
because no new lock can be granted) before copying KV pairs, and the
recovery copy path copies *stores only* - lock words never move between
nodes because they live per chain, not per node.  In-flight PREPAREs at
the moment of a freeze are therefore either granted before the freeze
(their txn completes normally) or NACKed by it; there is no third state.

Machine-checked by repro-lint: lock words are strong-int32 lanes of
``LockTable`` - RL003 rejects weak python literals flowing into them,
and RL001 guards the drain loops that wait on ``locks_all_free``
(every ``state = sim.tick(state, ...)`` rebinding is verified).

Lock-lease rules (bounded reclamation of abandoned locks)
---------------------------------------------------------
In-network lock state has no client process to die with (the NetChain
argument), so a lock whose holder abandons its transaction - the
documented overload pathology in ``core/loadgen.py`` - would otherwise
poison its key forever.  The lease discipline bounds that:

* ``LockTable.lease`` is a [C, K] traced leaf stamping each grant with
  its acquisition tick (``head_txn_stage`` writes it alongside
  ``holder``); ``LockTable.lease_ticks`` is the [C] per-chain lease
  length.  Both are *data*: sweeping the lease (or disabling it with
  ``types.LEASE_OFF``) is a ``_replace`` on the state
  (``txn.set_lease``), never a new program - at ``LEASE_OFF`` the
  engine is bit-identical to the pre-lease one.
* ``txn.lease_expiry_stage`` runs inside the jitted tick immediately
  *before* the lock stage: a key held past its lease is reclaimed
  (holder/client/lease cleared, counted in ``Metrics.lease_expiries``)
  and its **version counter is bumped**, so a straggler COMMIT from the
  expired holder - arriving this very tick or any later one - fails the
  ``holder == txn_id`` release validation and is NACKed
  (``OP_TXN_REPLY`` ``seq == -1``), never applied.  Expiry-then-locks
  ordering is the correctness hinge: there is no tick where an expired
  lock can still validate a release.
* The wave coordinator is lease-aware (``txn.wave_coordinator_step``):
  a PREP slot older than the lease can never hear its missing replies,
  so it force-aborts (outcome code ``txn.WAVE_EXPIRED``, decoded by
  ``TxnWaveDriver`` as ``mode == "wave_expired"``) and retires through
  the normal all-answered path - slot qids never alias, and the
  completion-log cursor can no longer be pinned by an abandoned slot.
* The CP never moves lease words: recovery and rebalancing copy stores
  plus the commit-version column only, and both already require
  ``holder == -1`` in the touched region - a residual lease stamp on a
  free key is inert by construction (expiry keys on ``holder != -1``).

Machine-checked by repro-lint: the lease stamp and length are strong
int32 ``LockTable`` lanes - RL003 rejects a weak python literal lease
(the weak->strong flip would recompile the donated tick mid-sweep) and
RL002 rejects a lease table or lease length closed over by a jitted
stage instead of riding the traced state.  The known-clean/known-bad
pair in tests/lint_corpus/lease_{clean,bad}.py pins this coverage.

Partition-epoch rules (the rebalancing extension of the same contract)
----------------------------------------------------------------------
``SimState.pmap`` is the versioned bucket->chain ``PartitionMap`` (see
``core/types.py``).  Like the role table it is **CP-owned**: only the
``Coordinator`` may rewrite it - the epoch is bumped exclusively by
``complete_rebalance`` (one bump per bucket move), published between
ticks with ``install_partition(state)``, and every leaf keeps its shape
and dtype, so a migration never recompiles the jitted data path.  The
migration lifecycle is strictly ordered:

1. **freeze** (``begin_rebalance``): the *source* chain's writes freeze
   (the PR-2 freeze/NACK path - client writes NACK ``OP_WRITE_NACK``, new
   transaction PREPAREs NACK ``OP_PREPARE_NACK``; reads keep serving).
   Publish with ``install_roles``.
2. **drain**: the CP ticks the engine until the source chain's in-flight
   writes commit and its lock table drains (``locks_drained`` - bounded,
   because the freeze admits no new lock; ``complete_rebalance`` asserts
   it).  Copying earlier could miss an admitted COMMIT's write.
3. **copy + publish** (``complete_rebalance``, between two ticks): the
   moving bucket's register slice - store leaves *and* the lock table's
   commit-version column, the snapshot coordinate multi-key reads pin -
   is copied to the destination region via the recovery copy path, the
   freed source region is reset to its initial state, the epoch-bumped
   map (``owner``/``base``/``slot_bucket``/``slot_epoch``) is installed
   with ``install_partition``, and the source chain unfreezes
   (``install_roles``).

The data plane's half of the bargain is the **stale-route check** at the
entry node: every client op carries the epoch of the map it was routed
under (``Msg.ver``), and the tick NACK-redirects (``OP_STALE_NACK``,
counted in ``Metrics.stale_routes``) any op whose stamp is older than
``slot_epoch`` of the slot it addresses, or that targets a slot no
bucket occupies - so a stale client can never read the old owner's
stale region (or a recycled region's foreign keys), while buckets the
migration did not touch keep serving stale-but-consistent clients
without interruption.  Chains not named by the move (neither source nor
destination) observe identical traffic and stay bit-identical to an
undisturbed run - asserted by ``benchmarks/fig_rebalance.py``.

Machine-checked by repro-lint: "every leaf keeps its shape and dtype"
is enforceable only if the dtypes are *strong* to begin with - RL003
pins the epoch stamps (``Msg.ver``, ``slot_epoch``) against weak-int
promotion, and RL002 keeps the published map a traced argument rather
than a constant baked into the executable at trace time.

Wave-table rules (the in-network coordinator extension of the contract)
-----------------------------------------------------------------------
With ``wave_depth > 0`` the state grows ``SimState.wave`` - a per-chain
``txn.WaveState`` of W coordinator slots that runs the 2PC state machine
*inside* the jitted tick (``txn.wave_coordinator_step``).  Ownership is
split along the same CP/DP line as the lock table:

* **Admission is host-owned and batched**: only ``txn.TxnWaveDriver``
  (or a test harness) writes FREE slots, only **between ticks**, and only
  FREE -> ADMITTED - it never touches an occupied slot.  Every leaf keeps
  its shape/dtype, so admitting a wave of transactions is a pure state
  swap: zero recompiles, the same contract as role/partition edits.
* **Everything after admission is device-owned**: the per-tick coordinator
  stage emits PREPAREs, collects ACK/NACKs, decides, emits COMMIT/ABORTs,
  retires slots and appends the completion log.  The host's only reads are
  the ``[C, W]`` phase leaf (to find free slots) and - once, at the end -
  the completion log; per-transaction round trips are gone.
* Coordinator sub-ops carry ``src``/``client`` >= ``WAVE_BASE``
  (``types.py``), so heads treat them exactly like client transaction
  traffic, while the fabric's exit stage diverts their replies to the
  cluster-level control router (back to the coordinator's chain) instead
  of the reply log.  A slot is recycled only after **every** sub-op it
  issued has been answered - phase-1 replies before the decision, phase-2
  completions before the slot frees - so a recycled slot's qids can never
  alias a predecessor's in-flight replies, and an abort releases every
  key the transaction touched (no early-abort: deciding before all
  phase-1 replies land would race the decision's ABORT past its own
  in-flight PREPARE at the head's release-before-acquire lock stage).
* The CP's freeze interacts as with host-driven transactions: frozen
  chains NACK the wave's PREPAREs (the txn aborts, retriable), COMMITs of
  already-held locks still land; ``Coordinator.waves_drained`` is the CP
  barrier for surgery that needs no wave in flight.

``wave_depth == 0`` (the default) keeps the wave machinery out of the
compiled program entirely - zero-size leaves ride the pytree and the tick
is bit-identical to the wave-less engine.

Machine-checked by repro-lint: every ``WaveState`` lane is strong int32
(RL003 - a weak admission write would flip the abstract value and
recompile the donated tick), the coordinator stage runs without host
control flow on traced slots (RL004), and the fabric underneath it all
stays scatter-free (RL005 via the ``segmented_route``/``cluster_route``
docstring tags).  Run ``repro-lint src benchmarks tests examples
--strict`` (or ``python -m repro.analysis ...``) to verify the whole
contract; the CI lint lane does it on every push.

Telemetry-leaves rules (the observability extension of the contract)
--------------------------------------------------------------------
With ``telemetry=True`` (the default) the state grows
``SimState.telemetry`` - a per-chain ``telemetry.Telemetry`` of three
device-side groups updated INSIDE the jitted tick: the [C, OPCLASS, BKT]
exit-latency histogram (scattered over the same masked exit batch
``ReplyLog.append`` consumes, AFTER wave-control diversion, so its
percentiles agree with the log's exactly whenever the log doesn't
overflow - and keep working after it does), the [C, W, F] flight-recorder
ring (one health row per tick at a wrapping cursor, written at the
cluster level from the tick's own metric deltas), and the qid-hash-
sampled [C, S, HOPS] per-hop trace buffer (fed from the pre-admission
arrival batch, so stale-NACKed arrivals are visible; exits are the reply
log's job).  Ownership is one-directional: the device writes, the host
only READS - ``obs.TelemetryHub.snapshot`` transfers telemetry leaves
(plus metrics and the tick counter) from the *returned* state and never
the reply-log body, so observation costs no device round-trips while the
engine runs.  ``telemetry=False`` follows the ``wave_depth == 0``
pattern: zero-size leaves ride the pytree and the compiled tick is
bit-identical to the telemetry-less engine.

Machine-checked by repro-lint: telemetry state is a *traced leaf* of the
donated tick, never a Python-level constant - RL002 rejects a histogram
or ring closed over at trace time, RL003 pins every ``Telemetry`` lane
to strong int32 (a weak bucket increment would flip the abstract value
and recompile the donated tick), RL001 guards snapshot-then-tick callers
against use-after-donate, and RL004 keeps the host from branching on
traced telemetry values inside the jitted stages
(``if self.telemetry:`` is static - self is position 0).  The
known-clean/known-bad pair in tests/lint_corpus/telemetry_{clean,bad}.py
pins this coverage.

Open-loop harness rules (on-device RNG as traced state)
-------------------------------------------------------
``ChainSim.run_openloop`` fuses workload *generation* into the donated
scan (``core/loadgen.py``): each tick's arrivals are drawn on device
from JAX's counter-based PRNG keyed by ``(seed, tick, lane)``, thinned
against the traced offered-load scalar, admitted against lane capacity
with a deferred-arrival backlog, and only then handed to ``tick``.  The
contract extends the traced-leaf discipline to the generator:

* every generator knob (``LoadGenState.qps``, op mix, key CDF, burst
  shape) and the backlog are TRACED leaves of the scan carry - sweeping
  offered load or swapping uniform->zipf popularity is ``_replace`` on
  the state, never a new program.  A 20-point hockey-stick sweep
  compiles ONCE;
* the PRNG is counter-based and stateless: lane draws are pure
  functions of ``(seed, t, lane)`` via ``fold_in``, never a carried
  PRNG key threaded through host code - so any tick's arrivals can be
  re-derived (the follow-up-COMMIT trick) and the whole stream can be
  host-materialized (``loadgen.materialize_stream``) for the
  bit-identical equivalence check against the ``route_stream`` path;
* both paths localize and pack through the SAME
  ``workload.localize_stream`` / ``workload.pack_tick`` helpers, so the
  equivalence contract holds by construction (below saturation - see
  ``core/loadgen.py``);
* ``run_openloop`` donates ``state`` AND ``gen``: callers rebind both
  (``state, gen = sim.run_openloop(state, gen, ticks)``).

Machine-checked by repro-lint: a generator rate/CDF baked in as a
Python-level constant of a jitted draw is RL002 (the compiled program
would replay one frozen load forever - the exact bug the traced ``qps``
leaf exists to prevent), weak python literals into ``LoadGenState`` or
arrival ``Msg`` lanes are RL003 (the weak->strong flip recompiles the
donated scan and silently forks the counter-based draws), and RL001
guards the rebind-both contract at the jitted scan's call sites.  The
known-clean/known-bad pair in tests/lint_corpus/loadgen_{clean,bad}.py
pins this coverage.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import craq, netchain, store as store_lib
from repro.core import loadgen as loadgen_lib
from repro.core import telemetry as telemetry_lib
from repro.core import txn as txn_lib
from repro.core.metrics import Metrics, ReplyLog
from repro.core.stages import stage
from repro.core.store import Store
from repro.core.telemetry import Telemetry
from repro.core.txn import LockTable, WaveState
from repro.core.types import (
    CLIENT_BASE,
    MULTICAST,
    N_OPCLASS,
    OP_READ_REPLY,
    NOWHERE,
    OP_NOP,
    OP_PREPARE_ACK,
    OP_PREPARE_NACK,
    OP_READ,
    OP_STALE_NACK,
    OP_TXN_REPLY,
    OP_WRITE,
    OP_WRITE_NACK,
    TO_CLIENT,
    WAVE_BASE,
    ChainConfig,
    ClusterConfig,
    Msg,
    PartitionMap,
    Roles,
    as_cluster,
    is_txn_op,
)

NODE_STEPS: dict[str, Callable] = {
    "netcraq": craq.node_step,
    "netchain": netchain.node_step,
}


class SimState(NamedTuple):
    stores: Store        # leading [C, n] axes
    inbox: Msg           # [C, n, cap]
    locks: LockTable     # [C, K] per-chain lock/intent registers (DP-owned;
                         #     see the lock-table rules in the docstring)
    metrics: Metrics     # [C] per-chain counters (Metrics.total() reduces)
    replies: ReplyLog    # [C, R]
    roles: Roles         # [C, n] live membership/role table (CP-owned; see
                         #     the module docstring's contract)
    pmap: PartitionMap   # versioned bucket->chain partition map (CP-owned;
                         #     see the partition-epoch rules above)
    wave: WaveState      # [C, W] in-network 2PC coordinator slots (device-
                         #     owned after host admission; see the wave-table
                         #     rules above - zero-size when wave_depth == 0)
    telemetry: Telemetry  # [C] per-chain telemetry plane (device-written,
                         #     host-read; see the telemetry-leaves rules
                         #     above - zero-size when telemetry=False)
    t: jax.Array         # [] int32 tick counter (shared; chains are in step)


def stale_route_admission(msg: Msg, slot_epoch: jax.Array,
                          slot_bucket: jax.Array, src_pos):
    """Partition-epoch admission, shared by both engines (the per-node
    code must stay identical - see the partition-epoch rules above).

    ``msg`` is a flat [M] batch already entry-stamped; ``slot_epoch``/
    ``slot_bucket`` are this chain's [K] occupancy rows; ``src_pos`` is
    the entry node id per slot ([M] array or scalar).  A client op whose
    map stamp predates the last migration that touched its slot - or that
    targets a slot no bucket occupies - is consumed and NACK-redirected.
    Returns ``(kept_msg, nack_replies, n_stale)``.
    """
    K = slot_epoch.shape[0]
    sk = jnp.clip(msg.key, 0, K - 1)
    slot_current = (
        (msg.key >= 0) & (msg.key < K)
        & (msg.ver >= slot_epoch[sk])
        & (slot_bucket[sk] >= 0)
    )
    is_stale = (
        (msg.op != OP_NOP) & (msg.src >= CLIENT_BASE) & ~slot_current
    )
    nack = msg._replace(
        op=jnp.where(is_stale, OP_STALE_NACK, OP_NOP),
        value=jnp.zeros_like(msg.value),
        seq=jnp.full_like(msg.seq, -1),
        src=jnp.broadcast_to(
            jnp.asarray(src_pos, jnp.int32), msg.src.shape),
        dst=jnp.where(is_stale, TO_CLIENT, NOWHERE),
    ).mask(is_stale)
    return msg.mask(~is_stale), nack, is_stale.sum()


def full_roles_table(n_nodes: int, n_chains: int) -> Roles:
    """[C, n] role table with every physical slot live (initial health)."""
    one = Roles.from_membership(n_nodes, range(n_nodes))
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_chains,) + x.shape), one
    )


# ---------------------------------------------------------------------------
# Routing fabric
# ---------------------------------------------------------------------------
# Both fabrics implement the same delivery contract over a flat [M] outbox:
# a live unicast message lands in its destination's inbox, a MULTICAST
# message lands in every live node's inbox except its sender's (multicast
# copies carry their per-recipient hop cost in ``extra``), each inbox keeps
# its deliveries in flat-outbox order (per-destination FIFO) truncated to
# ``c_route`` slots, and per-node overflow is counted.  They return
# ``(routed [n, c_route], dropped [n], mcast_copies, mcast_hop_sum)`` with
# bit-identical contents - ``dense_route`` is the original O(n*M log M)
# reference (one delivery matrix plus a per-node argsort over the whole
# outbox), ``segmented_route`` the O(M log M) production fabric (one
# segmented sort; see its docstring).  Contract: ``c_route <= M`` (the
# engine's outbox is always several times wider than the inbox it
# re-fills).  The equivalence is property-tested in tests/test_fabric.py
# and benchmarked in benchmarks/fig_tick_cost.py.

def fabric_masks(flat: Msg, alive: jax.Array):
    """Classify a flat outbox: (is_unicast, is_mcast, is_exit, dead_letters).

    ``dead_letters`` are lost traffic: unicast addressed to a dead node, or
    orphaned entirely (dst == NOWHERE, e.g. a CR reply retracing past a dead
    entry node runs off the head) - they must show up in drop accounting.
    """
    n = alive.shape[0]
    live = flat.op != OP_NOP
    in_range = (flat.dst >= 0) & (flat.dst < n)
    dst_alive = alive[jnp.clip(flat.dst, 0, n - 1)]
    is_mcast = live & (flat.dst == MULTICAST)
    is_exit = live & (flat.dst == TO_CLIENT)
    is_unicast = live & in_range & dst_alive
    dead_letters = (live & in_range & ~dst_alive) | (
        live & ~in_range & ~is_mcast & ~is_exit
    )
    return is_unicast, is_mcast, is_exit, dead_letters


def dense_route(flat: Msg, alive: jax.Array, chain_pos: jax.Array,
                c_route: int):
    """The pre-segmented reference fabric: materialize the full [n, M]
    delivery matrix, then per node gather + ``argsort(~mask, stable=True)``
    compaction.  Kept as the equivalence oracle for the property tests and
    the old-vs-new baseline in benchmarks/fig_tick_cost.py - the production
    engine uses ``segmented_route``.
    """
    n = alive.shape[0]
    is_unicast, is_mcast, _, _ = fabric_masks(flat, alive)
    node_ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    # per-destination delivery masks [n, M]; multicast (the PRE) fans out
    # only to the chain's *live* members (the CP pruned the group)
    deliver = (
        (is_unicast & (flat.dst[None, :] == node_ids))
        | (is_mcast[None, :] & (flat.src[None, :] != node_ids))
    ) & alive[:, None]
    pos_of = lambda i: chain_pos[jnp.clip(i, 0, n - 1)]
    mcast_hops = jnp.abs(chain_pos[:, None] - pos_of(flat.src)[None, :])
    mcast_deliver = deliver & is_mcast[None, :]
    mcast_copies = jnp.sum(mcast_deliver)
    mcast_hop_sum = jnp.sum(jnp.where(mcast_deliver, mcast_hops, 0))

    def gather_for(node_id):
        m = deliver[node_id]
        hop_add = jnp.where(is_mcast, mcast_hops[node_id], 0)
        msg = flat._replace(extra=flat.extra + hop_add).mask(m)
        order = jnp.argsort(~m, stable=True)
        msg = jax.tree.map(lambda x: x[order][:c_route], msg)
        dropped = jnp.maximum(m.sum() - c_route, 0)
        return msg, dropped

    routed, dropped = jax.vmap(gather_for)(node_ids[:, 0])
    return routed, dropped, mcast_copies, mcast_hop_sum


def segmented_route(flat: Msg, alive: jax.Array, chain_pos: jax.Array,
                    c_route: int, mcast_lane: int | None = None):
    """The production fabric: ONE stable sort of the flat [M] outbox keyed
    by ``(destination segment, original index)`` replaces the [n, M]
    delivery matrix and the n per-node argsorts - O(M log M) total.

    The composite key puts every unicast message in its destination's
    segment, every MULTICAST message in one shared segment and everything
    else (exits, dead letters, NOPs) in a sink, with the original flat
    index as the tie-break - so after one sort the per-destination runs are
    contiguous *and* in flat-outbox order (the same per-destination FIFO
    the dense fabric's ``argsort(~mask, stable=True)`` produced).  Unicast
    runs scatter straight into the ``[n, c_route]`` inbox; their slot also
    counts the multicast messages delivered ahead of them (a searchsorted
    against the multicast segment), so the interleaving is exact.

    Multicast is the one genuinely one-to-many part: its copies are
    materialized from a bounded ``mcast_lane`` slice of the multicast
    segment (hop accounting batched per copy through the same segment
    arithmetic).  A lane of ``c_route + max_per_source`` is exact, because
    a copy can only displace lane entries from its own source's exclusion:
    the engine passes ``c_route + M // n`` (every outbox message carries
    ``src == emitting node``, so one source contributes at most its own
    outbox width).  Callers feeding adversarial ``src`` fields (the
    property tests) pass ``mcast_lane=M``.  Drop counts never depend on the
    lane - they come from exact segment-length arithmetic.

    repro-lint: scatter-free - this fabric's O(M log M) headline depends
    on sort + searchsorted + gather only; RL005 rejects any ``.at[...]``
    batch scatter added to this function.
    """
    n = alive.shape[0]
    M = flat.op.shape[0]
    L = M if mcast_lane is None else min(M, mcast_lane)
    is_unicast, is_mcast, _, _ = fabric_masks(flat, alive)
    idx = jnp.arange(M, dtype=jnp.int32)
    i32 = jnp.int32

    # ---- the one sort: segment = dst | mcast(n) | sink(n+1) --------------
    # The composite key already carries the payload: its low half IS the
    # original index, so a plain value sort replaces an argsort (the
    # (key, iota) pair sort costs several times more on most backends)
    # and ``skey % M`` recovers the permutation.
    seg = jnp.where(is_unicast, flat.dst, jnp.where(is_mcast, n, n + 1))
    key = seg.astype(i32) * M + idx
    skey = jnp.sort(key)      # unique keys -> total (stable) order
    order = skey % M
    # segment boundaries: [seg_start[i], seg_start[i+1]) is node i's
    # unicast run; [seg_start[n], seg_start[n+1]) is the multicast run.
    seg_start = jnp.searchsorted(
        skey, jnp.arange(n + 2, dtype=i32) * M
    ).astype(i32)
    m_mc = seg_start[n + 1] - seg_start[n]

    # ---- per-source multicast index (for the src != node exclusion) ------
    src_ok = (flat.src >= 0) & (flat.src < n)
    src_key = jnp.where(
        is_mcast & src_ok, flat.src.astype(i32) * M + idx, i32(n) * M
    )
    src_key = jnp.sort(src_key)
    src_start = jnp.searchsorted(
        src_key, jnp.arange(n + 1, dtype=i32) * M
    ).astype(i32)

    # counts by segment arithmetic (no delivery matrix anywhere):
    #   mcast with original index < f            -> 1D prefix count
    #   mcast with original index < f, src == i  -> searchsorted(src seg i)
    #   unicast to i with original index < f     -> searchsorted(uni seg i)
    mc_cum = jnp.cumsum(is_mcast.astype(i32))

    def mc_before(f):
        return mc_cum[f] - is_mcast[f].astype(i32)

    def mc_src_before(i, f):
        return jnp.searchsorted(src_key, i * M + f).astype(i32) - src_start[i]

    def uni_before(i, f):
        return jnp.searchsorted(skey, i * M + f).astype(i32) - seg_start[i]

    # The inbox is built WITHOUT any batch scatter: each delivery's slot
    # is strictly increasing along its run, so the (row, slot) -> source
    # map is itself a sorted sequence and every output slot can *binary
    # search* its source instead (scatters serialize on most backends;
    # searches and gathers vectorize).

    # ---- unicast placement: slot of sorted entry j in its row ------------
    j = jnp.arange(M, dtype=i32)
    sdst = skey // M          # segment of sorted slot j
    sidx = skey % M           # original flat index of sorted slot j
    is_uni_j = sdst < n
    dc = jnp.clip(sdst, 0, n - 1)
    pos_u = (j - seg_start[dc]) + mc_before(sidx) - mc_src_before(dc, sidx)
    # (row, slot) placement key; strictly increasing over unicast entries
    # (rows ascend, slots ascend within a row), sink for everything else -
    # non-unicast sorted entries already sit at the tail, keeping it sorted
    S = M + 1
    place_u = jnp.where(is_uni_j, dc * S + jnp.minimum(pos_u, M), n * S)

    # ---- multicast placement: bounded lane, one copy per (node, entry) ---
    lane = jnp.arange(L, dtype=i32)
    p = jnp.clip(seg_start[n] + lane, 0, max(M - 1, 0))
    lane_live = lane < m_mc
    lane_idx = skey[p] % M
    lane_src = flat.src[order[p]]
    rows = jnp.arange(n, dtype=i32)[:, None]              # [n, 1]
    deliver_m = lane_live[None, :] & alive[:, None] & (lane_src[None, :] != rows)
    pos_m = (
        uni_before(rows, lane_idx[None, :])
        + lane[None, :]
        - mc_src_before(rows, lane_idx[None, :])
    )
    # Delivered copies' slots ascend within a row, but skipped lane entries
    # (the sender's own row, dead rows, beyond-m_mc padding) intersperse -
    # a suffix-min sweep replaces each skipped entry with its next
    # delivered successor's slot, restoring a searchable monotone array
    # while remembering which lane entry actually owns the slot.
    big = i32(M)
    rev = lambda x: jnp.flip(x, axis=-1)
    mono_m = rev(jax.lax.cummin(
        rev(jnp.where(deliver_m, jnp.minimum(pos_m, M), big)), axis=1
    ))                                                    # [n, L]
    next_del = rev(jax.lax.cummin(
        rev(jnp.where(deliver_m, lane[None, :], i32(L))), axis=1
    ))                                                    # [n, L]
    place_m = (rows * S + mono_m).reshape(-1)

    # ---- materialize: every inbox slot binary-searches its source --------
    slot_key = (jnp.arange(n, dtype=i32)[:, None] * S
                + jnp.arange(c_route, dtype=i32)[None, :]).reshape(-1)
    ju = jnp.clip(jnp.searchsorted(place_u, slot_key).astype(i32), 0, M - 1)
    jm = jnp.clip(
        jnp.searchsorted(place_m, slot_key).astype(i32), 0, n * L - 1
    )
    hit_u = place_u[ju] == slot_key
    hit_m = place_m[jm] == slot_key
    lane_of = jnp.clip(next_del.reshape(-1)[jm], 0, L - 1)
    # a slot is filled by exactly one delivery: its unicast entry or its
    # multicast lane copy (positions within a row are a permutation)
    src_sorted_pos = jnp.where(hit_u, ju, p[lane_of])
    fidx = order[src_sorted_pos]              # flat-outbox index per slot
    filled = hit_u | hit_m
    routed: Msg = jax.tree.map(lambda x: x[fidx], flat).mask(filled)
    routed = jax.tree.map(
        lambda x: x.reshape((n, c_route) + x.shape[1:]), routed
    )
    # multicast copies accumulate their per-recipient hop cost; delivered
    # copies are exactly the slots that gathered a MULTICAST-dst message
    # (sentinel slots gathered dst == NOWHERE)
    copy_hop = jnp.abs(
        chain_pos[:, None]
        - chain_pos[jnp.clip(routed.src, 0, n - 1)]
    )
    routed = routed._replace(
        extra=routed.extra
        + jnp.where(routed.dst == MULTICAST, copy_hop, 0)
    )

    # ---- exact counters from segment lengths (lane-independent) ----------
    uni_cnt = seg_start[1:n + 1] - seg_start[:n]          # [n]
    src_cnt = src_start[1:n + 1] - src_start[:n]          # [n]
    deliver_cnt = uni_cnt + jnp.where(alive, m_mc - src_cnt, 0)
    dropped = jnp.maximum(deliver_cnt - c_route, 0)

    n_alive = alive.sum()
    src_alive = src_ok & alive[jnp.clip(flat.src, 0, n - 1)]
    mcast_copies = jnp.sum(
        jnp.where(is_mcast, n_alive - src_alive.astype(i32), 0)
    )
    # hop total per multicast message: sum over live recipients of
    # |chain_pos[i] - chain_pos[src]| (the sender's own term is zero, so no
    # exclusion correction is needed)
    hop_to_all = jnp.sum(
        jnp.where(alive[None, :],
                  jnp.abs(chain_pos[None, :] - chain_pos[:, None]), 0),
        axis=1,
    )                                                     # [n] by source
    mcast_hop_sum = jnp.sum(
        jnp.where(is_mcast, hop_to_all[jnp.clip(flat.src, 0, n - 1)], 0)
    )
    return routed, dropped, mcast_copies, mcast_hop_sum


def cluster_route(flat: Msg, target: jax.Array, n_chains: int, cap: int):
    """Cluster-level router for coordinator traffic: deliver each live
    message of a flat [N] batch to the chain named by ``target`` ([N]
    int32; -1 = drop).  Same segmented-sort idiom as the per-chain fabric
    - one value sort of ``(target segment, original index)``, so each
    chain's deliveries arrive contiguous and in flat order - but across
    the *chain* axis, which the per-chain fabric never crosses.  Returns
    ``(routed [n_chains, cap] Msg, overflow [n_chains] counts)``; messages
    beyond ``cap`` in any chain's run are dropped (the engine sizes caps
    to the exact worst case, so overflow only occurs when a caller shrinks
    ``wave_route_capacity`` below it - and is then accounted in drops).

    repro-lint: scatter-free - same guarantee as ``segmented_route``;
    RL005 rejects any ``.at[...]`` batch scatter added here.
    """
    N = flat.op.shape[0]
    i32 = jnp.int32
    live = (flat.op != OP_NOP) & (target >= 0) & (target < n_chains)
    seg = jnp.where(live, target, n_chains)
    key = seg.astype(i32) * N + jnp.arange(N, dtype=i32)
    skey = jnp.sort(key)
    order = skey % N
    starts = jnp.searchsorted(
        skey, jnp.arange(n_chains + 1, dtype=i32) * N
    ).astype(i32)
    cnt = starts[1:] - starts[:-1]                        # [C]
    idx = starts[:-1][:, None] + jnp.arange(cap, dtype=i32)[None, :]
    valid = jnp.arange(cap, dtype=i32)[None, :] < cnt[:, None]
    gidx = order[jnp.clip(idx, 0, max(N - 1, 0))]
    routed: Msg = jax.tree.map(lambda x: x[gidx], flat)
    routed = jax.vmap(Msg.mask)(routed, valid)
    return routed, jnp.maximum(cnt - cap, 0)


def pack_lanes(msgs: list[Msg]) -> Msg:
    """Concatenate [n, w_k] message lanes along axis 1 by writing each lane
    into one pre-allocated [n, sum(w_k)] buffer (replaces the per-field
    ``jnp.concatenate`` chains on the tick's hot path; layout - and thus
    the fabric's flat-index FIFO order - is identical)."""
    total = sum(m.op.shape[1] for m in msgs)

    def pack(*cols):
        buf = jnp.zeros(
            cols[0].shape[:1] + (total,) + cols[0].shape[2:], cols[0].dtype
        )
        off = 0
        for c in cols:
            buf = jax.lax.dynamic_update_slice_in_dim(buf, c, off, axis=1)
            off += c.shape[1]
        return buf

    return jax.tree.map(pack, *msgs)


class ChainSim:
    """Cluster simulator with exact traffic accounting.

    Accepts a ``ClusterConfig`` (C chains) or a bare ``ChainConfig``
    (single chain).  All state is ``[C, n, ...]``; injection schedules are
    ``[T, C, n, q]`` (a legacy ``[T, n, q]`` schedule is lifted to C=1).
    """

    def __init__(
        self,
        cfg: ChainConfig | ClusterConfig,
        inject_capacity: int = 64,
        route_capacity: int = 256,
        reply_capacity: int = 4096,
        fabric: str = "segmented",
        wave_depth: int = 0,
        wave_keys: int = 4,
        wave_log_capacity: int = 256,
        wave_route_capacity: int | None = None,
        telemetry: bool = True,
        hist_buckets: int = telemetry_lib.DEFAULT_HIST_BUCKETS,
        ring_window: int = 64,
        trace_slots: int = 16,
        trace_hops: int = 32,
    ):
        assert fabric in ("segmented", "dense"), fabric
        self.cluster = as_cluster(cfg)
        self.cfg = self.cluster.chain
        self.C = self.cluster.n_chains
        self.n = self.cfg.n_nodes
        self.c_in = inject_capacity
        self.c_route = route_capacity
        self.capacity = inject_capacity + route_capacity
        self.reply_capacity = reply_capacity
        # In-network 2PC coordinator (wave-table rules, module docstring).
        # wave_depth == 0 (default) keeps every wave leaf zero-size and the
        # compiled tick identical to the wave-less engine.
        self.wave_depth = wave_depth
        self.wave_keys = wave_keys
        self.wave_log_capacity = wave_log_capacity
        # a chain's W slots have <= W*KT outstanding sub-ops with <= 1
        # reply each, so W*KT control-reply slots provably never overflow
        self.coord_capacity = max(wave_depth * wave_keys, 1)
        # worst case every chain's every slot addresses one chain: C*W*KT
        self.wave_sub_capacity = (
            wave_route_capacity
            if wave_route_capacity is not None
            else max(self.C * wave_depth * wave_keys, 1)
        )
        # Telemetry plane (telemetry-leaves rules, module docstring).
        # telemetry=False keeps every telemetry leaf zero-size and the
        # compiled tick identical to the telemetry-less engine.
        self.telemetry = bool(telemetry)
        if self.telemetry:
            assert hist_buckets >= 2 and ring_window >= 1
            assert trace_slots >= 1 and trace_hops >= 1
        self.hist_buckets = hist_buckets if self.telemetry else 0
        self.ring_window = ring_window if self.telemetry else 0
        self.trace_slots = trace_slots if self.telemetry else 0
        self.trace_hops = trace_hops if self.telemetry else 0
        # "segmented" (default) is the O(M log M) production fabric;
        # "dense" is the faithful pre-segmented engine - the [n, M]-matrix
        # router plus its O(B^2) txn-stage ranking and scatter-per-field
        # reply logging - kept as the bit-identical reference baseline
        # (see benchmarks/fig_tick_cost.py)
        self.fabric = fabric
        self.node_step = NODE_STEPS[self.cfg.protocol]

    # -- state ------------------------------------------------------------
    def _init_chain_state(self):
        """State of ONE chain (no chain axis) - vmapped over C in init."""
        stores = jax.vmap(lambda _: store_lib.init_store(self.cfg))(
            jnp.arange(self.n)
        )
        return (
            stores,
            # carry width is c_route: tick consumes [c_in + c_route] and
            # re-emits a routed inbox of width c_route (scan-stable shapes)
            jax.vmap(lambda _: Msg.empty(self.c_route, self.cfg.value_words))(
                jnp.arange(self.n)
            ),
            Metrics.zeros(self.cluster.num_buckets),
            ReplyLog.empty(self.reply_capacity),
            WaveState.empty(
                self.wave_depth, self.wave_keys, self.wave_log_capacity,
                self.coord_capacity, self.cfg.value_words,
            ),
            Telemetry.empty(
                self.hist_buckets, self.ring_window, self.trace_slots,
                self.trace_hops,
            ),
        )

    def init_state(self) -> SimState:
        stores, inbox, metrics, replies, wave, tel = jax.vmap(
            lambda _: self._init_chain_state()
        )(jnp.arange(self.C))
        return SimState(
            stores=stores,
            inbox=inbox,
            locks=jax.vmap(lambda _: txn_lib.init_locks(self.cfg))(
                jnp.arange(self.C)
            ),
            metrics=metrics,
            replies=replies,
            roles=full_roles_table(self.n, self.C),
            pmap=self.cluster.default_partition(),
            wave=wave,
            telemetry=tel,
            t=jnp.zeros((), jnp.int32),
        )

    def empty_injection(self) -> Msg:
        """All-NOP [C, n, c_in] injection with this engine's value width -
        the canonical drain tick (and the template for spare-lane edits)."""
        return jax.tree.map(
            lambda x: jnp.tile(
                x[None, None], (self.C, self.n) + (1,) * x.ndim
            ),
            Msg.empty(self.c_in, self.cfg.value_words),
        )

    # -- one tick of ONE chain (vmapped over the chain axis) ---------------
    def _chain_tick(self, stores, inbox, locks, metrics, replies, injected,
                    roles, pmap, t, sub_in=None, wave_final=None, tel=None):
        """stores [n,...], inbox [n,c_route], locks [K]-leaf LockTable,
        injected [n,c_in], roles [n]-leaf Roles table, pmap this chain's
        PartitionMap view ([K] slot rows, shared [G] columns), t [].

        Returns (stores', inbox', locks', metrics', replies').  The routing
        fabric is local to the chain: unicast/multicast destinations are
        chain positions, so nothing ever crosses into another chain's
        state.  Membership is read from ``roles`` - dead slots are masked
        out of injection, processing, delivery and hop accounting.  Client
        ops routed under a stale partition map are NACK-redirected at the
        entry node (see the partition-epoch rules), then transaction ops
        are resolved by the head's lock stage before the node step sees
        the batch (see txn.head_txn_stage).

        With ``wave_depth > 0`` two extra lanes ride the tick (wave-table
        rules, module docstring): ``sub_in`` [Xs] is the flat batch of
        coordinator sub-ops the cluster router delivered to this chain
        (they enter at the live head like client transaction traffic) and
        ``wave_final`` [W] is this chain's coordinator's final client
        replies (they exit through the fabric like any tail reply).  The
        return grows a sixth element ``ctrl_out``: the flat exit stream
        addressed back at coordinators (``client >= WAVE_BASE``) that the
        cluster-level control router delivers instead of the reply log.

        With ``telemetry=True`` this chain's ``tel`` Telemetry rides the
        tick as a trailing traced argument (telemetry-leaves rules): the
        latency histogram accumulates over the same masked exit batch the
        reply log consumes, and the trace buffer samples the pre-admission
        arrival batch; the updated Telemetry is appended to the return
        (the ring row is written at the cluster level, in ``tick``).
        """
        n, cfg = self.n, self.cfg
        alive = roles.alive          # [n] bool
        chain_pos = roles.chain_pos  # [n] int32 live-chain coordinate

        with stage("ingress"):
            # Stamp entry position on client queries, merge into inboxes.
            # The client->entry-node leg is one link traversal (counted here;
            # `extra` carries it into the query's hop total).  Queries injected
            # into a dead node's lane are black-holed (the client's redirect is
            # a host-side FailoverPolicy decision, not the fabric's) - they are
            # dropped before any packet accounting, as are in-flight messages
            # still parked at a node that died between ticks.
            injected = jax.vmap(craq.stamp_entry)(
                injected, jnp.arange(n, dtype=jnp.int32))
            dead_in = (
                ((injected.op != OP_NOP) & ~alive[:, None]).sum()
                + ((inbox.op != OP_NOP) & ~alive[:, None]).sum()
            )
            injected = jax.vmap(Msg.mask)(
                injected, jnp.broadcast_to(alive[:, None], injected.op.shape)
            )
            inbox = jax.vmap(Msg.mask)(
                inbox, jnp.broadcast_to(alive[:, None], inbox.op.shape)
            )
            inj_live = injected.op != OP_NOP
            injected = injected._replace(
                extra=injected.extra + inj_live.astype(jnp.int32)
            )
            n_injected = inj_live.sum()
            lanes = [injected, inbox]
            n_wave_in = jnp.zeros((), jnp.int32)
            if self.wave_depth:
                # Coordinator sub-ops enter at the live head (the node their
                # locks live at), entry-stamped and leg-accounted exactly like
                # a client query - the head cannot tell a wave PREPARE from a
                # host-planned one (src >= WAVE_BASE >= CLIENT_BASE).
                head = roles.head_pos[0]
                sub_live = sub_in.op != OP_NOP
                n_wave_in = sub_live.sum()
                sub_in = sub_in._replace(
                    entry=jnp.where(sub_live, head, sub_in.entry),
                    extra=sub_in.extra + sub_live.astype(jnp.int32),
                )
                at_head = jnp.arange(n, dtype=jnp.int32)[:, None] == head
                sub_lane: Msg = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), sub_in
                )
                sub_lane = jax.vmap(Msg.mask)(
                    sub_lane,
                    jnp.broadcast_to(at_head, (n, sub_in.op.shape[0])),
                )
                lanes.append(sub_lane)
            full_inbox = pack_lanes(lanes)
            # Pipeline passes are counted on arrival (pre-stage): a PREPARE
            # resolved by the lock stage is one match-action pass like any
            # other query.
            live_in = full_inbox.op != OP_NOP

            # Stale-route admission (partition-epoch rules, module docstring):
            # consumed here and NACK-redirected, before the lock stage can
            # grant a lock (or the store serve a read) this chain no longer
            # owns.  Ops on unmoved buckets pass regardless of their stamp.
            cap_total = full_inbox.op.shape[1]
            flat_in: Msg = jax.tree.map(
                lambda x: x.reshape((n * cap_total,) + x.shape[2:]), full_inbox
            )
            node_of_in = jnp.repeat(jnp.arange(n, dtype=jnp.int32), cap_total)
            kept, stale_out, n_stale = stale_route_admission(
                flat_in, pmap.slot_epoch, pmap.slot_bucket, node_of_in
            )
            lift_in = lambda m: jax.tree.map(
                lambda x: x.reshape((n, cap_total) + x.shape[1:]), m
            )
            full_inbox = lift_in(kept)
            stale_out = lift_in(stale_out)

            # Lease expiry BEFORE the lock stage (lock-lease rules, module
            # docstring): reclaim locks held past their lease and bump their
            # version counters, so an expired holder's straggler COMMIT in
            # this very batch already fails release validation and NACKs.
            locks, n_expired = txn_lib.lease_expiry_stage(locks, t)

            # Transaction stage at the live head: PREPARE/ABORT are consumed
            # (lock edits + ACK/NACK replies), validated COMMITs pass through
            # to the node step as write-like ops.
            new_locks, full_inbox, txn_out, txn_counts = txn_lib.head_txn_stage(
                locks, roles, stores, full_inbox, t=t,
                dense_rank=self.fabric == "dense",
            )

        with stage("node_step"):
            # Process: vmapped match-action pipeline pass on every node.
            new_stores, outbox = jax.vmap(
                functools.partial(self.node_step, cfg,
                                  dense_rank=self.fabric == "dense")
            )(stores, roles, full_inbox)
        with stage("fabric"):
            # The lock stage's and the stale stage's replies join the node
            # outboxes on the fabric (packet-accounted like any other reply).
            out_lanes = [outbox, txn_out, stale_out]
            if self.wave_depth:
                # the coordinator's final client replies exit from the head
                # like any tail reply (one client leg, reply-logged)
                wf_live = wave_final.op != OP_NOP
                wave_final = wave_final._replace(
                    src=jnp.where(wf_live, head, wave_final.src)
                )
                wf_lane: Msg = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                    wave_final,
                )
                wf_lane = jax.vmap(Msg.mask)(
                    wf_lane,
                    jnp.broadcast_to(at_head, (n, wave_final.op.shape[0])),
                )
                out_lanes.append(wf_lane)
            outbox = pack_lanes(out_lanes)
            # A dead node emits nothing (its inbox is already empty; this pins
            # the invariant even if a node_step ever emitted unsolicited).
            outbox = jax.vmap(Msg.mask)(
                outbox, jnp.broadcast_to(alive[:, None], outbox.op.shape)
            )

            # ---------------- routing fabric ----------------
            flat: Msg = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), outbox
            )  # [M]
            is_unicast, is_mcast, is_exit, dead_letters = fabric_masks(flat, alive)

            # link-traversal accounting in live-chain coordinates: a message
            # travels |chain_pos[dst] - chain_pos[src]| live hops - a failed
            # node is spliced out of the forwarding path, not traversed.
            pos_of = lambda i: chain_pos[jnp.clip(i, 0, n - 1)]
            uni_hops = jnp.abs(pos_of(flat.dst) - pos_of(flat.src))

            # accumulate hop counts onto messages for latency tracking (the
            # fabric adds the per-recipient multicast hops on each copy);
            # the exit-hop term is dtype-pinned - a weak int32 here would
            # flip Msg.extra's abstract value across the tick boundary
            flat = flat._replace(
                extra=flat.extra
                + jnp.where(is_unicast, uni_hops, 0)
                + is_exit.astype(jnp.int32)
            )

            # ---------------- per-node inbox build (capacity-limited) --------
            M = flat.op.shape[0]
            if self.fabric == "dense":
                routed, dropped, _, mcast_hop_sum = dense_route(
                    flat, alive, chain_pos, self.c_route
                )
            else:
                # every outbox message carries src == emitting node, so one
                # source contributes at most its own outbox width to the
                # multicast stream - c_route + M // n is an exact lane bound
                routed, dropped, _, mcast_hop_sum = segmented_route(
                    flat, alive, chain_pos, self.c_route,
                    mcast_lane=self.c_route + M // n,
                )

            packets = (
                jnp.sum(jnp.where(is_unicast, uni_hops, 0))
                + mcast_hop_sum
                + jnp.sum(is_exit)  # final leg to the client
                + n_injected        # client -> entry-node leg
                + n_wave_in         # coordinator -> head leg (wave sub-ops)
            )
            msg_bytes = cfg.header_bytes + cfg.payload_bytes

        with stage("reply_log"):
            # ---------------- exits -> reply log ----------------
            # Exits addressed back at a coordinator (client >= WAVE_BASE) are
            # 2PC control replies for the wave table: diverted to the cluster
            # control router (ctrl_out), never reply-logged.
            if self.wave_depth:
                wave_bound = is_exit & (flat.client >= WAVE_BASE)
                ctrl_out = flat.mask(wave_bound)
                is_exit = is_exit & ~wave_bound
            exits = flat.mask(is_exit)
            is_nack = exits.op == OP_WRITE_NACK
            # 2PC control exits (phase-1 ACKs, prepare NACKs, abort acks) and
            # stale-route redirects are logged for the planner/client but
            # excluded from the `replies` throughput counter: only completed
            # client operations count, and a committed transaction's
            # completion is its tail OP_TXN_REPLY (seq >= 0).
            is_ctrl = (
                (exits.op == OP_PREPARE_ACK)
                | (exits.op == OP_PREPARE_NACK)
                | (exits.op == OP_STALE_NACK)
                | ((exits.op == OP_TXN_REPLY) & (exits.seq < 0))
            )
            new_replies = replies.append(exits, t + 1,
                                         dense=self.fabric == "dense")

        if self.telemetry:
            # ---------------- telemetry plane (telemetry-leaves rules) ----
            with stage("telemetry"):
                # The histogram sees the SAME exit batch the reply log appends
                # (wave-control replies already diverted), at the same t_done
                # stamp - so histogram percentiles and exact ReplyLog ones are
                # the same multiset whenever the log doesn't overflow.  NOP
                # padding classifies to -1 and scatters out of bounds.
                tel = tel._replace(lat_hist=telemetry_lib.record_latency(
                    tel.lat_hist, exits.op, exits.seq, t + 1 - exits.t_inject
                ))
                # Hop events from the pre-admission arrival batch: every
                # message a live node observed this tick, including arrivals
                # the stale-route stage then NACKs.  Exit events are the reply
                # log's job.
                tel = telemetry_lib.record_trace(
                    tel, flat_in.op, flat_in.qid, node_of_in, t
                )

        with stage("counters"):
            # Per-bucket conflict heat (ROADMAP item-1 telemetry): every
            # PREPARE the lock stage denied, scattered onto the bucket that
            # owns the contended slot.  A raw integral the CP can EWMA-decay
            # host-side to find buckets worth splitting or rebalancing.
            B = metrics.conflict_heat.shape[0]
            tko = txn_out.op.reshape(-1)
            tkk = txn_out.key.reshape(-1)
            bi = pmap.slot_bucket[
                jnp.clip(tkk, 0, pmap.slot_bucket.shape[0] - 1)
            ]
            is_cnack = (tko == OP_PREPARE_NACK) & (bi >= 0)
            new_heat = metrics.conflict_heat.at[
                jnp.where(is_cnack, bi, B)
            ].add(1, mode="drop")
            store_rows = (
                jax.vmap(craq.commit_rows)(roles, full_inbox).sum()
                if cfg.protocol == "netcraq" else 0
            )

            new_metrics = Metrics(
                packets=metrics.packets + packets,
                bytes=metrics.bytes + packets * msg_bytes,
                kv_procs=metrics.kv_procs + live_in.sum(),
                reads_in=metrics.reads_in
                + jnp.sum(injected.op == OP_READ),
                writes_in=metrics.writes_in
                + jnp.sum(injected.op == OP_WRITE),
                replies=metrics.replies
                + (exits.live() & ~is_nack & ~is_ctrl).sum(),
                dirty_appends=metrics.dirty_appends
                + (new_stores.pending.sum() - stores.pending.sum()).clip(0),
                store_rows=metrics.store_rows + store_rows,
                drops=metrics.drops + dropped.sum() + dead_in + dead_letters.sum(),
                relay_procs=metrics.relay_procs
                + jnp.sum(live_in & (full_inbox.op == OP_READ_REPLY)),
                write_nacks=metrics.write_nacks + is_nack.sum(),
                txn_commits=metrics.txn_commits + txn_counts[0],
                txn_aborts=metrics.txn_aborts + txn_counts[1],
                lock_conflicts=metrics.lock_conflicts + txn_counts[2],
                stale_routes=metrics.stale_routes + n_stale,
                # bumped by the CP (complete_rebalance), never by the tick
                migration_moves=metrics.migration_moves,
                # bumped by the coordinator stage in ``tick`` (the wave vmap
                # runs outside this per-chain function)
                wave_commits=metrics.wave_commits,
                wave_aborts=metrics.wave_aborts,
                wave_occupancy=metrics.wave_occupancy,
                # bumped by the open-loop generator stage in ``run_openloop``
                # (admission happens before the injection reaches the tick)
                offered=metrics.offered,
                admission_drops=metrics.admission_drops,
                lease_expiries=metrics.lease_expiries + n_expired,
                conflict_heat=new_heat,
            )

        out = [new_stores, routed, new_locks, new_metrics, new_replies]
        if self.wave_depth:
            out.append(ctrl_out)
        if self.telemetry:
            out.append(tel)
        return tuple(out)

    def _lift(self, injected: Msg) -> Msg:
        """Accept legacy single-chain [n, q] injections when C == 1."""
        if injected.op.ndim == 2:
            assert self.C == 1, (
                f"injection lacks the chain axis but cluster has C={self.C}"
            )
            return jax.tree.map(lambda x: x[None], injected)
        return injected

    # -- one tick of the whole cluster -------------------------------------
    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def tick(self, state: SimState, injected: Msg) -> SimState:
        """injected: [C, n, c_in] client queries addressed to their entry
        node within their key's owning chain (see workload.make_schedule).

        Membership (``state.roles``) and the partition map (``state.pmap``)
        are traced leaves: the CP may swap either between ticks without
        triggering a recompile.

        The input ``state`` is DONATED: its buffers are reused for the
        output (ticking a [C, n, ...] cluster allocates no new state), so
        callers must follow the ``state = sim.tick(state, inj)`` rebinding
        pattern and never touch the pre-tick state object again.  Host-side
        readers (metrics, reply cursors, CP assertions) read the *returned*
        state; the CP's own truth lives outside the state pytree."""
        injected = self._lift(injected)
        # The per-chain view of the map: the [C, K] slot tables vmap over
        # the chain axis; the bucket columns and epoch are shared.
        pmap_axes = PartitionMap(
            owner=None, base=None, epoch=None, slot_bucket=0, slot_epoch=0
        )
        # telemetry rides the per-chain tick as a trailing traced argument
        # (telemetry-leaves rules; vmap in_axes is positional, so the lane
        # only exists when the plane is live)
        tel_axes = (0,) if self.telemetry else ()
        tel_args = (state.telemetry,) if self.telemetry else ()
        if self.wave_depth:
            # ---- in-network coordinator stage (wave-table rules) --------
            # Runs BEFORE the chain ticks on last tick's control replies
            # (wave.coord_in): transitions slots, emits this tick's
            # PREPARE/COMMIT/ABORT sub-ops and final client replies.
            # the per-chain lease length rides in so PREP slots older than
            # the lease force-abort (lock-lease rules, module docstring)
            with stage("wave"):
                wave, sub_out, sub_target, final_out, wstats = jax.vmap(
                    txn_lib.wave_coordinator_step, in_axes=(0, 0, None, 0)
                )(state.wave, jnp.arange(self.C, dtype=jnp.int32), state.t,
                  state.locks.lease_ticks)
                # sub-ops cross chains: one cluster-level segmented route to
                # each key's owning chain (the per-chain fabric never crosses)
                flat_sub: Msg = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), sub_out
                )
                sub_in, sub_drop = cluster_route(
                    flat_sub, sub_target.reshape(-1), self.C,
                    self.wave_sub_capacity,
                )
            outs = jax.vmap(
                self._chain_tick,
                in_axes=(0, 0, 0, 0, 0, 0, 0, pmap_axes, None, 0, 0)
                + tel_axes,
            )(state.stores, state.inbox, state.locks, state.metrics,
              state.replies, injected, state.roles, state.pmap, state.t,
              sub_in, final_out, *tel_args)
            stores, inbox, locks, metrics, replies, ctrl_out = outs[:6]
            # control replies ride back to their coordinator's chain and
            # land in its coord_in buffer for next tick's stage - the
            # coordinator id encodes the chain (client = WAVE_BASE +
            # chain * W + slot)
            with stage("wave"):
                flat_ctrl: Msg = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), ctrl_out
                )
                ctrl_tgt = jnp.where(
                    flat_ctrl.op != OP_NOP,
                    (flat_ctrl.client - WAVE_BASE) // self.wave_depth,
                    -1,
                )
                coord_in, ctrl_drop = cluster_route(
                    flat_ctrl, ctrl_tgt, self.C, self.coord_capacity
                )
                wave = wave._replace(coord_in=coord_in)
                metrics = metrics._replace(
                    drops=metrics.drops + sub_drop + ctrl_drop,
                    wave_commits=metrics.wave_commits + wstats[0],
                    wave_aborts=metrics.wave_aborts + wstats[1],
                    wave_occupancy=metrics.wave_occupancy + wstats[2],
                )
            occupancy = wstats[2]
        else:
            outs = jax.vmap(
                self._chain_tick,
                in_axes=(0, 0, 0, 0, 0, 0, 0, pmap_axes, None, None, None)
                + tel_axes,
            )(state.stores, state.inbox, state.locks, state.metrics,
              state.replies, injected, state.roles, state.pmap, state.t,
              None, None, *tel_args)
            stores, inbox, locks, metrics, replies = outs[:5]
            wave = state.wave
            occupancy = jnp.zeros((self.C,), jnp.int32)
        tel = outs[-1] if self.telemetry else state.telemetry
        if self.telemetry:
            # ---------------- flight-recorder ring (telemetry rules) -------
            # One [N_RING_FIELDS] health row per chain per tick: counter
            # deltas of this tick's metrics vs the donated input's (reads
            # of donated buffers are fine inside the trace - donation is a
            # buffer-reuse contract, not a read ban), plus end-of-tick
            # gauges from the freshly routed inbox.  Field order is
            # telemetry.RING_FIELDS.
            with stage("telemetry"):
                live = (inbox.op != OP_NOP).sum(axis=2)  # [C, n]
                delta = lambda f: getattr(metrics, f) - getattr(state.metrics, f)
                row = jnp.stack([
                    jnp.broadcast_to(state.t, (self.C,)),
                    live.sum(axis=1),
                    live.max(axis=1),
                    delta("drops"),
                    delta("lock_conflicts"),
                    occupancy,
                    delta("replies"),
                    delta("stale_routes"),
                ], axis=1)
                tel = jax.vmap(telemetry_lib.record_ring)(tel, row)
        return SimState(
            stores=stores,
            inbox=inbox,
            locks=locks,
            metrics=metrics,
            replies=replies,
            roles=state.roles,
            pmap=state.pmap,
            wave=wave,
            telemetry=tel,
            t=state.t + 1,
        )

    # -- run a schedule -----------------------------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def drain(self, state: SimState, ticks: int) -> SimState:
        """Tick ``ticks`` empty injections as one fused ``lax.scan`` (the
        old host-side drain loop paid per-tick dispatch; the scan is one
        device program).  ``state`` is donated, like ``tick``'s."""
        empty = self.empty_injection()

        def body(st, _):
            return self.tick(st, empty), None

        state, _ = jax.lax.scan(body, state, None, length=ticks)
        return state

    def run(self, state: SimState, schedule: Msg, extra_ticks: int = 16,
            assert_drained: bool = False) -> SimState:
        """schedule: [T, C, n, c_in] (or legacy [T, n, c_in]) injection per
        tick; then drain.  ``state`` is donated (see ``tick``).

        ``assert_drained=True`` raises if any op is still in flight after
        the ``extra_ticks`` drain (``inflight``) - throughput/latency math
        over a run that silently stranded ops undercounts both, so
        benchmarks opt in and size their drains to pass.  Deliberate
        under-drains (measuring a half-full pipeline) keep the default.
        """
        if schedule.op.ndim == 3:
            assert self.C == 1, (
                f"schedule lacks the chain axis but cluster has C={self.C}"
            )
            schedule = jax.tree.map(lambda x: x[:, None], schedule)

        def body(st, inj):
            return self.tick(st, inj), None

        state, _ = jax.lax.scan(body, state, schedule)
        if extra_ticks:
            state = self.drain(state, extra_ticks)
        if assert_drained:
            left = self.inflight(state)
            assert left == 0, (
                f"{left} ops still in flight after extra_ticks="
                f"{extra_ticks} drain - size the drain window up or the "
                "run's throughput/latency accounting is short"
            )
        return state

    def inflight(self, state: SimState) -> int:
        """Host-side count of ops still inside the engine: live inbox
        slots plus (with a wave table) occupied coordinator slots and
        buffered control replies.  Transfers only the masks it reduces -
        the end-of-run accounting ``run(..., assert_drained=True)`` and
        ``run_openloop(..., assert_drained=True)`` check."""
        n = int(jnp.sum(state.inbox.op != OP_NOP))
        if self.wave_depth:
            n += int(jnp.sum(state.wave.phase != txn_lib.WAVE_FREE))
            n += int(jnp.sum(state.wave.coord_in.op != OP_NOP))
        return n

    @functools.partial(jax.jit, static_argnums=(0, 3, 4, 5),
                       donate_argnums=(1, 2))
    def _openloop_scan(self, state: SimState, gen, ticks: int,
                       arrival_width: int, extra_ticks: int):
        """The fused generate+tick scan (one device program; see
        ``run_openloop``).  ``state`` AND ``gen`` are donated - callers
        must rebind both."""
        def body(carry, _):
            st, g = carry
            with stage("gen"):
                inj, g, offered, shed = loadgen_lib.gen_tick(
                    g, self.cluster, arrival_width, self.c_in, st.t
                )
                st = st._replace(metrics=st.metrics._replace(
                    offered=st.metrics.offered + offered,
                    admission_drops=st.metrics.admission_drops + shed,
                ))
            st = self.tick(st, inj)
            return (st, g), None

        (state, gen), _ = jax.lax.scan(
            body, (state, gen), None, length=ticks
        )
        if extra_ticks:
            state = self.drain(state, extra_ticks)
        return state, gen

    def run_openloop(self, state: SimState, gen, ticks: int,
                     arrival_width: int | None = None,
                     extra_ticks: int = 16,
                     assert_drained: bool = False):
        """Open-loop run: ``ticks`` ticks of on-device generation + tick
        fused into ONE donated ``lax.scan`` (then an in-program drain) -
        no host-materialized schedule, no H2D transfer, and the offered
        load/op-mix/popularity knobs are traced ``LoadGenState`` leaves,
        so a whole load sweep reuses one compiled program (open-loop
        harness rules, module docstring).

        ``arrival_width`` is the static fresh-candidate lane count per
        tick (default: one cluster's worth of injection lanes,
        ``C * n * c_in``); the same width again carries follow-up
        COMMITs.  Offered load beyond lane capacity defers into the
        generator's backlog and is shed (``Metrics.admission_drops``)
        only past backlog capacity.

        Returns ``(state, gen)`` - BOTH inputs are donated, rebind both:
        ``state, gen = sim.run_openloop(state, gen, ticks)``.
        """
        if arrival_width is None:
            arrival_width = self.C * self.n * self.c_in
        state, gen = self._openloop_scan(
            state, gen, ticks, arrival_width, extra_ticks
        )
        if assert_drained:
            left = self.inflight(state)
            assert left == 0, (
                f"{left} ops still in flight after extra_ticks="
                f"{extra_ticks} drain - size the drain window up or the "
                "run's throughput/latency accounting is short"
            )
        return state, gen


# ---------------------------------------------------------------------------
# Distributed engine (shard_map over mesh axes)
# ---------------------------------------------------------------------------
class ChainDist:
    """One chain node per device along ``axis`` of ``mesh``; optionally C
    chains side by side along ``group_axis`` (the cluster layout
    ``(chain_group, chain_pos)``).

    The step function is written for use under ``shard_map``; per-node code
    is identical to the simulator's.  Exchange primitives:

    * ``ppermute`` shifts write-forward traffic one hop toward the tail -
      the chain's next-hop propagation on the ICI ring.
    * a masked ``all_gather`` realizes both the dirty-read fetch (tail pulls
      queries addressed to it) and the ACK multicast (everyone sees the
      tail's ACKs) in one collective - the TPU analogue of the P4 PRE.

    Both collectives name only the position ``axis``, so when the mesh has
    a ``group_axis`` they are automatically scoped per chain group: chains
    exchange nothing with each other, matching the disjoint key partition.

    ``ChainDist`` carries the per-chain lock shard as a fifth step argument
    (``LockTable`` [C, K] leaves, replicated along the position axis like
    the partition map's slot tables): transaction candidates are
    all-gathered across the chain group and every device re-derives the
    *identical* head lock transition (``txn.head_txn_stage`` - the lock
    edits depend only on the gathered batch and the replicated table, so
    the output stays replicated; each device then keeps only its own row
    of the passed-through/reply batches).  Client txn opcodes reaching
    this engine thus get the same admission control as the simulator's;
    the in-network wave coordinator (wave-table rules) remains a
    ``ChainSim`` subsystem for now.
    """

    def __init__(
        self,
        cfg: ChainConfig | ClusterConfig,
        mesh,
        axis: str = "chain",
        group_axis: str | None = None,
    ):
        self.cluster = as_cluster(cfg)
        self.cfg = self.cluster.chain
        self.mesh = mesh
        self.axis = axis
        self.group_axis = group_axis
        self.n = self.cfg.n_nodes
        self.C = self.cluster.n_chains
        if self.C > 1:
            assert group_axis is not None, (
                "multi-chain ChainDist needs a group_axis on the mesh"
            )
        mesh_shape = dict(mesh.shape)
        assert mesh_shape[axis] == self.n, (
            f"mesh axis {axis!r} has {mesh_shape[axis]} devices but the "
            f"chain has {self.n} nodes"
        )
        if group_axis is not None:
            assert mesh_shape[group_axis] == self.C, (
                f"mesh axis {group_axis!r} has {mesh_shape[group_axis]} "
                f"groups but the cluster has {self.C} chains"
            )
        self.node_step = NODE_STEPS[self.cfg.protocol]

    @staticmethod
    def _compact(msg: Msg, cap: int) -> Msg:
        """Keep live slots first, truncate to a fixed inbox capacity."""
        order = jnp.argsort(msg.op == OP_NOP, stable=True)
        return jax.tree.map(lambda x: x[order][:cap], msg)

    def init_state(self):
        """Per-node replicated store: [n, ...] (or [C, n, ...]) sharded on
        the leading mesh axes."""
        stores = jax.vmap(lambda _: store_lib.init_store(self.cfg))(jnp.arange(self.n))
        if self.group_axis is None:
            return stores
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.C,) + x.shape), stores
        )

    def init_locks(self) -> LockTable:
        """All-free [C, K] lock shard shaped for ``make_step`` (C == 1 when
        ungrouped, like the partition map's slot tables)."""
        return jax.vmap(lambda _: txn_lib.init_locks(self.cfg))(
            jnp.arange(self.C)
        )

    def full_roles(self) -> Roles:
        """All-slots-live role table shaped for this engine: [n] leaves
        (ungrouped) or [C, n] (grouped).  Feed ``Coordinator.roles_table()``
        instead to run under edited membership - same shapes, no re-jit."""
        if self.group_axis is None:
            return Roles.from_membership(self.n, range(self.n))
        return full_roles_table(self.n, self.C)

    def default_pmap(self) -> PartitionMap:
        """The epoch-0 partition map shaped for ``make_step``.  Feed
        ``Coordinator.partition_map()`` instead to run under a rebalanced
        map - same shapes, no re-jit."""
        return self.cluster.default_partition()

    def _specs(self):
        if self.group_axis is None:
            return P(self.axis)
        return P(self.group_axis, self.axis)

    def init_telemetry(
        self, hist_buckets: int = telemetry_lib.DEFAULT_HIST_BUCKETS
    ) -> Telemetry:
        """Telemetry shard for ``make_step(..., telemetry=True)`` - the
        simulator plane's histogram piece on the production engine
        (telemetry-leaves rules): a per-device [n, OPCLASS, BKT] (or
        [C, n, ...] grouped, like ``init_state``) exit-latency histogram
        (the host sums over the node axis for the per-chain view) plus a
        per-device step clock riding the ``ring_cursor`` lane.  Ring and
        trace leaves are zero-size - the full flight-recorder/trace plane
        stays ``ChainSim``-side for now (ROADMAP item 3 parity track)."""
        lead = (self.n,) if self.group_axis is None else (self.C, self.n)
        z = lambda *s: jnp.zeros(lead + s, jnp.int32)
        return Telemetry(
            lat_hist=z(N_OPCLASS, hist_buckets),
            ring=z(0, telemetry_lib.N_RING_FIELDS),
            ring_cursor=z(),
            trace_qid=z(0),
            trace_node=z(0, 0),
            trace_tick=z(0, 0),
            trace_op=z(0, 0),
            trace_len=z(0),
        )

    def make_step(self, batch_per_node: int, telemetry: bool = False):
        cfg, axis, n = self.cfg, self.axis, self.n
        grouped = self.group_axis is not None
        node_step = self.node_step

        def step(stores: Store, inbox: Msg, roles: Roles,
                 pmap: PartitionMap, locks: LockTable, tel=None):
            """shard_map body: [1, ...] (or [1, 1, ...]) local shards; one
            chain tick under the CP-installed live role table, partition
            map and lock shard (traced arguments - membership edits,
            bucket migrations and lock churn re-run, never re-compile).
            Returns (stores', inbox', replies_local, locks'); with
            ``telemetry=True`` a sixth traced argument ``tel``
            (``init_telemetry()``) rides the step and an updated Telemetry
            is appended to the return - same contract as the simulator's
            plane (telemetry-leaves rules, module docstring)."""
            unshard = (lambda x: x[0, 0]) if grouped else (lambda x: x[0])
            my_roles: Roles = jax.tree.map(unshard, roles)
            my_pos = my_roles.my_pos
            local_store = jax.tree.map(unshard, stores)
            local_in = jax.tree.map(unshard, inbox)
            # this chain's slot rows (the [C, K] tables shard per group;
            # ungrouped engines carry the C=1 row)
            slot_epoch = pmap.slot_epoch[0]
            slot_bucket = pmap.slot_bucket[0]
            # ... and its lock shard, replicated along the position axis
            my_locks: LockTable = jax.tree.map(lambda x: x[0], locks)
            # a dead device receives nothing and processes nothing
            local_in = local_in.mask(
                jnp.broadcast_to(my_roles.alive, local_in.op.shape)
            )
            local_in = craq.stamp_entry(local_in, my_pos)

            # stale-route admission (partition-epoch rules): client ops
            # routed under a stale map NACK back to the client instead of
            # touching a store this chain no longer owns - the exact same
            # helper the simulator's tick runs.
            local_in, stale_out, _ = stale_route_admission(
                local_in, slot_epoch, slot_bucket, my_pos
            )

            # --- head lock stage, replicated (lock-table rules) -----------
            # Transaction candidates are all-gathered across the chain so
            # every device sees the same [n, B] batch and re-derives the
            # SAME lock transition (it depends only on the gathered batch,
            # the replicated shard and the gathered role row - never on
            # device-local store state) - the shard stays replicated with
            # no collective write-back.  Each device then keeps its own
            # row: passed-through COMMITs for the node step, its replies.
            cand = is_txn_op(local_in.op) & (local_in.src >= CLIENT_BASE)
            txn_feed = local_in.mask(cand)
            gather = lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
            txn_all: Msg = jax.tree.map(gather, txn_feed)     # [n*B]
            txn_all = jax.tree.map(
                lambda x: x.reshape((n, -1) + x.shape[1:]), txn_all
            )
            roles_all: Roles = jax.tree.map(
                lambda x: gather(x[None]), my_roles
            )                                                 # [n] leaves
            # only the head row's replies are consumed (the ACK snapshot
            # value is read from row head_pos), so broadcasting the local
            # store is sound on the head and immaterial elsewhere
            bstore = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                local_store,
            )
            new_locks, passed_all, rep_all, _ = txn_lib.head_txn_stage(
                my_locks, roles_all, bstore, txn_all
            )
            passed_me = jax.tree.map(lambda x: x[my_pos], passed_all)
            rep_me = jax.tree.map(lambda x: x[my_pos], rep_all)
            local_in = jax.tree.map(
                lambda a, b: jnp.where(
                    cand.reshape(cand.shape + (1,) * (a.ndim - 1)), b, a
                ),
                local_in, passed_me,
            )

            new_store, outbox = node_step(cfg, local_store, my_roles, local_in)
            # ... and emits nothing
            outbox = outbox.mask(
                jnp.broadcast_to(my_roles.alive, outbox.op.shape)
            )

            # --- next-hop traffic: ppermute one step toward the tail ------
            # (named axis = chain position, so each chain group exchanges
            # only within itself).  Only traffic for the *physical* ring
            # neighbour rides the ppermute; forwarding that skips a dead
            # device (dst == next_pos != my_pos+1) rides the fabric below.
            to_next = outbox.mask(outbox.dst == my_pos + 1)
            perm = [(i, i + 1) for i in range(n - 1)]
            from_prev = jax.tree.map(
                lambda x: jax.lax.ppermute(x, axis, perm), to_next
            )

            # --- fabric traffic: dirty-read fetch + multicast ACKs --------
            fabric = outbox.mask(
                (outbox.dst == MULTICAST)
                | ((outbox.dst >= 0) & (outbox.dst != my_pos + 1))
            )
            all_fab: Msg = jax.tree.map(
                lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True), fabric
            )
            take = (
                (all_fab.dst == my_pos)
                | ((all_fab.dst == MULTICAST) & (all_fab.src != my_pos))
            ) & my_roles.alive
            from_fabric = all_fab.mask(take)

            replies = self._compact(
                Msg.concat([
                    outbox.mask(outbox.dst == TO_CLIENT), stale_out, rep_me,
                ]),
                batch_per_node,
            )

            next_inbox = self._compact(
                Msg.concat([from_prev, from_fabric]), batch_per_node
            )
            reshard = (lambda x: x[None, None]) if grouped else (lambda x: x[None])
            out = [
                jax.tree.map(reshard, new_store),
                jax.tree.map(reshard, next_inbox),
                jax.tree.map(reshard, replies),
                jax.tree.map(lambda x: x[None], new_locks),
            ]
            if telemetry:
                # --- device-side latency histogram (telemetry rules) ------
                # Each device scatters its OWN local reply batch; the
                # ring_cursor lane doubles as the per-device step clock
                # (the dist engine has no shared SimState.t), so
                # ticks-in-flight = clock + 1 - t_inject, exactly the
                # simulator's t_done stamp.
                my_tel: Telemetry = jax.tree.map(unshard, tel)
                clock = my_tel.ring_cursor
                my_tel = my_tel._replace(
                    lat_hist=telemetry_lib.record_latency(
                        my_tel.lat_hist, replies.op, replies.seq,
                        clock + 1 - replies.t_inject,
                    ),
                    ring_cursor=jnp.asarray(clock + 1, jnp.int32),
                )
                out.append(jax.tree.map(reshard, my_tel))
            return tuple(out)

        spec = self._specs()
        spec_store = Store(*([spec] * len(Store._fields)))
        msg_spec = Msg(*([spec] * len(Msg._fields)))
        roles_spec = Roles(*([spec] * len(Roles._fields)))
        # bucket columns + epoch replicate everywhere; the [C, K] slot
        # tables shard one chain row per group (replicated when ungrouped,
        # where C == 1)
        slot_spec = P(self.group_axis) if grouped else P()
        pmap_spec = PartitionMap(
            owner=P(), base=P(), epoch=P(),
            slot_bucket=slot_spec, slot_epoch=slot_spec,
        )
        # the lock shard replicates along the position axis, like the
        # partition map's slot tables (every device re-derives the same
        # transition from the all-gathered batch)
        lock_spec = LockTable(
            holder=slot_spec, client=slot_spec, version=slot_spec,
            lease=slot_spec, lease_ticks=slot_spec,
        )
        # the telemetry shard is per-device state: every leaf shards on
        # the same (group, position) axes as the stores
        tel_spec = Telemetry(*([spec] * len(Telemetry._fields)))
        in_specs = (spec_store, msg_spec, roles_spec, pmap_spec, lock_spec)
        out_specs = (spec_store, msg_spec, msg_spec, lock_spec)
        if telemetry:
            in_specs = in_specs + (tel_spec,)
            out_specs = out_specs + (tel_spec,)
            fn = step
        else:
            fn = lambda s, i, r, p, l: step(s, i, r, p, l, None)
        # check_vma can't statically infer the lock shard's replication
        # through the sort/searchsorted ops inside the lock stage; the
        # replication is real by construction (the transition depends only
        # on the all-gathered batch, the gathered role row and the
        # replicated shard), asserted by test_chain_dist_lock_stage.
        return jax.jit(
            jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
        )
