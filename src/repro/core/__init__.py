"""NetCRAQ core: in-network coordination KVS for the data plane, in JAX.

Public surface:
  types      - Msg/ChainConfig/Roles, opcode and wire-format constants
  store      - versioned object store (objects_store register arrays)
  craq       - NetCRAQ node control logic (Algorithm 1)
  netchain   - NetChain/Chain-Replication baseline
  chain      - ChainSim (exact-accounting simulator) / ChainDist (shard_map)
  coordinator- control plane: roles, membership, two-phase failure recovery
  txn        - cross-chain multi-key transactions (in-network 2PC over the
               partition map: lock table, planner, driver, reference oracle,
               and the device-resident wave-table coordinator)
  workload   - paper-evaluation workload generators (incl. transactional)
  loadgen    - device-resident open-loop generator (traced qps/mix/CDF
               leaves, admission backpressure; ChainSim.run_openloop)
  chaos      - declarative disturbance scenarios (failure storms, migration
               waves, stale/abandoning clients) replayed as tick-indexed
               event tables between fused open-loop segments
  metrics    - packet/hop/byte accounting and reply latency log
  telemetry  - device-side telemetry plane (latency histograms, flight-
               recorder ring, sampled packet traces); host consumer lives
               in repro.obs
  stages     - the tick's named stages (``stage`` scopes) and the map
               from a compiled program's instructions to them
"""
from repro.core.types import (  # noqa: F401
    ChainConfig,
    ClusterConfig,
    PartitionMap,
    as_cluster,
    Msg,
    Roles,
    OP_STALE_NACK,
    OP_ACK,
    OP_ABORT,
    OP_COMMIT,
    OP_NOP,
    OP_PREPARE,
    OP_PREPARE_ACK,
    OP_PREPARE_NACK,
    OP_READ,
    OP_READ_REPLY,
    OP_TXN_REPLY,
    OP_WRITE,
    OP_WRITE_NACK,
    OP_WRITE_REPLY,
    CLIENT_BASE,
    MULTICAST,
    NOWHERE,
    TO_CLIENT,
    WAVE_BASE,
    LEASE_OFF,
    NETCRAQ_HEADER_BYTES,
    N_OPCLASS,
    OPCLASS_NAMES,
    is_txn_op,
    netchain_header_bytes,
    reply_op_class,
)
from repro.core.telemetry import (  # noqa: F401
    RING_FIELDS,
    Telemetry,
    latency_bucket,
)
from repro.core.store import Store, init_store  # noqa: F401
from repro.core.chain import (  # noqa: F401
    ChainDist,
    ChainSim,
    DistState,
    SimState,
    full_roles_table,
    ring_order,
)
from repro.core.coordinator import ChainMembership, Coordinator, FailoverPolicy  # noqa: F401
from repro.core.failure import FailureDetector, HedgedReadPolicy  # noqa: F401
from repro.core.metrics import Metrics, ReplyLog  # noqa: F401
from repro.core.txn import (  # noqa: F401
    LockTable,
    Txn,
    TxnDriver,
    TxnPlanner,
    TxnResult,
    TxnWaveDriver,
    WaveState,
    committed_view,
    held_locks,
    locks_all_free,
    reference_execute,
    serial_order,
    set_lease,
)
from repro.core.chaos import (  # noqa: F401
    ChaosEvent,
    ChaosScenario,
    failure_storm,
    migration_wave,
    none_scenario,
    run_scenario,
    stale_clients,
)
from repro.core.workload import (  # noqa: F401
    RoutedStream,
    TxnWorkloadConfig,
    WorkloadConfig,
    localize_stream,
    make_schedule,
    make_txn_workload,
    pack_tick,
    route_stream,
)
from repro.core.loadgen import (  # noqa: F401
    LoadGenState,
    make_loadgen,
    materialize_stream,
    zipf_cdf,
)
