"""One-chip driver: the program's open-loop engine, ``ChainSim.run_openloop``.

Set-up builds the cluster state on the device in one jitted call and loads
every record from the seed (the YCSB load phase), then runs warm-up
segments of the cell's own program.  The window is a run of segments of
``segment_ticks`` ticks, each one donated ``lax.scan`` of the on-device
generator and the tick (``extra_ticks=0``); after each segment the reply
log is handed to the host and emptied, and the segment's end is stamped on
the host clock.  After the window the offered rate is set to 0 and the
same program drains the engine.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import ycsb

FN_NAME = "_openloop_scan"  # the program's jitted scan, as the trace names it
WARMUP_SEGMENTS = 2
MAX_DRAIN_SEGMENTS = 16


@functools.partial(jax.jit, donate_argnums=0)
def _take_log(state):
    """Hand the reply log out and leave an empty one in the state."""
    log = state.replies
    return state._replace(replies=log._replace(
        cursor=jnp.zeros_like(log.cursor), lost=jnp.zeros_like(log.lost))), log


def _log_rows(log, n_chains: int) -> dict:
    """Host arrays of one emptied [C, R] reply log, in chain order, with the
    global key each reply answered for."""
    log = jax.device_get(log)
    cur = np.asarray(log.cursor)
    get = lambda f: np.concatenate(
        [np.asarray(getattr(log, f))[c, :cur[c]] for c in range(n_chains)])
    chain = np.concatenate([np.full(cur[c], c, np.int64) for c in range(n_chains)])
    return {
        "qid": get("qid"), "op": get("op"), "seq": get("seq"),
        "value0": get("value0"), "t_inject": get("t_inject").astype(np.int64),
        "t_done": get("t_done").astype(np.int64), "hops": get("hops"),
        "gkey": get("key").astype(np.int64) * n_chains + chain,
        "lost": int(np.asarray(log.lost).sum()),
    }


class Engine:
    """The cell's engine and generator on the device, driven segment by
    segment."""

    def __init__(self, cfg: dict, traffic: dict, ops_per_tick: float, seed: int,
                 annotate=lambda name: contextlib.nullcontext()):
        from repro.core import ChainConfig, ChainSim, ClusterConfig, make_loadgen

        self.cluster = cluster = ClusterConfig(
            chain=ChainConfig(n_nodes=cfg["replicas"],
                              num_keys=cfg["keys"] // cfg["chains"],
                              num_versions=cfg["num_versions"],
                              value_words=cfg["value_words"],
                              protocol=cfg["protocol"]),
            n_chains=cfg["chains"],
        )
        C, n, lanes = cluster.n_chains, cluster.n_nodes, cfg["lanes_per_node"]
        self.C, self.n = C, n
        self.seg = seg = traffic["segment_ticks"]
        route_cap = cfg["route_capacity"]
        # every op gets at most one reply: a segment's replies in one chain
        # are bounded by what it injects plus what was in flight before it
        self.sim = sim = ChainSim(
            cluster, inject_capacity=lanes, route_capacity=route_cap,
            reply_capacity=seg * n * lanes + n * route_cap)
        self.G = G = cluster.num_global_keys
        self.W = W = cluster.chain.value_words
        K = cluster.chain.num_keys
        self.width = C * n * lanes
        self.seed32 = ycsb.seed32(seed)
        self.annotate = annotate

        @jax.jit
        def build(seed):
            """Initial state with every record loaded: cell 0 of every
            replica holds the record's seeded value at seq 0."""
            st = sim.init_state()
            load = ycsb.load_values(seed, G, W)                  # [G, W]
            per_chain = load.reshape(K, C, W).transpose(1, 0, 2)  # [C, K, W]
            v = st.stores.values.at[:, :, :, 0, :].set(per_chain[:, None])
            return st._replace(stores=st.stores._replace(values=v))

        self.build = build
        self.state = build(jnp.asarray(self.seed32, jnp.int32))
        gen = make_loadgen(cluster, qps=ops_per_tick,
                           write_fraction=traffic["updateproportion"],
                           seed=self.seed32,
                           burst_period=traffic.get("burst_period", 1),
                           burst_len=traffic.get("burst_len", 0),
                           burst_mult=traffic.get("burst_mult", 1.0),
                           backlog_capacity=self.width)
        self.gen = gen._replace(key_cdf=jnp.asarray(ycsb.key_cdf(traffic, G)))
        self.t = 0

    def dispatch(self):
        """Start one segment; return its reply log (on the device)."""
        with self.annotate("dispatch"):
            state, self.gen = self.sim.run_openloop(self.state, self.gen, self.seg,
                                                    extra_ticks=0)
            self.state, log = _take_log(state)
        self.t += self.seg
        return log

    def wait(self, log) -> None:
        with self.annotate("wait"):
            jax.block_until_ready(log.cursor)

    def segment(self):
        """Run one segment; return its reply log once it has ended."""
        log = self.dispatch()
        self.wait(log)
        return log

    def rows(self, log) -> dict:
        with self.annotate("fetch_replies"):
            return _log_rows(log, self.C)

    def set_rate(self, ops_per_tick: float) -> None:
        self.gen = self.gen._replace(qps=jnp.asarray(ops_per_tick, jnp.float32))

    def backlog(self) -> int:
        return int(jnp.sum(self.gen.backlog.op != 0))

    def counters(self) -> dict:
        m = jax.device_get(self.state.metrics)
        return {
            "offered": int(np.asarray(m.offered).sum()),
            "admission_drops": int(np.asarray(m.admission_drops).sum()),
            "fabric_drops": int(np.asarray(m.drops).sum()),
            "inflight": self.sim.inflight(self.state),
        }

    def final_cells(self) -> dict:
        """Committed cell 0 and dirty count of every (replica, global key)."""
        st = self.state.stores
        f = jax.device_get({"value": st.values[:, :, :, 0, :],
                            "seq": st.seqs[:, :, :, 0], "pending": st.pending})
        n, G, W = self.n, self.G, self.W
        # [C, n, K, ...] -> [n, G, ...] with g = slot * C + chain
        return {
            "value": np.asarray(f["value"]).transpose(1, 2, 0, 3).reshape(n, G, W),
            "seq": np.asarray(f["seq"]).transpose(1, 2, 0).reshape(n, G),
            "pending": np.asarray(f["pending"]).transpose(1, 2, 0).reshape(n, G),
        }


def run(ctx) -> dict:
    eng = Engine(ctx.config, ctx.traffic, ctx.ops_per_tick, ctx.seed, ctx.annotate)
    replies, stamps, lost = [], [], 0

    def keep(log):
        nonlocal lost
        rows = eng.rows(log)
        lost += rows.pop("lost")
        replies.append(rows)

    # warm-up: the first segment loads (or compiles) the program
    for _ in range(WARMUP_SEGMENTS):
        keep(eng.segment())
        stamps.append((eng.t, time.perf_counter()))
    ctx.warm(stamps[-1][1])

    # the window: the next segment runs while the host reads the last one's
    # replies
    t_w0 = eng.t
    with ctx.window():
        log = eng.dispatch()
        eng.wait(log)
        stamps.append((eng.t, time.perf_counter()))
        while stamps[-1][1] - ctx.t_warm < ctx.seconds:
            nxt = eng.dispatch()
            keep(log)
            eng.wait(nxt)
            stamps.append((eng.t, time.perf_counter()))
            log = nxt
    t_w1 = eng.t
    keep(log)
    ctx.memory()

    # drain: the same program with no new arrivals
    eng.set_rate(0.0)
    for _ in range(MAX_DRAIN_SEGMENTS):
        keep(eng.segment())
        c = eng.counters()
        backlog = eng.backlog()
        if c["inflight"] == 0 and backlog == 0:
            break
    counters = dict(c, unresolved=c["inflight"] + backlog, log_lost=lost)
    final = eng.final_cells()
    G, W, width, s32, seg = eng.G, eng.W, eng.width, eng.seed32, eng.seg
    del eng
    rows = {k: np.concatenate([r[k] for r in replies]) for k in replies[0]}
    ops = ycsb.Arrivals(ctx.seed, ctx.traffic, ctx.ops_per_tick, G, width).draw(0, t_w1)
    load = np.asarray(jax.device_get(
        ycsb.load_values(jnp.asarray(s32, jnp.int32), G, W)))
    return {
        "ops": ops, "replies": rows, "final": final, "counters": counters,
        "load": load, "stamps": stamps, "window_ticks": (t_w0, t_w1),
        "fn_name": FN_NAME, "ticks_per_segment": seg,
        "msg_bytes": 4 * (11 + W),
        "record_bytes": 4 * (ctx.config["num_versions"] * (W + 1) + 2),
    }
