"""Four-chip driver: the program's open-loop engine on a mesh,
``ChainDist.run_openloop``, with chip ``p`` holding replica position ``p``
of every chain.

The run is ``bench/drivers/openloop.py``'s: set-up builds the sharded
state in one jitted call and loads every record from the seed on every
replica, then runs warm-up segments; the window is a run of donated
``segment_ticks``-tick scans whose reply logs are handed to the host;
after the window the rate is set to 0 and the same program drains the
engine.  The cell's chips are laid out as a ring of ICI neighbours
(``repro.core.ring_order``).  The record adds the cross-chip messages of
the window and a function that lowers the scan again (the stage map of
``bench/dist_stages.py``).
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import ycsb
from bench.drivers import openloop

FN_NAME = "_dist_openloop_scan"  # the program's jitted scan, as the trace names it


def build_engine(cfg: dict, segment_ticks: int, devices):
    """The cell's ``ChainDist`` on ``devices`` laid out as a ring."""
    from jax.sharding import AxisType, Mesh

    from repro.core import ChainConfig, ChainDist, ClusterConfig, ring_order

    cluster = ClusterConfig(
        chain=ChainConfig(n_nodes=cfg["replicas"],
                          num_keys=cfg["keys"] // cfg["chains"],
                          num_versions=cfg["num_versions"],
                          value_words=cfg["value_words"],
                          protocol=cfg["protocol"]),
        n_chains=cfg["chains"],
    )
    n, lanes, route_cap = cluster.n_nodes, cfg["lanes_per_node"], cfg["route_capacity"]
    ring = ring_order(devices)[:n]
    mesh = Mesh(np.asarray(ring), ("chain",), axis_types=(AxisType.Auto,))
    # a chain's replies in one segment are bounded by what it injects plus
    # what was in flight before it, whichever chip emits them
    return ChainDist(cluster, mesh, inject_capacity=lanes, route_capacity=route_cap,
                     reply_capacity=segment_ticks * n * lanes + n * route_cap)


def scan_lowering(dist, gen, segment_ticks: int):
    """The cell's scan lowered on abstract values placed as the run
    places them."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    on = lambda tree, shard: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shard)
    state = on(jax.eval_shape(dist.init_scan_state), dist.scan_shardings())
    rep = NamedSharding(dist.mesh, P())
    gen = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), gen)
    return type(dist)._dist_openloop_scan.lower(
        dist, state, gen, segment_ticks, dist.C * dist.n * dist.c_in, 0)


class Engine(openloop.Engine):
    """The cell's sharded engine and replicated generator, driven segment
    by segment (``openloop.Engine``'s segment, rate and backlog handling)."""

    def __init__(self, cfg: dict, traffic: dict, ops_per_tick: float, seed: int,
                 devices, annotate=lambda name: contextlib.nullcontext()):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core import make_loadgen

        self.seg = seg = traffic["segment_ticks"]
        self.dist = dist = build_engine(cfg, seg, devices)
        self.cluster = cluster = dist.cluster
        C, n, K = dist.C, dist.n, cluster.chain.num_keys
        self.C, self.n = C, n
        self.G = G = cluster.num_global_keys
        self.W = W = cluster.chain.value_words
        self.width = C * n * dist.c_in
        self.seed32 = ycsb.seed32(seed)
        self.annotate = annotate
        self.rep = NamedSharding(dist.mesh, P())
        shardings = dist.scan_shardings()

        @functools.partial(jax.jit, out_shardings=shardings)
        def build(seed):
            """Initial state with every record loaded: cell 0 of every
            replica holds the record's seeded value at seq 0."""
            st = dist.init_scan_state()
            load = ycsb.load_values(seed, G, W)                  # [G, W]
            per_chain = load.reshape(K, C, W).transpose(1, 0, 2)  # [C, K, W]
            v = st.stores.values.at[:, :, :, 0, :].set(per_chain[:, None])
            return st._replace(stores=st.stores._replace(values=v))

        @functools.partial(jax.jit, donate_argnums=0,
                           out_shardings=(shardings, shardings.replies))
        def take_log(state):
            """Hand the reply log out and leave an empty one in the state."""
            log = state.replies
            return state._replace(replies=log._replace(
                cursor=jnp.zeros_like(log.cursor), lost=jnp.zeros_like(log.lost))), log

        self.build, self._take = build, take_log
        self.state = build(jnp.asarray(self.seed32, jnp.int32))
        gen = make_loadgen(cluster, qps=ops_per_tick,
                           write_fraction=traffic["updateproportion"],
                           seed=self.seed32,
                           burst_period=traffic.get("burst_period", 1),
                           burst_len=traffic.get("burst_len", 0),
                           burst_mult=traffic.get("burst_mult", 1.0),
                           backlog_capacity=self.width)
        self.gen = jax.device_put(
            gen._replace(key_cdf=jnp.asarray(ycsb.key_cdf(traffic, G))), self.rep)
        self.t = 0

    def dispatch(self):
        """Start one segment; return its reply log (on the device)."""
        with self.annotate("dispatch"):
            state, self.gen = self.dist.run_openloop(self.state, self.gen, self.seg)
            self.state, log = self._take(state)
        self.t += self.seg
        return log

    def rows(self, log) -> dict:
        """Host rows of one emptied [C, n, R] log: each chip's [C, R] log
        in turn."""
        with self.annotate("fetch_replies"):
            log = jax.device_get(log)
            parts = [openloop._log_rows(jax.tree.map(lambda x: x[:, j], log), self.C)
                     for j in range(self.n)]
        out = {k: np.concatenate([p[k] for p in parts]) for k in parts[0] if k != "lost"}
        out["lost"] = sum(p["lost"] for p in parts)
        return out

    def set_rate(self, ops_per_tick: float) -> None:
        self.gen = self.gen._replace(
            qps=jax.device_put(jnp.asarray(ops_per_tick, jnp.float32), self.rep))

    def counters(self) -> dict:
        m = jax.device_get(self.state.metrics)
        return {
            "offered": int(np.asarray(m.offered).sum()),
            "admission_drops": int(np.asarray(m.admission_drops).sum()),
            "fabric_drops": int(np.asarray(m.drops).sum()),
            "inflight": self.dist.inflight(self.state),
        }

    def cross_chip(self) -> int:
        return int(np.asarray(jax.device_get(self.state.cross_chip)).sum())


def run(ctx) -> dict:
    eng = Engine(ctx.config, ctx.traffic, ctx.ops_per_tick, ctx.seed, ctx.devices,
                 ctx.annotate)
    replies, stamps, lost = [], [], 0

    def keep(log):
        nonlocal lost
        rows = eng.rows(log)
        lost += rows.pop("lost")
        replies.append(rows)

    for _ in range(openloop.WARMUP_SEGMENTS):
        keep(eng.segment())
        stamps.append((eng.t, time.perf_counter()))
    ctx.warm(stamps[-1][1])
    cross0 = eng.cross_chip()

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
    cross1 = eng.cross_chip()
    keep(log)
    ctx.memory()

    # drain: the same program with no new arrivals
    eng.set_rate(0.0)
    for _ in range(openloop.MAX_DRAIN_SEGMENTS):
        keep(eng.segment())
        c = eng.counters()
        backlog = eng.backlog()
        if c["inflight"] == 0 and backlog == 0:
            break
    counters = dict(c, unresolved=c["inflight"] + backlog, log_lost=lost)
    final = eng.final_cells()
    G, W, width, s32, seg = eng.G, eng.W, eng.width, eng.seed32, eng.seg
    dist, gen = eng.dist, jax.eval_shape(lambda: eng.gen)
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
        "chips": dist.n,
        "cross_chip_window": cross1 - cross0,
        "lower_scan": lambda: scan_lowering(dist, gen, seg),
    }
