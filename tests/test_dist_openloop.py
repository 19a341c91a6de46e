"""``ChainDist.run_openloop`` against ``ChainSim.run_openloop``: on the same
generator the four-chip scan gives the one-chip scan's replies (hops
included), committed stores and counters, counts the messages that cross
chips, and counts what overflows an inbox.  One subprocess on 4 emulated
devices runs every case through one compiled program of each engine."""
import json

import pytest

from helpers import run_with_devices

C, N, KEYS, LANES, ROUTE, TICKS = 4, 4, 256, 8, 16, 24


@pytest.fixture(scope="module")
def runs():
    out = run_with_devices(f"""
import json
import jax, numpy as np
from jax.sharding import AxisType, Mesh
from repro.core import (ChainConfig, ChainDist, ChainSim, ClusterConfig,
                        make_loadgen)
from repro.core.stages import STAGES, op_stages
from repro.core.types import OP_READ_REPLY, OP_WRITE_REPLY

C, N, K, LANES, ROUTE, T = {C}, {N}, {KEYS}, {LANES}, {ROUTE}, {TICKS}
cluster = ClusterConfig(chain=ChainConfig(n_nodes=N, num_keys=K, num_versions=4,
                                          value_words=4), n_chains=C)
mesh = Mesh(np.asarray(jax.devices()), ("chain",), axis_types=(AxisType.Auto,))
R = T * N * LANES + N * ROUTE
sim = ChainSim(cluster, inject_capacity=LANES, route_capacity=ROUTE,
               reply_capacity=R)
dist = ChainDist(cluster, mesh, inject_capacity=LANES, route_capacity=ROUTE,
                 reply_capacity=R)
FIELDS = ("qid", "op", "seq", "value0", "t_inject", "t_done", "hops")

def gen(qps, seed):
    return make_loadgen(cluster, qps=qps, write_fraction=0.3, key_skew="zipf",
                        zipf_a=1.1, seed=seed, backlog_capacity=C * N * LANES)

def rows(log):
    log = jax.device_get(log)
    cur = np.asarray(log.cursor).reshape(C, -1)
    f = lambda name: np.asarray(getattr(log, name)).reshape(C, cur.shape[1], -1)
    return sorted(tuple(int(f(k)[c, j, i]) for k in FIELDS)
                  for c in range(C) for j in range(cur.shape[1])
                  for i in range(cur[c, j]))

def case(qps, seed):
    st, _ = sim.run_openloop(sim.init_state(), gen(qps, seed), T, extra_ticks=8)
    ds, _ = dist.run_openloop(
        jax.device_put(dist.init_scan_state(), dist.scan_shardings()),
        gen(qps, seed), T, extra_ticks=8)
    a, b = rows(st.replies), rows(ds.replies)
    stores = {{f: bool((np.asarray(getattr(st.stores, f))
                        == np.asarray(getattr(ds.stores, f))).all())
              for f in ("values", "seqs", "pending")}}
    ms = st.metrics.asdict()
    md = {{k: int(np.asarray(v).sum())
          for k, v in jax.device_get(ds.metrics)._asdict().items()}}
    ops = np.asarray([r[1] for r in b])
    hops = np.asarray([r[6] for r in b])
    return {{
        "replies_equal": a == b, "n_replies": len(b), "stores_equal": stores,
        "metrics": {{k: [ms[k], md[k]] for k in ("offered", "admission_drops",
                    "drops", "packets", "replies", "kv_procs")}},
        "cross_chip": int(np.asarray(ds.cross_chip).sum()),
        "write_replies": int((ops == OP_WRITE_REPLY).sum()),
        "forwarded_reads": int(((ops == OP_READ_REPLY) & (hops > 2)).sum()),
        "inflight": [sim.inflight(st), dist.inflight(ds)],
    }}

res = {{"seed_a": case(24.0, 11), "seed_b": case(24.0, (1 << 31) - 9),
        "tiny": case(0.5, 3), "overload": case(240.0, 11)}}
state = jax.eval_shape(dist.init_scan_state)
g = jax.eval_shape(lambda: gen(1.0, 0))
text = ChainDist._dist_openloop_scan.lower(dist, state, g, T, C * N * LANES,
                                           0).compile().as_text()
smap = op_stages(text)
res["stages"] = sorted({{s for s in smap.values() if s is not None}})
res["unscoped_share"] = sum(s is None for s in smap.values()) / len(smap)
res["all_stages"] = list(STAGES)
print(json.dumps(res))
""", n_devices=N)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("name", ["seed_a", "seed_b", "overload"])
def test_dist_scan_equals_one_chip_scan(runs, name):
    r = runs[name]
    assert r["n_replies"] > 100
    assert r["replies_equal"], name
    assert all(r["stores_equal"].values()), r["stores_equal"]
    for key, (one_chip, four_chip) in r["metrics"].items():
        assert one_chip == four_chip, (name, key)
    assert r["inflight"] == [0, 0]


@pytest.mark.slow
def test_dist_scan_below_the_knee_drops_nothing(runs):
    for name in ("seed_a", "seed_b"):
        m = runs[name]["metrics"]
        assert m["drops"] == [0, 0] and m["admission_drops"] == [0, 0], name


@pytest.mark.slow
def test_dist_scan_counts_inbox_overflow(runs):
    """An inbox overflow on the mesh is counted in ``drops``, as the one-chip
    router counts it (equal totals: test_dist_scan_equals_one_chip_scan)."""
    assert runs["overload"]["metrics"]["drops"][1] > 0


@pytest.mark.slow
def test_cross_chip_counter_by_hand(runs):
    """One replica per chip: a clean read crosses no chip, a dirty read
    forwarded to the tail crosses once, and an update crosses N - 1 times
    down the chain and N - 1 times as the tail's ACK multicast."""
    for name in ("tiny", "seed_a"):
        r = runs[name]
        want = 2 * (N - 1) * r["write_replies"] + r["forwarded_reads"]
        assert r["cross_chip"] == want, (name, r)
    assert 0 < runs["tiny"]["n_replies"] < 40


@pytest.mark.slow
def test_dist_scan_stage_coverage(runs):
    assert "exchange" in runs["all_stages"]
    assert set(runs["stages"]) == set(runs["all_stages"]) - {"wave", "telemetry"}
    assert runs["unscoped_share"] < 0.25
