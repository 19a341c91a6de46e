"""The comparison that decides ``correct``: it accepts a store that keeps
the stated guarantee and refuses the control, which acknowledges writes
before they are replicated."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, oracle, ycsb

TRAFFIC = {"requestdistribution": "zipfian", "zipfian_constant": 0.99,
           "zipfian_items": 10000000001, "zipfian_zetan": 26.46902820178302,
           "exact_ranks": 1 << 16, "updateproportion": 0.05}


@pytest.fixture(scope="module")
def run():
    G = 2048
    ops = ycsb.Arrivals((1 << 35) + 5, TRAFFIC, 120.0, G, 512).draw(0, 240)
    load = np.asarray(ycsb.load_values(
        jnp.asarray(ycsb.seed32((1 << 35) + 5), jnp.int32), G, 4))
    return ops, load


@pytest.mark.parametrize("strong", [True, False])
def test_control_and_reference(run, strong):
    ops, load = run
    correct, table = control.evaluate(ops, load, 4, strong, (64, 240))
    assert correct == strong
    if not strong:
        assert table["stale_reads"]["value"] > 0


def _evaluate(ops, load, rep, final):
    return oracle.evaluate(ops, rep, load, final,
                           {"offered": ops["qid"].size, "unresolved": 0,
                            "log_lost": 0}, (64, 240), 3, 4)["numbers"]


def test_each_number_sees_its_fault(run):
    ops, load = run
    rep, final = control.reference_replies(ops, load, 4, strong=True)
    assert _evaluate(ops, load, rep, final)["ops_mismatch"] == 0
    reads = np.nonzero(rep["op"] == ycsb.OP_READ_REPLY)[0]
    writes = np.nonzero(rep["op"] == ycsb.OP_WRITE_REPLY)[0]

    bad = {k: v.copy() for k, v in rep.items()}
    bad["value0"][reads[:3]] += 1
    assert _evaluate(ops, load, bad, final)["read_value_mismatch"] == 3

    bad = {k: v.copy() for k, v in rep.items()}
    bad["gkey"][reads[5]] += 1
    assert _evaluate(ops, load, bad, final)["ops_mismatch"] == 1

    dropped = np.ones(rep["qid"].size, bool)
    dropped[writes[rep["t_inject"][writes] >= 64][:10]] = False
    part = {k: v[dropped] for k, v in rep.items()}
    n = _evaluate(ops, load, part, final)
    assert n["replica_mismatch"] > 0 and n["lost_writes"] > 0

    fin = {k: np.array(v) for k, v in final.items()}
    fin["pending"][2, 7] = 1
    fin["value"][1, 9, 3] += 1
    n = _evaluate(ops, load, rep, fin)
    assert n["dirty_after_drain"] == 1 and n["replica_mismatch"] == 1

    kept = np.ones(rep["qid"].size, bool)
    kept[reads[:4]] = False
    assert _evaluate(ops, load, {k: v[kept] for k, v in rep.items()}, final)["lost_reads"] == 4

    bad = {k: v.copy() for k, v in rep.items()}
    bad["qid"][reads[0]] = bad["qid"][reads[1]]
    assert _evaluate(ops, load, bad, final)["ops_mismatch"] >= 1


def test_a_lost_update_needs_a_full_window():
    # key 5: answered updates hold [t, t_done) at ticks 10-13, 11-14, 12-15
    ops = {"t": np.array([10, 11, 12, 12, 20]), "gkey": np.array([5, 5, 5, 5, 5]),
           "is_write": np.ones(5, bool)}
    answered = np.array([True, True, True, False, False])
    t_done = np.array([14, 15, 16, 0, 0])
    # the update sent at 12 met three held cells; the one at 20 met none
    assert oracle.lost_writes(ops, answered, t_done, 3, wait=1) == 1
    # a window of 4 dirty cells explains neither
    assert oracle.lost_writes(ops, answered, t_done, 4, wait=1) == 2
    # a configuration that drops no update explains none
    assert oracle.lost_writes(ops, answered, t_done, None, wait=1) == 2
    # two held cells do not fill a window of three
    answered[0] = False
    assert oracle.lost_writes(ops, answered, t_done, 3, wait=1) == 3
