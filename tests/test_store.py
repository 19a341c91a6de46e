"""Unit tests for the versioned object store (core/store.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import store as st
from repro.core.types import ChainConfig


@pytest.fixture
def cfg():
    return ChainConfig(n_nodes=4, num_keys=16, num_versions=4)


def test_init_clean(cfg):
    s = st.init_store(cfg)
    assert bool(st.is_clean(s, jnp.arange(16)).all())
    v, q = st.read_clean(s, jnp.asarray([3]))
    assert v.shape == (1, cfg.value_words)
    assert int(q[0]) == 0


def test_append_and_read_latest(cfg):
    s = st.init_store(cfg)
    keys = jnp.asarray([5, 5, 7], jnp.int32)
    vals = jnp.asarray([[1, 0, 0, 0], [2, 0, 0, 0], [3, 0, 0, 0]], jnp.int32)
    seqs = jnp.asarray([1, 2, 1], jnp.int32)
    active = jnp.asarray([True, True, True])
    s, acc = st.append_dirty(s, keys, vals, seqs, active)
    assert acc.tolist() == [True, True, True]
    assert int(s.pending[5]) == 2 and int(s.pending[7]) == 1
    lv, ls = st.read_latest(s, jnp.asarray([5, 7]))
    assert lv[:, 0].tolist() == [2, 3]
    assert ls.tolist() == [2, 1]
    # clean read still returns the committed (initial) version
    cv, cs = st.read_clean(s, jnp.asarray([5]))
    assert int(cv[0, 0]) == 0 and int(cs[0]) == 0


def test_window_overflow_drops(cfg):
    """Writes beyond the version window are dropped (Algorithm 1 l.22-23)."""
    s = st.init_store(cfg)
    n = cfg.num_versions  # window has n-1 dirty slots
    keys = jnp.full((n + 2,), 3, jnp.int32)
    vals = jnp.tile(jnp.arange(n + 2, dtype=jnp.int32)[:, None], (1, 4))
    seqs = jnp.arange(1, n + 3, dtype=jnp.int32)
    s, acc = st.append_dirty(s, keys, vals, seqs, jnp.ones(n + 2, bool))
    assert acc.tolist() == [True] * (n - 1) + [False] * 3
    assert int(s.pending[3]) == n - 1


def test_commit_compacts(cfg):
    s = st.init_store(cfg)
    keys = jnp.asarray([5, 5, 5], jnp.int32)
    vals = jnp.asarray([[10, 0, 0, 0], [20, 0, 0, 0], [30, 0, 0, 0]], jnp.int32)
    seqs = jnp.asarray([1, 2, 3], jnp.int32)
    s, _ = st.append_dirty(s, keys, vals, seqs, jnp.ones(3, bool))
    # ack seq 2: versions 1,2 deleted; version 3 shifts down; cell0 = 20
    s = st.commit(
        s, jnp.asarray([5]), jnp.asarray([[20, 0, 0, 0]]), jnp.asarray([2]),
        jnp.asarray([True]),
    )
    assert int(s.pending[5]) == 1
    assert int(s.values[5, 0, 0]) == 20 and int(s.seqs[5, 0]) == 2
    lv, ls = st.read_latest(s, jnp.asarray([5]))
    assert int(lv[0, 0]) == 30 and int(ls[0]) == 3


def test_commit_stale_ack_noop(cfg):
    s = st.init_store(cfg)
    s = st.commit(
        s, jnp.asarray([2]), jnp.asarray([[9, 0, 0, 0]]), jnp.asarray([5]),
        jnp.asarray([True]),
    )
    # older ack must not roll back
    s2 = st.commit(
        s, jnp.asarray([2]), jnp.asarray([[7, 0, 0, 0]]), jnp.asarray([3]),
        jnp.asarray([True]),
    )
    assert int(s2.values[2, 0, 0]) == 9 and int(s2.seqs[2, 0]) == 5


def test_batch_rank_serialization():
    keys = jnp.asarray([1, 2, 1, 1, 2], jnp.int32)
    active = jnp.asarray([True, True, True, False, True])
    rank = st.batch_rank(keys, active)
    assert rank.tolist() == [0, 0, 1, 0, 1]  # inactive rows don't count


def test_assign_seqs_monotone(cfg):
    s = st.init_store(cfg)
    keys = jnp.asarray([4, 4, 9], jnp.int32)
    s, seqs = st.assign_seqs(s, keys, jnp.ones(3, bool))
    assert seqs.tolist() == [1, 2, 1]
    s, seqs2 = st.assign_seqs(s, keys, jnp.ones(3, bool))
    assert seqs2.tolist() == [3, 4, 2]


def test_overwrite_clean_netchain(cfg):
    """CR single-version write: newest seq wins, stale writes ignored."""
    s = st.init_store(cfg)
    keys = jnp.asarray([1, 1], jnp.int32)
    vals = jnp.asarray([[5, 0, 0, 0], [6, 0, 0, 0]], jnp.int32)
    s = st.overwrite_clean(s, keys, vals, jnp.asarray([2, 1]), jnp.ones(2, bool))
    assert int(s.values[1, 0, 0]) == 5 and int(s.seqs[1, 0]) == 2


# ---------------------------------------------------------------------------
# Row-sparse commit against the dense reference (tests/helpers.py)
# ---------------------------------------------------------------------------
K_PROP, B_PROP = 12, 10


def _random_store(rng, cfg, pending_lo=0, pending_hi=None):
    """A store in its invariant: cell 0 clean at seq0, dirty cells
    1..pending at increasing seqs above it, cells past them blank (seq -1)
    over leftover values."""
    V, W = cfg.num_versions, cfg.value_words
    pending_hi = V - 1 if pending_hi is None else pending_hi
    seq0 = rng.integers(0, 6, K_PROP)
    pend = rng.integers(pending_lo, pending_hi + 1, K_PROP)
    seqs = np.full((K_PROP, V), -1)
    seqs[:, 0] = seq0
    for k in range(K_PROP):
        dirty = rng.choice(np.arange(1, 8), pend[k], replace=False)
        seqs[k, 1:1 + pend[k]] = np.sort(dirty) + seq0[k]
    return st.Store(
        values=jnp.asarray(rng.integers(0, 1000, (K_PROP, V, W)), jnp.int32),
        seqs=jnp.asarray(seqs, jnp.int32),
        pending=jnp.asarray(pend, jnp.int32),
        next_seq=jnp.asarray(rng.integers(1, 9, K_PROP), jnp.int32),
    )


def _random_batch(rng, cfg, shape=(B_PROP,), num_keys=K_PROP):
    """ACK batches with duplicate keys, stale and out-of-order seqs (from -2,
    below and at cell 0, to past the newest dirty cell) and inactive
    entries.  A value is a function of (key, seq), as a write's is: two
    entries that tie on a key's largest seq carry the same value."""
    keys = rng.integers(0, num_keys, shape)
    seqs = rng.integers(-2, 16, shape)
    active = rng.random(shape) < 0.7
    vals = (keys[..., None] * 1000 + seqs[..., None] * 10
            + np.arange(cfg.value_words))
    return (jnp.asarray(keys, jnp.int32), jnp.asarray(vals, jnp.int32),
            jnp.asarray(seqs, jnp.int32), jnp.asarray(active))


def _assert_same_store(a, b, msg=""):
    for f in st.Store._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{f} {msg}")


@pytest.mark.parametrize("windows", ["any", "empty", "full"])
def test_commit_matches_dense_reference(cfg, windows):
    """Leaf by leaf on random batches: duplicate keys, stale and
    out-of-order ACKs, inactive entries, ACKs for keys with no dirty cells,
    over stores whose version windows are anything, all empty or all
    full."""
    from tests.helpers import dense_commit

    lo, hi = {"any": (0, None), "empty": (0, 0), "full": (3, 3)}[windows]
    rng = np.random.default_rng(["any", "empty", "full"].index(windows))
    new, ref = jax.jit(st.commit), jax.jit(dense_commit)
    for i in range(60):
        s = _random_store(rng, cfg, lo, hi)
        batch = _random_batch(rng, cfg)
        _assert_same_store(new(s, *batch), ref(s, *batch), f"batch {i}")


def test_commit_vmapped_matches_dense_reference(cfg):
    """Batches vmapped over [C, n] node stores, as the tick runs them."""
    from tests.helpers import dense_commit

    rng = np.random.default_rng(7)
    C, n = 3, 4
    stores = [_random_store(rng, cfg) for _ in range(C * n)]
    s = jax.tree.map(lambda *xs: jnp.stack(xs).reshape((C, n) + xs[0].shape),
                     *stores)
    batch = _random_batch(rng, cfg, shape=(C, n, B_PROP))
    vm = lambda f: jax.jit(jax.vmap(jax.vmap(f)))
    _assert_same_store(vm(st.commit)(s, *batch), vm(dense_commit)(s, *batch))


def test_commit_leaves_unnamed_rows_alone(cfg):
    """An ACK of a key with no dirty cells rebuilds that row only; a batch
    whose active entries all carry a negative seq changes nothing."""
    rng = np.random.default_rng(3)
    s = _random_store(rng, cfg)
    keys = jnp.asarray([4, 9, 9], jnp.int32)
    seqs = jnp.asarray([-1, -2, 20], jnp.int32)
    vals = jnp.full((3, cfg.value_words), 77, jnp.int32)
    out = st.commit(s, keys, vals, seqs, jnp.asarray([True, True, False]))
    _assert_same_store(out, s)
    out = st.commit(s, keys, vals, seqs, jnp.ones(3, bool))
    assert int(out.seqs[9, 0]) == 20 and int(out.pending[9]) == 0
    assert int(out.values[9, 0, 0]) == 77
    keep = np.arange(K_PROP) != 9
    for f in ("values", "seqs", "pending"):
        np.testing.assert_array_equal(np.asarray(getattr(out, f))[keep],
                                      np.asarray(getattr(s, f))[keep])


def test_read_versions_is_read_clean_and_read_latest(cfg):
    """One gather of the rows gives what the two reads give."""
    rng = np.random.default_rng(5)
    for _ in range(5):
        s = _random_store(rng, cfg)
        keys = jnp.asarray(rng.integers(0, K_PROP, B_PROP), jnp.int32)
        clean, latest = st.read_versions(s, keys)
        for got, want in ((clean, st.read_clean(s, keys)),
                          (latest, st.read_latest(s, keys))):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("seed", [0, 1])
def test_append_dirty_matches_sequential_appends(cfg, seed):
    """The batched append equals appending the batch one entry at a time:
    each active entry takes its key's next cell, or is dropped when the
    window is full."""
    rng = np.random.default_rng(seed)
    V = cfg.num_versions
    for _ in range(20):
        s = _random_store(rng, cfg)
        keys, vals, seqs, active = _random_batch(rng, cfg, num_keys=4)
        out, acc = st.append_dirty(s, keys, vals, seqs, active)
        values = np.asarray(s.values).copy()
        sq = np.asarray(s.seqs).copy()
        pend = np.asarray(s.pending).copy()
        want = []
        for k, v, q, a in zip(*(np.asarray(x) for x in (keys, vals, seqs,
                                                         active))):
            ok = bool(a) and pend[k] + 1 <= V - 1
            if ok:
                pend[k] += 1
                values[k, pend[k]], sq[k, pend[k]] = v, q
            want.append(ok)
        assert np.asarray(acc).tolist() == want
        np.testing.assert_array_equal(np.asarray(out.values), values)
        np.testing.assert_array_equal(np.asarray(out.seqs), sq)
        np.testing.assert_array_equal(np.asarray(out.pending), pend)


def test_store_rows_counts_distinct_committed_keys():
    """``Metrics.store_rows`` of a hand-built run: three client writes to
    keys 5, 5 and 7 at the head of a 3-node chain.  The tail commits the
    two keys in one tick (2 rows), the head and the middle node apply
    their ACKs in the next (4 rows): 2 distinct keys x 3 replicas."""
    from repro.core import ChainSim
    from repro.core.types import CLIENT_BASE, OP_WRITE

    chain = ChainConfig(n_nodes=3, num_keys=16, num_versions=4)
    sim = ChainSim(chain, inject_capacity=4, route_capacity=32,
                   reply_capacity=64)
    m = sim.empty_injection()
    for lane, key in enumerate([5, 5, 7]):
        m = m._replace(
            op=m.op.at[0, 0, lane].set(OP_WRITE),
            key=m.key.at[0, 0, lane].set(key),
            value=m.value.at[0, 0, lane, 0].set(100 + lane),
            src=m.src.at[0, 0, lane].set(CLIENT_BASE + lane),
            client=m.client.at[0, 0, lane].set(CLIENT_BASE + lane),
            dst=m.dst.at[0, 0, lane].set(0),
            qid=m.qid.at[0, 0, lane].set(lane),
        )
    state = sim.tick(sim.init_state(), m)
    rows = []
    for _ in range(5):
        rows.append(state.metrics.asdict()["store_rows"])
        state = sim.tick(state, sim.empty_injection())
    assert rows == [0, 0, 2, 6, 6]
    assert int(state.stores.pending.sum()) == 0
    np.testing.assert_array_equal(np.asarray(state.stores.values)[0, :, 5, 0, 0],
                                  [101] * 3)
