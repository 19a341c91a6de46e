"""YCSB traffic arithmetic, kept with the benchmark so no change to the
program can move it.

* ``key_cdf`` is YCSB's request distribution over the record count.  For
  ``requestdistribution=zipfian`` that is YCSB's ``ScrambledZipfianGenerator``
  (Cooper et al., SoCC 2010; the YCSB core package): a rank drawn by
  ``ZipfianGenerator`` over ``zipfian_items`` items with the constant
  ``zipfian_zetan``, then ``fnvhash64(rank) % (records + 1)``, a draw past
  the last record drawn again.  The rank law is summed in closed form over
  the first ``exact_ranks`` ranks; the rest of the mass, spread over ranks
  the hash scatters, is spread evenly over the records.
* ``load_values`` is the YCSB load phase: every record holds a value drawn
  from the seed before the run phase starts.
* ``draw_ticks`` is the open-loop arrival law: a copy of
  ``repro.core.loadgen.draw_tick`` (counter-based threefry keyed by
  ``(seed, tick, lane)``), restricted to the read/update mixes the traffic
  files name.  The harness draws the same lanes on its own and checks the
  program's ops against them, so a change to the program's generator shows
  as a failed check and never as a different workload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Opcodes and id layout of the client protocol (repro/core/types.py).  They
# are the wire format the benchmark speaks, so they are restated here.
OP_NOP, OP_READ, OP_WRITE = 0, 1, 2
OP_READ_REPLY, OP_WRITE_REPLY, OP_WRITE_NACK = 4, 5, 6
CLIENT_BASE = 1 << 20
NOWHERE = -1


def seed32(seed: int) -> int:
    """A non-negative int32 from any whole number (the program's generator
    keys its PRNG with an int32)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] & 0x7FFFFFFF)


FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1 over the 8 low-order bytes of each
    (non-negative) value, then Java's ``Math.abs`` of the signed result;
    uint64."""
    v = np.asarray(vals, np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v = v >> np.uint64(8)
    neg = h >= np.uint64(1 << 63)
    if (h == np.uint64(1 << 63)).any():
        raise ValueError("fnvhash64 met Long.MIN_VALUE, which YCSB cannot use")
    return np.where(neg, np.uint64(0) - h, h)


def zipfian_rank_cdf(ranks: np.ndarray, items: int, theta: float,
                     zetan: float) -> np.ndarray:
    """P(rank <= r) of YCSB's ``ZipfianGenerator.nextLong`` over ``items``
    items: rank 0 below ``u * zetan < 1``, rank 1 below ``1 + 0.5**theta``,
    else ``floor(items * (eta * u - eta + 1) ** (1 / (1 - theta)))``."""
    r = np.asarray(ranks, np.float64)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = (((r + 1.0) / items) ** (1.0 - theta) - 1.0 + eta) / eta
    u = np.clip(u, zeta2 / zetan, 1.0)
    return np.where(r < 1, 1.0 / zetan, np.where(r < 2, zeta2 / zetan, u))


def scrambled_zipfian_pmf(records: int, items: int, theta: float, zetan: float,
                          exact_ranks: int) -> np.ndarray:
    """float64 [records]: the chance of each key under YCSB's scrambled
    zipfian generator (module docstring)."""
    ranks = np.arange(exact_ranks, dtype=np.int64)
    cdf = zipfian_rank_cdf(ranks, items, theta, zetan)
    mass = np.diff(cdf, prepend=0.0)
    slot = (fnvhash64(ranks) % np.uint64(records + 1)).astype(np.int64)
    pmf = np.bincount(slot, weights=mass, minlength=records + 1)
    pmf += (1.0 - cdf[-1]) / (records + 1)
    pmf = pmf[:records]          # key == records is drawn again
    return pmf / pmf.sum()


def uniform_cdf(num_keys: int) -> np.ndarray:
    w = np.ones((num_keys,), dtype=np.float64)
    return np.cumsum(w / w.sum()).astype(np.float32)


@functools.lru_cache(maxsize=4)
def _scrambled_cdf(num_keys, items, theta, zetan, exact_ranks) -> np.ndarray:
    pmf = scrambled_zipfian_pmf(num_keys, items, theta, zetan, exact_ranks)
    return np.cumsum(pmf).astype(np.float32)


def key_cdf(traffic: dict, num_keys: int) -> np.ndarray:
    """float32 cumulative popularity over global keys 0..num_keys-1."""
    dist = traffic["requestdistribution"]
    if dist == "zipfian":
        return _scrambled_cdf(num_keys, int(traffic["zipfian_items"]),
                              float(traffic["zipfian_constant"]),
                              float(traffic["zipfian_zetan"]),
                              int(traffic["exact_ranks"]))
    if dist == "uniform":
        return uniform_cdf(num_keys)
    raise ValueError(f"unknown requestdistribution {dist!r}")


@functools.partial(jax.jit, static_argnums=(1, 2))
def load_values(seed, num_keys: int, value_words: int):
    """[num_keys, value_words] int32: the value each record is loaded with."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), 0x10AD)
    return jax.random.randint(k, (num_keys, value_words), 1, 1 << 30, jnp.int32)


def _draw_tick(seed, qps, write_fraction, cdf, burst_period, burst_len,
               burst_mult, width: int, t):
    """Lane draws of tick ``t``: (live, is_write, gkey, value0), [width]."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
    k_thin, k_key, k_op, k_val = jax.random.split(key, 4)
    in_burst = (t % burst_period) < burst_len
    rate = qps * jnp.where(in_burst, burst_mult, jnp.float32(1.0))
    p = jnp.clip(rate / jnp.float32(width), 0.0, 1.0)
    live = jax.random.uniform(k_thin, (width,)) < p
    G = cdf.shape[0]
    u_key = jax.random.uniform(k_key, (width,))
    gkey = jnp.clip(jnp.searchsorted(cdf, u_key).astype(jnp.int32), 0, G - 1)
    u_op = jax.random.uniform(k_op, (width,))
    is_wr = u_op < write_fraction
    vals = jax.random.randint(k_val, (width,), 1, 1 << 20, jnp.int32)
    return live, is_wr, gkey, jnp.where(is_wr, vals, 0)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_block(params, width: int, ticks):
    def one(t):
        return _draw_tick(*params, width, t)

    return jax.vmap(one)(ticks)


class Arrivals:
    """The arrival law of one run: host-side parameters and a drawer."""

    def __init__(self, seed: int, traffic: dict, ops_per_tick: float,
                 num_keys: int, width: int):
        if traffic.get("txn_fraction", 0.0):
            raise ValueError("the benchmark's oracle covers reads and updates only")
        self.width = width
        self.params = (
            jnp.asarray(seed32(seed), jnp.int32),
            jnp.asarray(ops_per_tick, jnp.float32),
            jnp.asarray(traffic["updateproportion"], jnp.float32),
            jnp.asarray(key_cdf(traffic, num_keys)),
            jnp.asarray(traffic.get("burst_period", 1), jnp.int32),
            jnp.asarray(traffic.get("burst_len", 0), jnp.int32),
            jnp.asarray(traffic.get("burst_mult", 1.0), jnp.float32),
        )

    def draw(self, t0: int, t1: int, block: int = 64) -> dict:
        """Live lanes of ticks [t0, t1) as host arrays: qid, t, lane,
        is_write, gkey, value0 (qid = t * 2 * width + lane, the program's
        id layout)."""
        parts = []
        for lo in range(t0, t1, block):
            ticks = jnp.arange(lo, lo + block, dtype=jnp.int32)
            live, wr, gkey, val = jax.device_get(
                _draw_block(self.params, self.width, ticks))
            n = min(block, t1 - lo)
            live = live[:n]
            tt, lane = np.nonzero(live)
            parts.append(dict(
                t=(tt + lo).astype(np.int64), lane=lane.astype(np.int64),
                is_write=wr[:n][live], gkey=gkey[:n][live].astype(np.int64),
                value0=val[:n][live].astype(np.int64),
            ))
        out = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        out["qid"] = out["t"] * (2 * self.width) + out["lane"]
        return out
