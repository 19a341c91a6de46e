"""YCSB's scrambled zipfian request distribution, as ``bench/ycsb.py``
computes it, against YCSB's generator itself."""
import numpy as np

from bench import ycsb

ITEMS, THETA, ZETAN = 10000000001, 0.99, 26.46902820178302


def _fnv_java(val: int) -> int:
    """YCSB's ``Utils.fnvhash64``, with Java's 64-bit signed arithmetic."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & (2**64 - 1)
    signed = h - 2**64 if h >= 2**63 else h
    return abs(signed)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 2, 255, 256, 65537, 123456789, 9999999999]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [_fnv_java(v) for v in vals]


def _ycsb_draws(n: int, records: int, seed: int) -> np.ndarray:
    """Keys drawn as ``ScrambledZipfianGenerator.nextValue`` draws them."""
    u = np.random.default_rng(seed).random(n)
    zeta2 = 1 + 0.5 ** THETA
    eta = (1 - (2 / ITEMS) ** (1 - THETA)) / (1 - zeta2 / ZETAN)
    rank = np.where(u * ZETAN < 1, 0, np.where(
        u * ZETAN < zeta2, 1,
        np.floor(ITEMS * (eta * u - eta + 1) ** (1 / (1 - THETA))))).astype(np.int64)
    key = (ycsb.fnvhash64(rank) % np.uint64(records + 1)).astype(np.int64)
    return key[key < records]


def test_scrambled_zipfian_matches_the_generator():
    records = 4096
    pmf = ycsb.scrambled_zipfian_pmf(records, ITEMS, THETA, ZETAN, 1 << 20)
    assert pmf.sum() == np.float64(1.0) or abs(pmf.sum() - 1) < 1e-12
    keys = _ycsb_draws(2_000_000, records, 3)
    seen = np.bincount(keys, minlength=records) / keys.size
    top = np.argsort(-pmf)[:20]
    # the hottest key takes about 1 / zetan of the draws
    assert abs(pmf[top[0]] - 1 / ZETAN) < 0.004
    sd = np.sqrt(pmf[top] / keys.size)
    assert (np.abs(seen[top] - pmf[top]) < 5 * sd).all()
    # the hot ranks are scattered: they do not sit on the low key ids
    assert top[0] != 0 and np.median(top) > records / 8


def test_key_cdf_reads_the_traffic_file():
    import json
    import os

    from conftest import ROOT

    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic", "ycsb_b.json")))
    cdf = ycsb.key_cdf(traffic, 1 << 12)
    assert cdf.dtype == np.float32 and cdf.shape == (1 << 12,)
    assert abs(float(cdf[-1]) - 1) < 1e-6 and (np.diff(cdf) >= 0).all()
