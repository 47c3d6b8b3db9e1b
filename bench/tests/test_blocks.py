"""The harness's history and reference search, made and searched one
shard's block at a time for a sharded DB, and unchanged for an
unsharded one.

The hashes below were taken from the formula the benchmark used before
it made sharded histories in blocks (one make_history call, one search
over the whole panel), on the CPU, at a reduced row count: the
unsharded cells must draw the same bytes."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.lib import history as H
from bench.lib import router_ref as REF

ROOT = Path(__file__).resolve().parents[2]
ENV4 = dict(os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")

#: (config, seed) -> sha256 prefix of raw, a, b, o, n_rec and 64 queries
#: at 4096 rows of D=1536 (fit rows 2048, query noise 0.5)
HISTORY_HASHES = {
    ("eagle-paper-1m", 4242): "fcf0d840190f9b88",
    ("eagle-paper-1m", 3100000007): "002b44d46ee56a8c",
    ("eagle-olmo1b-served", 4242): "e24fbb8f1aca4fbf",
    ("eagle-olmo1b-served", 3100000007): "52eedfd770a68d5a",
}
#: control -> sha256 prefix of the candidates (scores, rows) of 300
#: queries over 4096 history rows and 512 feedback rows
SEARCH_HASHES = {False: "e4c9ae8495c42c85", True: "a61eacc0c4803bed"}


def _digest(*arrays):
    d = hashlib.sha256()
    for x in arrays:
        d.update(np.ascontiguousarray(x).tobytes())
    return d.hexdigest()[:16]


def _config(name):
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seed", sorted(HISTORY_HASHES))
def test_unsharded_history_draws_the_same_bytes(name, seed):
    cfg = _config(name)
    assert cfg["db"]["shards"] == 1
    hist, q = H.build_history(seed, rows=4096, dim=cfg["router"]["embed_dim"],
                              n_models=len(cfg["fleet"]["names"]), records=8,
                              fit_rows=2048, n_queries=64, noise=0.5,
                              shards=1, capacity=cfg["db"]["capacity"])
    got = _digest(hist.raw, hist.a, hist.b, hist.o, hist.n_rec, q)
    assert got == HISTORY_HASHES[(name, seed)]


@pytest.mark.parametrize("control,n", [(False, 32), (True, 20)])
def test_unsharded_search_finds_the_same_candidates(control, n):
    hist, q = H.build_history(4242, rows=4096, dim=1536, n_models=10,
                              records=8, fit_rows=2048, n_queries=300,
                              noise=0.5)
    fb = np.random.default_rng(7).normal(size=(512, 1536)).astype(np.float32)
    fb[300:] = 0
    sizes = np.where(np.arange(300) % 3 == 0, 4096 + 300, 4096 - 1000)
    s, i = REF.device_search([hist.raw, fb], q, sizes, n, control=control)
    assert _digest(np.asarray(s, np.float32),
                   np.asarray(i, np.int32)) == SEARCH_HASHES[control]


def _one_hot_panel(rows, dim, rng):
    """Rows that are scaled unit vectors: a row's cosine with a query is
    one coordinate of the normalized query, exact in any order of
    summation, so rows on one axis tie bit for bit."""
    axis = rng.integers(0, dim, rows)
    p = np.zeros((rows, dim), np.float32)
    p[np.arange(rows), axis] = rng.integers(1, 4, rows)
    return p


@pytest.mark.parametrize("case", ["gaussian", "ties", "live_inside",
                                  "small_blocks"])
@pytest.mark.parametrize("control", [False, True])
def test_blocked_search_equals_one_block(case, control):
    rng = np.random.default_rng(11)
    dim, n = 64, 32
    if case == "ties":
        # the same rows on both sides of the block boundary at 1024
        hist = _one_hot_panel(1024, dim, rng)
        hist = np.concatenate([hist, hist[::-1]])
    else:
        hist = rng.normal(size=(2048, dim)).astype(np.float32)
    fb = rng.normal(size=(512, dim)).astype(np.float32)
    q = rng.normal(size=(300, dim)).astype(np.float32)
    total = len(hist) + len(fb)
    sizes = np.full(300, total)
    if case == "live_inside":
        # live sizes inside the second and the last block, one query
        # whose live rows end inside the first
        sizes = np.where(np.arange(300) % 2 == 0, 1500, 2300)
        sizes[7] = 10
    block_rows = {"small_blocks": 384}.get(case, total // 2)
    one = REF.device_search([hist, fb], q, sizes, n, control=control)
    blocked = REF.device_search([hist, fb], q, sizes, n, control=control,
                                block_rows=block_rows)
    np.testing.assert_array_equal(blocked[1], one[1])
    np.testing.assert_array_equal(blocked[0], one[0])
    assert blocked[1].dtype == one[1].dtype


def test_fewer_live_rows_than_n_pick_the_lowest_dead_rows():
    rng = np.random.default_rng(3)
    hist = rng.normal(size=(256, 16)).astype(np.float32)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    sizes = np.asarray([3, 0, 100, 129, 256])
    one = REF.device_search([hist], q, sizes, 20)
    blocked = REF.device_search([hist], q, sizes, 20, block_rows=64)
    np.testing.assert_array_equal(blocked[1], one[1])
    np.testing.assert_array_equal(blocked[0], one[0])


def test_row_slices_cut_across_panels():
    a, b = np.arange(10)[:, None], np.arange(10, 14)[:, None]
    parts = REF._row_slices([a, b], 8, 12)
    assert [p[:, 0].tolist() for p in parts] == [[8, 9], [10, 11]]
    assert REF._row_slices([a, b], 12, 20)[0][:, 0].tolist() == [12, 13]


def test_sharded_blocks_on_four_devices():
    """On 4 host devices: the tiny 4-shard configuration's history is
    made one shard's block per device, the reference searches one
    shard's block per device, no device ever holds more than one block,
    and both agree with the one-block formulas."""
    p = subprocess.run([sys.executable, "bench/tests/_blocks4.py"],
                       cwd=ROOT, env=ENV4, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    block = r["block_rows"]
    # history: one block per shard that holds history rows, shard s's
    # on device s, none larger than a shard
    assert r["history_blocks"] == [[s, min(block, r["rows"] - s * block)]
                                   for s in range(r["history_shards"])]
    assert r["history_blocks_equal"] is True
    assert r["queries_equal"] is True
    # search: every shard's rows on its own device, one block each
    assert r["search_blocks"] == [[s, block] for s in range(4)]
    assert r["search_equal"] is True
    # no device held rows of more than one block at a time
    assert max(r["peak_rows_per_device"].values()) <= block


def test_rows_slice_and_gather_as_one_array():
    rng = np.random.default_rng(5)
    whole = rng.normal(size=(23, 3)).astype(np.float32)
    rows = H.Rows([whole[:8], whole[8:16], whole[16:]])
    assert rows.shape == whole.shape and len(rows) == 23
    for sl in (slice(0, 23), slice(2, 7), slice(5, 19), slice(16, 30),
               slice(9, 9), slice(None, 4), slice(-5, None)):
        np.testing.assert_array_equal(rows[sl], whole[sl])
    assert np.shares_memory(rows[9:15], whole)        # inside one block
    idx = rng.integers(0, 23, (40,))
    np.testing.assert_array_equal(rows[idx], whole[idx])
    np.testing.assert_array_equal(np.asarray(rows), whole)
    with pytest.raises(IndexError):
        rows[::2]
