"""Make the tiny 4-shard configuration's history and search it, on 4 host
devices, recording where each block is made or searched and how many
panel rows each device holds meanwhile. Prints one JSON line.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python bench/tests/_blocks4.py
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import history as H  # noqa: E402
from bench.lib import router_ref as REF  # noqa: E402
from bench.lib.traffic import seed32  # noqa: E402

CFG = ROOT / "bench/tests/data/bench/configs/tiny-route-4shard.json"
SEED = 3100000007
QUERY_BLOCK = 256
N_QUERIES = 100


def main():
    cfg = json.loads(CFG.read_text())
    db, dim = cfg["db"], cfg["router"]["embed_dim"]
    m = len(cfg["fleet"]["names"])
    cap, shards = db["capacity"], db["shards"]
    rows = cap - db["headroom"]
    block = cap // shards
    devs = jax.devices()
    assert len(devs) >= shards, devs
    peak = {d.id: 0 for d in devs}
    # on the CPU a host copy is a view of its device buffer: the
    # history's host blocks are left out once made
    host_copies = set()

    def held_rows():
        """Panel rows each device holds now: live (rows, D) f32 arrays,
        leaving out blocks of queries, the query pool and host copies."""
        now = {d.id: 0 for d in devs}
        seen = set(host_copies)     # a buffer may back several arrays
        for x in jax.live_arrays():
            if (x.ndim == 2 and x.shape[1] == dim and x.dtype == jnp.float32
                    and x.shape[0] not in (QUERY_BLOCK, N_QUERIES)
                    and x.unsafe_buffer_pointer() not in seen):
                seen.add(x.unsafe_buffer_pointer())
                for d in x.devices():
                    now[d.id] += x.shape[0]
        for k, v in now.items():
            peak[k] = max(peak[k], v)

    made, searched = [], []
    make, search = H.make_history, REF._search_block

    def make_spy(key, **kw):
        out = make(key, **kw)
        made.append([next(iter(key.devices())).id, kw["n"]])
        held_rows()
        return out

    def search_spy(parts, q, sizes, **kw):
        searched.append([next(iter(parts[0].devices())).id,
                         sum(p.shape[0] for p in parts)])
        held_rows()
        return search(parts, q, sizes, **kw)

    H.make_history, REF._search_block = make_spy, search_spy
    hist, queries = H.build_history(
        SEED, rows=rows, dim=dim, n_models=m,
        records=db["records_per_prompt"], fit_rows=db["fit_prompts"],
        n_queries=N_QUERIES, noise=0.5, shards=shards, capacity=cap)
    host_copies.update(b.ctypes.data for b in hist.raw.blocks)
    fb = np.random.default_rng(1).normal(size=(cap - rows, dim)).astype(
        np.float32)
    sizes = np.full(len(queries), cap - 7)
    blocked = REF.device_search([hist.raw, fb], queries, sizes, 20,
                                block_rows=block, devices=devs[:shards])
    H.make_history, REF._search_block = make, search
    # one search block a device, sorted by device
    search_blocks = sorted({tuple(b) for b in searched})

    blocks_equal = True
    for b, lo in enumerate(range(0, rows, block)):
        want = H.make_history(jax.random.key(seed32(SEED, 1, b)),
                              n=min(block, rows - lo), d=dim, m=m,
                              r=db["records_per_prompt"])
        got = (hist.raw, hist.a, hist.b, hist.o, hist.n_rec)
        hi = lo + want[0].shape[0]
        blocks_equal &= all(np.array_equal(np.asarray(w), g[lo:hi])
                            for w, g in zip(want, got))
    src = np.random.default_rng([SEED, 2]).integers(0, rows, N_QUERIES)
    q_want = H.make_queries(jax.random.key(seed32(SEED, 3)),
                            jnp.asarray(hist.raw), jnp.asarray(src, jnp.int32),
                            noise=0.5)
    one = REF.device_search([hist.raw, fb], queries, sizes, 20)
    print(json.dumps({
        "rows": rows, "block_rows": block,
        "history_shards": -(-rows // block),
        "history_blocks": sorted(made),
        "history_blocks_equal": bool(blocks_equal),
        "queries_equal": bool(np.array_equal(np.asarray(q_want), queries)),
        "search_blocks": search_blocks,
        "search_equal": bool(np.array_equal(one[0], blocked[0])
                             and np.array_equal(one[1], blocked[1])),
        "peak_rows_per_device": peak,
    }))


if __name__ == "__main__":
    main()
