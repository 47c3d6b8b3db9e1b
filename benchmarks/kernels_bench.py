"""Kernel microbenchmarks: wall time per call (CPU, reference backend) and
derived throughput. The Pallas variants are correctness-validated in
interpret mode (tests/test_kernels.py); wall-clock here measures the
XLA-compiled reference path this container actually serves with."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.kernels import ops


def run(verbose=True):
    rng = np.random.default_rng(0)
    rows = []

    # retrieval: 64 queries x 16k-entry DB, d=1536 (stella-sized)
    q = jnp.asarray(rng.normal(size=(64, 1536)), jnp.float32)
    db = jnp.asarray(rng.normal(size=(16384, 1536)), jnp.float32)
    us, _ = C.timer(lambda: ops.similarity_topk(q, db, 20))
    flops = 2 * 64 * 16384 * 1536
    rows.append(("similarity_topk_64x16k", us, f"{flops/us/1e3:.1f}GFLOP/s"))

    # local elo replay: 64 queries x 160 records x 10 models
    ratings = jnp.full((64, 10), 1000.0)
    a = jnp.asarray(rng.integers(0, 10, (64, 160)), jnp.int32)
    b = jnp.asarray((np.asarray(a) + 1) % 10, jnp.int32)
    s = jnp.asarray(rng.choice([0., .5, 1.], (64, 160)), jnp.float32)
    v = jnp.ones((64, 160), bool)
    from repro.core import elo
    us, _ = C.timer(lambda: elo.local_elo(ratings[0], a, b, s, v))
    rows.append(("elo_local_64x160", us,
                 f"{64*160/us:.2f}updates/us"))

    # fused routing retrieval: 64 queries x 16k-entry DB (d=1536, R=8,
    # N=20, M=10) — similarity + top-k + gather + ELO replay, one dispatch
    a = jnp.asarray(rng.integers(0, 10, (16384, 8)), jnp.int32)
    b = jnp.asarray((np.asarray(a) + 1) % 10, jnp.int32)
    o = jnp.asarray(rng.choice([0., .5, 1.], (16384, 8)), jnp.float32)
    v = jnp.ones((16384, 8), bool)
    init = jnp.full((10,), 1000.0, jnp.float32)
    costs = jnp.ones((10,), jnp.float32)
    budgets = jnp.full((64,), jnp.inf, jnp.float32)
    us, _ = C.timer(lambda: ops.retrieve_replay_select(
        q, db, a, b, o, v, jnp.int32(16384), init, init, costs, budgets,
        n=20)[0])
    # similarity panel + 160-step replay over (64,10) one-hot tiles;
    # the panel matmul dominates
    rr_flops = 2 * 64 * 16384 * 1536 + 160 * 64 * 10 * 8
    rows.append(("retrieve_replay_64x16k", us,
                 f"{rr_flops/us/1e3:.1f}GFLOP/s"))

    # flash attention prefill block: B1 S1024 H8 dh128
    qq = jnp.asarray(rng.normal(size=(1, 1024, 8, 128)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(size=(1, 1024, 8, 128)), jnp.bfloat16)
    us, _ = C.timer(lambda: ops.flash_attention(qq, kk, kk))
    flops = 4 * 1024 * 1024 * 8 * 128 / 2  # causal half
    rows.append(("flash_attention_1k", us, f"{flops/us/1e3:.1f}GFLOP/s"))

    # decode attention: B8 T8192 H8 dh128
    qd = jnp.asarray(rng.normal(size=(8, 8, 128)), jnp.bfloat16)
    kd = jnp.asarray(rng.normal(size=(8, 8192, 8, 128)), jnp.bfloat16)
    kl = jnp.full((8,), 8192, jnp.int32)
    us, _ = C.timer(lambda: ops.decode_attention(qd, kd, kd, kl))
    bts = 2 * 8 * 8192 * 8 * 128 * 2
    rows.append(("decode_attention_8k", us, f"{bts/us/1e3:.1f}GB/s"))

    if verbose:
        for n, us, d in rows:
            print(f"[kernels] {n},{us:.1f},{d}")
    C.save_json("kernels_bench.json",
                [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows])
    return rows


if __name__ == "__main__":
    run()
