"""The operation and byte counters against hand-worked values at the
shapes of route.paper1m.w256 (Q=256 live queries, C=2^20 rows, D=1536,
N=20 neighbours of R=8 records, M=10 models) and of OLMo-1B."""
import math

from bench.lib import work
from bench.lib.peaks import PEAKS, roofline_seconds

Q, C, D, N, R, M = 256, 1 << 20, 1536, 20, 8, 10
OLMO = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
        "head_dim": 128, "d_ff": 8192, "vocab": 50304}


def test_retrieval_at_cell_shapes():
    flops, nbytes = work.retrieval(Q, C, D, N)
    assert flops == 824_633_720_832            # 2*256*2^20*1536
    # panel 6_442_450_944 + queries 1_572_864 + (Q, N) scores and ids 40_960
    assert nbytes == 6_444_064_768


def test_retrieval_is_memory_bound_on_v5e():
    flops, nbytes = work.retrieval(Q, C, D, N)
    bound, t = roofline_seconds(flops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory"
    assert math.isclose(t, 6_444_064_768 / 819e9)   # 7.868 ms
    assert math.isclose(flops / 197e12, 4.18596e-3, rel_tol=1e-5)


def test_replay_at_cell_shapes():
    flops, nbytes = work.replay(Q, N * R, M)
    assert flops == 10 * 256 * 160 + 4 * 256 * 10        # 419_840
    # records 256*160*13, budgets 1024, prior/global/costs 120,
    # ratings 10_240 and choices 1024 out
    assert nbytes == 532_480 + 1024 + 120 + 10_240 + 1024


def test_route_step_is_the_sum():
    f, b = work.route_step(Q, C, D, N, R, M)
    assert f == 824_633_720_832 + 419_840
    assert b == 6_444_064_768 + 544_888


def test_olmo_layer_params():
    assert work.dense_layer_params(2048, 16, 128, 16, 8192) == 67_108_864
    # 16 layers: the published 1.07e9 non-embedding parameters
    assert 16 * 67_108_864 == 1_073_741_824


def test_olmo_token_flops():
    head = 2 * 2048 * 50304
    assert work.dense_decode_flops(OLMO, 127) == \
        16 * (2 * 67_108_864 + 4 * 128 * 2048) + head
    # prefill of 4 tokens: 4 positions through every block, causal
    # attention over 1+2+3+4 = 10 (query, key) pairs, head once
    assert work.dense_prefill_flops(OLMO, 4) == \
        16 * (2 * 67_108_864 * 4 + 4 * 10 * 2048) + head
    assert work.dense_request_flops(OLMO, 4, 3) == (
        work.dense_prefill_flops(OLMO, 4) + work.dense_decode_flops(OLMO, 4)
        + work.dense_decode_flops(OLMO, 5))
