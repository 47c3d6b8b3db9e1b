"""Sharded-equivalence suite (DESIGN.md §12): RouterState capacity-
sharded over a device mesh must be bit-identical to the single-device
oracle — routing choices, retrieval traces, and post-commit() state —
with zero post-warmup compiles per mesh shape.

The forced-host-device XLA flag must be set before jax initializes, so
the whole matrix runs ONCE in a subprocess (tests/_sharded_worker.py,
`XLA_FLAGS=--xla_force_host_platform_device_count=4`) that prints a
JSON report; the tests here assert over that report. One spawn per
pytest session — the memoized report is shared by every test below,
including the shim-replayed seeded property test."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from _hypothesis_compat import given, settings, st

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from bench.lib.readers import (ROUTE_MODULE, SCATTER_MODULE,  # noqa: E402
                               SHARD_SCATTER_MODULE)

_REPORT = {}

MESHES = ("1", "2", "4")


def report():
    """Memoized worker report (module-level, not a fixture: the
    hypothesis shim's fallback wrapper takes no pytest fixtures)."""
    if not _REPORT:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO / "src")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4"
                            ).strip()
        r = subprocess.run(
            [sys.executable, str(REPO / "tests" / "_sharded_worker.py")],
            env=env, capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
        _REPORT.update(json.loads(r.stdout.splitlines()[-1]))
    return _REPORT


def test_worker_saw_forced_devices():
    assert report()["n_devices"] == 4


def test_sharded_routing_bit_identical_all_meshes_modes_backends():
    """route_batch_choices on a DB mesh == on one device, bitwise
    (choices AND topk_idx), on 1/2/4-shard meshes for every routing
    mode x both exercisable backends."""
    equiv = report()["equiv"]
    assert set(equiv) == set(MESHES)
    for mesh, cases in equiv.items():
        assert len(cases) == 6, (mesh, sorted(cases))
        bad = [k for k, ok in cases.items() if not ok]
        assert not bad, f"mesh {mesh}: diverged on {bad}"


def test_tie_breaking_matches_oracle():
    """Duplicate embeddings straddling every shard boundary (exercised
    inside the main matrix's crafted queries) plus the dedicated
    empty-DB/flat-ratings cases: equal scores must break identically
    — the (shard, local rank) merge order is the contract."""
    ties = report()["ties"]
    for mesh in MESHES:
        assert ties[mesh] == {"combined": True, "local": True}, \
            (mesh, ties[mesh])


def test_incremental_sharded_commit_matches_oracle():
    """After new-row appends AND existing-row touches, the sharded
    owner-scatter commit must equal the oracle commit field for field,
    and the states must route identically."""
    for mesh, fields in report()["commit"].items():
        bad = [f for f, ok in fields.items() if not ok]
        assert not bad, f"mesh {mesh}: commit diverged on {bad}"


def test_packed_sharded_commit_bitwise_equals_per_field_commit():
    """Over a sequence of commits (a padded bucket, shards that own no
    dirty row, rows rewritten with -0.0, NaN payloads, denormals and
    mixed `valid`, rolled-back rows past the live count, a commit with
    no dirty row), the packed commit, one transfer a shard, leaves every
    field bit for bit as the per-field commit it replaced, and the live
    rows as the host buffer holds them: {step: differing fields}."""
    assert report()["packed_commit"] == {m: {} for m in MESHES}


def test_steady_commit_keeps_shardings_and_compiles_nothing():
    """Through RouteDispatcher's cached route executable on a
    DoubleBuffer: after warm-up, windows of route + feedback + packed
    commit compile nothing, and every front keeps the capacity
    partition's shardings, so the one executable runs on each."""
    for mesh, got in report()["dbuf_steady"].items():
        assert got == {"compiles": 0, "shardings": True, "misses": 1,
                       "packed": 8}, (mesh, got)


def test_zero_post_warmup_compiles_per_mesh():
    """Steady-state route+feedback+commit loops recompile nothing once
    warmed (warmup includes real feedback+commit cycles — the scatter
    only compiles on the first non-empty ledger)."""
    hot = report()["hot_compiles"]
    assert hot == {m: 0 for m in MESHES}, hot


@settings(max_examples=8)
@given(st.integers(0, 7))
def test_seeded_random_batches_match_oracle(seed):
    """Property-style: seeded random query batches (shape 1..8) under
    random budgets agree with the oracle on 2- and 4-shard meshes. The
    worker computes the seeded table; the shim (or real hypothesis)
    replays every seed here."""
    assert report()["seeded"][str(int(seed))] is True


def test_sharded_programs_carry_the_stage_scopes():
    """Each stage of the 4-shard route, the cross-shard merge among
    them, and the owner-scatter lower under their named scopes."""
    scopes = report()["scopes"]
    for bk in ("reference", "pallas_interpret"):
        assert scopes[bk] == sorted(["eagle.similarity", "eagle.topk",
                                     "eagle.gather", "eagle.merge",
                                     "eagle.replay"]), bk
    assert scopes["commit"] is True
    # bench/lib/readers.py finds commit_device_ms.shard by this name
    assert report()["scatter_module"] == SHARD_SCATTER_MODULE


def test_two_stage_topk_shards_bit_identical_and_counted():
    """A DB wide enough that every shard's top-k takes two stages, with
    ties straddling the shard boundaries: the dispatcher's route equals
    one device's bit for bit on every mesh and backend, on each of its
    dispatches; at the small capacity it dispatches the same."""
    wide = report()["wide"]
    assert wide["two_stage_per_shard"] == {m: True for m in MESHES}
    for mesh in MESHES:
        for bk in ("reference", "pallas_interpret"):
            assert wide[f"{mesh}/{bk}"] == {
                "equal": True, "calls": 3}, (mesh, bk)
    assert wide["narrow"] == {"calls": 3}


def test_route_and_scatter_compile_to_the_modules_the_readers_match():
    """The per-layer readers (bench/lib/readers.py) find the route by
    the prefix of its module, with and without a DB mesh, and the
    one-device commit scatter by its module's name."""
    modules = report()["modules"]
    for mesh, name in modules["route"].items():
        assert name.startswith(ROUTE_MODULE), (mesh, name)
    assert set(modules["route"]) == set(MESHES) | {"none"}
    assert modules["scatter"] == SCATTER_MODULE
