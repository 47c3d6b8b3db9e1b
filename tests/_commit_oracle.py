"""The per-field commit that state.commit's packed upload replaced, kept
as a plain oracle, and the sequence of commits the packed path is held
to bitwise (tests/test_router_state.py on one device,
tests/_sharded_worker.py on 1/2/4-shard meshes of host devices).

The oracle uploads the row indices and each of the five fields as
their own arrays and scatters field by field, as commit() did before
it packed the rows into one buffer; it drains the same ledgers, pads
to the same buckets and applies the same rollback guard."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as SHARD
from repro.core import elo
from repro.core.state import RouterState, state_from_buffer, state_shardings

FIELDS = ("global_ratings", "emb", "model_a", "model_b", "outcome",
          "valid", "size")

#: float32 bit patterns a lossy path would change: -0.0, a quiet NaN
#: with a payload, a negative NaN with another, the smallest and the
#: largest denormal, -inf
SPECIAL_BITS = np.array([0x80000000, 0x7FC12345, 0xFFC00ABC, 0x00000001,
                         0x807FFFFF, 0xFF800000], np.uint32)


def _set_rows(emb, model_a, model_b, outcome, valid, rows,
              e, a, b, o, v):
    return (emb.at[rows].set(e), model_a.at[rows].set(a),
            model_b.at[rows].set(b), outcome.at[rows].set(o),
            valid.at[rows].set(v))


def per_field_commit(db, global_ratings, prev, consumer, mesh=None):
    """commit() as it was before the packed upload."""
    if mesh is not None:
        return _per_field_sharded(db, global_ratings, prev, consumer, mesh)
    rows = db.drain_dirty(consumer)
    if (prev is None or prev.emb.shape != db.emb.shape
            or prev.model_a.shape != db.model_a.shape):
        return state_from_buffer(db, global_ratings)
    g = jnp.asarray(global_ratings, jnp.float32)
    rows = rows[rows < db.size]
    if rows.size == 0:
        return dataclasses.replace(prev, global_ratings=g,
                                   size=jnp.int32(db.size))
    bucket = elo._pad_bucket(rows.size)
    rows = np.concatenate([rows, np.full(bucket - rows.size, rows[0],
                                         rows.dtype)])
    host = (rows, db.emb[rows], db.model_a[rows], db.model_b[rows],
            db.outcome[rows], db.valid[rows])
    dev = [jnp.asarray(x) for x in host]
    emb, a, b, o, v = jax.jit(_set_rows)(
        prev.emb, prev.model_a, prev.model_b, prev.outcome, prev.valid,
        *dev)
    return RouterState(global_ratings=g, emb=emb, model_a=a, model_b=b,
                       outcome=o, valid=v, size=jnp.int32(db.size))


def _per_field_sharded(db, global_ratings, prev, consumer, mesh):
    shards = SHARD.check_db_mesh(mesh, db.capacity)
    per_shard = db.drain_dirty_sharded(consumer, shards)
    if (prev is None or prev.emb.shape != db.emb.shape
            or prev.model_a.shape != db.model_a.shape):
        return state_from_buffer(db, global_ratings, state_shardings(mesh))
    rep = NamedSharding(mesh, P())
    g = jax.device_put(jnp.asarray(global_ratings, jnp.float32), rep)
    size = jax.device_put(jnp.int32(db.size), rep)
    if not any(r.size for r in per_shard):
        return dataclasses.replace(prev, global_ratings=g, size=size)
    c_local = db.capacity // shards
    bucket = elo._pad_bucket(max(r.size for r in per_shard))
    rows = np.empty((shards, bucket), np.int32)
    for s, r in enumerate(per_shard):
        rows[s, :r.size] = r
        rows[s, r.size:] = r[0] if r.size else s * c_local
    flat = rows.reshape(-1)
    host = (flat % c_local, db.emb[flat], db.model_a[flat],
            db.model_b[flat], db.outcome[flat], db.valid[flat])
    shr = NamedSharding(mesh, P(SHARD.DB_AXIS))
    dev = [jax.device_put(x, shr) for x in host]
    spec = P(SHARD.DB_AXIS)
    fn = jax.jit(jax.shard_map(_set_rows, mesh=mesh, in_specs=(spec,) * 11,
                               out_specs=(spec,) * 5, check_vma=False))
    emb, a, b, o, v = fn(prev.emb, prev.model_a, prev.model_b, prev.outcome,
                         prev.valid, *dev)
    return RouterState(global_ratings=g, emb=emb, model_a=a, model_b=b,
                       outcome=o, valid=v, size=size)


def _touch(db, row, emb=None, outcome=None, valid=None):
    """Rewrite a stored row in place and mark it dirty on every
    ledger, as an update of that row's feedback would."""
    if emb is not None:
        db.emb[row] = emb
    if outcome is not None:
        db.outcome[row] = outcome
    if valid is not None:
        db.valid[row] = valid
    for ledger in db._dirty.values():
        ledger.add(row)


def _add(db, rng, n, m):
    d = db.emb.shape[1]
    for _ in range(n):
        db.add(rng.normal(size=(1, d)).astype(np.float32),
               [int(rng.integers(0, m))], [int(rng.integers(0, m))],
               [float(rng.integers(0, 3)) / 2], query_id=[db.size])


def commit_steps(db, rng, m):
    """Mutate `db` for each commit of the sequence; yields the step's
    name once the host buffer holds it. Starts from 40 stored rows of a
    128-row buffer: on 4 shards of 32 rows, shards 2 and 3 own none."""
    r = db.model_a.shape[1]
    d = db.emb.shape[1]
    special = SPECIAL_BITS.view(np.float32)
    _add(db, rng, 40, m)
    yield "full"
    _add(db, rng, 5, m)                       # 5 rows in a bucket of 64
    yield "padded"
    e = rng.normal(size=d).astype(np.float32)
    e[:special.size] = special
    o = np.resize(special, r)
    _touch(db, 3, emb=e, outcome=o,
           valid=np.arange(r) % 2 == 0)       # mixed True/False
    _touch(db, 41, emb=e[::-1].copy(), outcome=o[::-1].copy(),
           valid=np.arange(r) % 3 == 1)
    yield "special"
    for ledger in db._dirty.values():         # rolled-back rows
        ledger.update({db.size, db.size + 7, db.capacity - 1})
    _touch(db, 7, outcome=np.full(r, -0.0, np.float32))
    yield "stale"
    yield "none"                              # ratings alone change
    _add(db, rng, 30, m)                      # rows 45-74: shards 1, 2
    _touch(db, 0, valid=np.zeros(r, bool))
    yield "spread"


def bits(x) -> np.ndarray:
    """Host copy of an array with floats as their bit patterns."""
    x = np.asarray(jax.device_get(x))
    return x.view(np.uint32) if x.dtype == np.float32 else x


def differing(want: RouterState, got: RouterState):
    """Fields of two states whose bits differ."""
    return [f for f in FIELDS
            if not np.array_equal(bits(getattr(want, f)),
                                  bits(getattr(got, f)))]


def host_differing(db, got: RouterState):
    """Panels of a committed state whose live rows differ, bit for bit,
    from the host buffer's."""
    n = db.size
    return [f for f in FIELDS[1:6]
            if not np.array_equal(bits(getattr(got, f))[:n],
                                  bits(getattr(db, f)[:n]))]
