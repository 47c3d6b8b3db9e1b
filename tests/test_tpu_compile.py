"""Compile the routing path for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: it refuses what the chip would refuse
(unsupported Pallas lowerings, misaligned blocks, programs that do not
fit). Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the test
workers all import every test file. The persistent compilation cache
is off around these compiles: an entry written for a described chip
cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import sharding as SHARD
from repro.core import state as STATE
from repro.core.state import (RouterState, route_batch_choices,
                              route_batch_choices_sharded, state_shardings)
from repro.kernels.elo_scan import elo_scan_pallas, elo_scan_select_pallas
from repro.kernels.similarity_topk import similarity_pallas, two_stage_topk

D, M, N, R, Q = 1536, 10, 20, 8, 256   # paper router, fleet of 10
KW = dict(p_global=0.5, n_neighbors=N, k=32.0, backend="pallas",
          mode="combined", init_rating=1000.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(capacity, shardings):
    """RouterState of shapes at paper width; `shardings` is one
    sharding for every leaf or a RouterState of them."""
    sh = shardings if isinstance(shardings, RouterState) else RouterState(
        *(shardings,) * 7)
    return RouterState(
        global_ratings=_sds((M,), jnp.float32, sh.global_ratings),
        emb=_sds((capacity, D), jnp.float32, sh.emb),
        model_a=_sds((capacity, R), jnp.int32, sh.model_a),
        model_b=_sds((capacity, R), jnp.int32, sh.model_b),
        outcome=_sds((capacity, R), jnp.float32, sh.outcome),
        valid=_sds((capacity, R), bool, sh.valid),
        size=_sds((), jnp.int32, sh.size))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_similarity_kernel_compiles_at_paper_width(one_chip):
    c = jax.jit(similarity_pallas).lower(
        _sds((Q, D), jnp.float32, one_chip),
        _sds((1 << 16, D), jnp.float32, one_chip)).compile()
    assert _custom_calls(c) == 1


@pytest.mark.parametrize("select", [False, True],
                         ids=["elo_scan", "elo_scan_select"])
def test_elo_kernels_compile(one_chip, select):
    t = N * R
    args = [_sds((Q, M), jnp.float32, one_chip),
            _sds((Q, t), jnp.int32, one_chip),
            _sds((Q, t), jnp.int32, one_chip),
            _sds((Q, t), jnp.float32, one_chip),
            _sds((Q, t), bool, one_chip)]
    fn = elo_scan_pallas
    if select:
        args += [_sds((M,), jnp.float32, one_chip),
                 _sds((M,), jnp.float32, one_chip),
                 _sds((Q,), jnp.float32, one_chip)]
        fn = elo_scan_select_pallas
    assert _custom_calls(jax.jit(fn).lower(*args).compile()) == 1


def test_route_batch_choices_pallas_compiles_at_paper_width(one_chip):
    """The served route: both kernels are Mosaic custom calls, and the
    executable fits one chip's 16 GB."""
    c = route_batch_choices.lower(
        _state(1 << 18, one_chip), _sds((Q, D), jnp.float32, one_chip),
        _sds((Q,), jnp.float32, one_chip),
        _sds((M,), jnp.float32, one_chip), **KW).compile()
    assert _custom_calls(c) == 2
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def _entry_ops(compiled):
    """(element count, opcode, line) of each array-valued op of the
    entry computation: the buffers the program materialises."""
    txt = compiled.as_text()
    out = []
    for line in txt[txt.index("\nENTRY"):].splitlines()[1:]:
        m = _OP.match(line)
        if m:
            dims = [int(x) for x in m.group(1).split(",") if x]
            out.append((int(np.prod(dims)), m.group(2), line))
    return out


def test_route_topk_reads_the_paper_panel_once_in_place(one_chip):
    """At Q = 256 over 2^20 rows the route's top-k takes two stages
    (two TopK calls: the chunk maxima's and the candidates'). Nothing
    but the similarity kernel materialises an array of the panel's
    size: no masked panel, no relayout copy. Temporaries are the
    panel, the chunk maxima (a lane reduce writes 8 values of each
    (8, 128) tile row padded to a lane row: one eighth of the panel)
    and under 64 MiB besides; the one-stage top-k's were the panel and
    1.3 MB (1,075,001,856 B)."""
    cap = 1 << 20
    assert two_stage_topk(cap, N)
    c = route_batch_choices.lower(
        _state(cap, one_chip), _sds((Q, D), jnp.float32, one_chip),
        _sds((Q,), jnp.float32, one_chip),
        _sds((M,), jnp.float32, one_chip), **KW).compile()
    assert c.as_text().count('custom_call_target="TopK"') == 2
    big = [line for n, op, line in _entry_ops(c)
           if n >= Q * cap and op not in ("bitcast", "parameter")]
    assert len(big) == 1 and "eagle_similarity" in big[0] \
        and "f32[256,1048576]" in big[0], big
    panel_bytes = Q * cap * 4
    assert c.memory_analysis().temp_size_in_bytes < \
        panel_bytes + panel_bytes // 8 + (64 << 20)


def test_sharded_route_and_commit_compile_on_four_described_chips(topo):
    """The capacity-sharded route (DESIGN.md §12) at 2^20 rows on a
    4-device mesh: the merge is an all-gather, and the owner-scatter
    commit compiles for the same mesh."""
    mesh = Mesh(np.asarray(topo.devices[:4]), (SHARD.DB_AXIS,),
                axis_types=(AxisType.Auto,))
    rep = NamedSharding(mesh, P())
    c = route_batch_choices_sharded.lower(
        _state(1 << 20, state_shardings(mesh)),
        _sds((Q, D), jnp.float32, rep), _sds((Q,), jnp.float32, rep),
        _sds((M,), jnp.float32, rep), mesh=mesh, **KW).compile()
    assert _custom_calls(c) == 2
    assert "all-gather" in c.as_text()
    shd = NamedSharding(mesh, P(SHARD.DB_AXIS))
    cap, rows = 1 << 20, 4 * 64
    STATE._sharded_scatter(mesh).lower(
        _sds((cap, D), jnp.float32, shd), _sds((cap, R), jnp.int32, shd),
        _sds((cap, R), jnp.int32, shd), _sds((cap, R), jnp.float32, shd),
        _sds((cap, R), bool, shd),
        _sds((rows, STATE.packed_width(D, R)), jnp.int32, shd)).compile()
