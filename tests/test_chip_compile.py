"""The main path's device programs compile for a v5e chip, at real widths.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2):
what it refuses — a shape that does not fit, a kernel it cannot lower —
it refuses here, at no chip time. Nothing runs, so these tests say
nothing about results or times.

Rules this file keeps, because only one process may load the TPU's
library: the topology is described inside a module-scoped fixture,
never at import, not ``autouse`` and not in ``conftest.py``; every
compile happens in the test's own process; and the persistent compile
cache is off around them (a described-topology entry cannot be read back
without a chip). All such tests live in THIS file, so that one xdist
worker gets them all.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from zipkin_tpu.tpu.state import AggConfig, init_state

LANES = 65_536  # the largest device batch the server dispatches
CFG = AggConfig()  # the server's default state size


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _structs(tree, sharding, lead=()):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            lead + tuple(a.shape), a.dtype, sharding=sharding),
        tree,
    )


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    """Programs and argument shapes on a 1-device described mesh."""
    from zipkin_tpu.parallel import sharded

    mesh = Mesh(np.asarray(topo.devices[:1]), (sharded.SHARD_AXIS,))
    shard = NamedSharding(mesh, P(sharded.SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    leaf = jax.eval_shape(lambda: init_state(CFG))
    (
        _init, step_variants, _links, _merge, flush, rollup, _whist,
        _digest_read, _edges, edges_fresh, _edges_rolled, _qd, _qdn, _qh,
        _qw, _card, link_ctx, _snap, _sharding, _overview, ttread, raw,
    ) = sharded._compiled_programs(CFG, mesh)
    state = _structs(leaf, shard, lead=(1,))
    return {
        "device": topo.devices[0],
        "leaf": leaf,
        "state": state,
        "fused": jax.ShapeDtypeStruct((1, 11, LANES), jnp.uint32, sharding=shard),
        "u32": jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),
        "i32": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        "shard": shard,
        "step": step_variants,
        "flush": flush,
        "rollup": rollup,
        "edges_fresh": edges_fresh,
        "link_ctx": link_ctx,
        "ttread": ttread,
        "raw": raw,
    }


def _fits(compiled) -> None:
    """The compiler accepted it; its own accounting fits one v5e chip."""
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    assert 0 < total < 16 * 1024**3, ma


def test_ingest_step_single_shard(one_chip):
    from zipkin_tpu.parallel.sharded import unfuse_columns
    from zipkin_tpu.tpu import ingest as ing

    one = SingleDeviceSharding(one_chip["device"])
    cols = jax.eval_shape(
        unfuse_columns, jax.ShapeDtypeStruct((11, LANES), jnp.uint32))
    compiled = jax.jit(functools.partial(ing.ingest_step, CFG)).lower(
        _structs(one_chip["leaf"], one), _structs(cols, one)
    ).compile()
    _fits(compiled)


def test_step_variant(one_chip):
    # the steady-state step over the fused wire image, as dispatched
    step = one_chip["step"][(False, False)]
    _fits(step.lower(one_chip["state"], one_chip["fused"]).compile())


def test_edges_rolled(one_chip):
    u32 = one_chip["u32"]
    _fits(one_chip["raw"]["edges_rolled"].lower(
        one_chip["state"], u32, u32).compile())


@pytest.mark.parametrize("name", ["merge", "card"])
def test_merge_reads(one_chip, name):
    _fits(one_chip["raw"][name].lower(one_chip["state"]).compile())


@pytest.fixture(scope="module")
def four_chips(topo, no_persistent_cache):
    """Read programs and the state's shapes on a 4-device described mesh."""
    from zipkin_tpu.parallel import sharded

    mesh = Mesh(np.asarray(topo.devices[:4]), (sharded.SHARD_AXIS,))
    shard = NamedSharding(mesh, P(sharded.SHARD_AXIS))
    programs = sharded._compiled_programs(CFG, mesh)
    state = _structs(
        jax.eval_shape(lambda: init_state(CFG)), shard, lead=(4,))
    return {"mesh": mesh, "shard": shard, "state": state,
            "raw": programs[-1]}


HLL_WIDENED = "u32[1025,2048]"  # the register plane, crossing as u32


def _all_reduce_shapes(compiled) -> list:
    """Result shapes of every all-reduce in the compiled program."""
    import re

    return re.findall(
        r"= (\w+\[[\d,]*\])\S* all-reduce(?:-start)?\(", compiled.as_text())


def test_a_bare_u8_pmax_does_not_cross_as_u32_registers(four_chips):
    """What the guard below tells apart. On this compiler a bare u8
    pmax is bitcast to packed u32 WORDS (u32[2,264192]) and max-reduced
    as words, which is not a max of its bytes: the wrong answers the
    four-chip run showed (PR 22)."""
    from jax import shard_map

    bare = jax.jit(shard_map(
        lambda a: jax.lax.pmax(a[0], "shard"), mesh=four_chips["mesh"],
        in_specs=(P("shard"),), out_specs=P(), check_vma=False,
    ))
    x = jax.ShapeDtypeStruct(
        (4, 1025, 2048), jnp.uint8, sharding=four_chips["shard"])
    assert HLL_WIDENED not in _all_reduce_shapes(bare.lower(x).compile())


@pytest.mark.parametrize("name", ["merge", "card"])
def test_hll_registers_cross_the_chips_widened(four_chips, name):
    """sharded.pmax_registers: the HLL merge is an element-wise u32
    all-reduce of the logical [S+1, m] plane, never a u8 one."""
    compiled = four_chips["raw"][name].lower(four_chips["state"]).compile()
    _fits(compiled)
    shapes = _all_reduce_shapes(compiled)
    assert HLL_WIDENED in shapes, shapes
    assert not [s for s in shapes if s.startswith("u8[")], shapes


# One to two minutes EACH on this sandbox's CPUs (the ring-wide sorts of
# the digest flush and the link context are what the TPU compiler is
# slow on): compiled by hand before a chip call (CHANGES.md PR 22 has
# the result) and kept out of tier-1, which runs in under three minutes.
@pytest.mark.slow
def test_fused_step_variant(one_chip):
    # flush + rollup fused in front of the step: the largest variant
    step = one_chip["step"][(True, True)]
    _fits(step.lower(one_chip["state"], one_chip["fused"]).compile())


@pytest.mark.slow
@pytest.mark.parametrize("name", ["flush", "rollup"])
def test_maintenance_programs(one_chip, name):
    _fits(one_chip[name].lower(one_chip["state"]).compile())


@pytest.mark.slow
def test_edges_fresh(one_chip):
    u32 = one_chip["u32"]
    _fits(one_chip["edges_fresh"].lower(
        one_chip["state"], u32, u32).compile())


@pytest.mark.slow
def test_ttread(one_chip):
    ctx = _structs(
        jax.eval_shape(one_chip["link_ctx"], one_chip["state"]),
        one_chip["shard"],
    )
    i32 = one_chip["i32"]
    _fits(one_chip["ttread"].lower(
        ctx, one_chip["state"], i32, i32).compile())
