"""Compile rehearsal for the v5e: the kernels the served q7/q8 path
dispatches, lowered and compiled for a DESCRIBED `v5e:2x2` (the TPU
compiler is installed here; no chip is attached), at the sizes
`chip_smoke.py` runs them.

A compile that passes is not a chip run: nothing executes, so this says
nothing about results or times. What it guards is what the chip's
compiler refuses and the CPU tests cannot see — above all the 64-bit
rewrite, which has no f64→s64/u64 bitcast (the three float cases at the
bottom).

The real preludes come from a tiny served session on the CPU (the
`served` fixture): the tests take the deployed executors' own jitted
kernels (`InstrumentedJit._jit`) and lower them against
`ShapeDtypeStruct`s that carry a sharding on the described devices.
The sharded kernels' constructors `device_put` their state, which a
described device refuses, so their step builders run on an instance
that skipped `__init__` — in this file only.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU's library, and every xdist worker
imports every test file), and every compile happens in this process,
in this one file.
"""

import asyncio
import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from risingwave_tpu.frontend.session import Frontend
from risingwave_tpu.ops import hash_agg, hash_join, hash_table
from risingwave_tpu.ops.hash_agg import AggKind, AggSpec
from risingwave_tpu.utils import jaxtools

import chip_smoke

# the sizes chip_smoke.py reaches: q7's join side ends in 2^21 key slots
# and 2^22 row slots, `pairs` in a 2^20-slot table (2^21 here: the rung
# 4M events would reach). One size is NOT the smoke's: its join epochs
# carry 2^17 rows (32 chunks of 4096), and these programs' compile time
# grows steeply with that dimension (epoch_apply: 6 s at 2^14 rows, 26 s
# at 2^15, 30 s at 2^17, compiled here for the described chip) while
# what the compiler refuses does not depend on it, so the join tests use
# 2^14 and the suite stays light.
AGG_CAPACITY = 1 << 21
AGG_BATCH = hash_agg.GroupedAggKernel.BATCH_ROWS
CHUNK_ROWS = chip_smoke.CHUNK_ROWS
EPOCH_ROWS = 1 << 14
FLUSH_ROWS = 1 << 17
JOIN_KEYS = 1 << 21
JOIN_ROWS = 1 << 22
PROBE_OUT = EPOCH_ROWS


# -- the described chip -------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices), ("d",))


@contextlib.contextmanager
def _persistent_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run would warn
    and compile again): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(kernel, *args):
    """Lower an InstrumentedJit (or its jax.jit) and compile it; raises
    what the chip's compiler would raise."""
    jitted = getattr(kernel, "_jit", kernel)
    with _persistent_cache_off():
        return jitted.lower(*args).compile()


def _on(sharding, tree):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) on `sharding`;
    static (non-array) leaves pass through."""
    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        return x
    return jax.tree.map(leaf, tree)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _agg_state(sharding, capacity, key_width, specs):
    return _on(sharding, jax.eval_shape(
        lambda: hash_agg.make_agg_state(capacity, key_width, specs)))


def _chains(sharding, keys, rows, stack=()):
    """A join side's chain arrays (head, the runs' store, the
    sequences) at these capacities, `stack` a mesh's leading axis."""
    return _on(sharding, jax.eval_shape(
        lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, stack + a.shape),
            hash_join.empty_chains(keys, rows))))


def _join_state(sharding, kernel):
    """(table, chains, pay, deg) of a one-chip join side at the real
    capacities, with this kernel's key and payload widths."""
    table = _on(sharding, jax.eval_shape(
        lambda: hash_table.make_state(JOIN_KEYS, kernel.key_width)))
    i32 = jnp.int32
    chains = _chains(sharding, JOIN_KEYS, JOIN_ROWS)
    pay = _sds(sharding, (JOIN_ROWS, kernel.payload_width), i32)
    deg = _sds(sharding, (JOIN_ROWS,), i32)
    return table, chains, pay, deg


# -- a tiny served session: the real plans, preludes and jits -------------------


def _executors(fe, name):
    actor = fe.actors[fe.catalog.mvs[name].actor_id]
    return list(chip_smoke.walk_executors(actor.consumer))


def _agg_kernel(fe, name):
    (k,) = {id(k): k for ex in _executors(fe, name)
            for k in chip_smoke.kernels_of(ex)
            if isinstance(k, hash_agg.GroupedAggKernel)}.values()
    return k


def _join_sides(fe, name):
    (join,) = {id(ex.sides): ex for ex in _executors(fe, name)
               if hasattr(ex, "sides")}.values()
    return join.sides


def _serve_on_cpu(statements, steps):
    """Run the statements and `steps` checkpoint barriers in a session
    on the CPU; yields the live Frontend (a fixture body)."""
    async def run():
        fe = Frontend(rate_limit=4, min_chunks=4)
        for sql in statements:
            await fe.execute(sql)
        await fe.step(steps)
        return fe

    loop = asyncio.new_event_loop()
    try:
        fe = loop.run_until_complete(run())
        yield fe
        loop.run_until_complete(fe.close())
    finally:
        loop.close()


@pytest.fixture(scope="module")
def served():
    """q7 (full), q8, `pairs` and one standalone filter+project block,
    exactly as chip_smoke.py creates them, a few barriers deep. The
    100 ms event gap makes 10 s windows close inside the run, so the
    watermark retire path has run too."""
    sources = [
        "CREATE SOURCE {t} WITH (connector='nexmark', "
        "nexmark.table.type='{t}', nexmark.event.num=40000, "
        "nexmark.max.chunk.size={c}, "
        "nexmark.min.event.gap.in.ns=100000000)".format(t=t, c=CHUNK_ROWS)
        for t in ("bid", "auction", "person")]
    block = ("CREATE MATERIALIZED VIEW dear AS SELECT auction, "
             "price * 2 AS twice FROM bid WHERE price > 1000")
    yield from _serve_on_cpu(
        sources + [chip_smoke.Q7, chip_smoke.Q8.format(name="q8"),
                   chip_smoke.PAIRS, block], steps=6)


# -- one chip: the kernels of the served path -----------------------------------


def test_probe_insert(one_chip):
    table = _on(one_chip, jax.eval_shape(
        lambda: hash_table.make_state(AGG_CAPACITY, 6)))
    _compile(hash_table._probe_insert_jit, table,
             _sds(one_chip, (AGG_BATCH, 6), jnp.int32),
             _sds(one_chip, (AGG_BATCH,), jnp.bool_))


def test_agg_apply_unfused(one_chip):
    """The flagship step of __graft_entry__.entry(): MAX+SUM+COUNT over
    a two-lane key, 2^20 slots, one 8192-row chunk."""
    specs = (AggSpec(AggKind.MAX, np.dtype(np.int64)),
             AggSpec(AggKind.SUM, np.dtype(np.int64)),
             AggSpec(AggKind.COUNT))
    step = hash_agg.build_apply(2, specs)
    compiled = _compile(
        step, _agg_state(one_chip, 1 << 20, 2, specs),
        _sds(one_chip, (8192, hash_agg.packed_width(2, specs)),
             jnp.int32))
    assert compiled.memory_analysis() is not None


def test_agg_apply_fused_q7_prelude(served, one_chip):
    """hash_agg.apply_fused with q7's real prelude (project with
    tumble_start, then MAX(price) by window)."""
    k = _agg_kernel(served, "q7")
    assert k._prelude is not None, "q7's aggregate did not fuse"
    _compile(k._apply,
             _agg_state(one_chip, k.capacity, k.key_width, k.specs),
             _sds(one_chip, (AGG_BATCH, k._raw_width), jnp.int64))


def test_agg_apply_fused_pairs_at_a_million_keys(served, one_chip):
    """`pairs` at the capacity the growth ladder ends on."""
    k = _agg_kernel(served, "pairs")
    assert k._prelude is not None, "pairs did not fuse"
    _compile(k._apply,
             _agg_state(one_chip, AGG_CAPACITY, k.key_width, k.specs),
             _sds(one_chip, (AGG_BATCH, k._raw_width), jnp.int64))


def test_agg_flush_gather(served, one_chip):
    k = _agg_kernel(served, "pairs")
    _compile(k._gather,
             _agg_state(one_chip, AGG_CAPACITY, k.key_width, k.specs),
             FLUSH_ROWS)


def test_agg_grow(served, one_chip):
    """The last rung of the ladder: 2^20 -> 2^21 slots."""
    k = _agg_kernel(served, "pairs")
    _compile(k._grow_step,
             _agg_state(one_chip, AGG_CAPACITY // 2, k.key_width,
                        k.specs), AGG_CAPACITY)


def test_agg_retire(served, one_chip):
    """Watermark state cleaning of q7's windowed MAX."""
    k = _agg_kernel(served, "q7")
    retire = jaxtools.KERNELS["hash_agg.retire"]
    fills = tuple(f for _dt, f in hash_agg.dev_layout(k.specs))
    scalar = _sds(one_chip, (), jnp.int32)
    _compile(retire,
             _agg_state(one_chip, k.capacity, k.key_width, k.specs),
             scalar, scalar, 0, fills)


@pytest.mark.parametrize("side_idx", [0, 1], ids=["person", "auction"])
def test_join_epoch_apply_q8_prelude(served, one_chip, side_idx):
    """hash_join.epoch_apply with each q8 side's real prelude (project
    with tumble_start + the absorbed row-id stage)."""
    side = _join_sides(served, "q8")[side_idx]
    assert side.fused_input is not None, "q8's join side did not fuse"
    apply_jit, _probe = side.kernel._epoch_jits(
        side.prelude, side._prelude_cache_key)
    table, chains, pay, _deg = _join_state(one_chip, side.kernel)
    raw_w = apply_jit._args[3].shape[1]
    _compile(apply_jit, table, chains, pay,
             _sds(one_chip, (EPOCH_ROWS, raw_w), jnp.int64),
             _sds(one_chip, (EPOCH_ROWS, 4), jnp.int32),
             side.kernel.key_width)


def test_join_epoch_probe_q7_prelude(served, one_chip):
    """hash_join.epoch_probe: q7's bid side probing the windowed-MAX
    side, with the bid side's real prelude."""
    bid, wmax = _join_sides(served, "q7")
    assert bid.fused_input is not None, "q7's bid side did not fuse"
    _apply, probe_jit = wmax.kernel._epoch_jits(
        bid.prelude, bid._prelude_cache_key)
    table, chains, pay, deg = _join_state(one_chip, wmax.kernel)
    raw_w = probe_jit._args[5].shape[1]
    _compile(probe_jit, table, chains, pay, deg, deg,
             _sds(one_chip, (EPOCH_ROWS, raw_w), jnp.int64),
             _sds(one_chip, (EPOCH_ROWS, 4), jnp.int32),
             wmax.kernel.key_width, PROBE_OUT, False,
             _sds(one_chip, (), jnp.int32))   # the page's first pair


def test_fused_chain_step(served, one_chip):
    """fused.chain_step: the standalone filter+project block, one
    4096-row chunk (the shapes it ran with in the served session)."""
    (step,) = {id(ex._step): ex._step
               for ex in _executors(served, "dear")
               if getattr(ex, "_step", None) is not None}.values()
    assert step.label == "fused.chain_step" and step._args is not None
    assert step._args[2].shape == (CHUNK_ROWS,)
    _compile(step, *_on(one_chip, step._args))


# -- q5 at the cell's sizes (PR 33) ------------------------------------------------


@pytest.fixture(scope="module")
def served_q5():
    """The benchmark's `nexmark-q5` view, its text from the
    configuration file, three barriers deep; beside it `q5_outer`,
    the same text as a LEFT JOIN with the `>=` in a WHERE: an inner
    join evaluates such a condition itself, an outer join leaves it
    to a fused chain above it."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nexmark-q5.json")) as f:
        ddl = json.load(f)["ddl"]
    outer = ddl[-1].replace("VIEW q5 AS", "VIEW q5_outer AS").replace(
        "\nJOIN (", "\nLEFT JOIN (").replace(
        " AND AuctionBids.num >=", "\nWHERE AuctionBids.num >=")
    assert outer.count("q5_outer") == 1 and "LEFT JOIN" in outer \
        and "WHERE" in outer
    yield from _serve_on_cpu([d.format(seed=33) for d in ddl + [outer]],
                             steps=3)


def test_q5_count_aggregate_with_the_hop_prelude(served_q5, one_chip):
    """hash_agg.apply_fused with the absorbed HOP (five rows a bid,
    in-trace) and count(*) by (window_start, auction), at the 2^19
    slots `q5_steady`'s window runs on."""
    fused = list({id(k): k for ex in _executors(served_q5, "q5")
                  for k in chip_smoke.kernels_of(ex)
                  if isinstance(k, hash_agg.GroupedAggKernel)
                  and k._prelude is not None}.values())
    assert len(fused) == 2, "q5's two counting aggregates did not fuse"
    k = fused[0]
    _compile(k._apply,
             _agg_state(one_chip, 1 << 19, k.key_width, k.specs),
             _sds(one_chip, (AGG_BATCH, k._raw_width), jnp.int64))


def test_q5_probe_of_the_count_side_in_pages(served_q5, one_chip):
    """hash_join.epoch_probe: the max side's 64-row epoch probing the
    count side (2^16 key slots, 2^20 rows), into the pair buffer's
    last size, the first pair of the page a traced scalar."""
    counts, maxes = _join_sides(served_q5, "q5")
    assert maxes.fused_input is not None, "q5's max side did not fuse"
    _apply, probe_jit = counts.kernel._epoch_jits(
        maxes.prelude, maxes._prelude_cache_key)
    kernel = counts.kernel
    i32 = jnp.int32
    table = _on(one_chip, jax.eval_shape(
        lambda: hash_table.make_state(1 << 16, kernel.key_width)))
    chains = _chains(one_chip, 1 << 16, 1 << 20)
    pay = _sds(one_chip, (1 << 20, kernel.payload_width), i32)
    deg = _sds(one_chip, (1 << 20,), i32)
    raw_w = probe_jit._args[5].shape[1]
    assert kernel.PROBE_CAP_TOP == 1 << 16
    _compile(probe_jit, table, chains, pay, deg, deg,
             _sds(one_chip, (64, raw_w), jnp.int64),
             _sds(one_chip, (64, 4), i32),
             kernel.key_width, kernel.PROBE_CAP_TOP, False,
             _sds(one_chip, (), i32))


def test_q5_chain_above_the_join_at_its_top_rung(served_q5, one_chip):
    """fused.chain_step: the `>=` and the projection above q5's join
    where that join is an outer one, at the ladder's top, 65,536 rows
    (q5's own inner join takes the `>=` as its condition and has no
    chain above it)."""
    from risingwave_tpu.stream.executors.fused import CHAIN_CAP_TOP
    assert not [ex for ex in _executors(served_q5, "q5")
                if getattr(ex, "_step", None) is not None]
    (step,) = {id(ex._step): ex._step
               for ex in _executors(served_q5, "q5_outer")
               if getattr(ex, "_step", None) is not None}.values()
    assert step.label == "fused.chain_step" and step._args is not None

    def at_top(x):
        return _sds(one_chip, (CHAIN_CAP_TOP,), x.dtype)

    _compile(step, *jax.tree.map(at_top, step._args))


# -- q101's degree-tracked probe at the cell's sizes (PR 50) ----------------------


@pytest.fixture(scope="module")
def served_q101():
    """The benchmark's `nexmark-q101` view, its text from the
    configuration file, three barriers deep: a LEFT OUTER join, so
    both epoch probes track degrees."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nexmark-q101.json")) as f:
        ddl = json.load(f)["ddl"]
    yield from _serve_on_cpu([d.format(seed=50) for d in ddl], steps=3)


@pytest.mark.parametrize("probing", [0, 1], ids=["auctions", "max_bids"])
def test_q101_degree_tracked_probe(served_q101, one_chip, probing):
    """hash_join.epoch_probe with degrees: one side's epoch probing the
    other at the 2^19 key slots and 2^18 rows `q101_steady`'s window
    runs on, both sides' degree arrays updated in the dispatch and
    returned beside the pair matrix. No older cell's join tracks
    degrees: this arm compiled for no test before."""
    sides = _join_sides(served_q101, "nexmark_q101")
    me, probed = sides[probing], sides[1 - probing]
    assert sides[0].track_degrees and not sides[1].track_degrees
    assert me.fused_input is not None, "q101's join side did not fuse"
    _apply, probe_jit = probed.kernel._epoch_jits(
        me.prelude, me._prelude_cache_key)
    kernel = probed.kernel
    i32 = jnp.int32
    keys, rows = 1 << 19, 1 << 18
    table = _on(one_chip, jax.eval_shape(
        lambda: hash_table.make_state(keys, kernel.key_width)))
    chains = _chains(one_chip, keys, rows)
    pay = _sds(one_chip, (rows, kernel.payload_width), i32)
    deg = _sds(one_chip, (rows,), i32)
    assert probe_jit._args[9] is True            # it ran with degrees
    raw_w = probe_jit._args[5].shape[1]
    compiled = _compile(
        probe_jit, table, chains, pay, deg, deg,
        _sds(one_chip, (EPOCH_ROWS, raw_w), jnp.int64),
        _sds(one_chip, (EPOCH_ROWS, 4), i32),
        kernel.key_width, PROBE_OUT, True,
        _sds(one_chip, (), i32))
    # the matrix and the two degree arrays
    assert len(jax.tree.leaves(compiled.out_info)) == 3


# -- four chips: the sharded steps on a Mesh of the described devices -----------


def _bare(cls, **attrs):
    """An instance that skipped __init__ (which device_puts state onto
    its mesh — a described device refuses that): just the attributes the
    step builders read."""
    k = object.__new__(cls)
    for name, value in attrs.items():
        setattr(k, name, value)
    return k


def test_parallel_agg_step(mesh):
    """parallel_agg.step: vnode routing, all_to_all, per-shard probe and
    scatter, as one SPMD program over the 2x2 mesh."""
    from risingwave_tpu.common.hash import VNODE_COUNT
    from risingwave_tpu.parallel.agg import ShardedAggKernel
    specs = (AggSpec(AggKind.MAX, np.dtype(np.int64)),
             AggSpec(AggKind.COUNT))
    n_dev, kw, shard_cap = mesh.devices.size, 3, 1 << 16
    sharded = NamedSharding(mesh, P("d"))
    one = jax.eval_shape(
        lambda: hash_agg.make_agg_state(shard_cap, kw, specs))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n_dev,) + a.shape, a.dtype,
                                       sharding=sharded), one)
    k = _bare(ShardedAggKernel, mesh=mesh, n_dev=n_dev, specs=specs,
              key_width=kw, state=state)
    width = hash_agg.packed_width(kw, specs)
    rows = ShardedAggKernel.BATCH_ROWS
    step = k._build_packed_step(bucket=rows // n_dev)
    compiled = _compile(
        step, state, _sds(sharded, (rows, width), jnp.int32),
        _sds(NamedSharding(mesh, P()), (VNODE_COUNT,), jnp.int32))
    assert "all-to-all" in compiled.as_text()


def test_parallel_join_epoch_apply(mesh):
    """parallel_join.epoch_apply: route one epoch's rows to their owner
    shards and link them, over the 2x2 mesh."""
    from risingwave_tpu.common.hash import VNODE_COUNT
    from risingwave_tpu.parallel.join import ShardedJoinKernel
    n_dev, kw, keys, rows = mesh.devices.size, 6, 1 << 15, 1 << 17
    sharded = NamedSharding(mesh, P("d"))

    def stacked(shape, dtype=jnp.int32):
        return _sds(sharded, (n_dev,) + shape, dtype)

    table = hash_table.TableState(keys=stacked((keys, kw)),
                                  occ=stacked((keys,), jnp.bool_))
    chains = _chains(sharded, keys, rows, stack=(n_dev,))
    k = _bare(ShardedJoinKernel, mesh=mesh, n_dev=n_dev, key_width=kw,
              key_capacity=keys, _row_capacity=rows, table=table,
              chains=chains)
    epoch = EPOCH_ROWS
    step = k._build_epoch_apply(bucket=epoch // n_dev, width=kw,
                                raw=False)
    compiled = _compile(
        step, table, chains, _sds(sharded, (epoch, kw), jnp.int32),
        _sds(sharded, (epoch, 4), jnp.int32),
        _sds(NamedSharding(mesh, P()), (VNODE_COUNT,), jnp.int32))
    assert "all-to-all" in compiled.as_text()


@pytest.fixture(scope="module")
def served_q8_mesh4():
    """The benchmark's `nexmark-q8-mesh4` DDL in a session at
    parallelism 4 on the CPU's virtual devices, two barriers deep: the
    deployed sharded kernels carry the real fused prelude."""
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "nexmark-q8-mesh4.json")) as f:
        config = json.load(f)

    async def run():
        fe = Frontend(rate_limit=1, min_chunks=1, parallelism=4)
        for ddl in config["ddl"]:
            await fe.execute(ddl.format(seed=7))
        await fe.step(2)
        return fe

    loop = asyncio.new_event_loop()
    try:
        fe = loop.run_until_complete(run())
        yield fe
        loop.run_until_complete(fe.close())
    finally:
        loop.close()


def test_parallel_agg_step_fused_q8(served_q8_mesh4, mesh):
    """parallel_agg.step_fused with q8's seller-side prelude (TUMBLE,
    GROUP BY seller, window_start) traced ahead of the routing, at the
    cell's epoch: 4 chunks of 4,096 rows and their separator rows pad
    to 2^15, and without host owners the bucket is the worst case."""
    from risingwave_tpu.common.hash import VNODE_COUNT
    from risingwave_tpu.parallel.agg import ShardedAggKernel
    (live,) = {id(k): k for ex in _executors(served_q8_mesh4, "q8")
               for k in chip_smoke.kernels_of(ex)
               if isinstance(k, ShardedAggKernel)
               and k._prelude is not None}.values()
    n_dev, shard_cap, rows = mesh.devices.size, 1 << 16, 1 << 15
    sharded = NamedSharding(mesh, P("d"))
    one = jax.eval_shape(lambda: hash_agg.make_agg_state(
        shard_cap, live.key_width, live.specs))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n_dev,) + a.shape, a.dtype,
                                       sharding=sharded), one)
    k = _bare(ShardedAggKernel, mesh=mesh, n_dev=n_dev,
              specs=live.specs, key_width=live.key_width, state=state,
              _prelude=live._prelude, _prelude_key=live._prelude_key)
    step = k._build_raw_step(bucket=rows // n_dev)
    assert jaxtools.program_name(step.label).startswith(
        "parallel_agg_step_fused_")
    compiled = _compile(
        step, state, _sds(sharded, (rows, live._raw_width), jnp.int64),
        _sds(NamedSharding(mesh, P()), (VNODE_COUNT,), jnp.int32))
    assert "all-to-all" in compiled.as_text()


# -- the three float cases the 64-bit rewrite used to refuse --------------------


FLOAT_MVS = {
    # a DOUBLE group key
    "key": "CREATE MATERIALIZED VIEW fk AS SELECT level, COUNT(*) AS n "
           "FROM ticks GROUP BY level",
    # a DOUBLE MIN/MAX argument
    "minmax": "CREATE MATERIALIZED VIEW fm AS SELECT k, MIN(px) AS lo, "
              "MAX(px) AS hi FROM ticks GROUP BY k",
    # a join that carries DOUBLE columns on both sides
    "payload": "CREATE MATERIALIZED VIEW fj AS SELECT t.id, t.px, f.fee "
               "FROM ticks AS t JOIN fees AS f ON t.k = f.k",
}


@pytest.fixture(scope="module")
def served_floats():
    sources = [chip_smoke.create_source_sql(name, options)
               for name, options in chip_smoke.float_sources(
                   rows=2000, seed=7).items()]
    yield from _serve_on_cpu(sources + list(FLOAT_MVS.values()),
                             steps=4)


@pytest.mark.parametrize("case", sorted(FLOAT_MVS))
def test_float_columns_compile(served_floats, one_chip, case):
    """Under the default settings (fusion on) a DOUBLE group key, MIN/MAX
    argument or join column traces from the int64 image it was uploaded
    as: no f64→s64/u64 bitcast reaches the chip's compiler."""
    fe = served_floats
    if case == "payload":
        for side in _join_sides(fe, "fj"):
            assert side.fused_input is not None, "join side did not fuse"
            apply_jit, _probe = side.kernel._epoch_jits(
                side.prelude, side._prelude_cache_key)
            table, chains, pay, _deg = _join_state(one_chip, side.kernel)
            assert side.kernel.payload_width > 0
            raw_w = apply_jit._args[3].shape[1]
            _compile(apply_jit, table, chains, pay,
                     _sds(one_chip, (CHUNK_ROWS, raw_w), jnp.int64),
                     _sds(one_chip, (CHUNK_ROWS, 4), jnp.int32),
                     side.kernel.key_width)
        return
    k = _agg_kernel(fe, {"key": "fk", "minmax": "fm"}[case])
    assert k._prelude is not None, "the aggregate did not fuse"
    _compile(k._apply,
             _agg_state(one_chip, k.capacity, k.key_width, k.specs),
             _sds(one_chip, (AGG_BATCH, k._raw_width), jnp.int64))


def test_the_refused_bitcast_is_still_refused(one_chip):
    """The reason for all of the above, kept as a canary: when the
    compiler learns the bitcast this fails and the image plumbing in
    ops/fused.py can go."""
    x = _sds(one_chip, (CHUNK_ROWS,), jnp.float64)
    with pytest.raises(Exception, match="(?i)x64|bitcast"):
        _compile(jax.jit(
            lambda v: jax.lax.bitcast_convert_type(v, jnp.int64)), x)
    _compile(jax.jit(
        lambda v: jax.lax.bitcast_convert_type(v, jnp.float64)),
        _sds(one_chip, (CHUNK_ROWS,), jnp.int64))
