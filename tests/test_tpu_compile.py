"""Ask the v5e compiler, with no chip attached, whether the programs of
the main path compile at their real sizes.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.  The compiles run in the test's own process for the same reason.
Nothing here runs on a device; a pass says the chip's compiler accepts the
program, not that the chip ran it (``chip_smoke.py`` is that check).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import __graft_entry__ as ge
from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.ops import pallas_kernels as PK

HBM_BYTES = 15.75 * (1 << 30)  # what the v5e compiler calls its hbm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("mf", [2, 0], ids=["int_float", "int_only"])
def test_onehot_groupby_kernel_compiles(one_chip, mf):
    n = 1 << 22
    PK._onehot_gb_call.lower(
        _sds((n,), jnp.int32, one_chip), _sds((n, 9), jnp.int8, one_chip),
        _sds((n, mf), jnp.float32, one_chip),
        domain=101, interpret=False).compile()


@pytest.mark.parametrize("float_mode", ["f32x3", "f64"])
def test_q6_step_compiles_and_fits(one_chip, monkeypatch, float_mode):
    n = 1 << 24
    batch = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: ge._device_batch(0, n)))
    # the engines' "auto" asks jax.default_backend(): answer as the chip
    # does, or this compiles the CPU's scatter branch for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config.set("q6_float_mode", float_mode)
    try:
        # a function of its own: jit keeps one trace per function, and the
        # knob is read while tracing
        lowered = jax.jit(lambda b: ge._q6_step(b)).lower(batch)
    finally:
        config.reset("q6_float_mode")
    mem = lowered.compile().memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
    if float_mode == "f64":
        # the exact sum is int8 slots of the one contraction: no operand
        # of an emulated f64 one (6.87 GB of them at this size before)
        assert mem.temp_size_in_bytes < 2 << 30


def _lower_plan(config_name, one_chip, monkeypatch, the_plan=None):
    """A benchmark configuration's IR plan (or ``the_plan`` over its
    tables) over one partition of the configuration's own size (and the
    tables its partitions share), under its knobs, lowered for the v5e; the
    plan's decisions; the configuration and its module."""
    from benchmark import lib
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config(config_name)
    rows = mod.rows_per_query(cfg)

    def tables():
        key = jax.random.PRNGKey(0)
        shared = getattr(mod, "make_shared", None)
        return {**mod.make_partition(cfg, key, rows),
                **(shared(cfg, key, rows) if shared else {})}

    inputs = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), jax.eval_shape(tables))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k, v in cfg["knobs"].items():
        config.set(k, v)
    try:
        cp = plan.compile_plan(the_plan or mod.plan(cfg), inputs)
        lowered = cp.fn.lower({n: inputs[n] for n in cp.input_names}, ())
    finally:
        config.reset()
        plan.reset_plan_cache()
    return lowered, cp.decisions, cfg, mod


def _plan_memory(config_name, one_chip, monkeypatch):
    """The memory the v5e compiler gives that program; and the
    configuration."""
    lowered, _decisions, cfg, mod = _lower_plan(config_name, one_chip,
                                                monkeypatch)
    mem = lowered.compile().memory_analysis()
    # what a query reads, and the tiles a row count that is no power of
    # two is padded to
    assert 0 <= mem.argument_size_in_bytes - mod.query_bytes(cfg) < 1 << 20
    return mem, cfg


@pytest.mark.parametrize("config_name", ["q6-scan-agg", "tpch-q1"])
def test_benchmark_plans_fit_two_in_flight(one_chip, monkeypatch,
                                           config_name):
    """``q6_plan`` under ``f64`` at 2^25 rows and ``tpch_q1_plan`` at
    59,986,052: under 2 GB of temporaries each, so that two queries in
    flight (``served-2callers``) beside the resident partitions stay under
    what the compiler calls the chip's memory.  Of Q1's, 1.79 GiB are the
    halves the compiler splits the four 64-bit columns into before the
    first operation; what a 2^19-row slice of the aggregate's loop takes
    is the rest, and no 128-bit column is ever whole."""
    mem, cfg = _plan_memory(config_name, one_chip, monkeypatch)
    assert mem.temp_size_in_bytes < 2 << 30
    resident = int(cfg["partitions"]) * mem.argument_size_in_bytes
    in_flight = 2 * (mem.temp_size_in_bytes + mem.output_size_in_bytes)
    assert resident + in_flight < HBM_BYTES, (resident, in_flight)


def _gathers(text):
    """``(scope path, rows of the operand read, rows of the result)`` of
    every gather in a lowered program's text
    (``as_text(debug_info=True)``); a tensor's rows are its largest
    dimension (a row gather's ``[words, rows]`` matrix has them last)."""
    import re

    def rows(dims):
        return max(int(d) for d in dims.split("x") if d)

    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    return [(locs.get(m.group(3), ""), rows(m.group(1)), rows(m.group(2)))
            for m in re.finditer(
                r'"stablehlo\.gather"\(.*\(tensor<((?:\d+x)+)\w+>.*'
                r'-> tensor<((?:\d+x)+)\w+>.*loc\((#loc\d+)\)', text)]


def test_q95_plan_compiles_with_its_dense_joins_as_lookups(one_chip,
                                                           monkeypatch):
    """``q95_plan`` at the cell's size (2^22 fact rows, ``dim1`` 2^19,
    ``dim2`` 25) with the chip's engines: both joins hand on a row mask, so
    under ``plan.join.*`` only the general branch (the engine that expands
    rows) gathers a column of the fact; the dense branch reads the small
    tables alone.  The fused aggregate reads its grouped rows in place and
    fetches its scans at the narrowest of 4096, 65,536 and 1,048,576 group
    slots that holds the groups: under ``agg.sortscan_reduce`` only the
    branch that more than 1,048,576 groups take gathers a row-wide result,
    and each branch past the head moves its words as two matrices.  And
    the chip's compiler takes the program."""
    lowered, decisions, cfg, mod = _lower_plan("q95-join-agg", one_chip,
                                               monkeypatch)
    rows = mod.rows_per_query(cfg)
    assert rows == 1 << 22
    assert [decisions[k]["output"] for k in ("join0:k", "join1:wh")] \
        == ["mask", "mask"]
    tiers = (4096, 65536, 1048576)
    assert decisions["aggregate0:seg"] == {"head": 4096, "tiers": tiers}
    gathers = _gathers(lowered.as_text(debug_info=True))
    in_joins = [(path, n) for path, n, _out in gathers
                if "/plan.join." in path]
    # the parser sees the fact's columns gathered where they still are ...
    assert [1 for path, n in in_joins
            if "/join.general/join.gather_left/" in path and n == rows]
    # ... the dense branch's probe of the small table ...
    assert [1 for path, n in in_joins
            if path.endswith("/join.dense_probe/gather") and n < rows]
    # ... and nothing of the fact's size outside the general branch
    assert not [(path, n) for path, n in in_joins
                if "/join.general/" not in path and n >= rows]
    in_reduce = [(path, out) for path, _n, out in gathers
                 if "/plan.aggregate.seg/agg.sortscan_reduce/" in path]
    # at the head one gather a buffer: the key's data and validity, and
    # count(*), the value's non-null count and sum(v), each read once at
    # the group ends ...
    assert [out for path, out in in_reduce
            if "/agg.sortscan_head/" in path] == [4096] * 5
    # ... at each wider slot count two matrices: the three scans' four
    # words, and the key's word with its validity ...
    for w, name in zip(tiers[1:], ("agg.sortscan_tier.65536",
                                   "agg.sortscan_tier.1048576")):
        assert [out for path, out in in_reduce
                if f"/{name}/" in path] == [w] * 2, name
    # ... the same two a row wide where the data has more groups ...
    assert [out for path, out in in_reduce
            if "/agg.sortscan_full/" in path] == [rows] * 2
    # ... and nothing outside the branches
    assert len(in_reduce) == 11
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30


# what TPC-H Q18's first aggregate took with one gather a buffer at the
# group ends (the v5e compiler, no chip; PERF.md section 6)
_Q18_PER_ORDER_TEMP = 804_550_656


def test_q18_per_order_aggregate_fetches_word_matrices(one_chip,
                                                       monkeypatch):
    """TPC-H Q18's first aggregate as the plan lowers it (LINEITEM's two
    columns through its exchange, the sort engine over the grouped rows)
    at the cell's size, 6,001,215 rows: the branch that more groups than
    1,048,576 take reads the values at the group ends as a matrix of eight
    u32 words and one of two, and the key at its groups' first rows as one
    of three (its halves and the validity word), each ``[words, rows]`` and
    laid out rows minor, not padded to 128 lanes; and the program's
    temporaries stay within a tenth of what one gather a buffer took."""
    import re

    from spark_rapids_jni_tpu.plan.ir import (Agg, Aggregate, Exchange,
                                              Project, Scan)

    per_order = Aggregate(
        Exchange(Project(Scan("lineitem"), ("l_orderkey", "l_quantity")),
                 "l_orderkey"),
        keys=("l_orderkey",), aggs=(Agg("sum", "l_quantity", "sum_qty"),))
    lowered, _decisions, cfg, mod = _lower_plan("tpch-q18", one_chip,
                                                monkeypatch, per_order)
    rows = mod.rows_per_query(cfg)
    assert rows == 6001215
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    full = sorted(m.group(1) for m in re.finditer(
        r'"stablehlo\.gather"\(.*-> tensor<([^>]*)>.*loc\((#loc\d+)\)', text)
        if "/agg.sortscan_full/" in locs.get(m.group(2), ""))
    assert full == [f"{k}x{rows}xui32" for k in (2, 3, 8)]
    compiled = lowered.compile()
    laid = re.findall(r"= u32\[(\d+),(\d+)\]\{(\d),\d[^}]*\} gather\(.*"
                      r"agg\.sortscan_full/gather", compiled.as_text())
    assert sorted(laid) == [(str(k), str(rows), "1") for k in (2, 3, 8)]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.1 * _Q18_PER_ORDER_TEMP


def _lower_slot_build(s):
    n, S = 1 << 16, 4096
    return PK._slot_build_call.lower(
        _sds((n,), jnp.int32, s), _sds((n, 2), jnp.uint32, s),
        _sds((n,), jnp.bool_, s), num_slots=S, max_rounds=S,
        interpret=False)


def _lower_slot_probe(s):
    n, S = 1 << 16, 4096
    return PK._slot_probe_call.lower(
        _sds((S,), jnp.int32, s), _sds((S, 2), jnp.uint32, s),
        _sds((n,), jnp.int32, s), _sds((n, 2), jnp.uint32, s),
        _sds((n,), jnp.bool_, s), _sds((1,), jnp.int32, s),
        n_build=n, interpret=False)


def _lower_partition_scatter(s):
    P, C, M = 8, 4096, 8192

    def f(chunk, occ, morsel, cnts, base, rnd):
        return PK.partition_scatter(chunk, occ, morsel, cnts, base, rnd,
                                    P, C, interpret=False)

    return jax.jit(f).lower(
        (_sds((P * C,), jnp.int64, s), _sds((P * C,), jnp.float32, s)),
        _sds((P * C,), jnp.bool_, s),
        (_sds((M,), jnp.int64, s), _sds((M,), jnp.float32, s)),
        _sds((P,), jnp.int32, s), _sds((P,), jnp.int32, s),
        _sds((), jnp.int32, s))


# The opt-in "pallas" engine tier has only ever run in interpret mode; the
# chip's compiler refuses all three kernels as written.  strict xfail: the
# PR that rewrites or deletes them has to touch these lines.
@pytest.mark.parametrize("lower", [
    pytest.param(_lower_slot_build, id="slot_build", marks=pytest.mark.xfail(
        strict=True, raises=NotImplementedError,
        reason="Unimplemented primitive in Pallas TPU lowering for "
               "KernelType.TC: scatter-min")),
    pytest.param(_lower_slot_probe, id="slot_probe", marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason="Only arrays with 32-bit element types can be converted to "
               "scalars, but got: float64")),
    pytest.param(_lower_partition_scatter, id="partition_scatter",
                 marks=pytest.mark.xfail(
                     strict=True, raises=NotImplementedError,
                     reason="Unimplemented primitive in Pallas TPU lowering "
                            "for KernelType.TC: cumsum")),
])
def test_pallas_engine_tier_is_refused(one_chip, lower):
    lower(one_chip).compile()
