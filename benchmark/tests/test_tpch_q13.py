"""TPC-H Q13's configuration: what the generator promises (comment lengths,
keys, the customers with no order, the share the pattern removes), the two
references held equal (the numpy form the cell compares against,
``benchmark/reference/tpch_q13.py``, and the row-at-a-time one in plain
Python, ``tests/tpch_q13_reference.py``), the control, the answer over the
wire, the ``like_char_slots`` and ``like_roofline_share`` readers, and
``like_ops`` against the v5e compiler's program at a rehearsal size (no chip).

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests)."""

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import lib  # noqa: E402
from benchmark import trace as bench_trace  # noqa: E402
from benchmark.reference import tpch_q13 as ref  # noqa: E402

import tpch_q13_reference as plain  # noqa: E402

LOG2 = 14
PATTERN = "%special%requests%"
slots = lib.load_module("metrics", "like_char_slots").read
share = lib.load_module("metrics", "like_roofline_share").read


@pytest.fixture(scope="module")
def generated():
    """The configuration's databases at 2^14 ORDERS rows, as numpy, and the
    program's counters after set-up's one query."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q13", LOG2)
    state = mod.build(cfg, mod, 2147483659, jax.devices()[:1])
    counters = {"plan_cache": plan.plan_cache_metrics()}
    out = [(cfg, mod, state.host_tables(p)) for p in range(state.partitions)]
    plan.reset_plan_cache()
    return out, counters


def _strings(t):
    chars, lengths = t["orders.o_comment.chars"], t["orders.o_comment.lengths"]
    return [bytes(c[:n]).decode("ascii") for c, n in zip(chars, lengths)]


def _plain(t, pattern=PATTERN):
    return plain.tpch_q13_reference(
        {"c_custkey": [int(x) for x in t["customer.c_custkey"]]},
        {"o_orderkey": [int(x) for x in t["orders.o_orderkey"]],
         "o_custkey": [int(x) for x in t["orders.o_custkey"]],
         "o_comment": _strings(t)}, pattern)


def _numpy(t, pattern=PATTERN, **kw):
    return ref.tpch_q13_reference(
        t["customer.c_custkey"], t["orders.o_orderkey"],
        t["orders.o_custkey"], t["orders.o_comment.chars"],
        t["orders.o_comment.lengths"], pattern, **kw)


def test_the_generator_keeps_dbgens_promises(generated):
    for cfg, mod, t in generated[0]:
        n = mod.table_rows(cfg)
        assert n == {"orders": 1 << LOG2, "customer": (1 << LOG2) // 10}
        okey = t["orders.o_orderkey"]
        i = np.arange(1, len(okey) + 1)
        assert np.array_equal(okey, ((i >> 3) << 5) | (i & 7))
        ckey = t["orders.o_custkey"]
        assert (ckey % 3 != 0).all() and ckey.min() >= 1 \
            and ckey.max() <= n["customer"]
        assert np.array_equal(t["customer.c_custkey"],
                              np.arange(1, n["customer"] + 1))
        # a third of the customers have no order
        assert len(np.setdiff1d(t["customer.c_custkey"], ckey)) \
            >= n["customer"] // 3
        # comments: 19..78 characters of the grammar's text, zeros after
        chars, lengths = t["orders.o_comment.chars"], \
            t["orders.o_comment.lengths"]
        assert cfg["comment_width"] == 80 and chars.shape == (len(okey), 80)
        assert lengths.min() == 19 and lengths.max() == 78
        assert abs(lengths.mean() - mod.mean_comment(cfg)) < 0.5
        past = np.arange(80)[None, :] >= lengths[:, None]
        assert not chars[past].any() and chars[~past].min() >= 32
        words = set(re.findall(r"[a-z]+", " ".join(_strings(t))))
        assert {"special", "requests", "furiously", "deposits"} <= words
        # the share of ORDERS the pattern removes (1.07-1.08% in 2^21
        # cuts from the published pool, tpch-q13.json)
        removed = ref.like(chars, lengths, PATTERN).mean()
        assert 0.008 < removed < 0.013, removed
    cfg, mod = lib.load_config("tpch-q13")
    assert mod.rows_per_query(cfg) == 15_000_000
    assert mod.like_bytes(cfg) == 15_000_000 * 53.5
    assert mod.query_bytes(cfg) == 15_000_000 * 71.5 + 1_500_000 * 9


def test_the_pool_is_the_grammars_text_and_a_cut_is_a_slice_of_it():
    import jax
    import jax.numpy as jnp

    WORD = r"[A-Za-z]+(?:-[a-z]+)?"

    cfg, mod = lib.load_config("tpch-q13", LOG2)
    pool = mod.text_pool(cfg, 2147483659)
    assert pool.shape == (mod.REHEARSAL_POOL_BYTES,)
    text = bytes(pool).decode("ascii")
    t = cfg["text"]
    vocab = {w for k, v in t.items()
             if k not in ("grammar", "noun_phrase", "verb_phrase")
             for phrase, _w in v for w in re.findall(WORD, phrase)}
    seen = set(re.findall(WORD, text)[1:-1])   # not cut ones
    assert seen <= vocab | {"the"}, seen - vocab
    assert re.search(r"[a-z], [a-z]", text) and re.search(r"[a-z][.;:?!] ",
                                                          text)
    lib_cfg, _ = lib.load_config("tpch-q13")
    assert mod.pool_bytes(lib_cfg) == 300 << 20
    # the cut, at every shift and up to the pool's end
    rng = np.random.default_rng(3)
    small = rng.integers(1, 256, 5003).astype(np.uint8)
    offset = np.concatenate([np.arange(64), rng.integers(0, 5003, 500),
                             [5003 - 19, 5002]]).astype(np.int32)
    got = np.asarray(jax.jit(lambda p, o: mod.cut(p, o, 80))(
        jnp.asarray(small), jnp.asarray(offset)))
    padded = np.pad(small, (0, 80))
    assert np.array_equal(got, np.stack([padded[o:o + 80] for o in offset]))


def test_the_two_references_agree_on_generated_tables(generated):
    for _cfg, _mod, t in generated[0]:
        for p in (PATTERN, "%requests%special%", "%furiously%",
                  "furiously%", "%deposits", "%s%s%s%s%", "%"):
            assert _numpy(t, p) == _plain(t, p), p
    want = _numpy(generated[0][0][2])
    assert want["c_count"][0] == 0 or 0 in want["c_count"]
    assert sum(want["custdist"]) == (1 << LOG2) // 10


def test_the_numpy_like_is_python_like_on_anchors_order_and_overlap():
    strs = ["", "aaa", "aaaa", "ab", "ba", "abab", "special requests",
            "requests special", "xspecialrequestsx", "a b"]
    width = 20
    chars = np.zeros((len(strs), width), np.uint8)
    for i, s in enumerate(strs):
        chars[i, :len(s)] = np.frombuffer(s.encode(), np.uint8)
    lengths = np.array([len(s) for s in strs])
    for p in ("%aa%aa%", "a%", "%a", "a%a", "ab", "", "%", "%%", PATTERN,
              "special%", "%special", "%ab%ab%", "a%b%"):
        got = ref.like(chars, lengths, p)
        assert [bool(x) for x in got] == [plain.like(s, p) for s in strs], p


def test_the_control_fails(generated):
    cfg, mod, t = generated[0][0]
    want = mod.reference(cfg, t)
    assert ref.wrong_values(want, want) == 0
    assert ref.wrong_values(mod.control(cfg, t), want) > 0
    chars, lengths = t["orders.o_comment.chars"], t["orders.o_comment.lengths"]
    assert ref.like(chars, lengths, PATTERN, ordered=False).sum() \
        > ref.like(chars, lengths, PATTERN).sum()


def test_the_answer_over_the_wire_is_the_reference():
    """``query``'s two columns against the reference; a count changed and
    a row less are counted."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from benchmark import planrun
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q13", 12)
    state = mod.build(cfg, mod, 11, jax.devices()[:1])
    try:
        want = mod.reference(cfg, state.host_tables(0))
        res = state.query(0, 0, lib.Spans())
        assert tuple(res) == mod.RESULT_COLUMNS
        got, nulls = planrun.plain(res)
        assert nulls == 0 and len(got["c_count"]) > 5
        assert mod.compare(cfg, got, want) == {"wrong_exact_values": 0}
        bad = dict(got, custdist=got["custdist"] + (
            np.arange(len(got["custdist"])) == 1))
        assert mod.compare(cfg, bad, want) == {"wrong_exact_values": 1}
        bad = {c: v[:-1] for c, v in got.items()}
        assert mod.compare(cfg, bad, want) == {"wrong_exact_values": 2}
    finally:
        plan.reset_plan_cache()


def test_like_char_slots_reads_the_programs_counter(generated):
    counters = generated[1]
    assert slots({"counters": counters}) == (1 << LOG2) * 80
    assert slots({"counters": {"plan_cache": {"joins_masked": 1}}}) is None
    assert slots({"counters": {}}) is None


def _ctx(mod, cfg, ops, records=3):
    return {"mod": mod, "cfg": cfg, "records": [{}] * records, "chips": 1,
            "device": {"kind": "TPU v5 lite"},
            "peaks": lib.load_json("peaks.json"),
            "trace": {"device_ops": ops}}


def test_like_roofline_share_sums_the_kernels_operations():
    cfg, mod = lib.load_config("tpch-q13")
    names = mod.like_ops(cfg)
    peak = lib.load_json("peaks.json")["TPU v5 lite"]["hbm_bytes_per_s"]
    ops = [[names[0], 0.01], ["fusion u32[15000000]", 5.0]] \
        + [[n, 0.02] for n in names[1:]]
    busy = 0.01 + 0.02 * (len(names) - 1)
    want = 100.0 * 3 * mod.like_bytes(cfg) / peak / busy
    assert share(_ctx(mod, cfg, ops)) == pytest.approx(want)
    # nothing to read: no record, none of the kernel's operations, or a
    # configuration with no LIKE
    assert share(_ctx(mod, cfg, ops, records=0)) is None
    assert share(_ctx(mod, cfg, [["fusion u32[15000000]", 5.0]])) is None
    q18cfg, q18 = lib.load_config("tpch-q18")
    assert share(_ctx(q18, q18cfg, ops)) is None


def _compiled_ops(text):
    """``(short name, scope path)`` of every top-level operation of a
    compiled program (``compiled.as_text()``): the instructions of the entry
    computation and of the computations it runs as a loop, a branch or a
    call (not a fusion's body, nor a reduction's), named as the trace names
    them (``benchmark/trace.py:short_name``)."""
    comps, cur, entry = {}, None, None
    for ln in text.splitlines():
        head = re.match(r"^(ENTRY )?(%?[\w.\-]+) .*\{\s*$", ln)
        if head:
            cur = comps.setdefault(head.group(2).lstrip("%"), [])
            entry = entry or (head.group(2).lstrip("%") if head.group(1)
                              else None)
            continue
        m = re.match(r"^\s+(?:ROOT )?(%?[\w.\-]+ = .*)$", ln)
        if m and cur is not None:
            cur.append(m.group(1))
    out, todo, seen = [], [entry], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for inst in comps.get(name, []):
            op = re.search(r"\s([a-z\-]+)\(", inst.split(" = ", 1)[1])
            opcode = op.group(1) if op else ""
            for ref in re.findall(
                    r"(?:body|condition|branch_computations|to_apply)="
                    r"\{?([%\w.\-, ]+)\}?", inst):
                if opcode in ("while", "conditional", "call"):
                    todo.extend(r.strip().lstrip("%")
                                for r in ref.split(","))
            if opcode in ("parameter", "constant", "get-tuple-element",
                          "tuple", "bitcast", "while", "conditional", "call",
                          "after-all", "partition-id", "replica-id", ""):
                continue
            scope = re.search(r'op_name="([^"]*)"', inst)
            out.append((bench_trace.short_name(inst),
                        scope.group(1) if scope else ""))
    return out


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_like_ops_are_the_kernels_operations_in_the_chips_program(
        one_chip, monkeypatch):
    """The plan at a rehearsal size compiled for the v5e: every top-level
    operation under ``strings.like`` has a name of ``like_ops``, and no
    operation outside that scope has one of them."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q13", LOG2)
    rows = mod.rows_per_query(cfg)
    inputs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mod.make_partition(
            cfg, jax.random.PRNGKey(0), rows)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        cp = plan.compile_plan(mod.plan(cfg), inputs)
        text = cp.fn.lower({n: inputs[n] for n in cp.input_names},
                           ()).compile().as_text()
    finally:
        plan.reset_plan_cache()
    ops = _compiled_ops(text)
    names = set(mod.like_ops(cfg))
    inside = {name for name, path in ops if "/strings.like/" in path}
    assert inside == names, inside
    assert not [(name, path) for name, path in ops
                if name in names and "/strings.like/" not in path]
