"""tpch-q13: the table recipe, the plan, the state and the comparison of TPC-H
Q13 over one scale-factor-10 database (see tpch-q13.json for the source, what
is assumed, the cut and the guarantees).

CUSTOMER and ORDERS are made on the device from the seed by dbgen's rules
(specification clause 4.2.3), as ``tpch-q3`` makes them: every order to a
customer whose key is no multiple of 3.  ``o_comment`` is dbgen's ``TEXT``:
a 300 MiB pool of the specification's grammar (clause 4.2.2.13), made in
numpy from the seed (``text_pool``), and each comment cut from it at a
seeded offset and length on the device (``cut``: one row gather a comment
from a matrix of the pool's 32-byte blocks).  The state is ``planrun.PlanState`` over two
tables a partition, with the plan run once at the end of set-up so that its
compile falls there; the answer's two int64 columns are cut to
``result_capacity`` on the device (``PlanState.query``)."""

import numpy as np

from benchmark import lib, planrun
from benchmark.reference.tpch_q13 import (tpch_q13_control,
                                          tpch_q13_reference, wrong_values)

RESULT_COLUMNS = ("c_count", "custdist")
# what the two TPC-H configurations share: dbgen's sparse order keys
sparse_key = lib.load_module("configs", "tpch-q3").sparse_key


def table_rows(cfg):
    """Rows of each table.  ``--rows LOG2`` (a rehearsal) puts 2^LOG2 in the
    place of ORDERS' count and keeps ten orders a customer."""
    if not cfg.get("rehearsal"):
        return {t: int(n) for t, n in cfg["rows"].items()}
    orders = 1 << int(cfg["log2_rows"])
    return {"orders": orders, "customer": max(orders // 10, 3)}


def rows_per_query(cfg):
    return table_rows(cfg)["orders"]


def mean_comment(cfg):
    lo, hi = cfg["dbgen"]["comment_length"]
    return (lo + hi) / 2


def like_bytes(cfg):
    """What the ``LIKE`` has to read of one query: ``o_comment``'s live
    characters (their mean length), its int32 length and its validity."""
    return table_rows(cfg)["orders"] * (mean_comment(cfg) + 4 + 1)


def query_bytes(cfg):
    """Bytes one query has to read, whatever implements the plan:
    ``o_orderkey`` and ``o_custkey`` with a validity byte each,
    ``o_comment`` (``like_bytes``) and ``c_custkey`` with its validity."""
    n = table_rows(cfg)
    return n["orders"] * 18 + like_bytes(cfg) + n["customer"] * 9


def like_ops(cfg):
    """The short names (``benchmark/trace.py:short_name``) of the device
    operations the ``LIKE`` kernel runs as, over ``rows_per_query`` rows
    (``benchmark/tests/test_tpch_q13.py`` holds them to the v5e compiler's
    program: every top-level operation under ``strings.like`` has one of
    these names, and none outside it)."""
    n, width = rows_per_query(cfg), int(cfg["comment_width"])
    # the lengths in int16, each segment's leftmost place (a lane
    # reduction), the place past the first; each segment's places plus
    # its length, a constant of a few dozen lanes
    return (f"convert_element_type s16[{n}]", f"fusion s16[{n}]",
            f"broadcast_add_fusion s16[{n}]") + tuple(
                f"iota_add_fusion s16[{width - len(cfg[w]) + 1}]"
                for w in ("word1", "word2"))


# a rehearsal's pool (``--rows LOG2``): the published one is 300 MiB
REHEARSAL_POOL_BYTES = 1 << 20


def pool_bytes(cfg):
    return REHEARSAL_POOL_BYTES if cfg.get("rehearsal") \
        else int(cfg["dbgen"]["pool_bytes"])


def _grammar(t):
    """The grammar of ``text`` as rules: each symbol that expands, with its
    templates (tuples of symbols) and their weights.  The other symbols are
    word lists, drawn by weight, or the literal words ``the`` and ``,``."""
    def templates(name, parts):
        out = []
        for template, weight in t[name]:
            syms = []
            for sym in template.split(" "):
                syms.append(parts[sym.rstrip(",")])
                if sym.endswith(","):
                    syms.append(",")
            out.append((tuple(syms), weight))
        return out

    return {
        "sentence": templates("grammar", {"N": "noun_phrase",
                                          "V": "verb_phrase",
                                          "P": "prepositional_phrase",
                                          "T": "terminators"}),
        "noun_phrase": templates("noun_phrase", {"N": "nouns",
                                                 "J": "adjectives",
                                                 "D": "adverbs"}),
        "verb_phrase": templates("verb_phrase", {"V": "verbs",
                                                 "X": "auxiliaries",
                                                 "D": "adverbs"}),
        "prepositional_phrase": [(("prepositions", "the", "noun_phrase"),
                                  1)]}


def text_pool(cfg, seed):
    """``pool_bytes`` of the grammar's text (clause 4.2.2.13), as uint8,
    from the seed: sentences of the five templates, each phrase and word
    drawn by its weight, words joined by spaces, a comma after the first of
    two adjectives, a terminator after a sentence's last word and a space
    between sentences.  Made in numpy a block of sentences at a time: each
    round replaces every symbol that expands by one of its templates, and
    the words' bytes are cut out of one padded row each."""
    t = cfg["text"]
    size = pool_bytes(cfg)
    rng = np.random.default_rng(lib.seed_words(seed, 4, salt=13))
    rules = _grammar(t)
    lists = [k for k in t if k not in ("grammar", "noun_phrase",
                                       "verb_phrase")]
    symbols = list(rules) + lists + ["the", ","]
    code = {s: i for i, s in enumerate(symbols)}
    # every template, and each other symbol as a template of itself
    rows = [tpl for s in rules for tpl, _w in rules[s]] \
        + [(s,) for s in symbols[len(rules):]]
    width = max(len(r) for r in rows)
    table = np.array([[code[x] for x in r] + [0] * (width - len(r))
                      for r in rows], np.int32)
    table_len = np.array([len(r) for r in rows], np.int32)
    first, at = {}, 0
    for s in rules:
        first[code[s]] = at
        at += len(rules[s])
    itself = np.arange(len(symbols), dtype=np.int32) - len(rules) + at

    def expand(syms):
        tid = itself[syms]
        for s, base in first.items():
            where = np.flatnonzero(syms == s)
            w = np.asarray([w for _t, w in rules[symbols[s]]], np.float64)
            tid[where] = base + rng.choice(len(w), where.size, p=w / w.sum())
        lens = table_len[tid]
        owner = np.repeat(np.arange(syms.size), lens)
        place = np.arange(owner.size) - np.repeat(np.cumsum(lens) - lens,
                                                  lens)
        return table[tid[owner], place]

    # the words: a space before each but a comma and a terminator
    vocab, base = [], {}
    for s in lists:
        base[code[s]] = len(vocab)
        vocab += [w if s == "terminators" else " " + w for w, _w in t[s]]
    base[code["the"]], base[code[","]] = len(vocab), len(vocab) + 1
    vocab += [" the", ","]
    wide = max(len(w) for w in vocab)
    spelled = np.zeros((len(vocab), wide), np.uint8)
    for i, w in enumerate(vocab):
        spelled[i, :len(w)] = np.frombuffer(w.encode("ascii"), np.uint8)
    spelled_len = np.array([len(w) for w in vocab])

    blocks, total = [], 0
    while total <= size:
        syms = np.full(1 << 17, code["sentence"], np.int32)
        while np.isin(syms, list(first)).any():
            syms = expand(syms)
        word = np.empty(syms.size, np.int64)
        for s, b in base.items():
            where = np.flatnonzero(syms == s)
            if symbols[s] in lists:
                w = np.asarray([w for _x, w in t[symbols[s]]], np.float64)
                word[where] = b + rng.choice(len(w), where.size,
                                             p=w / w.sum())
            else:
                word[where] = b
        block = spelled[word][np.arange(wide)[None, :]
                              < spelled_len[word][:, None]]
        blocks.append(block)
        total += block.size
    return np.concatenate(blocks)[1:size + 1]   # from the first word


def cut(pool, offset, width):
    """``pool[offset : offset + width]`` for each offset (uint8[n, width],
    zeros past the pool's end), through a matrix of the pool's 32-byte
    blocks: a row holds the bytes of four blocks, so a comment lies in the
    row of the block its offset falls in, shifted left by ``offset % 32``
    in five steps of 16, 8, 4, 2 and 1 bytes.  What the chip holds for it
    is four times the pool, where a matrix of every offset's window would
    be ``width`` times."""
    import jax.numpy as jnp

    span = 32
    per_row = -(-(width + span - 1) // span)
    n_blocks = -(-pool.shape[0] // span)
    padded = jnp.pad(pool, (0, (n_blocks + per_row - 1) * span
                            - pool.shape[0]))
    blocks = padded.reshape(-1, span)
    rows = jnp.concatenate([blocks[i:i + n_blocks] for i in range(per_row)],
                           axis=1)
    got = rows[offset // span]
    shift = offset % span
    for step in (16, 8, 4, 2, 1):
        keep = got.shape[1] - step
        got = jnp.where((shift & step)[:, None] != 0,
                        got[:, step:step + keep], got[:, :keep])
    return got[:, :width]


def make_partition(cfg, key, rows, pool=None):
    """One database: CUSTOMER and an ORDERS of exactly ``rows`` rows by
    dbgen's rules, each comment cut from ``pool`` (uint8; seed 0's
    ``text_pool`` where none is given)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import (Column, ColumnBatch,
                                                      StringColumn)

    n_cust = table_rows(cfg)["customer"]
    width = int(cfg["comment_width"])
    lo, hi = cfg["dbgen"]["comment_length"]
    kcust, klen, koff = jax.random.split(key, 3)

    def draw(k, count, lo, hi):   # uniform over lo..hi, both ends in
        return jax.random.randint(k, (count,), lo, hi + 1, jnp.int32)

    def col(data, dtype):
        return Column(data.astype(dtype.jnp_dtype),
                      jnp.ones(data.shape, jnp.bool_), dtype)

    customer = ColumnBatch({
        "c_custkey": col(jnp.arange(1, n_cust + 1), T.INT64)})
    # o_custkey: uniform over the customer keys that are no multiple of 3
    r = draw(kcust, rows, 0, n_cust - n_cust // 3 - 1)
    # o_comment: dbgen's TEXT(49): a length uniform in 19..78, an offset
    # uniform in 0..pool - length
    if pool is None:
        pool = jnp.asarray(text_pool(cfg, 0))
    length = draw(klen, rows, lo, hi)
    offset = draw(koff, rows, 0, pool.shape[0] - length)
    chars = jnp.where(jnp.arange(width, dtype=jnp.int32)[None, :]
                      < length[:, None], cut(pool, offset, width),
                      jnp.uint8(0))
    orders = ColumnBatch({
        "o_orderkey": col(sparse_key(jnp.arange(1, rows + 1,
                                                dtype=jnp.int32)), T.INT64),
        "o_custkey": col(3 * (r // 2) + 1 + r % 2, T.INT64),
        "o_comment": StringColumn(chars, length, jnp.ones((rows,), jnp.bool_))})
    return {"customer": customer, "orders": orders}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    return queries.tpch_q13_plan(
        cfg["word1"], cfg["word2"],
        custkey_domain=table_rows(cfg)["customer"] + 1)


class State(planrun.PlanState):
    """``PlanState`` over two tables a partition, ORDERS of
    ``rows_per_query`` rows, the plan compiled inside set-up."""

    def __init__(self, cfg, mod, seed, devs):
        import jax

        self.cfg, self.mod, self.devs = cfg, mod, devs
        self.rows = rows_per_query(cfg)
        self.partitions = int(cfg["partitions"])
        self.plan = plan(cfg)
        key = jax.random.PRNGKey(lib.seed_words(seed, 1)[0] & 0x7FFFFFFF)
        cap = int(cfg["result_capacity"])
        self._head = jax.jit(lambda res: jax.tree_util.tree_map(
            lambda a: a[:cap], res))
        # one program makes every database: the index is an argument
        gen = jax.jit(lambda kk, pool, part: make_partition(
            cfg, jax.random.fold_in(kk, part), self.rows, pool))
        with jax.default_device(devs[0]):
            pool = jax.device_put(text_pool(cfg, seed), devs[0])
            self.inputs = [gen(key, pool, np.int32(p))
                           for p in range(self.partitions)]
            jax.block_until_ready(self.inputs)
            del pool
            # the plan's cold compile (the LIKE, the outer join's two
            # branches, both aggregates' ladders, the sort) belongs to
            # set-up, not to the first query a caller waits for
            self.query(0, -1, lib.Spans())

    def host_tables(self, part):
        """Partition ``part`` as numpy columns, for the reference; the
        comments as their padded bytes and their lengths."""
        from spark_rapids_jni_tpu.columnar.column import StringColumn

        self._start_copies(part)
        out = {}
        for name, batch in self.inputs[part].items():
            for c in batch.names:
                col = batch[c]
                if not np.asarray(col.validity).all():
                    raise lib.BenchError(f"null in generated {name}.{c}")
                if isinstance(col, StringColumn):
                    out[f"{name}.{c}.chars"] = np.asarray(col.chars)
                    out[f"{name}.{c}.lengths"] = np.asarray(col.lengths)
                else:
                    out[f"{name}.{c}"] = np.asarray(col.data)
        return out


def build(cfg, mod, seed, devs):
    return State(cfg, mod, seed, devs)


def _columns(tables):
    return (tables["customer.c_custkey"], tables["orders.o_orderkey"],
            tables["orders.o_custkey"], tables["orders.o_comment.chars"],
            tables["orders.o_comment.lengths"])


def _pattern(cfg):
    return f"%{cfg['word1']}%{cfg['word2']}%"


def reference(cfg, tables):
    return tpch_q13_reference(*_columns(tables), _pattern(cfg))


def control(cfg, tables):
    return tpch_q13_control(*_columns(tables), pattern=_pattern(cfg))


def compare(cfg, got, want):
    """Values of the answer's two columns that differ from the reference's,
    row for row in ``ORDER BY`` order (a total order: no two rows share a
    ``c_count``)."""
    return {"wrong_exact_values": wrong_values(
        {c: [int(x) for x in got[c]] for c in RESULT_COLUMNS}, want)}
