"""Plain numpy reference of TPC-H Q13 (specification clause 2.4.13) as Spark
SQL answers it, over the columns the query reads, none of them null:

    customer  c_custkey int64
    orders    o_orderkey, o_custkey int64; o_comment as its bytes, a
              uint8 [rows, width] matrix, and its lengths

``LIKE`` is matched a chunk of rows at a time, each row's bytes one numpy
bytes value: each literal segment of the pattern found leftmost
(``np.strings.find``) after the end of the segment before, the first at the
start and the last at the end (``startswith``, ``endswith``) where the
pattern has no ``%`` there.  The bytes past a row's length are taken as
zeros whatever the matrix holds; a comment holds no zero byte of its own
(the grammar's text has none).  The counts are ``np.bincount`` over
the customer keys' ranks, the distribution ``np.bincount`` over the
counts, and the answer sorted.

The answer: ``{"c_count": [...], "custdist": [...]}`` as Python ints, in
``ORDER BY custdist desc, c_count desc`` order (total: no two rows share a
``c_count``).

``tpch_q13_control`` breaks one guarantee: the pattern's order is dropped,
so that a comment holding every segment in any order is left out.
"""

import numpy as np

COLUMNS = ("c_count", "custdist")
_CHUNK = 1 << 20


def _segments(pattern):
    if "_" in pattern or "\\" in pattern:
        raise NotImplementedError(f"LIKE pattern {pattern!r}: only literal "
                                  "text between '%'")
    segs = [p.encode("utf-8") for p in pattern.split("%") if p]
    return segs, pattern.startswith("%"), pattern.endswith("%")


def like(chars, lengths, pattern, ordered=True):
    """bool per row: ``LIKE pattern`` (with ``ordered=False``: every segment
    somewhere, in any order)."""
    segs, free_start, free_end = _segments(pattern)
    rows, width = chars.shape
    out = np.empty(rows, bool)
    for a in range(0, rows, _CHUNK):
        ln = lengths[a:a + _CHUNK].astype(np.int64)
        c = np.where(np.arange(width)[None, :] < ln[:, None],
                     chars[a:a + _CHUNK], 0).astype(np.uint8)
        # each row's bytes as one string (the zeros past its length are
        # what numpy drops from the end of a bytes value)
        text = np.ascontiguousarray(c).view(f"S{width}").ravel()
        ok = np.ones(len(c), bool) if "%" in pattern or segs else ln == 0
        after = np.zeros(len(c), np.int64)
        for k, seg in enumerate(segs):
            m = len(seg)
            start = after if ordered else 0
            if k == len(segs) - 1 and not free_end:
                at = ln - m
                good = np.strings.endswith(text, seg) & (at >= start)
                if k == 0 and not free_start:
                    good &= at == 0
                at = np.where(good, at, -1)
            elif k == 0 and not free_start:
                at = np.where(np.strings.startswith(text, seg), 0, -1)
            else:
                at = np.strings.find(text, seg, start)
            ok &= at >= 0
            after = np.where(at >= 0, at + m, width + 1)
        out[a:a + _CHUNK] = ok
    return out


def tpch_q13_reference(c_custkey, o_orderkey, o_custkey, comment_chars,
                       comment_lengths, pattern="%special%requests%",
                       ordered=True):
    """The answer; ``o_orderkey`` is never null here, so ``count(o_orderkey)``
    counts every order that joins."""
    del o_orderkey   # counted, not read: no order key is null
    kept = ~like(comment_chars, comment_lengths, pattern, ordered)
    keys = np.asarray(c_custkey)
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    ok = np.asarray(o_custkey)[kept]
    at = np.searchsorted(sk, ok)
    joins = (at < len(sk)) & (sk[np.minimum(at, len(sk) - 1)] == ok)
    if len(np.unique(sk)) != len(sk):
        raise ValueError("customer keys repeat: this reference takes them "
                         "unique, as dbgen makes them")
    counts = np.bincount(at[joins], minlength=len(sk))
    dist = np.bincount(counts)
    c_count = np.flatnonzero(dist)
    custdist = dist[c_count]
    rank = np.lexsort((-c_count, -custdist))
    return {"c_count": [int(x) for x in c_count[rank]],
            "custdist": [int(x) for x in custdist[rank]]}


def tpch_q13_control(*columns, pattern="%special%requests%"):
    return tpch_q13_reference(*columns, pattern=pattern, ordered=False)


def wrong_values(got, want):
    """Values of ``got`` (name -> list of ints) that differ from ``want``'s,
    row for row; a row missing or extra counts each of its values."""
    wrong = 0
    for c in COLUMNS:
        g, w = list(got[c]), list(want[c])
        wrong += sum(1 for a, b in zip(g, w) if a != b) + abs(len(g) - len(w))
    return wrong
