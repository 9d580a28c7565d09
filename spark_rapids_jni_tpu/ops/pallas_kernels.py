"""Pallas kernels — one that the TPU compiler accepts, three that it refuses.

PALLAS_MEMO.md's decision rule admits a hand-written kernel in exactly
three situations; four kernels live here today:

- the fused one-hot group-by contraction (rule 1: XLA materializes a
  multi-GB ``[n, K]`` one-hot in HBM just to contract it once; the
  kernel rebuilds each row-tile's one-hot in VMEM and feeds the MXU
  directly).  It compiles for a v5e at 4M rows
  (``tests/test_tpu_compile.py``).  It is NOT a default anywhere:
  ``q6_onehot_engine=auto`` resolves to ``xla`` off the CPU and to
  ``scatter`` on it (``relational/aggregate.py``); ``"pallas"`` must be
  asked for;
- the fused slot-table build and probe (rule 3: the lax formulation in
  :mod:`relational.hashtable` is a ``while_loop`` whose whole-table
  carry round-trips HBM every CAS round; the kernels keep the table
  resident in VMEM across rounds, emitting bit-identical
  ``(owner, slot, overflow)`` / ``(found, slot)``), and
- the fused radix partition scatter for the shuffle map step (rule 2:
  XLA lowers the per-row routed write into per-element dynamic-update
  scatters; the kernel walks a morsel tile once and routes rows to
  partition chunks in a single pass).

The last three are an opt-in engine tier (``groupby_engine`` /
``join_engine`` / ``shuffle_scatter_engine`` = ``"pallas"``) that has only
ever run in interpret mode.  **The TPU compiler refuses all three as
written** (asked in PR 24, v5e described, not attached; kept as strict
xfails in ``tests/test_tpu_compile.py``):

- ``_slot_build_call``: ``Unimplemented primitive in Pallas TPU lowering
  for KernelType.TC: scatter-min``;
- ``_slot_probe_call``: ``Only arrays with 32-bit element types can be
  converted to scalars, but got: float64`` (x64 is forced at package
  import, and a weak-typed Python scalar inside the kernel becomes f64);
- ``partition_scatter``: ``Unimplemented primitive in Pallas TPU lowering
  for KernelType.TC: cumsum``.

So ``"pallas"`` for any of those three knobs raises on a chip.  They stay
off the ``auto`` path; ``tests/test_pallas_kernels.py`` runs them on the
CPU in interpret mode for parity only (PALLAS_MEMO.md r14 ledger).

Four hash kernels (murmur3/xxhash64 x int64/string) lived here through
round 4 "for parity/API only".  They were measured on real v5e (r3
session, corrected no-dedupe protocol) at 10-130x SLOWER than the jnp
formulations XLA fuses itself — murmur3_int64 6.8 vs 71.3 Mrows/s,
xxhash64_int64 6.1 vs 65.4, murmur3_string 0.16 vs 21.3, xxhash64_string
0.16 vs 10.4 — and were never the default path.  Deleted in r5: every
kernel in this file must be measured-faster-than-XLA on some shape or
gone.  The winning jnp path lives in :mod:`hashing` (reference parity:
``murmur_hash.cu:187``, ``xxhash64.cu:330``).

``interpret=None`` means the Pallas interpreter on the CPU backend and a
compiled kernel on any other: no other backend falls back to it.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# fused one-hot group-by contraction (the q6 aggregation hot loop)
# ---------------------------------------------------------------------------

# rows per grid step.  At 1024 rows the ~11KB int-payload DMA per step was
# grid-overhead dominated (16K steps at 16M rows); at 8192 the step's
# scoped VMEM — one-hot tile as int8 (1MB) AND f32 (4MB), the lanes iota
# (4MB), payload windows, all double-buffered — hit 21.24M against the
# 16M scoped-vmem limit on real v5e (Mosaic stack OOM, session r3b).
# 4096 halves the scaling terms (~10.6M) while keeping steps 4x fewer
# than the 1024 tiling.
GB_ROWS = 4096


def _onehot_tile(bucket_ref, kblock):
    """The tile's one-hot, built on the fly from [rows, 1] bucket ids —
    it lives only in VMEM/registers.  (The XLA formulation in
    :func:`relational.aggregate.group_by_onehot` materializes ``[n, K]``
    one-hots in HBM at every contraction dtype — multi-GB at bench row
    counts; here HBM traffic is just the payload columns.)"""
    b = bucket_ref[:]  # [rows, 1] int32; -1 = dead row (matches no lane)
    lanes = (jax.lax.broadcasted_iota(jnp.int32, (b.shape[0], LANES), 1)
             + kblock * LANES)
    return b == lanes


# Grid order: the K block is the OUTER dim and rows the INNER dim, so each
# output block is visited on consecutive grid steps — Pallas TPU keeps an
# output window resident in VMEM only across consecutive steps, and a
# revisited block would otherwise read back undefined HBM contents.
# Accumulation: int32 / f32; partials bound by |payload| <= 128 per row
# ⇒ callers chunk at 2^23 rows.

def _onehot_gb_kernel(bucket_ref, pi_ref, pf_ref, oi_ref, of_ref):
    i = pl.program_id(1)  # row tile (inner)

    @pl.when(i == 0)
    def _():
        oi_ref[:] = jnp.zeros_like(oi_ref)
        of_ref[:] = jnp.zeros_like(of_ref)

    oh = _onehot_tile(bucket_ref, pl.program_id(0))
    oi_ref[:] += jax.lax.dot_general(
        oh.astype(jnp.int8), pi_ref[:],
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    of_ref[:] += jax.lax.dot_general(
        oh.astype(jnp.float32), pf_ref[:],
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _onehot_gb_kernel_int(bucket_ref, pi_ref, oi_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        oi_ref[:] = jnp.zeros_like(oi_ref)

    oh = _onehot_tile(bucket_ref, pl.program_id(0))
    oi_ref[:] += jax.lax.dot_general(
        oh.astype(jnp.int8), pi_ref[:],
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)


@partial(jax.jit, static_argnames=("domain", "interpret"))
def _onehot_gb_call(bucket, pi, pf, domain, interpret):
    n = bucket.shape[0]
    npad = -(-max(n, 1) // GB_ROWS) * GB_ROWS
    if npad != n:
        bucket = jnp.pad(bucket, (0, npad - n), constant_values=-1)
        pi = jnp.pad(pi, ((0, npad - n), (0, 0)))
        pf = jnp.pad(pf, ((0, npad - n), (0, 0)))
    KP = -(-domain // LANES) * LANES
    mi, mf = pi.shape[1], pf.shape[1]
    grid = (KP // LANES, npad // GB_ROWS)
    row_spec = lambda mcols: pl.BlockSpec(  # noqa: E731
        (GB_ROWS, mcols), lambda j, i: (i, jnp.int32(0)))
    out_spec = lambda mcols: pl.BlockSpec(  # noqa: E731
        (LANES, mcols), lambda j, i: (j, jnp.int32(0)))
    if mf == 0:  # int-only aggregations skip the float pass entirely
        oi = pl.pallas_call(
            _onehot_gb_kernel_int,
            out_shape=jax.ShapeDtypeStruct((KP, mi), jnp.int32),
            grid=grid,
            in_specs=[row_spec(1), row_spec(mi)],
            out_specs=out_spec(mi),
            interpret=interpret,
        )(bucket[:, None], pi)
        return oi[:domain], jnp.zeros((domain, 0), jnp.float32)
    oi, of = pl.pallas_call(
        _onehot_gb_kernel,
        out_shape=(jax.ShapeDtypeStruct((KP, mi), jnp.int32),
                   jax.ShapeDtypeStruct((KP, mf), jnp.float32)),
        grid=grid,
        in_specs=[row_spec(1), row_spec(mi), row_spec(mf)],
        out_specs=(out_spec(mi), out_spec(mf)),
        interpret=interpret,
    )(bucket[:, None], pi, pf)
    return oi[:domain], of[:domain]


def onehot_groupby_parts(bucket, int_payload, float_payload, domain,
                         interpret=None):
    """Fused group-by contraction: per-bucket column sums without an HBM
    one-hot.

    ``bucket``: int32[n], values in [0, domain) (use -1 for dead rows).
    ``int_payload``: int8[n, mi], |x| <= 128 per element (byte limbs,
    validity flags, count ones).  ``float_payload``: f32[n, mf] (Dekker
    limbs of f64 values).  Returns (int64[domain, mi], float64[domain,
    mf]) — int sums exact; float sums accumulate in f32 per 2^23-row
    chunk, then f64 across chunks.
    """
    interp = _auto_interpret(interpret)
    n = bucket.shape[0]
    CH = 1 << 23  # int32 partials hold n * 128 < 2^31 per chunk
    oi64 = jnp.zeros((domain, int_payload.shape[1]), jnp.int64)
    of64 = jnp.zeros((domain, float_payload.shape[1]), jnp.float64)
    for lo in range(0, max(n, 1), CH):
        oi, of = _onehot_gb_call(
            bucket[lo:lo + CH], int_payload[lo:lo + CH],
            float_payload[lo:lo + CH], domain, interp)
        oi64 = oi64 + oi.astype(jnp.int64)
        of64 = of64 + of.astype(jnp.float64)
    return oi64, of64


# ---------------------------------------------------------------------------
# fused slot-table build / probe (scatter group-by + hash-probe join engines)
# ---------------------------------------------------------------------------

# The lax formulation in relational/hashtable.py pays O(probe-chain)
# FULL passes over n-sized HBM arrays per round: one scatter-min claim,
# one owner gather, one gather+compare per key word, every round.  These
# kernels keep the whole slot table (owner ids, per-round proposals, and
# the owner's key words) resident in VMEM and stream the rows once per
# round as tiles, so HBM traffic per round drops from O(n * words) to
# the row tiles themselves.  Contract and bit-identity: same
# FNV-1a+lowbias32 candidate chain (cand0 is computed with
# hashtable.fold_hash and round r probes (cand0 + r) & (S-1)), same
# empty-slots-only minimum-row-id election, same retire rule — the
# (owner, slot, overflow) / (found, slot) products are bit-identical to
# build_slot_table / probe_slot_table, which is what lets the engines
# above dispatch on a knob with zero semantic change.

# rows per grid tile.  Per-step row state is SLOT_ROWS * (4+4+1+1+4W)
# bytes (cand0, rowid, live, active, W key words); at 512 rows and W<=4
# that is ~13KB, noise next to the resident tables.
SLOT_ROWS = 512

# resident-table budget: owner (4S) + proposals (4S) + owner key words
# (4*S*W) must sit in VMEM across the whole grid, so the pallas path
# bows out past ~4MB of table (S*(8+4W) bytes) and the caller's lax
# formulation runs instead — at the default 4096-slot group-by table
# with 2 key words that is 64KB, two orders under the ceiling.
_SLOT_TABLE_MAX_BYTES = 4 << 20


def _slot_build_kernel(n, S, W, cand0_ref, w_ref, live_ref,
                       owner_ref, prop_ref, slotw_ref, slot_ref, act_ref):
    """One grid step of the synchronous build rounds.

    Grid is (max_rounds, 3 phases, row tiles); the claim/elect/retire
    round of hashtable.build_slot_table is schedule-DEPENDENT (a later
    round's smaller row id may not steal, so tiles cannot insert
    sequentially), hence the three *global* phases per round: phase 0
    scatter-mins every tile's claims into ``prop``; phase 1 merges
    ``prop`` into empty ``owner`` slots once (tile 0) and each winning
    row publishes its key words to ``slotw``; phase 2 matches every
    still-active row against its candidate slot's published words and
    retires the hits.  ``owner``/``prop``/``slotw`` use constant index
    maps (table resident across the grid); ``slot``/``act`` are per-tile
    carried state revisited every round.
    """
    r = pl.program_id(0)
    ph = pl.program_id(1)
    t = pl.program_id(2)
    sent = jnp.int32(n)
    mask = jnp.int32(S - 1)
    cand = (cand0_ref[:] + r) & mask
    first = (r == 0) & (ph == 0)

    @pl.when(first & (t == 0))
    def _():
        owner_ref[:] = jnp.full((S,), sent, jnp.int32)
        slotw_ref[:] = jnp.zeros((S, W), jnp.uint32)

    @pl.when(first)
    def _():
        slot_ref[:] = jnp.full((SLOT_ROWS,), S, jnp.int32)
        act_ref[:] = live_ref[:]

    rid = (jax.lax.broadcasted_iota(jnp.int32, (SLOT_ROWS,), 0)
           + t * SLOT_ROWS)

    @pl.when(ph == 0)
    def _():
        @pl.when(t == 0)
        def _():
            prop_ref[:] = jnp.full((S,), sent, jnp.int32)

        claim = jnp.where(act_ref[:], rid, sent)
        prop_ref[:] = prop_ref[:].at[cand].min(claim)

    @pl.when(ph == 1)
    def _():
        @pl.when(t == 0)
        def _():
            ow = owner_ref[:]
            owner_ref[:] = jnp.where(ow == sent, prop_ref[:], ow)

        # a row that just won its candidate slot publishes its key words
        # so phase 2 compares against the OWNER's words without gathering
        # from other tiles' rows (the lax formulation's full-array gather)
        won = act_ref[:] & (jnp.take(owner_ref[:], cand) == rid)
        idx = jnp.where(won, cand, S)
        slotw_ref[:] = slotw_ref[:].at[idx].set(w_ref[:], mode="drop")

    @pl.when(ph == 2)
    def _():
        act = act_ref[:]
        ow = jnp.take(slotw_ref[:], cand, axis=0)
        w = w_ref[:]
        match = act
        for j in range(W):
            match = match & (ow[:, j] == w[:, j])
        slot_ref[:] = jnp.where(match, cand, slot_ref[:])
        act_ref[:] = act & ~match


@partial(jax.jit, static_argnames=("num_slots", "max_rounds", "interpret"))
def _slot_build_call(cand0, wstack, live, num_slots, max_rounds, interpret):
    n, W = wstack.shape
    S = num_slots
    npad = -(-max(n, 1) // SLOT_ROWS) * SLOT_ROWS
    if npad != n:
        cand0 = jnp.pad(cand0, (0, npad - n))
        wstack = jnp.pad(wstack, ((0, npad - n), (0, 0)))
        live = jnp.pad(live, (0, npad - n))
    row1 = pl.BlockSpec((SLOT_ROWS,), lambda r, p, t: (t,))
    roww = pl.BlockSpec((SLOT_ROWS, W), lambda r, p, t: (t, 0))
    tab1 = pl.BlockSpec((S,), lambda r, p, t: (0,))
    tabw = pl.BlockSpec((S, W), lambda r, p, t: (0, 0))
    owner, _prop, _slotw, slot, active = pl.pallas_call(
        partial(_slot_build_kernel, n, S, W),
        out_shape=(jax.ShapeDtypeStruct((S,), jnp.int32),
                   jax.ShapeDtypeStruct((S,), jnp.int32),
                   jax.ShapeDtypeStruct((S, W), jnp.uint32),
                   jax.ShapeDtypeStruct((npad,), jnp.int32),
                   jax.ShapeDtypeStruct((npad,), jnp.bool_)),
        grid=(max_rounds, 3, npad // SLOT_ROWS),
        in_specs=[row1, roww, row1],
        out_specs=(tab1, tab1, tabw, row1, row1),
        interpret=interpret,
    )(cand0, wstack, live)
    return owner, slot, active


def slot_table_build(words, live, num_slots: int, max_rounds=None,
                     interpret=None):
    """Pallas twin of :func:`relational.hashtable.build_slot_table` —
    same ``(owner, slot, overflow)`` contract, bit-identical.

    Falls back to the lax formulation when the resident tables exceed
    the VMEM budget or the round bound is degenerate, so callers can
    dispatch unconditionally on the engine knob.
    """
    from ..relational import hashtable as H

    n = words[0].shape[0]
    S = int(num_slots)
    if S & (S - 1):
        raise ValueError(f"num_slots must be a power of two, got {S}")
    mr = S if max_rounds is None else int(max_rounds)
    if mr <= 0 or S * (8 + 4 * len(words)) > _SLOT_TABLE_MAX_BYTES:
        return H.build_slot_table(words, live, S, max_rounds=mr)
    cand0 = (H.fold_hash(words) & jnp.uint32(S - 1)).astype(jnp.int32)
    wstack = jnp.stack([w.astype(jnp.uint32) for w in words], axis=1)
    owner, slot, active = _slot_build_call(
        cand0, wstack, live.astype(jnp.bool_), S, mr,
        _auto_interpret(interpret))
    return owner, slot[:n], jnp.any(active)


def _slot_probe_kernel(n, S, W, rounds_ref, owner_ref, slotw_ref,
                       cand0_ref, pw_ref, live_ref, found_ref, slot_ref):
    """Read-only chain walk, one probe tile per grid step.

    Unlike the build, probing has no cross-row interaction (the table is
    frozen), so each tile walks its own chains to completion with the
    owner table and the owners' key words resident — the whole
    O(chain) loop happens in VMEM with zero per-round HBM passes.
    """
    sent = jnp.int32(n)
    mask = jnp.int32(S - 1)
    owner = owner_ref[:]
    slotw = slotw_ref[:]
    pw = pw_ref[:]
    rounds = rounds_ref[0]

    def cond(state):
        rnd, _cand, _slot, _found, act = state
        return (rnd < rounds) & jnp.any(act)

    def body(state):
        rnd, cand, slot, found, act = state
        o = jnp.take(owner, cand)
        empty = o == sent
        ow = jnp.take(slotw, cand, axis=0)
        match = ~empty
        for j in range(W):
            match = match & (ow[:, j] == pw[:, j])
        hit = act & match
        slot = jnp.where(hit, cand, slot)
        found = found | hit
        # an empty slot ends the chain: the key cannot live past it
        act = act & ~match & ~empty
        return rnd + 1, (cand + 1) & mask, slot, found, act

    state = (jnp.int32(0), cand0_ref[:],
             jnp.full((SLOT_ROWS,), S, jnp.int32),
             jnp.zeros((SLOT_ROWS,), jnp.bool_), live_ref[:])
    _, _, slot, found, _ = jax.lax.while_loop(cond, body, state)
    found_ref[:] = found
    slot_ref[:] = slot


@partial(jax.jit, static_argnames=("n_build", "interpret"))
def _slot_probe_call(owner, slotw, cand0, pwstack, live, rounds, n_build,
                     interpret):
    m, W = pwstack.shape
    S = owner.shape[0]
    mpad = -(-max(m, 1) // SLOT_ROWS) * SLOT_ROWS
    if mpad != m:
        cand0 = jnp.pad(cand0, (0, mpad - m))
        pwstack = jnp.pad(pwstack, ((0, mpad - m), (0, 0)))
        live = jnp.pad(live, (0, mpad - m))
    row1 = pl.BlockSpec((SLOT_ROWS,), lambda t: (t,))
    roww = pl.BlockSpec((SLOT_ROWS, W), lambda t: (t, 0))
    const1 = pl.BlockSpec((1,), lambda t: (0,))
    tab1 = pl.BlockSpec((S,), lambda t: (0,))
    tabw = pl.BlockSpec((S, W), lambda t: (0, 0))
    found, slot = pl.pallas_call(
        partial(_slot_probe_kernel, n_build, S, W),
        out_shape=(jax.ShapeDtypeStruct((mpad,), jnp.bool_),
                   jax.ShapeDtypeStruct((mpad,), jnp.int32)),
        grid=(mpad // SLOT_ROWS,),
        in_specs=[const1, tab1, tabw, row1, roww, row1],
        out_specs=(row1, row1),
        interpret=interpret,
    )(rounds, owner, slotw, cand0, pwstack, live)
    return found, slot


def slot_table_probe(owner, build_words, probe_words, live, max_rounds=None,
                     interpret=None):
    """Pallas twin of :func:`relational.hashtable.probe_slot_table` —
    same ``(found, slot)`` contract, bit-identical for any ``max_rounds``
    the lax walk would be given (the bound only gates termination).

    The owners' key words are gathered once up front (exactly the values
    the lax walk re-gathers every round) so the in-kernel chain walk
    needs no access to the full build-side arrays.
    """
    from ..relational import hashtable as H

    S = owner.shape[0]
    n = build_words[0].shape[0]
    m = probe_words[0].shape[0]
    if S * (4 + 4 * len(build_words)) > _SLOT_TABLE_MAX_BYTES:
        return H.probe_slot_table(owner, build_words, probe_words, live,
                                  max_rounds=max_rounds)
    mr = S if max_rounds is None else max_rounds
    oc = jnp.clip(owner, 0, max(n - 1, 0))
    slotw = jnp.stack(
        [jnp.take(w.astype(jnp.uint32), oc) for w in build_words], axis=1)
    cand0 = (H.fold_hash(probe_words) & jnp.uint32(S - 1)).astype(jnp.int32)
    pwstack = jnp.stack([w.astype(jnp.uint32) for w in probe_words], axis=1)
    rounds = jnp.asarray(mr, jnp.int32).reshape((1,))
    found, slot = _slot_probe_call(
        owner, slotw, cand0, pwstack, live.astype(jnp.bool_), rounds, n,
        _auto_interpret(interpret))
    return found[:m], slot[:m]


# ---------------------------------------------------------------------------
# fused radix partition scatter (the shuffle map step's morsel -> chunk hop)
# ---------------------------------------------------------------------------

def _part_scatter_kernel(P, C, M, cnts_ref, base_ref, r_ref, occ_in_ref,
                         *refs):
    """pid + per-partition cumulative offsets + round-chunk scatter, one
    pass.  ``refs`` is ``chunk_in.. morsel.. occ_out chunk_out..`` — the
    XLA formulation runs these as separate cumsum / searchsorted /
    per-column scatter programs with the row->slot map rematerialized in
    HBM between them; here the map lives in registers and every column
    scatters from the same resident morsel."""
    nleaf = (len(refs) - 1) // 3
    chunk_in = refs[:nleaf]
    morsel = refs[nleaf:2 * nleaf]
    occ_out = refs[2 * nleaf]
    chunk_out = refs[2 * nleaf + 1:]
    cnts = cnts_ref[:]
    ends = jnp.cumsum(cnts)
    offs = ends - cnts
    i = jax.lax.broadcasted_iota(jnp.int32, (M,), 0)
    # searchsorted(ends, i, side="right") == how many ends are <= i
    d = jnp.sum((i[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    d_c = jnp.minimum(d, P - 1)
    k = jnp.take(base_ref[:], d_c) + (i - jnp.take(offs, d_c))
    r = r_ref[0]
    in_round = (d < P) & (k >= r * C) & (k < (r + 1) * C)
    t = jnp.where(in_round, d_c * C + (k - r * C), P * C)
    occ_out[:] = occ_in_ref[:].at[t].set(True, mode="drop")
    for ci, mo, co in zip(chunk_in, morsel, chunk_out):
        co[:] = ci[:].at[t].set(mo[:], mode="drop")


def partition_scatter(chunk_leaves, occ, morsel_leaves, cnts, base, rnd,
                      partitions: int, capacity: int, interpret=None):
    """Fused twin of the shuffle map step's scatter
    (:mod:`shuffle.service` ``_scatter_step``): bit-identical
    ``(chunk_leaves, occ)`` for the same ``(cnts, base, rnd)`` routing
    inputs, with the row->slot map never leaving the kernel."""
    P = int(partitions)
    C = int(capacity)
    M = int(morsel_leaves[0].shape[0])
    chunk_leaves = tuple(chunk_leaves)
    morsel_leaves = tuple(morsel_leaves)
    full = lambda a: pl.BlockSpec(a.shape, lambda: (0,) * a.ndim)  # noqa: E731
    rarr = jnp.asarray(rnd, jnp.int32).reshape((1,))
    ins = (cnts, base, rarr, occ) + chunk_leaves + morsel_leaves
    outs = pl.pallas_call(
        partial(_part_scatter_kernel, P, C, M),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in (occ,) + chunk_leaves),
        in_specs=[full(a) for a in ins],
        out_specs=tuple(full(a) for a in (occ,) + chunk_leaves),
        interpret=_auto_interpret(interpret),
    )(*ins)
    return tuple(outs[1:]), outs[0]
