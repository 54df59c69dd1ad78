"""Structure-packed shared constraint matrix: the matvec representation
that stops the ADMM hot loop from streaming gigabytes of zeros.

The reference hands each scenario LP/MIP to Gurobi, whose simplex works
the ~101k-nonzero sparse matrix directly (ref. examples/uc/2013-05-11:
~0.03% dense at 25836 x 25836-ish scale). The TPU kernel's dense matmul
formulation (ops/qp_solver._Ax) instead reads the full (m, n) f32 pair
from HBM on every pass — at reference-UC scale that is ~2.7 GB per
split matvec and ~80% of the hot loop's memory traffic, which is why
the round-4 dense kernel measured 3.8% MFU (the chip spends its
bandwidth on zeros).

TPUs have no efficient general gather/scatter sparse matmul, but SP
constraint matrices are not generally sparse — they are STRUCTURED:

 - a few GLOBAL rows coupling most columns (UC: the per-hour balance
   and reserve rows — 96 of 25836 rows), and
 - a block-local remainder: rows touching only one small column group
   (UC: capacity/startup/min-up/min-down/ramp rows of one generator
   touch only that generator's u/st/p columns).

Union-find on the host sparsity pattern (already in hand at ship time —
core/spbase.ship_shared_matrix scatters from it) discovers this
generically, with no model-specific code: rows above an nnz threshold
go global, the rest partition into connected components of shared
columns. The packed form is then

    A x  =  [ einsum over (C, mr, nc) component blocks | G @ x | 0 ][row_src]
    Aᵀ y =  [ einsum over the same blocks | 0 ][col_src]  +  y[g_rows] @ G

with G the (R, n) global rows — one small batched MXU matmul plus one
thin dense matmul plus two gathers, all XLA-native: one gather brings
the operand into block slots, one PLACES the block results through an
inverse index (``row_src`` / ``col_src``: for every output element, the
slot that holds it, or the trailing zero slot). On the 90x48 UC instance the packed
operand set is ~1.5% of the dense matrix's bytes (C=90 components of
286 x 144 plus 96 global rows), turning every A-pass from ~3.4 ms of
HBM streaming into ~0.2 ms of mostly-MXU work. Models without local
structure simply fail the profitability test and keep the dense path.

A structure that EXISTS is not yet one worth USING: the ratio test
above is relative, and a matrix can pass it with nothing to save.
SIPLIB's sslp_10_50 does: (61, 520), ten server rows global, 51
one-row blocks of ten columns, 18% of dense, where dense is 254 KB.
Packed, every A-pass of its 2000-row call gathered two (2000, 510)
operands and placed an f64 (2000, 520) result through 51 one-row blocks
to skip 0.2 MB of zeros: 1.6x SLOWER than the dense split product
(0.230 against 0.140 ms an Ax + Aᵀy pair on a v5e; PERF.md §6, PR 33).
So there is a second test, ``pack_profitable(m, n, packed_elems,
rows)``: bytes (and MXU time) the packed form saves a pass against the
vector bytes its gathers move for the rows ONE device call solves.
``qp_solver._qp_setup_split`` applies it once per set of factors, the
engine naming its rows per call (``PHBase._rows_per_call``). UC packs
at any row count; sslp_10_50 is dense from four rows a call up. Left
dense, ``A_s`` keeps the skeleton and no packed values, and the dense
split Aᵀy still sums its leading pass by the skeleton's two row classes
(``global_row_mask``): local and global contributions meet in f64 in
the packed form, and that grouping, not the packing, is what holds the
df32 tail's residual floor (doc/kernels.md §3c).

Exactness: each nonzero lands in exactly one term (component blocks are
bounding boxes over disjoint row/column sets; global rows are disjoint
from local rows), so packed apply equals dense apply up to f32 summation
order. df32 callers accumulate the three split passes in f64 exactly as
the dense path does (ops/qp_solver.SplitMatrix).

The same disjointness is why the results are PLACED, not accumulated:
every output row (column) is owned by at most one block slot or global
row, so scattering the slots into a zero vector with ``.at[].add`` only
ever adds one value to a zero. Written that way it is a serial loop on
the TPU, and for the df32 tail's f64 results a variadic scatter over the
emulated (hi, lo) f32 pair with a two-sum combiner: 73 ns an index, four
times per tail ADMM iteration (doc/kernels.md §3b). The inverse index is
the same permutation read from the output's side, so the placement is a
plain gather. ``structure_from_lists`` builds it on the host and refuses
a skeleton whose blocks overlap: there a gather would silently drop what
the accumulation summed.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class PackStructure(NamedTuple):
    """Host-derived index skeleton (values not yet attached). Index
    arrays are pytree children (device-shippable); padding entries are
    -1 and masked at pack() time — padding with a real index would
    gather that row's true values into slots that must read zero."""
    g_rows: jax.Array      # (R,) int32 global-row indices (may be empty)
    l_rows: jax.Array      # (C, mr) int32, -1 padded
    l_cols: jax.Array      # (C, nc) int32, -1 padded
    # inverse index: where each output element of a matvec is read from
    row_src: jax.Array     # (m,) int32 into [l_rows slots | g_rows | zero]
    col_src: jax.Array     # (n,) int32 into [l_cols slots | zero]


class Packed(NamedTuple):
    """PackStructure + gathered values for ONE dense matrix. Indices
    here are clamped to valid range (masking already applied to vals)."""
    g_rows: jax.Array      # (R,) int32
    g_vals: jax.Array      # (R, n)
    l_rows: jax.Array      # (C, mr) int32, padding clamped to 0
    l_cols: jax.Array      # (C, nc) int32, padding clamped to 0
    l_vals: jax.Array      # (C, mr, nc), padded rows/cols zeroed
    row_src: jax.Array     # (m,) int32, as PackStructure's
    col_src: jax.Array     # (n,) int32, as PackStructure's


def analyze_structure(rows, cols, m, n, nnz_thresholds=None,
                      max_tile=2048, max_traffic_ratio=0.35,
                      max_global_frac=0.25, max_attempts=16):
    """Host structure discovery from the COO pattern (rows, cols).
    Returns a PackStructure, or None when the matrix has no profitable
    global/local split (callers keep the dense path).

    Tries progressively stricter nnz thresholds for the global-row set:
    a looser threshold keeps more rows local (cheaper), but a hub-like
    row (UC balance: 182 nnz) left local would union every generator
    into one giant component. The ladder is DERIVED from the distinct
    per-row nnz values (descending) — fixed rungs miss instances whose
    coupling rows (reserve: G nnz) sit between them at small G.
    Accepts the first threshold whose components fit (max_tile) and
    whose packed operand bytes are below ``max_traffic_ratio`` of
    dense."""
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    if rows.size == 0:
        return None
    row_nnz = np.bincount(rows, minlength=m)
    if nnz_thresholds is None:
        # thr = v keeps rows with nnz <= v local; each distinct value
        # is a potential cut between "local" and "coupling" rows
        distinct = np.unique(row_nnz[row_nnz > 1])[::-1]
        if distinct.size > max_attempts:
            # keep the small end dense (fine cuts matter there) and
            # subsample the large end
            head = distinct[distinct <= 64]
            tail = distinct[distinct > 64]
            if tail.size > max_attempts - head.size:
                sel = np.linspace(0, tail.size - 1,
                                  max(1, max_attempts - head.size))
                tail = tail[sel.astype(int)]
            distinct = np.concatenate([tail, head])[:max_attempts]
        nnz_thresholds = [int(v) for v in distinct]

    for thr in nnz_thresholds:
        g_mask = row_nnz > thr
        if g_mask.sum() > max_global_frac * m:
            continue
        local = ~g_mask[rows]
        lr, lc = rows[local], cols[local]
        if lr.size == 0:
            return None
        # connected components of the bipartite row/column adjacency
        # graph through scipy's C union-find (ADVICE r5: the previous
        # pure-Python per-threshold union-find cost seconds of
        # single-core host time per shipped matrix at reference scale;
        # csgraph runs the same partition in milliseconds). Nodes
        # 0..n-1 are columns, n.. are the local rows (reindexed); a
        # row node links every column it touches, so column components
        # match the row-merged column partition exactly.
        from scipy.sparse import coo_matrix, csgraph
        row_ids, rpos = np.unique(lr, return_inverse=True)
        g = coo_matrix((np.ones(lr.size, np.int8), (lc, n + rpos)),
                       shape=(n + row_ids.size, n + row_ids.size))
        _, labels = csgraph.connected_components(g, directed=False)
        used_cols = np.unique(lc)
        # deterministic component ids: first appearance over ascending
        # used-column index (the layout the union-find produced)
        comp_ids = {}
        for lab in labels[used_cols]:
            comp_ids.setdefault(int(lab), len(comp_ids))
        C = len(comp_ids)
        col_lists = [[] for _ in range(C)]
        for c in used_cols:
            col_lists[comp_ids[int(labels[c])]].append(c)
        row_lists = [[] for _ in range(C)]
        for i, r in enumerate(row_ids):
            row_lists[comp_ids[int(labels[n + i])]].append(r)
        mr = max(len(x) for x in row_lists)
        nc = max(len(x) for x in col_lists)
        if mr > max_tile or nc > max_tile:
            continue
        R = int(g_mask.sum())
        packed_elems = C * mr * nc + R * n
        if packed_elems > max_traffic_ratio * m * n:
            continue
        return structure_from_lists(row_lists, col_lists,
                                    np.flatnonzero(g_mask), m, n)
    return None


def packed_elems(structure: PackStructure) -> int:
    """Matrix elements ONE packed pass streams (padded component blocks
    plus the global rows): what ``analyze_structure`` held against
    ``max_traffic_ratio`` of m * n, read back from the skeleton."""
    C, mr = structure.l_rows.shape
    nc = structure.l_cols.shape[1]
    n = structure.col_src.shape[0]
    return int(C * mr * nc + structure.g_rows.shape[0] * n)


def global_row_mask(structure: PackStructure):
    """(m,) bool: which rows the skeleton holds as GLOBAL rows (read
    from ``row_src``: the slots after the C * mr local ones). The
    packed matvecs sum local and global contributions apart; a
    structured matrix left dense keeps that grouping by this mask
    (qp_solver._ATy)."""
    n_local = structure.l_rows.shape[0] * structure.l_rows.shape[1]
    src = structure.row_src
    return (src >= n_local) & (src < n_local + structure.g_rows.shape[0])


# The rule's two constants, read on one v5e chip (PERF.md §6, PR 33:
# packed against dense Ax + Aᵀy pairs, f32 and split, at sslp_10_50's
# (61, 520), a 20-block UC-like (5,800, 2,944) and UC's (26,016, 13,056)
# over 4 … 2,000 rows a call).
#
# _MXU_BYTES: what the three f32 dots of a split pass cost per matrix
# element and row, as bytes of HBM stream: a dense pass's seconds grow
# by 0.17 (UC) … 0.23 (middle shape) bytes' worth a row, so past ~40
# rows a call the zeros cost MXU time, not bandwidth.
_MXU_BYTES = 0.2
# _PACK_MARGIN: gathered bytes are not streamed bytes. Dense won (2.2x,
# both forms at launch latency) where the ratio below read 12.3 (sslp's
# matrix at 4 rows) and everywhere under it; packed won 1.5x (split) /
# 6.7x (f32) where it read 47 (the middle shape at 2,000 rows) and 7x
# where it read 350 (UC at 64). The margin sits at the low end of that
# gap: a matrix wrongly left dense pays for every zero on every pass
# (7x at UC), one wrongly packed pays three sweeps (1.6x at sslp).
_PACK_MARGIN = 14.0


def pack_profitable(m, n, packed_elems, rows):
    """Whether the packed matvec form pays for the call that runs it:
    the second test a structured df32 matrix meets, after
    ``analyze_structure``'s (is there a structure at all), made by
    ``qp_solver._qp_setup_split`` from shapes alone, the way
    ``kernels.l_inv_profitable`` decides the explicit inverse.

    In bytes a split pass (hi and lo, f32): packing SAVES the
    ``m * n - packed_elems`` elements it no longer touches, 8 bytes each
    to stream plus ``_MXU_BYTES`` a row to multiply; it MOVES about
    ``8 * (m + n)`` bytes a row that the dense form does not (two f32
    operand gathers and one f64 placement through ``row_src`` /
    ``col_src``; an Ax / Aᵀy pair averaged). Pack iff saved exceeds
    ``_PACK_MARGIN`` times moved. ``rows`` is the rows ONE device call
    solves (the chunk, per device on a mesh): the matrix is read once a
    pass whatever the rows, the vectors are gathered row by row.

    UC (m = 26,016, n = 13,056, 4.96 M packed elements of 340 M) at 64
    rows: 7.0 GB against 20 MB, packed, and at any row count (its zeros
    outweigh the gathers in MXU time alone). sslp_10_50 (m = 61,
    n = 520, 5,710 of 31,720) at 2000 rows: 10.6 MB saved, nearly all of
    it MXU time, against 9.3 MB moved: under the margin, dense. It
    packs at three rows or fewer, where both forms cost ~10–20 µs a
    pair on the chip and the verdict is worth nothing either way.
    Monotone: more rows never turn dense into packed, a taller matrix
    of the same width and packed share never turns packed into dense."""
    dropped = int(m) * int(n) - int(packed_elems)
    rows = max(int(rows), 1)
    saved = dropped * (8.0 + _MXU_BYTES * rows)
    moved = 8.0 * rows * (int(m) + int(n))
    return saved > _PACK_MARGIN * moved


def _padded(lists):
    out = np.full((len(lists), max(len(x) for x in lists)), -1, np.int32)
    for i, x in enumerate(lists):
        out[i, :len(x)] = x
    return out


def _inverse_index(owners, size, what):
    """(size,) int32: for each output element, the position in
    ``owners`` (flat, -1 = padding) of the one slot that holds it, or
    ``owners.size`` (the zero slot appended by the matvecs) where no
    slot does. An element two slots claim makes the placement wrong
    where the accumulation it replaces was right: refused here, once."""
    slots = np.flatnonzero(owners >= 0)
    if slots.size and owners[slots].max() >= size:
        raise ValueError(f"packed structure names {what} beyond {size}")
    claimed = np.bincount(owners[slots], minlength=size)
    if (claimed > 1).any():
        raise ValueError(
            f"packed structure is not disjoint: {what} "
            f"{np.flatnonzero(claimed > 1)[:8].tolist()} of {size} are "
            "claimed by more than one block slot or global row")
    src = np.full(size, owners.size, np.int32)
    src[owners[slots]] = slots
    return src


def structure_from_lists(row_lists, col_lists, g_rows, m, n):
    """PackStructure of C components given as lists of row and column
    indices plus the global rows, with the inverse index the matvecs
    place their results by. Raises ValueError unless the components'
    row sets, their column sets and the global rows are disjoint."""
    l_rows = _padded(row_lists)
    l_cols = _padded(col_lists)
    g_rows = np.asarray(g_rows, np.int32)
    row_src = _inverse_index(
        np.concatenate([l_rows.reshape(-1), g_rows]), m, "rows")
    col_src = _inverse_index(l_cols.reshape(-1), n, "columns")
    return PackStructure(
        g_rows=jnp.asarray(g_rows), l_rows=jnp.asarray(l_rows),
        l_cols=jnp.asarray(l_cols), row_src=jnp.asarray(row_src),
        col_src=jnp.asarray(col_src))


def pk_nbytes(pk: Packed) -> int:
    """Bytes of matrix operands one packed A-pass streams from HBM (the
    value arrays; index vectors are noise): the hi+lo sum is
    ``phase_timing()["solve_shape"]["pk_pass_bytes"]``, the byte basis
    of the benchmark's roofline share (benchmarks/bytes_model.py)."""
    return int(pk.g_vals.size * pk.g_vals.dtype.itemsize
               + pk.l_vals.size * pk.l_vals.dtype.itemsize)


@jax.jit
def pack(structure: PackStructure, dense) -> Packed:
    """Gather one dense (m, n) device matrix into packed form. Padded
    index slots (-1) clamp to 0 for the gather and their values are
    zeroed — position (0, c) holds real matrix data, which must not
    leak into padding."""
    lr = jnp.maximum(structure.l_rows, 0)
    lc = jnp.maximum(structure.l_cols, 0)
    vals = dense[lr[:, :, None], lc[:, None, :]]
    mask = (structure.l_rows >= 0)[:, :, None] \
        & (structure.l_cols >= 0)[:, None, :]
    vals = jnp.where(mask, vals, 0)
    return Packed(g_rows=structure.g_rows, g_vals=dense[structure.g_rows],
                  l_rows=lr, l_cols=lc, l_vals=vals,
                  row_src=structure.row_src, col_src=structure.col_src)


def _place(slots, src):
    """Block results (S, slots) -> output order: element j of the
    result is ``[slots | 0][:, src[j]]``. ``src`` is the structure's
    host-built inverse index (in bounds by construction; its length is
    the output's width), so this is a plain gather; no value is added
    to another."""
    S = slots.shape[0]
    padded = jnp.concatenate([slots, jnp.zeros((S, 1), slots.dtype)], axis=1)
    return padded.at[:, src].get(mode="promise_in_bounds")


def pk_Ax(pk: Packed, x):
    """A x via the packed form: x (S, n) -> (S, m)."""
    S = x.shape[0]
    xg = x[:, pk.l_cols]                          # (S, C, nc)
    loc = jnp.einsum("scn,cmn->scm", xg, pk.l_vals).reshape(S, -1)
    if pk.g_rows.size:
        loc = jnp.concatenate([loc, x @ pk.g_vals.T], axis=1)
    return _place(loc, pk.row_src)


def pk_ATy(pk: Packed, y):
    """Aᵀ y via the packed form: y (S, m) -> (S, n)."""
    S = y.shape[0]
    yg = y[:, pk.l_rows]                          # (S, C, mr)
    loc = jnp.einsum("scm,cmn->scn", yg, pk.l_vals)
    out = _place(loc.reshape(S, -1), pk.col_src)
    if pk.g_rows.size:
        out = out + y[:, pk.g_rows] @ pk.g_vals
    return out


def pk_Ax_split(pk_hi: Packed, pk_lo: Packed, xh, xl):
    """The df32 three-pass matvec (hi·xh + lo·xh + hi·xl, f64 accum —
    the SplitMatrix contract) through the packed form. hi and lo share
    one index skeleton, so x gathers once per operand and the three
    f32 einsum results accumulate in f64 BEFORE a single placement —
    one f64 gather instead of three f32 ones."""
    S = xh.shape[0]
    f64 = jnp.float64
    xgh = xh[:, pk_hi.l_cols]
    xgl = xl[:, pk_hi.l_cols]
    loc = (jnp.einsum("scn,cmn->scm", xgh, pk_hi.l_vals).astype(f64)
           + jnp.einsum("scn,cmn->scm", xgh, pk_lo.l_vals).astype(f64)
           + jnp.einsum("scn,cmn->scm", xgl, pk_hi.l_vals).astype(f64))
    loc = loc.reshape(S, -1)
    if pk_hi.g_rows.size:
        g = ((xh @ pk_hi.g_vals.T).astype(f64)
             + (xh @ pk_lo.g_vals.T).astype(f64)
             + (xl @ pk_hi.g_vals.T).astype(f64))
        loc = jnp.concatenate([loc, g], axis=1)
    return _place(loc, pk_hi.row_src)


def pk_ATy_split(pk_hi: Packed, pk_lo: Packed, yh, yl):
    """Transpose twin of pk_Ax_split."""
    S = yh.shape[0]
    f64 = jnp.float64
    ygh = yh[:, pk_hi.l_rows]
    ygl = yl[:, pk_hi.l_rows]
    loc = (jnp.einsum("scm,cmn->scn", ygh, pk_hi.l_vals).astype(f64)
           + jnp.einsum("scm,cmn->scn", ygh, pk_lo.l_vals).astype(f64)
           + jnp.einsum("scm,cmn->scn", ygl, pk_hi.l_vals).astype(f64))
    out = _place(loc.reshape(S, -1), pk_hi.col_src)
    if pk_hi.g_rows.size:
        g = ((yh[:, pk_hi.g_rows] @ pk_hi.g_vals).astype(f64)
             + (yh[:, pk_hi.g_rows] @ pk_lo.g_vals).astype(f64)
             + (yl[:, pk_hi.g_rows] @ pk_hi.g_vals).astype(f64))
        out = out + g
    return out
