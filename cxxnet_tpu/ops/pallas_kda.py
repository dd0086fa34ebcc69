"""Pallas TPU kernels for the chunk-local part of Kimi Delta Attention
(ops/kda.py): what a chunk computes from its own q, k, g and beta
before the scan over chunks. One forward and one backward kernel under
a `custom_vjp`; a grid step takes a few (head, chunk) units, a unit at
a time.

    G[t]    = sum_{i <= t} g[i]                    (the chunk's running sum)
    A[t, i] = beta_t sum_c k_tc k_ic exp(G_tc - G_ic)     i <  t, else 0
    P[t, i] =        sum_c q_tc k_ic exp(G_tc - G_ic)     i <= t, else 0

The mathematics is `ops/kda.py _pair_matrices`, in the same precisions:
g, G, every `exp` and every pair-by-pair product in float32; a chunk
cut into sub-blocks of `SUB` positions; inside a diagonal sub-block
the decays pair by pair, `exp(min(G_t - G_i, 0))`; a row block against
the columns before it through R = G just before the block, both
factors at most 1, as one product on the MXU with operands in the
activations' dtype and float32 accumulation. What `_pair_matrices`
writes to HBM as `f32[..., SUB, SUB, d_k]` (2.15 GB a tensor at the
Kimi cell's size, four of them forward and more backward) is here a
vreg at a time: column i of a sub-block against a tile of eight rows
under the diagonal is `(8, d_k)`, reduced over lanes and put at lane i
of the result.

Layout. Positions on sublanes, channels on lanes. A and P leave the
kernel side by side as one `(C, 2C)` block (128 lanes at C = 64: whole
stores), G as `(C, d_k)`; beta comes and dbeta goes as a row `(1, C)`
and is turned by a diagonal select and a reduction.

The backward kernel takes the cotangents of (A | P) and of G and
recomputes G, the decays and the strips from q, k, g, beta: nothing of
size C x C x d_k is saved or read. The row-side gradients accumulate as
`(8, d_k)` tiles, the column-side ones are sums over sublanes, and
both shares of dG follow from them elementwise; dbeta is `sum_c k_tc
dk1_tc` with dk1 the row-side gradient before beta, plus the strips'
recomputed products against dA.

The bodies are small programs. Every process traces and lowers the
step before it can ask the compile cache for it, so what a kernel body
costs to trace is set-up no cache saves: the sub-blocks and the
columns are `lax.fori_loop`s whose index reaches the data through
refs (a row is `ref[pl.ds(lo + i, 1)]`, a lane an `iota` compare), a
body is traced once, and Mosaic's lowering unrolls it (`unroll` = the
trip count: the index is a constant there). Both kernels are called
through one jitted function a direction, so the four `kda` layers of
a step and `remat`'s second forward share one lowered body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16                # positions a sub-block: a bf16 sublane tile
_UNITS = 8              # (head, chunk) units a grid step, at most
_ROWS = 8               # a float32 sublane tile
_TILES = SUB // _ROWS

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _loop(n, body, init):
    """`body(i, carry)` for i in 0 .. n-1, traced once; Mosaic's
    lowering repeats the body n times, the index a constant in each
    (`unroll` = the trip count), so the scheduler overlaps one turn's
    reductions and products with the next turn's loads and `exp`s:
    with the sub-blocks as a loop on the chip a forward call took 3.62
    ms for 2.53 and a backward call 7.38 for 3.88 (my chip runs, PR
    31, 4,096 units)."""
    return lax.fori_loop(0, n, body, init, unroll=n) if n else init


def _running_sum(x, reverse=False):
    """sum_{i <= t} x[i] down the sublanes (`reverse`: i >= t), as a
    product with a triangle of ones at full float32 precision."""
    c = x.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    tri = (col >= row) if reverse else (col <= row)
    return jnp.dot(tri.astype(_F32), x, precision=lax.Precision.HIGHEST,
                   preferred_element_type=_F32)


def _diag(lo, c):
    """`(SUB, C)`: entry (r, lo + r), which turns a row's entries
    lo .. lo+SUB into a column and back."""
    return _iota((SUB, c), 1) == _iota((SUB, c), 0) + lo


def _column(row, lo):
    """A `(1, C)` row's entries lo .. lo+SUB as a `(SUB, 1)` column."""
    return jnp.sum(jnp.where(_diag(lo, row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _lane(x, at):
    """Lane `at` of every row of `x`, as a column."""
    return jnp.sum(jnp.where(_iota(x.shape, 1) == at, x, 0.0), axis=1,
                   keepdims=True)


def _masks(lo, c):
    """For the rows [lo, lo + SUB) of the `(C, 2C)` block (A | P): its
    left half, the lanes of the columns before the row block (`strip`),
    and the entries that stay (`keep`: i < t left, i <= t right)."""
    lane = _iota((SUB, 2 * c), 1)
    t = _iota((SUB, 2 * c), 0) + lo
    left = lane < c
    i = jnp.where(left, lane, lane - c)
    strip = i < lo
    keep = i < jnp.where(left, t, t + 1)
    return left, strip, keep


def _tiles(x):
    """The rows of a sub-block in tiles of `_ROWS`."""
    return [x[r:r + _ROWS] for r in range(0, SUB, _ROWS)]


def _pair_decay(g_rows, g_i, diagonal):
    """exp(G_t - G_i) for a tile of rows t against position i. In the
    tile that holds the diagonal the rows above i (t < i, masked by the
    caller) are held to exp(0): their difference is positive and may
    overflow; under it every row has t > i."""
    diff = g_rows - g_i
    return jnp.exp(jnp.minimum(diff, 0.0) if diagonal else diff)


def _block(lo):
    """The rows [lo, lo + SUB) of a ref; lo is a multiple of SUB."""
    return pl.ds(pl.multiple_of(lo, SUB), SUB)


def _rows(ref, lo):
    """The sub-block [lo, lo + SUB) of a `(C, d)` float32 ref."""
    return ref[_block(lo), :]


def _strip_operands(lo, gs, ks, gb, kb, qb, dt):
    """Row block [lo, lo + SUB), lo > 0, against the columns before it,
    through R = G just before the block: the two decays, the rows'
    operand `(2 SUB, d)` (k rows over q rows) and the columns' `(2C, d)`
    (every column of the chunk, twice: those from lo on are masked
    where the product is used), both in `dt`."""
    r = gs[pl.ds(lo - 1, 1), :]
    rows = jnp.exp(gb - r)                                   # <= 1
    cols = jnp.exp(jnp.minimum(r - gs[...], 0.0))
    lhs = jnp.concatenate([kb * rows, qb * rows], 0).astype(dt)
    kcol = (ks[...] * cols).astype(dt)
    return rows, cols, lhs, jnp.concatenate([kcol, kcol], 0)


def _fwd_block(lo, strip_too, gs, ks, qs, beta, dt):
    """Rows [lo, lo + SUB) of (A | P), `(SUB, 2C)`. gs, ks, qs: `(C, d)`
    float32 refs of G, k and q; beta `(1, C)`."""
    c = gs.shape[0]
    gb, kb, qb = _rows(gs, lo), _rows(ks, lo), _rows(qs, lo)
    left, strip, keep = _masks(lo, c)
    gt, kt, qt = _tiles(gb), _tiles(kb), _tiles(qb)
    lane = _iota((_ROWS, 2 * c), 1)

    def columns(first):
        # columns lo + first*8 .. +8 of the diagonal block against the
        # row tiles that reach under the diagonal: rows t >= i hold
        # exp(G_t - G_i); `keep` masks the rest
        def body(i, acc):
            at = lo + first * _ROWS + i
            g_i, k_i = gs[pl.ds(at, 1), :], ks[pl.ds(at, 1), :]
            acc = list(acc)
            for j in range(first, _TILES):
                ke = _pair_decay(gt[j], g_i, j == first) * k_i
                kk = jnp.sum(kt[j] * ke, axis=1, keepdims=True)
                qk = jnp.sum(qt[j] * ke, axis=1, keepdims=True)
                acc[j] = jnp.where(lane == at, kk,
                                   jnp.where(lane == c + at, qk, acc[j]))
            return tuple(acc)
        return body

    acc = tuple(jnp.zeros((_ROWS, 2 * c), _F32) for _ in gt)
    for first in range(_TILES):
        acc = _loop(_ROWS, columns(first), acc)
    acc = jnp.concatenate(acc, 0)
    if strip_too:
        _, _, lhs, kcol2 = _strip_operands(lo, gs, ks, gb, kb, qb, dt)
        s = lax.dot_general(lhs, kcol2, _NT,
                            preferred_element_type=_F32)     # (2 SUB, 2C)
        acc = jnp.where(strip, jnp.where(left, s[:SUB], s[SUB:]), acc)
    scale = jnp.where(left, _column(beta, lo), 1.0)
    return jnp.where(keep, acc * scale, 0.0)


def _fwd_kernel(q_ref, k_ref, g_ref, b_ref, ap_ref, gs_ref, gs, ks, qs):
    """Blocks of `units` (head, chunk) units; gs, ks, qs: a unit's G, k
    and q in float32, where a row can be read by its number."""
    c = k_ref.shape[1]

    def unit(u, carry):
        gs[...] = _running_sum(g_ref[u])
        gs_ref[u] = gs[...]
        ks[...], qs[...] = k_ref[u].astype(_F32), q_ref[u].astype(_F32)
        beta = b_ref[u]

        def block(lo, strip_too):
            ap_ref[u, _block(lo), :] = _fwd_block(
                lo, strip_too, gs, ks, qs, beta, k_ref.dtype)

        block(0, False)

        def later(s, carry):
            block((s + 1) * SUB, True)
            return carry

        return _loop(c // SUB - 1, later, carry)

    lax.fori_loop(0, k_ref.shape[0], unit, 0)


def _bwd_block(lo, strip_too, dbeta, gs, ks, qs, beta, dap, dt,
               dq_ref, dks, dgs, dkc):
    """Rows [lo, lo + SUB) of the cotangent `dap` `(C, 2C)` of (A | P).

    A pair (t, i) gives to its row t and to its column i. The row side
    accumulates in `(8, d)` tiles: dk1 (of A, before beta) and dq; the
    rows' share of dG is then `k dk + q dq` of these, elementwise. The
    column side of k, through the decays, is a sum over sublanes a
    column: it goes row by row into `dkc` `(C, d)`, and the columns'
    share of dG is `-k dkc` (the caller's). Writes rows of dq, of dk's
    row side (`dks`) and of dG's (`dgs`); returns dbeta `(1, C)`."""
    c, d = gs.shape
    gb, kb, qb = _rows(gs, lo), _rows(ks, lo), _rows(qs, lo)
    left, strip, keep = _masks(lo, c)
    bcol = _column(beta, lo)
    dm = jnp.where(keep, _rows(dap, lo), 0.0)
    gt, kbt, qt, dmt = (_tiles(x) for x in (gb, kb * bcol, qb, dm))

    def columns(first):
        def body(i, acc):
            at = lo + first * _ROWS + i
            g_i, k_i = gs[pl.ds(at, 1), :], ks[pl.ds(at, 1), :]
            dk1, dqb = list(acc[0]), list(acc[1])
            dki = jnp.zeros((1, d), _F32)
            for j in range(first, _TILES):
                e = _pair_decay(gt[j], g_i, j == first)
                ke = e * k_i
                wk = _lane(dmt[j], at)                       # dA'[:, i]
                wq = _lane(dmt[j], c + at)                   # dP[:, i]
                dk1[j] = dk1[j] + wk * ke
                dqb[j] = dqb[j] + wq * ke
                dki = dki + jnp.sum((wk * kbt[j] + wq * qt[j]) * e,
                                    axis=0, keepdims=True)
            dkc[pl.ds(at, 1), :] = dki
            return tuple(dk1), tuple(dqb)
        return body

    acc = tuple(tuple(jnp.zeros((_ROWS, d), _F32) for _ in gt)
                for _ in range(2))
    for first in range(_TILES):
        acc = _loop(_ROWS, columns(first), acc)
    dk1, dqb = (jnp.concatenate(x, 0) for x in acc)
    dbeta_col = jnp.sum(kb * dk1, axis=1, keepdims=True)
    dkb = dk1 * bcol
    if strip_too:
        rows, cols, lhs, kcol2 = _strip_operands(lo, gs, ks, gb, kb, qb, dt)
        kk = lax.dot_general(lhs[:SUB], kcol2[:c], _NT,
                             preferred_element_type=_F32)       # (SUB, C)
        ds = jnp.where(strip, dm, 0.0)
        dbeta_col = dbeta_col + jnp.sum(kk * ds[:, :c], axis=1,
                                        keepdims=True)
        zero = jnp.zeros_like(ds)
        ds2 = jnp.concatenate(
            [jnp.where(left, ds * bcol, zero),
             jnp.where(left, zero, ds)], 0).astype(dt)       # (2 SUB, 2C)
        dlhs = jnp.dot(ds2, kcol2, preferred_element_type=_F32)
        dcol2 = lax.dot_general(ds2, lhs, _TN,
                                preferred_element_type=_F32)  # (2C, d)
        # nought from column lo on: `ds` is masked there
        dcol = (dcol2[:c] + dcol2[c:]) * cols
        dkc[...] += dcol
        dkb = dkb + dlhs[:SUB] * rows
        dqb = dqb + dlhs[SUB:] * rows
        # R = G[lo - 1] stands in both decays: with the columns',
        # against the rows'
        dr = (jnp.sum(dcol * ks[...], axis=0, keepdims=True)
              - jnp.sum((dlhs[:SUB] * kb + dlhs[SUB:] * qb) * rows,
                        axis=0, keepdims=True))
        dgs[pl.ds(lo - 1, 1), :] += dr
    dq_ref[_block(lo), :] = dqb.astype(dq_ref.dtype)
    dks[_block(lo), :] = dkb
    dgs[_block(lo), :] = dkb * kb + dqb * qb
    return dbeta + jnp.sum(jnp.where(_diag(lo, c), dbeta_col, 0.0), axis=0,
                           keepdims=True)


def _bwd_kernel(q_ref, k_ref, g_ref, b_ref, dap_ref, dgs_ref,
                dq_ref, dk_ref, dg_ref, db_ref, gs, ks, qs, dks, dgs, dkc):
    """The cotangents `dap` of (A | P) and `dgs` of G -> dq, dk, dg
    `(C, d)` and dbeta `(1, C)`, a unit at a time; six `(C, d)` float32
    scratch blocks: G, k, q, and the sums of dk's row side, dG's row
    side and dk's column side."""
    c = k_ref.shape[1]

    def unit(u, carry):
        gs[...] = _running_sum(g_ref[u])
        ks[...], qs[...] = k_ref[u].astype(_F32), q_ref[u].astype(_F32)
        beta = b_ref[u]

        def block(lo, strip_too, dbeta):
            # ascending: a block sets its own rows of `dkc` and `dgs`
            # before a later block's strip adds to them
            return _bwd_block(lo, strip_too, dbeta, gs, ks, qs, beta,
                              dap_ref.at[u], k_ref.dtype, dq_ref.at[u],
                              dks, dgs, dkc)

        dbeta = block(0, False, jnp.zeros((1, c), _F32))
        dbeta = _loop(c // SUB - 1,
                      lambda s, db: block((s + 1) * SUB, True, db), dbeta)
        col = dkc[...]
        dk_ref[u] = (dks[...] + col).astype(dk_ref.dtype)
        dg_ref[u] = _running_sum(dgs[...] - ks[...] * col + dgs_ref[u],
                                 reverse=True)
        db_ref[u] = dbeta
        return carry

    lax.fori_loop(0, k_ref.shape[0], unit, 0)


def _specs(n, c, d):
    units = next(u for u in (_UNITS, 4, 2, 1) if n % u == 0)

    def spec(*tail):
        return pl.BlockSpec((units,) + tail, lambda i: (i, 0, 0))

    return units, spec(c, d), spec(1, c), spec(c, 2 * c)


def _scratch(c, d, n):
    return [pltpu.VMEM((c, d), _F32) for _ in range(n)]


# ONE jitted function a direction: the `kda` layers of a step and
# `remat`'s second forward call it with one shape, so the step's text
# holds one lowered body a direction however many layers call it
# (tests/test_pallas_kda.py counts them). The names are what
# `kda_kernel_ms` and chip_smoke.py find the kernels by.
@functools.partial(jax.jit, static_argnames="interpret")
def _local_fwd(q, k, g, beta, interpret):
    n, c, d = k.shape
    units, wide, row, pair = _specs(n, c, d)
    return pl.pallas_call(
        _fwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, c, 2 * c), _F32),
                   jax.ShapeDtypeStruct((n, c, d), _F32)),
        grid=(n // units,),
        in_specs=[wide, wide, wide, row],
        out_specs=(pair, wide),
        scratch_shapes=_scratch(c, d, 3),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="kda_local_fwd",
    )(q, k, g, beta)


@functools.partial(jax.jit, static_argnames="interpret")
def _local_bwd(q, k, g, beta, dap, dgs, interpret):
    n, c, d = k.shape
    units, wide, row, pair = _specs(n, c, d)
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, c, d), q.dtype),
                   jax.ShapeDtypeStruct((n, c, d), k.dtype),
                   jax.ShapeDtypeStruct((n, c, d), _F32),
                   jax.ShapeDtypeStruct((n, 1, c), _F32)),
        grid=(n // units,),
        in_specs=[wide, wide, wide, row, pair, wide],
        out_specs=(wide, wide, wide, row),
        scratch_shapes=_scratch(c, d, 6),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="kda_local_bwd",
    )(q, k, g, beta, dap, dgs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def kda_local(q, k, g, beta, interpret=False):
    """q, k `(N, C, d)` in the activations' dtype, g `(N, C, d)` and
    beta `(N, 1, C)` float32, N (head, chunk) units -> (A | P)
    `(N, C, 2C)` and G `(N, C, d)`, float32, masked and with beta
    applied."""
    return _local_fwd(q, k, g, beta, interpret)


def _vjp_fwd(q, k, g, beta, interpret=False):
    return _local_fwd(q, k, g, beta, interpret), (q, k, g, beta)


def _vjp_bwd(interpret, res, cts):
    return _local_bwd(*res, *cts, interpret)


kda_local.defvjp(_vjp_fwd, _vjp_bwd)


def local_pallas(q, k, g, beta, interpret=False):
    """`ops/kda.py local_xla` through the kernels, under its signature:
    q, k, g `(..., C, d)`, beta `(..., C, 1)` -> A, P `(..., C, C)` and
    G `(..., C, d)`. Which shapes come here is `ops/kda.py
    _kernel_route`'s to say; the kernels take any chunk of whole
    sub-blocks."""
    c, d = k.shape[-2:]
    ap, g_cum = kda_local(*(x.reshape(-1, c, d) for x in (q, k, g)),
                          beta.reshape(-1, 1, c), interpret)
    ap = ap.reshape(k.shape[:-2] + (c, 2 * c))
    return ap[..., :c], ap[..., c:], g_cum.reshape(g.shape)
