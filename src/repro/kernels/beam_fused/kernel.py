"""Pallas TPU kernel: the fused beam-hop serve loop, resident or streamed.

One grid step owns a TB-row query tile and runs the *entire* hop loop --
frontier select, adjacency gather, neighbor scoring, pool merge -- as a
`fori_loop` whose (TB, L) pool state never leaves VMEM.  The unfused
engine round-trips pool/frontier arrays through HBM between four XLA
programs per hop; here one program launch serves all `max_hops` hops.

TPU adaptation of each stage:

- **frontier select**: the pool is kept sorted, so the pop is the first
  unexpanded valid entry -- a masked iota min + one-hot readout, no
  argsort.
- **adjacency / code / vector gather**: resident, rows are pulled from
  the VMEM corpus blocks by one-hot @ matrix MXU contractions (the
  `pq_adc` trick), chunked over N (`n_chunk`) so the one-hot tile, not
  the corpus, bounds the live footprint; streamed, each row is one DMA
  from HBM addressed by its id (`_gather_rows_dma`).
- **scoring**: mode="adc" inlines the `pq_adc_rowwise` one-hot LUT
  lookup against the tile's private (TB, M, K) tables
  (`pq_adc.kernel.adc_rowwise_part`).  A 128-lane part of at most 64
  sub-spaces is scored as one unrolled group (M=64); a wider part is
  written sub-space-major to a (128, TB*R) scratch and scored 32
  sub-spaces a `fori_loop` iteration, each group's table slice read from
  the tables ref, so one group's (TB, R, K) one-hots are live at a time.
  The streamed kernel needs 9.10 MiB of scoped VMEM at M=64, 7.07 MiB at
  128 and 6.95 MiB at 240 (B=64, L = max_hops = 256, R=32; the form that
  unrolled every sub-space needed 9.60 MiB at 64, ran out at 128 and
  asked 31.1 MB at 240); mode="l2" is the
  build frontier's dot-form exact distance vs (N, D+1) vectors carrying
  their squared norms in the last column.
- **merge**: `pool_merge_ranked` verbatim -- lexicographic (dist, id)
  merge ranks from elementwise comparisons, then a slot-match scatter
  (rank == slot-iota one-hots); no sort anywhere in the hop.

Every hop also records its frontier pick into a (TB, max_hops) trace
(the build frontier's visited set), and the program ends by emitting the
*next* frontier pick and a done mask so callers can chain hop programs.

The ADC kernels take the count of live rows as a scalar-prefetch operand
(`n_live`): a grid step whose TB rows are all padding runs no hop loop,
writes the empty result of its rows, and asks for no new input block
(`_guarded`, `_tile_specs`).  The serve path pads each batch to one
compiled shape, so a batch of few real rows costs the tiles it fills.

Two execution modes share the hop loop and differ only in where the
corpus lives:

- **resident** (`beam_hops_{adc,l2}_pallas`): adjacency + codes/vectors
  are VMEM blocks, gather chunks are `pl.ds` slices of them.  Footprint
  per grid step is N*(R + M)*4 bytes (adc) or N*(R + D + 1)*4 (l2) plus
  the (TB*R, n_chunk) gather one-hot and (TB, R|L, L) merge tensors --
  see `vmem_bytes`.  A 100k-node shard at R=32, M=16 is ~20 MB, past
  most cores' VMEM.
- **streaming** (`beam_hops_{adc,l2}_stream`): the corpus stays in HBM
  (`memory_space=ANY`).  Each hop moves the TB frontier ids to SMEM and
  DMAs their TB adjacency rows into a VMEM row buffer, then does the
  same for the TB*R neighbors' code/vector rows: every DMA of a round is
  issued before any is waited on, so a hop waits on two rounds of row
  DMAs (each after one small id copy) and moves bytes in proportion to
  R, not N.  ADC code rows wider than 128 lanes (M > 128) come one
  128-lane part a round, each part scored before the next is fetched,
  so the code row buffer is (TB*R, 128) at any M.  The footprint is
  `stream_vmem_bytes` -- independent of N and n_chunk -- which is what
  lets one grid step serve a shard far larger than VMEM instead of
  requiring `serve.frontend.ShardedFrontend` to slice the corpus down
  to fast-memory size.  The rows land in the order the resident
  gather's one-hot contraction returns them, with the same values, so
  both modes are bit-identical on every output (streaming changes timing
  and memory traffic, never results).

Ids and flags travel as exact f32 (N < 2^24) so every stage stays on
the VPU/MXU datapath.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pq_adc.kernel import (adc_rowwise_part, code_parts,
                                         subspace_scratch)

_SENT = float(2 ** 31)   # f32 id sentinel: -1 ids rank last, like pool_merge
_LANES = 128             # f32 vreg width: VMEM/HBM rows pad to it
# one-hot contractions carry exact f32 ids and table entries; a bf16 MXU
# pass would round every id above 256, so their precision is pinned
_EXACT = jax.lax.Precision.HIGHEST

# resident-fused VMEM budget the auto backend compares `vmem_bytes`
# against; ~16 MiB is a safe per-core figure across TPU generations
_DEFAULT_VMEM_BUDGET = 16 * 2 ** 20


def vmem_budget_bytes() -> int:
    """The resident-fused VMEM budget (bytes); REPRO_VMEM_BUDGET overrides."""
    return int(os.environ.get("REPRO_VMEM_BUDGET", _DEFAULT_VMEM_BUDGET))


def _lanes(c: int) -> int:
    """`c` columns rounded up to whole 128-lane vregs."""
    return -(-c // _LANES) * _LANES


def _mode_dims(m, d):
    if (m is None) == (d is None):
        raise ValueError("pass exactly one of m= (adc mode) / d= (l2 mode)")
    # corpus row width beyond adjacency: codes (M) or vectors+norm (D+1)
    return (m, 0) if m is not None else (d + 1, d)


def vmem_bytes(n: int, r: int, *, m: int | None = None, d: int | None = None,
               l: int = 64, max_hops: int = 32, tile_b: int = 8,
               n_chunk: int = 2048, k: int = 256) -> int:
    """Estimated VMEM footprint (bytes) of one *resident* fused grid step.

    n/r: padded corpus rows and adjacency width; exactly one of m (PQ
    subquantizers, adc mode) / d (vector dim, l2 mode); l the pool
    width, k the PQ centroid count.  Terms: the VMEM-resident corpus
    blocks (the part streaming eliminates), the per-tile private
    operands (ADC tables / query tile), the (TB*R, n_chunk) gather
    one-hot, the scoring scratch (adc: one (TB, R, K) LUT one-hot and the
    (128, TB*R) sub-space-major codes, whatever M is), the merge
    rank/scatter tensors, and the pool + trace state.
    """
    row_w, dd = _mode_dims(m, d)
    f = 4
    corpus = n * (r + row_w) * f
    if m is not None:
        private = tile_b * m * k * f               # (TB, M, K) ADC tables
        score = (tile_b * r * k * f                # (TB, R, K) LUT one-hot
                 + _LANES * _lanes(tile_b * r) * 4)  # `subspace_scratch`
    else:
        private = tile_b * dd * f                  # (TB, D) query tile
        score = tile_b * r * (dd + 1) * f          # gathered rows + dots
    gather = tile_b * r * n_chunk * f              # (TB*R, n_chunk) one-hot
    merge = 4 * tile_b * (l * l + 2 * r * l + r * r) * f
    state = (6 * tile_b * l + 4 * tile_b * max_hops) * f
    return corpus + private + score + gather + merge + state


def stream_vmem_bytes(n: int, r: int, *, m: int | None = None,
                      d: int | None = None, l: int = 64, max_hops: int = 32,
                      tile_b: int = 8, n_chunk: int = 2048,
                      k: int = 256) -> int:
    """Estimated VMEM footprint of one *streaming* fused grid step: the
    resident estimate minus the corpus blocks and the (TB*R, n_chunk)
    gather one-hot, plus the row-gather scratch (`_stream_scratch`): the
    TB adjacency rows and TB*R codes/vector rows, lane-padded to 128, and
    their int32 ids (the SMEM copy of the ids is not VMEM).  ADC code
    rows arrive one 128-lane part at a time, so their buffer is 128 lanes
    wide at any M.  Independent of n and of n_chunk, which the streamed
    gather does not use."""
    row_w, _ = _mode_dims(m, d)
    resident = vmem_bytes(n, r, m=m, d=d, l=l, max_hops=max_hops,
                          tile_b=tile_b, n_chunk=n_chunk, k=k)
    f = 4
    onehot = tile_b * r * n_chunk * f
    gathered = min(row_w, _LANES) if m is not None else row_w
    rows = tile_b * (_lanes(r) + r * _lanes(gathered)) * f
    ids = tile_b * (_lanes(1) + _lanes(r)) * 4
    return resident - n * (r + row_w) * f - onehot + rows + ids


def fits_vmem(n: int, r: int, *, m: int | None = None, d: int | None = None,
              l: int = 64, max_hops: int = 32, tile_b: int = 8,
              n_chunk: int = 2048, k: int = 256,
              budget: int | None = None) -> bool:
    """Whether the resident fused kernel's footprint fits the VMEM budget
    (the `backend="auto"` rule: resident when it fits, streaming when
    not)."""
    budget = vmem_budget_bytes() if budget is None else int(budget)
    return vmem_bytes(n, r, m=m, d=d, l=l, max_hops=max_hops, tile_b=tile_b,
                      n_chunk=n_chunk, k=k) <= budget


def _check_tiling(b: int, tile_b: int, n: int, n_chunk: int) -> None:
    """Public-kernel shape contract, raised (not asserted: asserts vanish
    under `python -O`, and these kernels are callable without the
    ops-layer padding)."""
    if tile_b <= 0 or b % tile_b != 0:
        raise ValueError(
            f"pool batch b={b} is not divisible by tile_b={tile_b}; pad the "
            f"pool rows to a tile_b multiple (ops.beam_hops does this)")
    if n_chunk <= 0 or n % n_chunk != 0:
        raise ValueError(
            f"corpus rows n={n} are not divisible by n_chunk={n_chunk}; pad "
            f"the corpus arrays to an n_chunk multiple (ops.beam_hops does "
            f"this)")


def _iota_f32(shape, dim: int):
    """f32 index iota (Mosaic's iota is integer-only; ids < 2^24 are exact)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _onehot_dot(onehot, mat):
    """(S, C) one-hot @ (C, W) at full f32 precision: an exact row gather."""
    return jax.lax.dot_general(onehot, mat, (((1,), (0,)), ((), ())),
                               precision=_EXACT,
                               preferred_element_type=jnp.float32)


def _column(a):
    """(T, R) -> (T*R, 1) in row-major order.  Mosaic refuses the direct
    lane->sublane reshape, so transpose and stack the columns instead."""
    at = a.T
    return jnp.concatenate([at[:, i:i + 1] for i in range(a.shape[0])],
                           axis=0)


def _gather_rows(ids_col, mat_ref, n: int, n_chunk: int):
    """One-hot gather of `mat_ref` rows: ids_col (S, 1) exact-int f32 with
    all values in [0, n); mat_ref (N, C) f32.  Returns (S, C).  Chunked over
    N so only an (S, n_chunk) one-hot tile is live per iteration; each id
    matches exactly one column of exactly one chunk."""
    s = ids_col.shape[0]
    c = mat_ref.shape[1]
    col = _iota_f32((s, n_chunk), 1)

    def body(ci, acc):
        off = (ci * n_chunk).astype(jnp.float32)
        onehot = (col + off == ids_col).astype(jnp.float32)
        start = pl.multiple_of(ci * n_chunk, n_chunk)
        return acc + _onehot_dot(onehot, mat_ref[pl.ds(start, n_chunk), :])

    return jax.lax.fori_loop(0, n // n_chunk, body,
                             jnp.zeros((s, c), jnp.float32))


def _stage_ids(ids, scratch, n: int) -> None:
    """Clamp ids (T, K) exact-int f32 to [0, n) (a valid id is unchanged;
    no DMA can leave the corpus), write them as int32 to the VMEM block of
    `scratch` (one `_row_gather_scratch` set) and move them to its SMEM
    block by one local DMA, so the scalar core can address rows with
    them."""
    _, id_vmem, id_smem, sem = scratch
    id_vmem[...] = jnp.clip(ids, 0.0, n - 1.0).astype(jnp.int32)
    ids_copy = pltpu.make_async_copy(id_vmem, id_smem, sem)
    ids_copy.start()
    ids_copy.wait()


def _dma_rows(hbm_ref, scratch, n: int, part: int | None = None):
    """One round of row DMAs for the ids `_stage_ids` left in `scratch`:
    rows (T*K, C) f32 in row-major order (row t*K + j holds corpus row
    `ids[t, j]`, the order `_column` gives the resident gather).

    `hbm_ref` is the lane-padded (N, C) corpus viewed as (N*P, 128), P =
    C / 128 (`_row_view`): Mosaic DMAs whole 128-lane rows.  With `part`
    given, each id moves only its row's 128-lane part `part` and the
    result is (T*K, 128).  Every DMA of the round is issued on one
    semaphore before any is waited on: T*K copies of one corpus row each,
    so the bytes moved grow with K, not with n.  DMAs copy values, so the
    rows are exactly the ones the one-hot contraction of the resident
    gather returns."""
    rows, _, id_smem, sem = scratch
    t, k = id_smem.shape
    p = hbm_ref.shape[0] // n
    first, width = (0, p) if part is None else (part, 1)

    def row_copy(i, v):
        return pltpu.make_async_copy(
            hbm_ref.at[pl.ds(v * p + first, width), :],
            rows.at[pl.ds(i * width, width), :], sem)

    def issue(q, c):
        def one(j, c):
            row_copy(q * k + j, id_smem[q, j]).start()
            return c
        return jax.lax.fori_loop(0, k, one, c, unroll=True)

    def wait(q, c):
        def one(j, c):
            row_copy(q * k + j, 0).wait()
            return c
        return jax.lax.fori_loop(0, k, one, c, unroll=True)

    jax.lax.fori_loop(0, t, issue, 0)
    jax.lax.fori_loop(0, t, wait, 0)
    if width == 1:
        return rows[pl.ds(0, t * k), :]
    return jnp.concatenate(
        [rows[pl.ds(j, t * k, stride=p), :] for j in range(p)], axis=1)


def _gather_rows_dma(ids, hbm_ref, scratch, n: int):
    """Row gather with the corpus in HBM: ids (T, K) exact-int f32 ->
    rows (T*K, C) f32, every 128-lane part of each row in one round
    (`_stage_ids`, then `_dma_rows`)."""
    _stage_ids(ids, scratch, n)
    return _dma_rows(hbm_ref, scratch, n)


def _merge_ranked(pids, pd, pexp, cids, cd, tb: int, l: int, r: int):
    """In-kernel `pool_merge_ranked` (see repro.build.pool), f32 ids."""
    cd = jnp.where(cids < 0.0, jnp.inf, cd)
    dup_pool = jnp.any((pids[:, None, :] == cids[:, :, None])
                       & (cids[:, :, None] >= 0.0), axis=2)
    earlier = (jax.lax.broadcasted_iota(jnp.int32, (tb, r, r), 1)
               > jax.lax.broadcasted_iota(jnp.int32, (tb, r, r), 2))
    dup_cand = jnp.any((cids[:, :, None] == cids[:, None, :])
                       & (cids[:, :, None] >= 0.0) & earlier, axis=2)
    valid = (cids >= 0.0) & ~dup_pool & ~dup_cand
    cd = jnp.where(valid, cd, jnp.inf)
    cids = jnp.where(valid, cids, -1.0)

    pkid = jnp.where(pids < 0.0, _SENT, pids)
    ckid = jnp.where(cids < 0.0, _SENT, cids)
    c_lt_p = ((cd[:, :, None] < pd[:, None, :])
              | ((cd[:, :, None] == pd[:, None, :])
                 & (ckid[:, :, None] < pkid[:, None, :])))
    pos_p = (jax.lax.broadcasted_iota(jnp.int32, (tb, l), 1)
             + c_lt_p.astype(jnp.int32).sum(axis=1))
    p_le_c = ((pd[:, :, None] < cd[:, None, :])
              | ((pd[:, :, None] == cd[:, None, :])
                 & (pkid[:, :, None] <= ckid[:, None, :])))
    ctie = cd[:, :, None] == cd[:, None, :]
    c_lt_c = ((cd[:, :, None] > cd[:, None, :])
              | (ctie & (ckid[:, :, None] > ckid[:, None, :]))
              | (ctie & (ckid[:, :, None] == ckid[:, None, :]) & earlier))
    pos_c = (p_le_c.astype(jnp.int32).sum(axis=1)
             + c_lt_c.astype(jnp.int32).sum(axis=2))

    # slot-match scatter: rank >= l simply matches no slot; every slot
    # < l has exactly one owning source (merge ranks are a bijection)
    mp = pos_p[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (tb, l, l), 2)
    mc = pos_c[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (tb, r, l), 2)
    out_ids = (jnp.where(mp, pids[:, :, None], 0.0).sum(axis=1)
               + jnp.where(mc, cids[:, :, None], 0.0).sum(axis=1))
    out_d = (jnp.where(mp, pd[:, :, None], 0.0).sum(axis=1)
             + jnp.where(mc, cd[:, :, None], 0.0).sum(axis=1))
    out_exp = jnp.where(mp, pexp[:, :, None], 0.0).sum(axis=1)
    return out_ids, out_d, out_exp


def _hop_loop(gather_adj, ids_ref, d_ref, exp_ref, score, outs,
              *, max_hops: int, r: int):
    """Shared hop loop; `gather_adj(v_col (TB, 1)) -> (TB, R)` pulls the
    frontier adjacency rows (resident one-hot chunks or streamed row
    DMAs) and `score(nbrs, valid) -> (TB, R)` closes over the
    mode-specific operands.  Writes the eight output refs in `outs`."""
    (oi_ref, od_ref, oe_ref, oh_ref, oti_ref, otd_ref,
     onx_ref, odn_ref) = outs
    tb, l = ids_ref.shape
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (tb, l), 1)
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (tb, max_hops), 1)

    def pick(ids, d, exp):
        fm = (exp == 0.0) & (ids >= 0.0) & (d < jnp.inf)
        jmin = jnp.min(jnp.where(fm, iota_l, l), axis=1)        # (TB,)
        has = jmin < l
        onej = iota_l == jmin[:, None]                          # all-0 if !has
        v = jnp.where(onej, ids, 0.0).sum(axis=1)
        vd = jnp.where(has, jnp.where(onej, d, 0.0).sum(axis=1), jnp.inf)
        return onej, has, v, vd

    def hop(h, carry):
        ids, d, exp, hops, tid, td = carry
        onej, has, v, vd = pick(ids, d, exp)
        exp = jnp.maximum(exp, onej.astype(jnp.float32))
        nbrs = gather_adj(v[:, None])                           # (TB, R)
        nbrs = jnp.where(has[:, None], nbrs, -1.0)
        nd = score(nbrs, nbrs >= 0.0)
        ids, d, exp = _merge_ranked(ids, d, exp, nbrs, nd, tb, l, r)
        hops = hops + has.astype(jnp.float32)
        at_h = iota_h == h
        tid = jnp.where(at_h, jnp.where(has, v, -1.0)[:, None], tid)
        td = jnp.where(at_h, vd[:, None], td)
        return ids, d, exp, hops, tid, td

    ids, d, exp, hops, tid, td = jax.lax.fori_loop(
        0, max_hops, hop,
        (ids_ref[...], d_ref[...], exp_ref[...], jnp.zeros(tb, jnp.float32),
         jnp.full((tb, max_hops), -1.0, jnp.float32),
         jnp.full((tb, max_hops), jnp.inf, jnp.float32)))

    _, has, v, _ = pick(ids, d, exp)
    oi_ref[...] = ids.astype(jnp.int32)
    od_ref[...] = d
    oe_ref[...] = exp.astype(jnp.int32)
    oh_ref[...] = hops.astype(jnp.int32)[:, None]
    oti_ref[...] = tid.astype(jnp.int32)
    otd_ref[...] = td
    onx_ref[...] = jnp.where(has, v, -1.0).astype(jnp.int32)[:, None]
    odn_ref[...] = (~has).astype(jnp.int32)[:, None]


def _adc_score_from(gather_codes, tables_ref, ct_ref, tb: int, r: int):
    """ADC scoring closure shared by the resident and streaming kernels:
    `gather_codes(ids (TB, R))` readies the frontier neighbors' PQ codes
    and returns `part(m0, w) -> (TB*R, >= w)` f32, their sub-spaces
    m0 .. m0+w-1 (row-major), for each 128-lane part of the codes
    (`pq_adc.kernel.code_parts`); each part is scored by
    `adc_rowwise_part`, the `pq_adc_rowwise` one-hot lookup against the
    tile's private (TB, M, K) tables, a group of sub-spaces at a time
    (through the (128, TB*R) scratch `ct_ref` when a part is more than
    one group)."""
    m_sub = tables_ref.shape[1]

    def score(nbrs, valid):
        part = gather_codes(jnp.maximum(nbrs, 0.0))
        nd = jnp.zeros((tb, r), jnp.float32)
        for m0, w in code_parts(m_sub):
            codes = part(m0, w)[:, :w].astype(jnp.int32)         # (TB*R, w)
            nd = adc_rowwise_part(nd, codes, ct_ref, tables_ref, m0)
        return jnp.where(valid, nd, jnp.inf)

    return score


def _l2_score_from(gather_xn, q, dd: int, tb: int, r: int):
    """Exact-L2 scoring closure shared by the resident and streaming
    kernels: gather (vector, squared-norm) rows (`gather_xn(ids (TB, R))
    -> (TB*R, D+1)`, row-major), dot-form distance."""
    qn = jnp.sum(q * q, axis=1)

    def score(nbrs, valid):
        rows = gather_xn(jnp.maximum(nbrs, 0.0))                 # (TB*R, D+1)
        rows = rows.reshape(tb, r, dd + 1)
        vecs = rows[:, :, :dd]
        n2g = rows[:, :, dd]
        dot = jax.lax.dot_general(vecs, q, (((2,), (1,)), ((0,), (0,))),
                                  precision=_EXACT,
                                  preferred_element_type=jnp.float32)
        dist = jnp.maximum(n2g - 2.0 * dot + qn[:, None], 0.0)
        return jnp.where(valid, dist, jnp.inf)

    return score


def _beam_adc_kernel(adj_ref, codes_ref, tables_ref, ids_ref, d_ref, exp_ref,
                     *outs_scratch, max_hops: int, n: int, n_chunk: int):
    outs, (ct_ref,) = outs_scratch[:8], outs_scratch[8:]
    tb = ids_ref.shape[0]
    r = adj_ref.shape[1]

    def gather_codes(ids):
        codes = _gather_rows(_column(ids), codes_ref, n, n_chunk)
        return lambda m0, w: codes[:, m0:m0 + w]

    score = _adc_score_from(gather_codes, tables_ref, ct_ref, tb, r)
    _hop_loop(lambda v: _gather_rows(v, adj_ref, n, n_chunk),
              ids_ref, d_ref, exp_ref, score, outs,
              max_hops=max_hops, r=r)


def _beam_l2_kernel(adj_ref, xn_ref, q_ref, ids_ref, d_ref, exp_ref,
                    *outs, max_hops: int, n: int, n_chunk: int):
    tb = ids_ref.shape[0]
    r = adj_ref.shape[1]
    dd = xn_ref.shape[1] - 1                     # last column = squared norm
    score = _l2_score_from(
        lambda ids: _gather_rows(_column(ids), xn_ref, n, n_chunk),
        q_ref[...], dd, tb, r)
    _hop_loop(lambda v: _gather_rows(v, adj_ref, n, n_chunk),
              ids_ref, d_ref, exp_ref, score, outs,
              max_hops=max_hops, r=r)


def _split_stream_refs(refs):
    """A streamed kernel's trailing refs: its eight outputs, then the two
    `_row_gather_scratch` sets of `_stream_scratch` (adjacency rows,
    codes/vector rows), then any scratch of its scoring."""
    return refs[:8], refs[8:12], refs[12:16], refs[16:]


def _beam_adc_stream_kernel(adj_ref, codes_ref, tables_ref, ids_ref, d_ref,
                            exp_ref, *outs_scratch,
                            max_hops: int, n: int, r: int):
    """ADC hop loop with adj/codes left in HBM (`memory_space=ANY`) and
    every gather a round of row DMAs: the frontier adjacency rows, then
    the neighbors' code rows one 128-lane part a round, each part scored
    before the next is fetched, so the code row buffer holds one part
    whatever M is."""
    outs, adj_scratch, code_scratch, (ct_ref,) = _split_stream_refs(
        outs_scratch)
    tb = ids_ref.shape[0]

    def gather_codes(ids):
        _stage_ids(ids, code_scratch, n)
        return lambda m0, w: _dma_rows(codes_ref, code_scratch, n,
                                       part=m0 // _LANES)

    score = _adc_score_from(gather_codes, tables_ref, ct_ref, tb, r)
    _hop_loop(lambda v: _gather_rows_dma(v, adj_ref, adj_scratch, n)[:, :r],
              ids_ref, d_ref, exp_ref, score, outs,
              max_hops=max_hops, r=r)


def _beam_l2_stream_kernel(adj_ref, xn_ref, q_ref, ids_ref, d_ref, exp_ref,
                           *outs_scratch, max_hops: int, n: int, r: int):
    """Exact-L2 hop loop with adj/vectors left in HBM and every gather a
    round of row DMAs (`_gather_rows_dma`)."""
    outs, adj_scratch, xn_scratch, _ = _split_stream_refs(outs_scratch)
    tb = ids_ref.shape[0]
    dd = q_ref.shape[1]
    score = _l2_score_from(
        lambda ids: _gather_rows_dma(ids, xn_ref, xn_scratch, n)[:, :dd + 1],
        q_ref[...], dd, tb, r)
    _hop_loop(lambda v: _gather_rows_dma(v, adj_ref, adj_scratch, n)[:, :r],
              ids_ref, d_ref, exp_ref, score, outs,
              max_hops=max_hops, r=r)


def _out_shapes(b, l, max_hops):
    i32, f32 = jnp.int32, jnp.float32
    return (jax.ShapeDtypeStruct((b, l), i32),        # pool ids
            jax.ShapeDtypeStruct((b, l), f32),        # pool dists
            jax.ShapeDtypeStruct((b, l), i32),        # pool expanded
            jax.ShapeDtypeStruct((b, 1), i32),        # hops used
            jax.ShapeDtypeStruct((b, max_hops), i32), # frontier trace ids
            jax.ShapeDtypeStruct((b, max_hops), f32), # frontier trace dists
            jax.ShapeDtypeStruct((b, 1), i32),        # next frontier pick
            jax.ShapeDtypeStruct((b, 1), i32))        # done mask


def _out_specs(tile_b, l, max_hops):
    # `*_`: a guarded kernel's index maps also get the live-row count
    return tuple(pl.BlockSpec((tile_b, w), lambda i, *_: (i, 0))
                 for w in (l, l, l, 1, max_hops, max_hops, 1, 1))


def _live_tile(i, n_live_ref, tile_b: int):
    """Grid step `i` clamped to the last tile that holds a live row: a
    skipped step asks for the block the pipeline already holds, so it
    issues no input DMA."""
    last = jax.lax.div(jnp.maximum(n_live_ref[0] - 1, 0), tile_b)
    return jnp.minimum(i, last)


def _write_empty(outs) -> None:
    """The eight outputs of rows whose pool has no frontier: empty pool
    (-1, +inf, unexpanded), no hop, an empty trace, no next pick, done."""
    for ref, fill in zip(outs, (-1, jnp.inf, 0, 0, -1, jnp.inf, -1, 1)):
        ref[...] = jnp.full(ref.shape, fill, ref.dtype)


def _guarded(kernel, tile_b: int):
    """`kernel(*refs)` behind the live-row guard: the scalar-prefetch
    count of live rows comes first, a grid step whose rows are all
    padding (rows >= n_live) runs no hop loop and writes the empty result
    of each row instead (`_write_empty`; the outputs follow the six
    inputs)."""
    def run(n_live_ref, *refs):
        live = pl.program_id(0) * tile_b < n_live_ref[0]
        pl.when(live)(lambda: kernel(*refs))
        pl.when(jnp.logical_not(live))(lambda: _write_empty(refs[6:14]))
    return run


def _live_rows(n_live, b: int):
    """The live-row operand: `n_live` (None = all b rows) clipped to
    [0, b], as int32 (1,)."""
    n = b if n_live is None else n_live
    return jnp.clip(jnp.asarray(n, jnp.int32), 0, b).reshape(1)


def _tile_specs(tile_b: int, l: int, tables_shape):
    """Block specs of the per-tile inputs (tables, pool ids, dists,
    expanded), clamped to the last live tile (`_live_tile`)."""
    def at(nd):
        return lambda i, n_live_ref: (_live_tile(i, n_live_ref, tile_b),
                                      ) + (0,) * (nd - 1)
    return [pl.BlockSpec((tile_b,) + tuple(tables_shape[1:]), at(3)),
            pl.BlockSpec((tile_b, l), at(2)),
            pl.BlockSpec((tile_b, l), at(2)),
            pl.BlockSpec((tile_b, l), at(2))]


def _guarded_call(kernel, corpus_specs, scratch_shapes, *, b: int, l: int,
                  max_hops: int, tile_b: int, tables_shape, interpret: bool):
    """The `pallas_call` of an ADC hop-loop kernel behind the live-row
    guard (`_guarded`): one scalar-prefetch operand (`_live_rows`) ahead
    of the two corpus operands, the tables and the pool triplet."""
    return pl.pallas_call(
        _guarded(kernel, tile_b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b // tile_b,),
            in_specs=corpus_specs + _tile_specs(tile_b, l, tables_shape),
            out_specs=_out_specs(tile_b, l, max_hops),
            scratch_shapes=scratch_shapes),
        out_shape=_out_shapes(b, l, max_hops),
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_hops", "tile_b", "n_chunk",
                                             "interpret"))
def beam_hops_adc_pallas(adj, codes, tables, pool_ids, pool_d, pool_exp,
                         max_hops: int, tile_b: int = 8, n_chunk: int = 2048,
                         interpret: bool = False, n_live=None):
    """adj (N, R) f32, codes (N, M) f32, tables (B, M, K) f32, seeded pool
    (B, L) f32 triplet.  B % tile_b == 0 and N % n_chunk == 0 (ops pads).
    `n_live` (traced int32; None = all B) is the count of live rows: a
    grid step of rows >= n_live only writes their empty result
    (`_guarded`).  Returns the 8-tuple of `_out_shapes` (hops/next/done
    as (B, 1))."""
    b, l = pool_ids.shape
    n = adj.shape[0]
    _check_tiling(b, tile_b, n, n_chunk)
    full = lambda shape: pl.BlockSpec(shape,
                                      lambda *_: tuple(0 for _ in shape))
    return _guarded_call(
        functools.partial(_beam_adc_kernel, max_hops=max_hops, n=n,
                          n_chunk=n_chunk),
        [full(adj.shape), full(codes.shape)],
        [subspace_scratch(tile_b * adj.shape[1])],
        b=b, l=l, max_hops=max_hops, tile_b=tile_b,
        tables_shape=tables.shape, interpret=interpret,
    )(_live_rows(n_live, b), adj, codes, tables, pool_ids, pool_d, pool_exp)


@functools.partial(jax.jit, static_argnames=("max_hops", "tile_b", "n_chunk",
                                             "interpret"))
def beam_hops_l2_pallas(adj, xn, queries, pool_ids, pool_d, pool_exp,
                        max_hops: int, tile_b: int = 8, n_chunk: int = 2048,
                        interpret: bool = False):
    """adj (N, R) f32, xn (N, D+1) f32 with squared norms in the last
    column, queries (B, D) f32, seeded pool (B, L) f32 triplet.  Same
    contract as `beam_hops_adc_pallas` with exact-L2 scoring."""
    b, l = pool_ids.shape
    n = adj.shape[0]
    _check_tiling(b, tile_b, n, n_chunk)
    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))
    return pl.pallas_call(
        functools.partial(_beam_l2_kernel, max_hops=max_hops, n=n,
                          n_chunk=n_chunk),
        grid=(b // tile_b,),
        in_specs=[
            full(adj.shape),
            full(xn.shape),
            pl.BlockSpec((tile_b, queries.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
        ],
        out_specs=_out_specs(tile_b, l, max_hops),
        out_shape=_out_shapes(b, l, max_hops),
        interpret=interpret,
    )(adj, xn, queries, pool_ids, pool_d, pool_exp)


def _row_view(a):
    """An (N, C) HBM operand as (N*P, 128) rows, its columns zero-padded to
    P whole 128-lane vregs: Mosaic DMAs only 128-lane rows out of HBM, and
    a single-row slice only of a 128-lane-wide array."""
    pad = _lanes(a.shape[1]) - a.shape[1]
    a = a if pad == 0 else jnp.pad(a, ((0, 0), (0, pad)))
    return a.reshape(-1, _LANES)


def _row_gather_scratch(t: int, k: int, width: int):
    """Scratch of one `_gather_rows_dma` round of t*k ids: the row buffer
    (t*k lane-padded corpus rows, as 128-lane rows), the ids as int32 in
    VMEM and in SMEM, and the DMA semaphore every copy of the round
    signals."""
    return [pltpu.VMEM((t * k * _lanes(width) // _LANES, _LANES),
                       jnp.float32),
            pltpu.VMEM((t, k), jnp.int32),
            pltpu.SMEM((t, k), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


def _stream_scratch(tile_b: int, r: int, row_w: int):
    """The streamed kernels' scratch: one row-gather set for the TB
    frontier adjacency rows and one for the TB*R codes/vector rows."""
    return (_row_gather_scratch(tile_b, 1, r)
            + _row_gather_scratch(tile_b, r, row_w))


@functools.partial(jax.jit, static_argnames=("max_hops", "tile_b", "n_chunk",
                                             "interpret"))
def beam_hops_adc_stream(adj, codes, tables, pool_ids, pool_d, pool_exp,
                         max_hops: int, tile_b: int = 8, n_chunk: int = 2048,
                         interpret: bool = False, n_live=None):
    """`beam_hops_adc_pallas` with adj/codes streamed from HBM: the corpus
    operands get `memory_space=ANY` block specs (never staged into VMEM by
    the pipeline) and each hop DMA-copies the TB frontier adjacency rows,
    then the TB*R neighbor code rows, into VMEM row buffers.  Bit-
    identical outputs to the resident kernel at every config, the
    `n_live` guard included; a skipped grid step also moves no tables
    block (`_tile_specs`).  VMEM footprint is `stream_vmem_bytes` --
    independent of N, so shards far larger than VMEM serve from one grid
    step.  `n_chunk` is only the row multiple N must have
    (`ops.beam_hops` pads to it); the gather does not depend on it."""
    b, l = pool_ids.shape
    n = adj.shape[0]
    _check_tiling(b, tile_b, n, n_chunk)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return _guarded_call(
        functools.partial(_beam_adc_stream_kernel, max_hops=max_hops, n=n,
                          r=adj.shape[1]),
        [any_spec, any_spec],
        (_stream_scratch(tile_b, adj.shape[1], min(codes.shape[1], _LANES))
         + [subspace_scratch(tile_b * adj.shape[1])]),
        b=b, l=l, max_hops=max_hops, tile_b=tile_b,
        tables_shape=tables.shape, interpret=interpret,
    )(_live_rows(n_live, b), _row_view(adj), _row_view(codes), tables,
      pool_ids, pool_d, pool_exp)


@functools.partial(jax.jit, static_argnames=("max_hops", "tile_b", "n_chunk",
                                             "interpret"))
def beam_hops_l2_stream(adj, xn, queries, pool_ids, pool_d, pool_exp,
                        max_hops: int, tile_b: int = 8, n_chunk: int = 2048,
                        interpret: bool = False):
    """`beam_hops_l2_pallas` with adj/vectors streamed from HBM by row
    DMAs, as `beam_hops_adc_stream`; same contract and bit-identical
    outputs, `stream_vmem_bytes` footprint."""
    b, l = pool_ids.shape
    n = adj.shape[0]
    _check_tiling(b, tile_b, n, n_chunk)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_beam_l2_stream_kernel, max_hops=max_hops, n=n,
                          r=adj.shape[1]),
        grid=(b // tile_b,),
        in_specs=[
            any_spec,
            any_spec,
            pl.BlockSpec((tile_b, queries.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, l), lambda i: (i, 0)),
        ],
        out_specs=_out_specs(tile_b, l, max_hops),
        out_shape=_out_shapes(b, l, max_hops),
        scratch_shapes=_stream_scratch(tile_b, adj.shape[1], xn.shape[1]),
        interpret=interpret,
    )(_row_view(adj), _row_view(xn), queries, pool_ids, pool_d, pool_exp)
