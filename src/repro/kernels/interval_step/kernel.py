"""Pallas TPU kernels: fused per-interval fast path of the scan engine.

Four kernels over lane-batched page rows, each fusing a stage of
scan_engine's interval body that the unfused path spreads over many
small XLA ops:

  * ``topk_mask_kernel``    — exact top-k mask by threshold bisection over
    the order key (32 count-passes) and, in the one block that holds the
    boundary, an index bisection for the tie-break (no ``lax.top_k``
    partial sort, no scatter, no cumsum — the tie rule still matches
    ``lax.top_k`` exactly: strictly-greater first, ascending index among
    threshold-equal values);
  * ``tier_migrate_kernel`` — the adjacent-pair hop-chain migration engine
    as a per-lane sequential sweep over the padded plans with per-tier
    occupancy counters (equivalent to the vectorized simjax form for
    plans whose valid page indices are unique — the padded-index
    contract);
  * ``interval_account_kernel`` — per-tier access split, interval cost and
    oracle recall in ONE pass over the [n] row;
  * ``ewma_update_kernel``  — the lane-batched dual-EWMA + score update
    (kernels/score_update generalized to [B, n] with per-lane weights).

Layout (the Mosaic rules every block obeys at any B and n):

  * a lane's [n] page row is padded to whole 128-wide rows and walked in
    page blocks of ``(rows, 128)`` (``_blocks``): one block of all its
    rows while they fit ``BLOCK_ROWS``, so the block's last two dims
    equal the array's, else the fewest blocks of at most ``BLOCK_ROWS``
    rows, whole 8-row tiles each, streamed from HBM.  A row of one block
    is the many-block case with one block: the grid is ``(B, blocks)``
    (top-k: ``(B, sweeps, blocks)``) either way;
  * what a kernel carries across a row's blocks lives in scratch: the
    accounting's partial sums in VMEM, the top-k bisection's threshold
    and counts in SMEM; outputs are written at the block that completes
    them;
  * per-lane scalars (machine rows, caps, EWMA weights) and scalar
    outputs live in SMEM as one ``(1, w)`` row per lane — VMEM takes no
    scalar stores and no dynamically indexed scalar reads;
  * the migration kernel's page-indexed state (tier row, plans) is
    copied HBM -> SMEM per lane, updated there by scalar loads/stores, and
    copied back; its SMEM footprint bounds the shapes it takes
    (``tier_migrate_fits``).

All four run compiled on TPU and in interpret mode elsewhere; their
contracts are the references in ref.py (tests/test_interval_step:
integer outputs bitwise, f32 outputs to the last ulps — a kernel's f32
row reductions may associate differently from XLA's).  The ops layer
only selects these kernels on TPU, where every path goes through them
consistently.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.simulator.machine import CACHELINE, PAGE_BYTES

LANE = 128          # f32 / i32 minor-dim tile
#: SMEM words the migration kernel may fill with its per-lane state.  A
#: v5e core has 1 MiB = 2**18 words of SMEM; the rest is left to the
#: compiler.  tests/test_tpu_compile.py compiles the kernel for v5e at
#: exactly this footprint; other TPU generations are not checked.
SMEM_WORDS = 3 << 16
#: Page rows (of LANE pages) of a lane's row that the top-k, accounting
#: and EWMA kernels hold in one VMEM block; a longer row is walked in
#: blocks of at most this many.  The widest, EWMA, double-buffers six
#: such f32 blocks: 6 MiB, inside v5e's default scoped VMEM (16 MiB) at
#: any n (tests/test_tpu_compile.py compiles all three at 2**21 pages).
#: 1024 rows keep the 65536-page rows of the benchmark's cells in one.
BLOCK_ROWS = 1024


def _padded(n: int) -> int:
    return max(LANE, -(-n // LANE) * LANE)


def _blocks(n: int) -> tuple[int, int]:
    """(blocks, rows per block) a lane's [n] page row is walked in: all
    its rows in one block while they fit ``BLOCK_ROWS``, else the fewest
    blocks of at most ``BLOCK_ROWS`` rows, whole 8-row tiles each."""
    rows = _padded(n) // LANE
    if rows <= BLOCK_ROWS:
        return 1, rows
    nb = -(-rows // BLOCK_ROWS)
    return nb, -(-rows // (8 * nb)) * 8


def _tiles(x, fill, rows: int):
    """[B, n] -> [B, rows, LANE] (padding filled with ``fill``)."""
    B, n = x.shape
    x = jnp.pad(x, ((0, 0), (0, rows * LANE - n)), constant_values=fill)
    return x.reshape(B, rows, LANE)


def _untile(x, n: int):
    return x.reshape(x.shape[0], -1)[:, :n]


#: open ``count_blocks`` tallies (nesting is fine: each sees every trace)
_block_counters: list = []


@contextlib.contextmanager
def count_blocks():
    """Page blocks per row that each blocked kernel is traced with inside
    the region, in trace order:

        with kernel.count_blocks() as blocks:
            experiment.sweep(...)
        assert blocks["topk_mask_kernel"] == [16]

    A call that hits JAX's caches traces nothing and records nothing."""
    rec: dict = {}
    _block_counters.append(rec)
    try:
        yield rec
    finally:
        _block_counters.remove(rec)


def _record_blocks(kernel: str, n: int) -> tuple[int, int]:
    nb, br = _blocks(n)
    for rec in _block_counters:
        rec.setdefault(kernel, []).append(nb)
    return nb, br


def _row_block(br: int):
    """Block ``j`` of lane ``b``'s row, on a ``(B, blocks)`` grid."""
    return pl.BlockSpec((None, br, LANE), lambda b, j: (b, j, 0))


def _lane_block(x):
    """One lane's block of a lane-batched array (leading axis squeezed)."""
    rest = x.shape[1:]
    return pl.BlockSpec((None, *rest), lambda b: (b,) + (0,) * len(rest))


def _lane_smem(w: int):
    """One lane's ``(1, w)`` row of a ``[B, 1, w]`` per-lane scalar array,
    in SMEM (on a grid whose first axis is the lane)."""
    return pl.BlockSpec((None, 1, w), lambda b, *_: (b, 0, 0),
                        memory_space=pltpu.SMEM)


def _page_iota(shape):
    """Page index of every element of a ``(rows, LANE)`` row block."""
    i32 = jnp.int32
    return (jax.lax.broadcasted_iota(i32, shape, 0) * LANE
            + jax.lax.broadcasted_iota(i32, shape, 1))


# ------------------------------------------------------------ top-k mask
_MIN = -(1 << 31)                   # i32 sign bit
_T, _CNT, _GE, _AFTER, _M = range(5)  # the bisection's SMEM words


def _topk_body(n: int, k: int, nb: int, br: int, sweeps: int, x_ref,
               out_ref, st_ref):
    """Grid step (lane, sweep, block).  Sweeps ``0 .. sweeps-1`` bisect the
    k-th largest order key t, ``32 // sweeps`` bits each: a bit is decided
    once its count covers the whole row, so a row of several blocks takes
    one bit a sweep and a row of one block all 32 in its one sweep.  The
    last sweep walks the blocks from the end and writes the mask: keys
    above t, and of the keys equal to t all but the ``count(key >= t) - k``
    of highest index (``lax.top_k``'s tie rule)."""
    i32 = jnp.int32
    s, j = pl.program_id(1), pl.program_id(2)
    blk = jnp.where(s == sweeps, nb - 1 - j, j)
    x = x_ref[...]                                        # (br, LANE) f32
    local = _page_iota(x.shape)
    valid = local + blk * (br * LANE) < n
    # i32 order key: ref._order_key with the sign bit flipped, so that a
    # signed compare ranks like ref's unsigned one (sign BIT, not x < 0:
    # +0.0 ranks strictly above -0.0 exactly like lax.top_k); pads take
    # the least key and never win.
    u = jax.lax.bitcast_convert_type(x, i32)
    key = jnp.where(valid, u ^ ((u >> 31) & ~_MIN), _MIN)

    @pl.when((s == 0) & (j == 0))
    def _():
        st_ref[_T] = 0                   # t's unsigned bit pattern
        st_ref[_CNT] = 0                 # count of this bit so far
        st_ref[_GE] = n                  # count(key >= t)
        st_ref[_AFTER] = 0               # ties in the blocks written

    @pl.when(s < sweeps)
    def _():
        last = j == nb - 1
        bits = 32 // sweeps

        def val_bit(i, carry):
            cand = st_ref[_T] | jnp.left_shift(i32(1), 31 - (s * bits + i))
            cnt = st_ref[_CNT] + jnp.sum((key >= (cand ^ _MIN)).astype(i32))
            take = last & (cnt >= k)
            st_ref[_T] = jnp.where(take, cand, st_ref[_T])
            st_ref[_GE] = jnp.where(take, cnt, st_ref[_GE])
            st_ref[_CNT] = jnp.where(last, 0, cnt)
            return carry

        jax.lax.fori_loop(0, bits, val_bit, 0)

    @pl.when(s == sweeps)
    def _():
        thr = st_ref[_T] ^ _MIN
        eq = (key == thr) & valid
        ties = jnp.sum(eq.astype(i32))
        # this block keeps its first ``need`` ties; the ones dropped are
        # the row's last, counted from its end
        drop = jnp.clip(st_ref[_GE] - k - st_ref[_AFTER], 0, ties)
        need = ties - drop
        st_ref[_AFTER] = st_ref[_AFTER] + ties
        st_ref[_M] = jnp.where(drop == 0, br * LANE, -1)

        idx_bits = (br * LANE - 1).bit_length()

        @pl.when((need > 0) & (drop > 0))
        def _():
            # largest m with count(eq & local < m) < need; the ties kept
            # are then local <= m
            def idx_bit(i, m):
                cand = m + jnp.left_shift(i32(1), idx_bits - 1 - i)
                cnt = jnp.sum((eq & (local < cand)).astype(i32))
                return jnp.where(cnt < need, cand, m)

            st_ref[_M] = jax.lax.fori_loop(0, idx_bits, idx_bit, i32(0))

        out_ref[...] = ((key > thr) | (eq & (local <= st_ref[_M]))
                        ).astype(i32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_mask_kernel(x, k: int, *, interpret: bool = True):
    B, n = x.shape
    nb, br = _record_blocks("topk_mask_kernel", n)
    sweeps = 1 if nb == 1 else 32
    xt = _tiles(jnp.asarray(x, jnp.float32), 0.0, nb * br)

    def at(b, s, j):
        # value sweeps read blocks in order; the mask sweep from the end.
        # The output block stays at the last until the mask sweep writes
        # it, so nothing unwritten goes back to HBM.
        return b, jnp.where(s == sweeps, nb - 1 - j, j), 0

    def out_at(b, s, j):
        return b, jnp.where(s == sweeps, nb - 1 - j, nb - 1), 0

    out = pl.pallas_call(
        functools.partial(_topk_body, n, k, nb, br, sweeps),
        grid=(B, sweeps + 1, nb),
        in_specs=[pl.BlockSpec((None, br, LANE), at)],
        out_specs=pl.BlockSpec((None, br, LANE), out_at),
        out_shape=jax.ShapeDtypeStruct(xt.shape, jnp.int32),
        scratch_shapes=[pltpu.SMEM((5,), jnp.int32)],
        interpret=interpret,
    )(xt)
    return _untile(out, n) != 0


# ------------------------------------------------------- tier migrations
def tier_migrate_fits(n: int, P: int, D: int) -> bool:
    """Does one lane's migration state (tier row + both plans) fit the
    kernel's SMEM budget?"""
    return _padded(n) + _padded(max(P, 1)) + _padded(max(D, 1)) \
        <= SMEM_WORDS


def _migrate_body(R: int, caps_ref, rows_ref, tier_hbm, promote_hbm,
                  demote_hbm, tier_out, pexec_out, dexec_out, mig_ref,
                  tier_s, promote_s, demote_s):
    i32 = jnp.int32
    b = pl.program_id(0)
    pltpu.sync_copy(tier_hbm.at[b], tier_s)
    pltpu.sync_copy(promote_hbm.at[b], promote_s)
    pltpu.sync_copy(demote_hbm.at[b], demote_s)
    rows = rows_ref[...]                  # (rows, LANE) i32; pads hold R
    P, D = promote_s.shape[1], demote_s.shape[1]

    def occupancy(r):
        return jnp.sum((rows == r).astype(i32))

    # pass 1: per-tier departure counts.  Sources are read from the
    # ORIGINAL placement, as the vectorized form gathers them up front.
    def dep_step(i, dep):
        d = demote_s[0, i]
        src = tier_s[0, jnp.maximum(d, 0)]
        dx = (d >= 0) & (src < R - 1)
        return tuple(dep[r] + (dx & (src == r)).astype(i32)
                     for r in range(R))

    dep = jax.lax.fori_loop(0, D, dep_step, (i32(0),) * R)

    # per-middle-tier slack once departures free their slots (the same
    # "occupancy after ALL departures" the vectorized form ranks against).
    slack = {r: caps_ref[0, r] - (occupancy(r) - dep[r])
             for r in range(1, R - 1)}

    # pass 2: land each demotion at the first middle tier below its
    # source with room left (entry order within a tier matches the cumsum
    # rank) and apply it in place.  Valid entries are unique pages, so an
    # entry's source is still the original placement when it is read.
    def down_step(i, carry):
        land, mig_down = carry
        d = demote_s[0, i]
        idx = jnp.maximum(d, 0)
        src = tier_s[0, idx]
        dx = (d >= 0) & (src < R - 1)
        dest = i32(R - 1)
        for r in range(R - 2, 0, -1):          # try lowest r > src first
            room = (slack[r] - land[r]) > 0
            dest = jnp.where((src < r) & room, i32(r), dest)
        tier_s[0, idx] = jnp.where(dx, dest, src)
        demote_s[0, i] = dx.astype(i32)        # plan slot -> executed flag
        land = {r: land[r] + (dx & (dest == r)).astype(i32) for r in land}
        mig_down = tuple(
            mig_down[j] + (dx & (src <= j) & (dest > j)).astype(i32)
            for j in range(R - 1))
        return land, mig_down

    _, mig_down = jax.lax.fori_loop(
        0, D, down_step,
        ({r: i32(0) for r in range(1, R - 1)}, (i32(0),) * (R - 1)))

    # pass 3: promotions to tier 0, capped by room after demotions (every
    # executed demotion leaves its source, so tier 0 lost exactly dep[0]
    # pages); the rank counts every valid request, not only executed
    # ones, matching the vectorized cumsum rule.  Sources are read
    # post-demotion.
    room0 = caps_ref[0, 0] - (occupancy(0) - dep[0])

    def up_step(i, carry):
        cnt, mig_up = carry
        p = promote_s[0, i]
        idx = jnp.maximum(p, 0)
        src = tier_s[0, idx]
        ok = (p >= 0) & (src > 0)
        ex = ok & (cnt < room0)
        tier_s[0, idx] = jnp.where(ex, i32(0), src)
        promote_s[0, i] = ex.astype(i32)
        mig_up = tuple(mig_up[j] + (ex & (src > j)).astype(i32)
                       for j in range(R - 1))
        return cnt + ok.astype(i32), mig_up

    _, mig_up = jax.lax.fori_loop(0, P, up_step,
                                  (i32(0), (i32(0),) * (R - 1)))
    for j in range(R - 1):
        mig_ref[0, j] = mig_up[j]
        mig_ref[0, R - 1 + j] = mig_down[j]
    pltpu.sync_copy(tier_s, tier_out.at[b])
    pltpu.sync_copy(promote_s, pexec_out.at[b])
    pltpu.sync_copy(demote_s, dexec_out.at[b])


@functools.partial(jax.jit, static_argnames=("interpret",))
def tier_migrate_kernel(tier, promote, demote, caps, *,
                        interpret: bool = True):
    B, n = tier.shape
    R = caps.shape[1]
    P, D = promote.shape[1], demote.shape[1]
    if not tier_migrate_fits(n, P, D):
        raise ValueError(f"tier_migrate_kernel: n={n}, P={P}, D={D} "
                         "exceed the SMEM budget (tier_migrate_fits)")

    def smem_row(x, w, fill):
        # [B, w] -> [B, 1, w_pad]: whole 128-wide rows, so the per-lane
        # HBM <-> SMEM copies are tile-aligned; zero-width plans become
        # one row of always-invalid entries.
        x = jnp.pad(x, ((0, 0), (0, _padded(w) - w)), constant_values=fill)
        return x[:, None, :]

    tier_hbm = smem_row(tier, n, R)          # pad tier R: matches no r
    promote_hbm = smem_row(promote, P, -1)
    demote_hbm = smem_row(demote, D, -1)
    rows = tier_hbm.reshape(B, -1, LANE)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.int32)  # noqa: E731
    new_tier, pexec, dexec, mig = pl.pallas_call(
        functools.partial(_migrate_body, R),
        grid=(B,),
        in_specs=[_lane_smem(R), _lane_block(rows), hbm, hbm, hbm],
        out_specs=[hbm, hbm, hbm, _lane_smem(2 * (R - 1))],
        out_shape=[shape(tier_hbm), shape(promote_hbm), shape(demote_hbm),
                   jax.ShapeDtypeStruct((B, 1, 2 * (R - 1)), jnp.int32)],
        scratch_shapes=[pltpu.SMEM(tier_hbm.shape[1:], jnp.int32),
                        pltpu.SMEM(promote_hbm.shape[1:], jnp.int32),
                        pltpu.SMEM(demote_hbm.shape[1:], jnp.int32)],
        interpret=interpret,
    )(caps.astype(jnp.int32)[:, None, :], rows, tier_hbm, promote_hbm,
      demote_hbm)
    return (new_tier[:, 0, :n], pexec[:, 0, :P] != 0,
            dexec[:, 0, :D] != 0, mig[:, 0, :R - 1], mig[:, 0, R - 1:])


# --------------------------------------------------- interval accounting
def _account_body(R: int, k: int, prm_ref, true_ref, tier_ref, orc_ref,
                  out_ref, acc_ref, hits_ref):
    # prm row: lat[0:R] br[R:2R] bw[2R:3R] up[3R:4R-1] down[4R-1:5R-2] mlp
    j = pl.program_id(1)

    def total(x):                                    # -> (1, 1)
        return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1,
                       keepdims=True)

    # this block's partial sums: accesses on tiers 0 .. R-2, all accesses
    # (acc_ref[R-1]) and oracle hits; the first block sets the row's sums,
    # each later one adds to them
    true = true_ref[...]                             # (rows, LANE) f32
    tier = tier_ref[...]
    parts = [total(jnp.where(tier == r, true, 0.0)) for r in range(R - 1)]
    parts.append(total(true))
    hits = total(((tier == 0) & (orc_ref[...] != 0)).astype(jnp.int32))
    lane_row = (1, LANE)

    @pl.when(j == 0)
    def _():
        for r in range(R):
            acc_ref[r] = jnp.broadcast_to(parts[r], lane_row)
        hits_ref[...] = jnp.broadcast_to(hits, lane_row)

    @pl.when(j > 0)
    def _():
        for r in range(R):
            acc_ref[r] = acc_ref[r] + parts[r]
        hits_ref[...] = hits_ref[...] + hits

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _account_out(R, k, prm_ref, acc_ref, hits_ref, out_ref)


def _account_out(R: int, k: int, prm_ref, acc_ref, hits_ref, out_ref):
    """The lane's six outputs from its whole row's sums."""
    prm = [prm_ref[0, i] for i in range(5 * R - 1)]
    lat, br, bw = prm[:R], prm[R:2 * R], prm[2 * R:3 * R]
    up, down = prm[3 * R:4 * R - 1], prm[4 * R - 1:5 * R - 2]
    mlp = prm[5 * R - 2]

    accs, rest = [], acc_ref[R - 1]
    for r in range(R - 1):
        a = acc_ref[r]
        accs.append(a)
        rest = rest - a
    accs.append(rest)

    t_lat = accs[0] * lat[0]
    for r in range(1, R):
        t_lat = t_lat + accs[r] * lat[r]
    t_lat = t_lat * 1e-9 / mlp

    times = [(accs[0] * CACHELINE + (up[0] + down[0]) * PAGE_BYTES)
             / br[0]]
    for r in range(1, R):
        rd = up[r - 1]
        if r < R - 1:
            rd = rd + down[r]
        wr = down[r - 1]
        if r < R - 1:
            wr = wr + up[r]
        times.append((accs[r] * CACHELINE + rd * PAGE_BYTES) / br[r]
                     + wr * PAGE_BYTES / bw[r])

    rest_max = times[1]
    for r in range(2, R):
        rest_max = jnp.maximum(rest_max, times[r])
    wall = jnp.maximum(jnp.maximum(t_lat, times[0]),
                       jnp.maximum(rest_max, 1e-12))

    rest_acc = accs[1]
    for r in range(2, R):
        rest_acc = rest_acc + accs[r]
    slow_share = rest_acc / jnp.maximum(accs[0] + rest_acc, 1e-9)
    app_raw = times[0] / jnp.maximum(t_lat, jnp.maximum(rest_max, 1e-12))
    recall = hits_ref[...].astype(jnp.float32) / k

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    row = jnp.zeros((1, LANE), jnp.float32)
    for j, v in enumerate((accs[0], rest_acc, wall, slow_share, app_raw,
                           recall)):
        row = jnp.where(lane == j, v, row)
    out_ref[...] = row


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def interval_account_kernel(lat, br, bw, mlp, true, tier, mig_up, mig_down,
                            oracle, k: int, *, interpret: bool = True):
    """Fused per-lane accounting: lat/br/bw [B, R] f32, mlp [B] f32,
    true [B, n] f32, tier [B, n] i32, mig_up/mig_down [B, R-1] f32,
    oracle [B, n] bool.  Returns the six [B] f32 outputs of
    ``ref.interval_account_ref``."""
    B, n = true.shape
    R = lat.shape[1]
    f32 = jnp.float32
    nb, rows = _record_blocks("interval_account_kernel", n)
    prm = jnp.concatenate([lat, br, bw, mig_up, mig_down, mlp[:, None]],
                          axis=1).astype(f32)[:, None, :]
    pages = [_tiles(true.astype(f32), 0.0, nb * rows),
             _tiles(tier, R, nb * rows),
             _tiles(oracle.astype(jnp.int32), 0, nb * rows)]
    out = pl.pallas_call(
        functools.partial(_account_body, R, k),
        grid=(B, nb),
        in_specs=[_lane_smem(prm.shape[2])] + [_row_block(rows)] * 3,
        out_specs=pl.BlockSpec((None, 1, LANE), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, LANE), f32),
        scratch_shapes=[pltpu.VMEM((R, 1, LANE), f32),
                        pltpu.VMEM((1, LANE), jnp.int32)],
        interpret=interpret,
    )(prm, *pages)
    return tuple(out[:, 0, i] for i in range(6))


# -------------------------------------------------------- EWMA + score
def _ewma_body(p_ref, s_ref, l_ref, c_ref, s_out, l_out, score_out):
    a_s, a_l = p_ref[0, 0], p_ref[0, 1]
    w_s, w_l = p_ref[0, 2], p_ref[0, 3]
    c = c_ref[...]
    s = a_s * c + (1 - a_s) * s_ref[...]
    ll = a_l * c + (1 - a_l) * l_ref[...]
    s_out[...] = s
    l_out[...] = ll
    score_out[...] = w_s * s + w_l * ll


@functools.partial(jax.jit, static_argnames=("interpret",))
def ewma_update_kernel(ewma_s, ewma_l, counts, *, alpha_s, alpha_l, w_s,
                       w_l, interpret: bool = True):
    """Lane-batched dual-EWMA + score: arrays [B, n] f32; each smoothing /
    weight param a scalar or [B] (per-lane traced values — mode-dependent
    score weights ride the lane axis)."""
    B, n = ewma_s.shape
    nb, rows = _record_blocks("ewma_update_kernel", n)
    params = jnp.stack([jnp.broadcast_to(jnp.asarray(v, jnp.float32), (B,))
                        for v in (alpha_s, alpha_l, w_s, w_l)],
                       axis=1)[:, None, :]
    pages = [_tiles(x, 0.0, nb * rows) for x in (ewma_s, ewma_l, counts)]
    row = _row_block(rows)
    outs = pl.pallas_call(
        _ewma_body,
        grid=(B, nb),
        in_specs=[_lane_smem(4), row, row, row],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct(pages[0].shape, jnp.float32)
                   for _ in range(3)],
        interpret=interpret,
    )(params, *pages)
    return tuple(_untile(o, n) for o in outs)
