"""Fused Pallas CDC front end: Gear scan + in-kernel min/max cut selection.

One kernel pass over the resident block replaces the three-stage XLA front
end of ops/resident.py (``_prep``'s MXU BE word image + gear scan + bitmap
pack, the packed-candidate D2H, and the host ``native.cdc_select`` round
trip re-expressing DataDeduplicator.java:264-307).  The kernel fuses, per
(R, 128)-word supertile of the raw block:

1. **Gear map** — ``G[b] = fmix32(b * 0x9E3779B1)`` computed arithmetically
   per byte *phase* of the little-endian u32 word image (native/src/cdc.cpp
   pre-tabulates the same function; a 256-entry gather scalarizes on TPU,
   PERF_NOTES.md round 2).
2. **Window-32 hash** — the log-doubling recurrence of ops/gear.py
   (``A_{2m}[i] = A_m[i] + (A_m[i-m] << m)``, gear.py:66-79) decomposed by
   byte phase: a window-4 cross-phase combine, then three per-phase
   doublings whose byte lags (4, 8, 16) are exact word lags (1, 2, 4) —
   every shift is a ``pltpu.roll`` flat word shift, with the previous
   supertile's last row carried in VMEM scratch so tile boundaries are
   seamless.
3. **Candidate mask** — ``(h & mask) == 0`` at positions
   ``gear.MIN_CANDIDATE_POS1 <= pos1 <= true_n`` (the shared window-warmup
   convention, gear.py:85-104), reduced to per-word candidate nibbles and a
   per-row first-candidate summary.  The skip-ahead variant (default)
   additionally masks the static min-size dead zone up front:
   ``pos1 < gear.skip_ahead_threshold(min_chunk)`` can never be selected
   (every window opens at ``prev+min``), so those candidates never reach
   the summaries — the SIMD-chunking min-skip of arXiv:2508.05797 mapped
   onto the 8x128 lane grid.
4. **Cut selection** — the frontier semantics of ``hdrf_cdc_select``
   (native/src/cdc.cpp:74-92: ``lo = start+min``,
   ``hi = min(start+max, len)``, first candidate in [lo, hi] else ``hi``).
   PR 4's scan walked the summaries word-by-word per cut
   (O(candidate words) SMEM trips).  The sequence-based select (the
   arXiv:2505.21194 two-phase trick, default on) instead reduces each
   supertile's per-word first-candidate array to VECTORIZED suffix-min
   summaries — within-row (lane log-doubling rolls) and cross-row over
   the two-slab window — so the per-cut walk collapses to O(1): one
   nibble resolve in ``lo``'s own word, one within-row suffix read, one
   cross-row suffix read.  Frontier/counters still carry across
   supertiles in SMEM scratch; cuts land in an on-device table; each
   chunk is binned (by padded SHA block count) into one of two
   device-resident offset/length lane tables that feed
   ``_bucket_sha_best`` (ops/resident.py) with **no host round trip** —
   the SHA dispatch enqueues before the cut table is ever read back.
   ``FusedPlan.skip_ahead`` statically selects the variant, so the PR 4
   scan remains compilable as the A/B baseline
   (``benchmarks cdc --no-skip-ahead``).

The kernel additionally emits the big-endian word image (in-kernel byteswap
of the LE words — the separate ``be_word_image`` MXU pass of
ops/resident.py:89-103 disappears from the fused path) and a header
``[n_cuts, overflow, n_small, n_big]``: a block whose candidate density
exceeds the static cut capacity sets ``overflow`` and the caller falls back
to the XLA prep + host-select oracle path — boundaries are never silently
truncated (tests/test_cdc_pallas.py pins this with a low-entropy corpus).

The fused select kernel is OFF by default everywhere: Mosaic refuses it
(``cdc_pallas_mode``), so it runs only through the Pallas interpreter on the
CPU test mesh (``HDRF_CDC_PALLAS=interpret``) until it is repaired.  The
scan-only kernel below does lower and is the TPU default of the sharded
scan (``scan_pallas_mode``).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hdrf_tpu.ops import gear

WINDOW = gear.WINDOW
_GOLD = np.uint32(0x9E3779B1)
_INF = np.int32(0x7FFFFFFF)

# Header lanes at the front of the cut table readback.  H_SURV / H_CANDS
# are the sequence-select telemetry lanes (zero under the PR 4 scan):
# slab survivors = rows whose first-candidate summary is finite (the
# per-slab survivor list the two-phase select reduces the scan to),
# candidates = masked candidate population that survived the skip-ahead
# dead zone.
TABLE_HDR = 8
H_COUNT, H_OVERFLOW, H_SMALL, H_BIG = 0, 1, 2, 3
H_SURV, H_CANDS = 4, 5


def cdc_pallas_mode() -> str:
    """Gate of the fused select kernel: 'off' unless HDRF_CDC_PALLAS asks.

    Mosaic does not lower ``_select_kernel`` (compiled for v5e, PR 22:
    ``ValueError: Cannot store scalars to VMEM`` at the cut-table store;
    with the tables in SMEM, ``Can only store scalars to SMEM`` at their
    vector init; the select loop also scalar-reads VMEM summaries at
    dynamic indices).  So the TPU default front end is the XLA one
    (ops/resident.py: ``_prep`` scan + host cut select + Pallas gather +
    Pallas SHA) and the platform alone never selects this kernel.
    ``HDRF_CDC_PALLAS=1`` asks for it: through Mosaic on a TPU backend
    (for whoever repairs the kernel), through the Pallas interpreter
    elsewhere.  ``=interpret`` is the tier-1 setting that executes the
    kernel program on the CPU mesh; on a chip it is refused — nothing runs
    interpreted there."""
    env = os.environ.get("HDRF_CDC_PALLAS", "")
    if env in ("", "0"):
        return "off"
    on_tpu = jax.default_backend() == "tpu"
    if env == "interpret":
        if on_tpu:
            raise RuntimeError("HDRF_CDC_PALLAS=interpret on a TPU backend: "
                               "no kernel runs interpreted on a chip")
        return "interpret"
    return "mosaic" if on_tpu else "interpret"


def scan_pallas_mode() -> str:
    """Gate of the scan-only kernel (``_scan_call``, the per-shard candidate
    scan of parallel/sharded.py), which Mosaic does lower: 'mosaic' on a
    TPU backend, otherwise what HDRF_CDC_PALLAS asks of the interpreter
    ('off' by default on the CPU mesh)."""
    if jax.default_backend() == "tpu":
        return "mosaic"
    return cdc_pallas_mode()


def cdc_skip_ahead() -> bool:
    """Static gate for the skip-ahead + sequence-select scan variant
    (ISSUE 15 tentpole; arXiv:2505.21194's two-phase select).  Default on;
    ``HDRF_CDC_SKIP_AHEAD=0`` pins the PR 4 sequential frontier scan — the
    A/B baseline ``benchmarks cdc`` sweeps.  Like ``cdc_pallas_mode`` it is
    resolved once per reducer construction (ops/resident.py:224) so a
    mid-process flip selects a different cached reducer instead of
    mutating one."""
    return os.environ.get("HDRF_CDC_SKIP_AHEAD", "1") != "0"


# --------------------------------------------------------------------------
# Static per-block plan (jit/pallas cache key material)
# --------------------------------------------------------------------------

def _r128(n: int) -> int:
    return max(128, -(-n // 128) * 128)


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """Static shape plan of one fused-CDC block invocation."""
    true_n: int      # unpadded byte length
    n_pad: int       # bytes padded to the supertile grid
    R: int           # supertile rows (x128 u32 words = R*512 bytes)
    T: int           # supertiles
    cap: int         # cut-table capacity (header-counted overflow past it)
    Ls: int          # small-bucket lane capacity (128-grid)
    Lb: int          # big-bucket lane capacity (128-grid)
    b_small: int     # small bucket width, 64-byte SHA blocks
    b_big: int       # big bucket width (max_chunk rounded), SHA blocks
    mask: int
    min_chunk: int
    max_chunk: int
    skip_ahead: bool = True   # sequence-select scan (False = PR 4 scan)


def plan_for(true_n: int, mask: int, mask_bits: int, min_chunk: int,
             max_chunk: int, b_small: int, b_big: int,
             skip_ahead: bool | None = None) -> FusedPlan:
    """Shape plan: supertile >= max_chunk so a chunk search window spans at
    most two tiles (the revisited two-slab scratch); cut capacity =
    min(hard bound n/min_chunk, ~2x the expected chunk count) — the
    distributional cap is what a pathological low-entropy block overflows
    into the XLA fallback.

    Under ``skip_ahead`` the distributional cap accounts for the min-size
    dead zone (the ISSUE 15 overflow-header fix): cuts renew at least
    ``min_chunk`` apart before the geometric candidate wait, so the
    expected count follows the renewal spacing ``min_chunk + 2^mask_bits``
    rather than the raw candidate density — never LOOSER than the PR 4
    cap, so every corpus that overflowed into the XLA fallback before
    (zeros at any controller-emitted geometry included) still does
    (regression-pinned at the controller's smallest min-size in
    tests/test_cdc_pallas.py)."""
    if skip_ahead is None:
        skip_ahead = cdc_skip_ahead()
    min_chunk = max(1, min_chunk)
    R = -(-max(65536, max_chunk) // 512)
    R = -(-R // 8) * 8
    B = R * 512
    n_pad = true_n + (-true_n) % B
    hard = true_n // min_chunk + 2
    if skip_ahead:
        spacing = min_chunk + (1 << min(max(mask_bits, 0), 30))
        distr = 2 * (true_n // spacing) + 1024
    else:
        distr = 2 * (true_n >> max(mask_bits, 0)) + 1024
    cap = max(2, min(hard, distr))
    bs = max(1, min(b_small, b_big))
    big_min_len = max(bs * 64 - 72, 1)
    Lb = _r128(min(cap, true_n // big_min_len + 1))
    return FusedPlan(true_n=true_n, n_pad=n_pad, R=R, T=n_pad // B,
                     cap=cap, Ls=_r128(cap), Lb=Lb, b_small=bs, b_big=b_big,
                     mask=mask & 0xFFFFFFFF, min_chunk=min_chunk,
                     max_chunk=max_chunk, skip_ahead=bool(skip_ahead))


# --------------------------------------------------------------------------
# Shared vector core: phase-decomposed gear hashes over one supertile
# --------------------------------------------------------------------------

def _fmix32v(z):
    z = z ^ (z >> np.uint32(16))
    z = z * np.uint32(0x85EBCA6B)
    z = z ^ (z >> np.uint32(13))
    z = z * np.uint32(0xC2B2AE35)
    return z ^ (z >> np.uint32(16))


def _shift_words(x, m: int, prev_row):
    """Row-major flat shift right by ``m`` words of a (R, 128) register
    array: out_flat[i] = x_flat[i - m], with lanes wrapping into the
    previous sublane row and row 0 fed from ``prev_row`` — the previous
    supertile's last row carried in scratch (zeros at stream start, which
    reproduces the zero-pad semantics of gear._doubling_hashes)."""
    R = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 0)
    x_l = pltpu.roll(x, m, 1)
    x_up = pltpu.roll(x, 1, 0)
    x_up = jnp.where(row == 0, jnp.broadcast_to(prev_row, (R, 128)), x_up)
    x_ul = pltpu.roll(x_up, m, 1)
    return jnp.where(lane < m, x_ul, x_l)


def _tile_hashes(w, hist_ref):
    """Window-32 gear hashes of one (R, 128) LE-word supertile, by phase.

    Returns (h0..h3) where h_p[r, l] is the hash ending at byte
    4*(128r + l) + p.  Reads the 16 carried last-rows (4 stages x 4 phases)
    from ``hist_ref`` and writes this tile's own before returning."""
    R = w.shape[0]
    b = [(w >> np.uint32(8 * p)) & np.uint32(0xFF) for p in range(4)]
    g = [_fmix32v(bp * _GOLD) for bp in b]
    gs = [None] + [_shift_words(g[p], 1, hist_ref[p:p + 1, :])
                   for p in (1, 2, 3)]
    u = np.uint32
    s4 = [g[0] + (gs[3] << u(1)) + (gs[2] << u(2)) + (gs[1] << u(3)),
          g[1] + (g[0] << u(1)) + (gs[3] << u(2)) + (gs[2] << u(3)),
          g[2] + (g[1] << u(1)) + (g[0] << u(2)) + (gs[3] << u(3)),
          g[3] + (g[2] << u(1)) + (g[1] << u(2)) + (g[0] << u(3))]
    a8 = [s4[p] + (_shift_words(s4[p], 1, hist_ref[4 + p:5 + p, :]) << u(4))
          for p in range(4)]
    a16 = [a8[p] + (_shift_words(a8[p], 2, hist_ref[8 + p:9 + p, :]) << u(8))
           for p in range(4)]
    h = [a16[p] + (_shift_words(a16[p], 4,
                                hist_ref[12 + p:13 + p, :]) << u(16))
         for p in range(4)]
    for p in range(4):
        hist_ref[p:p + 1, :] = g[p][R - 1:R, :]
        hist_ref[4 + p:5 + p, :] = s4[p][R - 1:R, :]
        hist_ref[8 + p:9 + p, :] = a8[p][R - 1:R, :]
        hist_ref[12 + p:13 + p, :] = a16[p][R - 1:R, :]
    return h


# --------------------------------------------------------------------------
# The fused select kernel
# --------------------------------------------------------------------------

def _select_kernel(w_ref, wbe_ref, table_ref, ols_ref, olb_ref,
                   cmask_ref, rfc_ref, *scratch, p: FusedPlan):
    if p.skip_ahead:
        wsx_ref, rsx_ref, hist_ref, st_ref = scratch
    else:
        hist_ref, st_ref = scratch
    R, cap, Ls, Lb = p.R, p.cap, p.Ls, p.Lb
    B = R * 512
    t = pl.program_id(0)
    T = pl.num_programs(0)
    i32 = jnp.int32

    @pl.when(t == 0)
    def _init():
        for i in range(8):
            st_ref[i] = 0
        hist_ref[...] = jnp.zeros_like(hist_ref)
        table_ref[...] = jnp.zeros_like(table_ref)
        ols_ref[...] = jnp.zeros_like(ols_ref)
        olb_ref[...] = jnp.zeros_like(olb_ref)
        cmask_ref[...] = jnp.zeros_like(cmask_ref)
        rfc_ref[...] = jnp.full_like(rfc_ref, _INF)
        if p.skip_ahead:
            wsx_ref[...] = jnp.full_like(wsx_ref, _INF)

    @pl.when(t > 0)
    def _slide():  # two-tile window: current tile -> slab 1, previous -> 0
        cmask_ref[0] = cmask_ref[1]
        rfc_ref[0] = rfc_ref[1]
        if p.skip_ahead:
            wsx_ref[0] = wsx_ref[1]

    w = w_ref[...]
    # In-kernel BE word image (replaces the separate MXU combine pass).
    u = np.uint32
    wbe_ref[...] = (((w & u(0xFF)) << u(24)) | ((w >> u(8) & u(0xFF)) << u(16))
                    | ((w >> u(16) & u(0xFF)) << u(8)) | (w >> u(24)))

    h = _tile_hashes(w, hist_ref)
    row = jax.lax.broadcasted_iota(i32, (R, 128), 0)
    lane = jax.lax.broadcasted_iota(i32, (R, 128), 1)
    word_g = t * (R * 128) + row * 128 + lane
    pos0 = word_g * 4 + 1                       # pos1 of phase 0
    mask = u(p.mask)
    # Skip-ahead dead zone: positions below gear.skip_ahead_threshold can
    # never be selected (every window opens at prev+min), so masking them
    # here is cut-identical and keeps dead candidates out of every summary
    # the select walks or jumps over.
    thr = (gear.skip_ahead_threshold(p.min_chunk) if p.skip_ahead
           else gear.MIN_CANDIDATE_POS1)
    cand, fc = [], jnp.full((R, 128), _INF, i32)
    for ph in range(4):
        pos = pos0 + ph
        c = ((h[ph] & mask) == 0) & (pos >= thr) & (pos <= p.true_n)
        cand.append(c.astype(i32))
        fc = jnp.minimum(fc, jnp.where(c, pos, _INF))
    cmask_ref[1] = (cand[0] | (cand[1] << 1) | (cand[2] << 2)
                    | (cand[3] << 3))
    row_min = jnp.min(fc, axis=1, keepdims=True)
    rfc_ref[1] = row_min

    if p.skip_ahead:
        # ---- phase 1 of the sequence-based select: vectorized suffix-min
        # summaries.  wsx[r, l] = min first-candidate over lanes l.. of row
        # r (7 log-doubling rolls; pltpu.roll is circular, so wrapped lanes
        # are masked to _INF before each min).  rsx[sr] = min row summary
        # over window rows sr.. of the two-slab window (recomputed per tile
        # from the slid + fresh row summaries).  Together they make the
        # per-cut frontier lookup O(1) in place of the PR 4 word walk.
        sfx = fc
        step = 1
        while step < 128:
            y = pltpu.roll(sfx, 128 - step, 1)
            sfx = jnp.minimum(sfx, jnp.where(lane < 128 - step, y, _INF))
            step *= 2
        wsx_ref[1] = sfx
        rwin = jnp.concatenate([rfc_ref[0], row_min], axis=0)
        rowi2 = jax.lax.broadcasted_iota(i32, (2 * R, 1), 0)
        rsx = rwin
        step = 1
        while step < 2 * R:
            y = pltpu.roll(rsx, 2 * R - step, 0)
            rsx = jnp.minimum(rsx, jnp.where(rowi2 < 2 * R - step, y, _INF))
            step *= 2
        rsx_ref[...] = rsx
        # Telemetry for the H_SURV/H_CANDS header lanes (benchmarks cdc /
        # bench.py's cdc_adaptive block): per-slab survivor count = rows
        # with any viable candidate, plus the masked candidate population.
        st_ref[6] = st_ref[6] + jnp.sum((row_min != _INF).astype(i32))
        st_ref[7] = st_ref[7] + jnp.sum(cand[0] + cand[1]
                                        + cand[2] + cand[3])

    # ---- sequential frontier scan over the two-slab candidate summaries
    base_row = (t - 1) * R
    covered = (t + 1) * B
    last = t == T - 1

    def rd_nib(jg):
        sr = jnp.clip(jg // 128 - base_row, 0, 2 * R - 1)
        return cmask_ref[sr // R, sr % R, jnp.clip(jg % 128, 0, 127)]

    def rd_rfc(r):
        sr = jnp.clip(r - base_row, 0, 2 * R - 1)
        return rfc_ref[sr // R, sr % R, 0]

    def first_in_word(jg, lo, hi):
        nib = rd_nib(jg)
        best = jnp.full((), _INF, i32)
        for ph in (3, 2, 1, 0):
            pos = 4 * jg + 1 + ph
            hit = (((nib >> ph) & 1) == 1) & (pos >= lo) & (pos <= hi)
            best = jnp.where(hit, pos, best)
        return best

    def find_seq(lo, hi):
        """Phase 2 of the sequence-based select: first candidate >= ``lo``
        in O(1).  ``lo``'s own word resolves by nibble; later words of the
        row come from the within-row suffix-min at ``lane_lo + 1``; later
        rows from the cross-row suffix-min at ``sr + 1``.  Positions in
        words past ``lo``'s are provably >= 4*j_lo + 5 > lo, so the
        suffix reads never surface a pre-``lo`` candidate; a result past
        ``hi`` means "no candidate in window" and the caller's
        ``cpos <= hi`` clamp forces the cut at ``hi`` — identical
        semantics to the PR 4 walk below."""
        j_lo = (lo - 1) // 4
        row_lo = j_lo // 128
        lane_lo = j_lo % 128
        sr = jnp.clip(row_lo - base_row, 0, 2 * R - 1)
        inf = jnp.full((), _INF, i32)
        a = first_in_word(j_lo, lo, inf)
        b = jnp.where(lane_lo < 127,
                      wsx_ref[sr // R, sr % R,
                              jnp.clip(lane_lo + 1, 0, 127)], inf)
        c = jnp.where(sr < 2 * R - 1,
                      rsx_ref[jnp.clip(sr + 1, 0, 2 * R - 1), 0], inf)
        return jnp.minimum(a, jnp.minimum(b, c))

    def find_walk(lo, hi):
        """First candidate pos1 in [lo, hi] (else _INF) via the summaries:
        whole rows skip on the per-row first-candidate value; only the
        partial row containing ``lo`` word-scans."""
        j_lo, j_hi = (lo - 1) // 4, (hi - 1) // 4
        row_lo = j_lo // 128
        rfc0 = rd_rfc(row_lo)
        scan0 = rfc0 < lo          # candidates before lo share lo's row
        row_end_j = row_lo * 128 + 127

        def wbody(i, st):
            j, best = st
            act = scan0 & (best == _INF) & (j <= jnp.minimum(row_end_j,
                                                             j_hi))
            nb = first_in_word(jnp.clip(j, 0, None), lo, hi)
            return (j + 1, jnp.where(act, nb, best))

        _, best0 = jax.lax.fori_loop(0, 128, wbody,
                                     (j_lo, jnp.full((), _INF, i32)))

        def rbody(i, st):
            r, best, dead = st
            act = (best == _INF) & (dead == 0) & (r <= j_hi // 128)
            v = rd_rfc(r)
            found = act & (v >= lo) & (v <= hi)
            # first cand of this row beyond hi => later rows only larger
            stop = act & (v != _INF) & (v > hi)
            return (r + 1, jnp.where(found, v, best),
                    jnp.where(stop, 1, dead))

        r0 = row_lo + scan0.astype(i32)
        trips = p.max_chunk // 512 + 3
        _, best, _ = jax.lax.fori_loop(
            0, trips, rbody, (r0, best0, jnp.full((), 0, i32)))
        return best

    find = find_seq if p.skip_ahead else find_walk

    def cbody(i, s):
        f, nc, ns, nbg, of, done = s
        lo = f + p.min_chunk
        hi = jnp.minimum(f + p.max_chunk, p.true_n)
        go = (done == 0) & (of == 0) & (f < p.true_n) \
            & ((hi <= covered) | last)
        cpos = find(lo, hi)
        cut = jnp.where(cpos <= hi, cpos, hi)
        ln = cut - f
        small = (ln + 9 + 63) // 64 <= p.b_small
        of2 = jnp.where(go & ((nc >= cap) | jnp.where(small, ns >= Ls,
                                                      nbg >= Lb)), 1, of)
        emit = go & (of2 == 0)

        @pl.when(emit)
        def _():
            table_ref[0, TABLE_HDR + nc] = cut

            @pl.when(small)
            def _s():
                ols_ref[0, ns] = f
                ols_ref[1, ns] = ln

            @pl.when(jnp.logical_not(small))
            def _b():
                olb_ref[0, nbg] = f
                olb_ref[1, nbg] = ln

        e = emit.astype(i32)
        return (jnp.where(emit, cut, f), nc + e,
                ns + e * small.astype(i32), nbg + e * (1 - small.astype(i32)),
                of2, jnp.where(emit & (cut >= p.true_n), 1, done))

    trips = 2 * B // p.min_chunk + 2
    s0 = (st_ref[0], st_ref[1], st_ref[2], st_ref[3], st_ref[4], st_ref[5])
    f, nc, ns, nbg, of, done = jax.lax.fori_loop(0, trips, cbody, s0)
    st_ref[0], st_ref[1], st_ref[2] = f, nc, ns
    st_ref[3], st_ref[4], st_ref[5] = nbg, of, done

    @pl.when(last)
    def _hdr():
        table_ref[0, H_COUNT] = nc
        table_ref[0, H_OVERFLOW] = of
        table_ref[0, H_SMALL] = ns
        table_ref[0, H_BIG] = nbg
        table_ref[0, H_SURV] = st_ref[6]
        table_ref[0, H_CANDS] = st_ref[7]


@functools.cache
def _select_call(p: FusedPlan, interpret: bool):
    R, tw = p.R, TABLE_HDR + p.cap
    return pl.pallas_call(
        functools.partial(_select_kernel, p=p),
        grid=(p.T,),
        in_specs=[pl.BlockSpec((R, 128), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((R, 128), lambda t: (t, 0)),
                   pl.BlockSpec((1, tw), lambda t: (0, 0)),
                   pl.BlockSpec((2, p.Ls), lambda t: (0, 0)),
                   pl.BlockSpec((2, p.Lb), lambda t: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((p.T * R, 128), jnp.uint32),
                   jax.ShapeDtypeStruct((1, tw), jnp.int32),
                   jax.ShapeDtypeStruct((2, p.Ls), jnp.int32),
                   jax.ShapeDtypeStruct((2, p.Lb), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((2, R, 128), jnp.int32),
                        pltpu.VMEM((2, R, 1), jnp.int32)]
        + ([pltpu.VMEM((2, R, 128), jnp.int32),     # wsx: within-row sfx-min
            pltpu.VMEM((2 * R, 1), jnp.int32)]      # rsx: cross-row sfx-min
           if p.skip_ahead else [])
        + [pltpu.VMEM((16, 128), jnp.uint32),
           pltpu.SMEM((8,), jnp.int32)],
        interpret=interpret,
    )


def fused_block(w2d: jax.Array, p: FusedPlan, interpret: bool):
    """Run the fused kernel on one block's (n_pad/512, 128) LE u32 word
    image.  Returns (words_be u32[n_pad/4/128, 128], table i32[1, 8+cap],
    ol_small i32[2, Ls], ol_big i32[2, Lb]); traceable under jit."""
    return _select_call(p, interpret)(w2d)


# --------------------------------------------------------------------------
# Host-facing single-block helper (tests / benchmarks)
# --------------------------------------------------------------------------

def chunks_fused(data: bytes | np.ndarray, mask: int, min_chunk: int,
                 max_chunk: int, *, mask_bits: int = 13,
                 interpret: bool | None = None,
                 skip_ahead: bool | None = None):
    """(cuts, overflowed) with selection fully on device; same cut contract
    as native.cdc_chunk (asserted bit-identical in tests/test_cdc_pallas.py).
    ``overflowed`` reports that cap was exceeded and cuts are INVALID —
    callers must take the oracle path (the resident pipeline's fallback).
    ``skip_ahead`` pins the scan variant (None = the process-level
    ``cdc_skip_ahead()`` gate) — both variants must produce identical cuts,
    which the A/B tests sweep."""
    a = (np.frombuffer(data, dtype=np.uint8)
         if not isinstance(data, np.ndarray) else data)
    if a.size == 0:
        return np.empty(0, dtype=np.uint64), False
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p = plan_for(a.size, mask, mask_bits, min_chunk, max_chunk,
                 b_small=1 << 30, b_big=1 << 30, skip_ahead=skip_ahead)
    buf = np.zeros(p.n_pad, dtype=np.uint8)
    buf[:a.size] = a
    w2d = jax.device_put(buf.view(np.uint32).reshape(-1, 128))
    _, table, _, _ = fused_block(w2d, p, interpret)
    tb = np.asarray(table)[0]
    nc, of = int(tb[H_COUNT]), int(tb[H_OVERFLOW])
    return tb[TABLE_HDR:TABLE_HDR + nc].astype(np.uint64), bool(of)


# --------------------------------------------------------------------------
# Scan-only kernel: per-shard candidate nibbles for parallel/sharded.py
# --------------------------------------------------------------------------

def _scan_kernel(pos_ref, mask_ref, w_ref, nib_ref, hist_ref, *, R: int,
                 m: int):
    t = pl.program_id(0)
    i32 = jnp.int32

    @pl.when(t == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    w = w_ref[...]
    h = _tile_hashes(w, hist_ref)
    row = jax.lax.broadcasted_iota(i32, (R, 128), 0)
    lane = jax.lax.broadcasted_iota(i32, (R, 128), 1)
    byte0 = (t * (R * 128) + row * 128 + lane) * 4    # ext byte of phase 0
    mask = mask_ref[0, 0]
    base = pos_ref[0, 0]
    nib = jnp.zeros((R, 128), i32)
    for ph in range(4):
        e = byte0 + ph
        pos1 = base + e - (WINDOW - 1)                 # ext prefix = 32 bytes
        c = ((h[ph] & mask) == 0) & (pos1 >= gear.MIN_CANDIDATE_POS1) \
            & (e >= WINDOW) & (e < WINDOW + m)
        nib = nib | (c.astype(i32) << ph)
    nib_ref[...] = nib


@functools.cache
def _scan_call(T: int, R: int, m: int, interpret: bool):
    return pl.pallas_call(
        functools.partial(_scan_kernel, R=R, m=m),
        grid=(T,),
        in_specs=[pl.BlockSpec((1, 1), lambda t: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, 1), lambda t: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((R, 128), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((R, 128), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T * R, 128), jnp.int32),
        scratch_shapes=[pltpu.VMEM((16, 128), jnp.uint32)],
        interpret=interpret,
    )


@functools.cache
def _le_weights(b0: int) -> np.ndarray:
    """(256, 64) f32 block-diagonal for LITTLE-endian 16-bit halves:
    output t = byte[4t+b0] + 256*byte[4t+b0+1] (exact in f32; the BE
    variant is ops/resident.py _combine_weights)."""
    w = np.zeros((256, 64), dtype=np.float32)
    for t in range(64):
        w[4 * t + b0, t] = 1.0
        w[4 * t + b0 + 1, t] = 256.0
    return w


def le_word_image(block: jax.Array) -> jax.Array:
    """u8[N] -> native little-endian u32[N/4] words via the same two-matmul
    MXU combine as resident.be_word_image (a u8->u32 bitcast materializes
    the 32x-padded minor-dim-4 layout, PERF_NOTES.md round 2)."""
    bf = block.astype(jnp.float32).reshape(-1, 256)
    lo = jnp.dot(bf, jnp.asarray(_le_weights(0)),
                 preferred_element_type=jnp.float32)
    hi = jnp.dot(bf, jnp.asarray(_le_weights(2)),
                 preferred_element_type=jnp.float32)
    return ((hi.astype(jnp.uint32) << 16)
            | lo.astype(jnp.uint32)).reshape(-1)


def _pack_nibbles(nib: jax.Array) -> jax.Array:
    """Per-word candidate nibbles -> little-endian u32 bitmap words (8
    nibbles per word), the exact bit layout of gear.pack_bitmap_words:
    two exact-f32 matmul halves (< 2^16) + shift-or."""
    f = nib.astype(jnp.float32).reshape(-1, 8)
    wv = jnp.asarray(np.array([1.0, 16.0, 256.0, 4096.0], np.float32))
    lo = jnp.dot(f[:, :4], wv, preferred_element_type=jnp.float32)
    hi = jnp.dot(f[:, 4:], wv, preferred_element_type=jnp.float32)
    return lo.astype(jnp.uint32) | (hi.astype(jnp.uint32) << 16)


def local_candidate_words_pallas(local: jax.Array, mask: jax.Array,
                                 n_seq: int, *, interpret: bool):
    """Pallas form of sharded._local_candidate_words: same ppermute halo,
    same packed-bitmap contract (bit k of word w = pos 32w+k+1), the scan
    itself fused in one kernel.  Runs inside shard_map; ``local`` u8[m],
    m % 256 == 0."""
    m = local.shape[0]
    idx = jax.lax.axis_index("seq")
    halo = jax.lax.ppermute(local[-(WINDOW - 1):], "seq",
                            [(i, i + 1) for i in range(n_seq - 1)])
    # One leading zero byte word-aligns the 31-byte halo; G[0] == 0 so it
    # never perturbs a hash (same zero-identity the halo itself relies on).
    ext = jnp.concatenate([jnp.zeros(1, jnp.uint8), halo, local])
    R = 128
    B = R * 512
    ext = jnp.pad(ext, (0, (-ext.shape[0]) % B))
    w2d = le_word_image(ext).reshape(-1, 128)
    T = w2d.shape[0] // R
    pos_base = (idx * m).astype(jnp.int32).reshape(1, 1)
    m32 = jax.lax.bitcast_convert_type(mask.astype(jnp.uint32),
                                       jnp.uint32).reshape(1, 1)
    nib = _scan_call(T, R, m, interpret)(pos_base, m32, w2d)
    nib_local = nib.reshape(-1)[WINDOW // 4:WINDOW // 4 + m // 4]
    words = _pack_nibbles(nib_local)
    bits = (nib_local & 1) + ((nib_local >> 1) & 1) \
        + ((nib_local >> 2) & 1) + ((nib_local >> 3) & 1)
    return words, jnp.sum(bits)
