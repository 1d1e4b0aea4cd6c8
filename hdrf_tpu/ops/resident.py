"""Device-resident block reduction pipeline.

The naive composition (ops.gear then ops.sha256) moves the block host->device
for the CDC scan, back to the host, and *again* to the device as padded SHA
lane buffers — ~2.2x the block over the wire.  On the host-device link that
transfer dominates end-to-end throughput (PERF_NOTES.md); the reference has
the same structural flaw in CPU terms: the reference re-walks the block
once per stage (chunking DataDeduplicator.java:264-307, then hashing
:536-650, then storing :652-845) from Java heap buffers.

This pipeline crosses the block to HBM **once** and keeps every per-byte pass
on device:

1. ``_prep`` (one dispatch): big-endian u32 word image + all-position Gear
   candidate scan; only the sparse candidate words come back (O(chunks)).
2. Host: min/max cut selection over sparse candidates (native C++), chunk
   bucketing — O(chunks) control work.
3. ``_bucket_sha`` (one dispatch per size bucket): lanes are *gathered on
   device* from the resident word image (vmapped dynamic_slice = Mosaic DMAs),
   byte-aligned with a VPU funnel shift (chunk offsets are arbitrary bytes;
   the gather is word-granular), SHA-padded in word space, and hashed by the
   lane-parallel compression scan (ops.sha256.sha256_words).  Only digests
   come back.

Host<->device traffic per 64 MiB block: 64 MiB H2D + ~100 KiB of offsets
down, ~250 KiB of candidates+digests up.  All readbacks are started with
``copy_to_host_async`` so a caller that overlaps blocks (submit k+1 before
finishing k) hides dispatch and D2H latency entirely.

Fused front end (off by default — Mosaic refuses the kernel, see
ops/cdc_pallas.cdc_pallas_mode; HDRF_CDC_PALLAS asks for it): the batched
path routes stages 1-2 through ops/cdc_pallas.py instead — one Pallas
kernel forms the BE word image AND selects the final cuts on device,
binning chunk offset/length lanes into two fixed-capacity device tables
that feed the bucket SHA **without any host round trip**: the SHA
dispatches are enqueued before the cut table is read back, so the
candidate D2H and one awaited dispatch boundary per group disappear from
the steady state.  A kernel-reported
capacity overflow (header count) falls back to this module's XLA prep +
host native-select path, which also remains the oracle and the CPU-mesh /
device-resident-input path.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from hdrf_tpu.config import CdcConfig
from hdrf_tpu.ops import gear
from hdrf_tpu.ops.dispatch import gear_mask
from hdrf_tpu.ops.sha256 import sha256_words
from hdrf_tpu.utils import device_ledger as _ledger
from hdrf_tpu.utils import metrics as _metrics
from hdrf_tpu.utils import profiler as _profiler

# prep_retries: reduce ops whose candidates overflowed _prep's capacity and
# ran it again; prep_cap_words: the capacity of the latest dispatch (gauge)
_M = _metrics.registry("resident")


# Block padding grid: lcm of the bitmap pack row (256 bytes) and the
# 128-word (512-byte) row tiling of the Pallas DMA gather's word image.
_PAD_GRID = 512

# Block-length ladder.  A block's length is a shape: of ``_prep``, of the
# two bucket SHA programs (through the word image) and of the upload that
# lands it.  The last block of a file has whatever length its bytes left
# over, and a stream of small files has one a file, so blocks are padded
# with zeros up to a fixed rung and the true length rides along as a traced
# scalar (``_prep`` clears the candidates past it).  Two rungs an octave,
# ``top`` and ``3/4 top``, above a 1 MiB floor: 15 rungs up to a 128 MiB
# block.  A pad byte costs H2D and device time, under a microsecond a KiB;
# a program costs seconds to trace, lower and load in every process that
# meets it, cache or no cache (PERF.md section 6, PR 31) — so few rungs,
# and under 1 MiB, where a program's device time is below one awaited
# dispatch, one.  Every power of two from the floor up is a rung of its
# own, so a full block runs unpadded.
_RUNG_FLOOR = 1 << 20


def block_rung(n: int) -> int:
    """The smallest rung of the block-length ladder that holds ``n`` bytes:
    a function of the length alone, a multiple of ``_PAD_GRID``."""
    if n <= _RUNG_FLOOR:
        return _RUNG_FLOOR
    top = 1 << int(n - 1).bit_length()
    mid = top // 4 * 3
    return mid if n <= mid else top


def _bucket_of(nb: int) -> int:
    """Bucket = next power of two of the padded SHA block count (<=2x waste)."""
    return 1 << int(nb - 1).bit_length()


def _lane_count(n: int) -> int:
    if n <= 128:
        return 128
    return 1 << int(n - 1).bit_length()


def _lane_count_geo(n: int) -> int:
    """Lane count rounded up to steps of 1/16th of the next power of two:
    pad waste <= 12.5% even just above a power of two (vs <= 50% for pow2
    rounding), with a small jit shape space (8 distinct lane counts per
    octave, since an octave spans top/2..top in top/16 steps)."""
    if n <= 128:
        return 128
    top = 1 << int(n - 1).bit_length()
    step = max(top // 16, 128)
    return -(-n // step) * step


_COMBINE_ROW = 256  # input bytes per matmul row -> 64 output words


@functools.cache
def _combine_weights(byte0: int) -> "np.ndarray":
    """(256, 64) f32 block-diagonal: output t = byte[4t+byte0]*256 +
    byte[4t+byte0+1] — one 16-bit big-endian half per word, exact in f32."""
    w = np.zeros((_COMBINE_ROW, _COMBINE_ROW // 4), dtype=np.float32)
    for t in range(_COMBINE_ROW // 4):
        w[4 * t + byte0, t] = 256.0
        w[4 * t + byte0 + 1, t] = 1.0
    return w


def be_word_image(block: jax.Array) -> jax.Array:
    """u8[N] -> big-endian u32[N/4] word image, via MXU block-diagonal
    combines.  Neither astype(u32) on a (N/4, 4) view nor a u8->u32 bitcast
    works at speed here: both make XLA materialize a 32x-padded minor-dim-4
    intermediate (measured 27 ms per 64 MiB — the dominant _prep cost).  Two
    matmuls build the 16-bit halves exactly in f32 (values <= 2^16-1 < 2^24),
    then one integer shift-or fuses them: pure bandwidth + trivial MXU work.
    Shared by the CDC prep pass and the LZ4 match scan (ops/lz4_tpu.py)."""
    bf = block.astype(jnp.float32).reshape(-1, _COMBINE_ROW)
    hi = jnp.dot(bf, jnp.asarray(_combine_weights(0)),
                 preferred_element_type=jnp.float32)
    lo = jnp.dot(bf, jnp.asarray(_combine_weights(2)),
                 preferred_element_type=jnp.float32)
    return ((hi.astype(jnp.uint32) << 16)
            | lo.astype(jnp.uint32)).reshape(-1)


def _prep_impl(block: jax.Array, n: jax.Array, mask: int, cap: int,
               pad_words: int):
    """One pass over the resident block: BE word image + candidate scan.

    ``n`` (uint32 scalar, traced: no program per length) is the block's
    true length; the bytes past it are the zero pad up to its rung
    (``block_rung``) and give no candidate.  Returns
    (words u32[N/4 + pad_words], cand i32[1 + 2*cap]) where cand
    packs [count, word_idx..., word_val...] into a single D2H transfer.
    """
    words = be_word_image(block)
    words = jnp.concatenate([words, jnp.zeros(pad_words, jnp.uint32)])

    cw = gear.candidate_bitmap_words(block, jnp.uint32(mask), n_valid=n)
    nz = cw != 0
    (idx,) = jnp.nonzero(nz, size=cap, fill_value=cw.shape[0])
    vals = jnp.take(cw, idx, fill_value=0)
    count = jnp.sum(nz.astype(jnp.int32))
    cand = jnp.concatenate([count[None], idx.astype(jnp.int32),
                            jax.lax.bitcast_convert_type(vals, jnp.int32)])
    return words, cand


_prep = functools.partial(jax.jit, static_argnames=("mask", "cap",
                                                    "pad_words"))(_prep_impl)


@functools.partial(jax.jit, static_argnames=("mask", "cap", "pad_words"))
def _prep_batch(blocks: jax.Array, ns: jax.Array, mask: int, cap: int,
                pad_words: int):
    """Per-block _prep over K blocks padded to one length (``ns``: u32[K]
    true lengths) in ONE device program:
    one dispatch and one candidate readback for the whole group.  The loop
    is UNROLLED (K is a shape, so a jit-cache key): measured 8.5x faster
    than ``lax.map`` (whose per-iteration staging defeats cross-stage
    fusion) and — unlike ``vmap`` — free of the 32x-padded minor-dim-4
    batch layouts that OOM at group scale.  Where an awaited round trip
    is dear, dispatch count dominates device time and stage batching is the
    lever (PERF_NOTES.md; ~1 ms per awaited dispatch on the v5e host, PR 22)."""
    outs = [_prep_impl(blocks[k], ns[k], mask, cap, pad_words)
            for k in range(blocks.shape[0])]
    return (jnp.stack([w for w, _ in outs]),
            jnp.stack([c for _, c in outs]))


def sha_pad_messages(words: jax.Array, ol: jax.Array,
                     bucket: int) -> tuple[jax.Array, jax.Array]:
    """Gather + byte-align + SHA-pad one size bucket of chunks into padded
    message words (no hashing).  Shared by :func:`_bucket_sha` and the
    mesh-sharded reduction step (parallel/sharded.py), which hashes the
    same messages per shard under shard_map.

    words: u32[NW] resident BE word image (zero-padded so no slice clamps).
    ol: i32[2, L] — row 0 chunk byte offsets, row 1 chunk byte lengths,
    lens + 9 <= bucket * 64.  Returns (msgs u32[L, bucket*16], nb i64[L]).
    """
    offs, lens = ol[0], ol[1]
    W = bucket * 16  # u32 words per lane
    q = offs // 4
    s8 = ((offs % 4) * 8).astype(jnp.uint32)[:, None]

    lanes = jax.vmap(lambda o: jax.lax.dynamic_slice(words, (o,), (W + 1,)))(q)
    a, b = lanes[:, :W], lanes[:, 1:]
    # Funnel shift: byte-misaligned chunk words from two adjacent aligned words.
    c = jnp.where(s8 == 0, a, (a << s8) | (b >> (jnp.uint32(32) - s8)))

    # SHA padding in word space: keep data words, splice 0x80 at byte ``len``,
    # zero the tail, write the 64-bit big-endian bit length in the last words.
    wl = (lens // 4)[:, None]
    r8 = ((lens % 4) * 8).astype(jnp.uint32)[:, None]
    j = jnp.arange(W, dtype=jnp.int32)[None, :]
    keep = jnp.where(r8 == 0, jnp.uint32(0),
                     jnp.uint32(0xFFFFFFFF) << (jnp.uint32(32) - r8))
    marker = jnp.uint32(0x80) << (jnp.uint32(24) - r8)
    boundary = (c & keep) | marker
    out = jnp.where(j < wl, c, jnp.where(j == wl, boundary, jnp.uint32(0)))
    nb = (lens + 9 + 63) // 64
    last = nb * 16 - 1
    bitlen = (lens.astype(jnp.uint32) * 8)[:, None]
    out = jnp.where(j == last[:, None], bitlen, out)
    return out, nb


@functools.partial(jax.jit, static_argnames=("bucket",))
def _bucket_sha(words: jax.Array, ol: jax.Array, bucket: int) -> jax.Array:
    """Gather + byte-align + SHA-pad + hash one size bucket of chunks.

    words: u32[NW] resident BE word image (zero-padded so no slice clamps).
    ol: i32[2, L] — row 0 chunk byte offsets, row 1 chunk byte lengths
    (one packed upload: each tiny H2D pays a fixed cost),
    lens + 9 <= bucket * 64.  Returns u8[L, 32].
    """
    out, nb = sha_pad_messages(words, ol, bucket)
    if jax.default_backend() == "cpu":
        return sha256_words(out, nb.astype(jnp.int32))
    from hdrf_tpu.ops.sha256_pallas import sha256_words_pallas

    return sha256_words_pallas(out, nb.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bucket",))
def _bucket_sha_dma(words: jax.Array, ol: jax.Array, bucket: int):
    """TPU fast path for _bucket_sha: the Pallas DMA gather kernel builds
    the padded messages (~0.3 us/lane vs ~2-5 us/lane for the XLA gather —
    the dominant device cost once dispatches are batched), then the Pallas
    SHA kernel hashes them.  Same contract and bit-identical output."""
    from hdrf_tpu.ops.gather_pallas import gather_pad_messages
    from hdrf_tpu.ops.sha256_pallas import sha256_words_pallas

    msgs = gather_pad_messages(words, ol, bucket)
    nb = (ol[1] + 9 + 63) // 64
    return sha256_words_pallas(msgs, nb.astype(jnp.int32))


def _bucket_sha_best(words: jax.Array, ol, bucket: int):
    """DMA-gather path on TPU when the word image tiles into 128-word rows;
    XLA gather otherwise (CPU backend, odd image sizes)."""
    if jax.default_backend() != "cpu" and words.shape[0] % 128 == 0:
        return _bucket_sha_dma(words, jax.device_put(ol), bucket)
    return _bucket_sha(words, jax.device_put(ol), bucket)


def _bucket_sha_dev(words: jax.Array, ol: jax.Array, bucket: int):
    """_bucket_sha_best for an ALREADY-device-resident ol table (the fused
    CDC path: the offset/length lanes never visit the host, so there is no
    device_put).  Traceable under jit."""
    if jax.default_backend() != "cpu" and words.shape[0] % 128 == 0:
        return _bucket_sha_dma(words, ol, bucket)
    return _bucket_sha(words, ol, bucket)


@functools.partial(jax.jit, static_argnames=("plan", "pad_words", "b_big",
                                             "interpret"))
def _fused_batch(w3d: jax.Array, plan, pad_words: int, b_big: int,
                 interpret: bool):
    """K-block fused CDC + bucket SHA in ONE device program (the loop is
    unrolled per the _prep_batch precedent).  Per block: the cdc_pallas
    select kernel emits the BE word image, the cut table, and two binned
    offset/length lane tables; lane offsets are rebased to the flat
    multi-block word image and the two bucket SHA passes run over the
    concatenated fixed-capacity lanes — chunk COUNTS are not needed to
    enqueue, which is what removes the awaited prep boundary.

    Returns (tables i32[K, 8+cap], digests u8[K*Ls + K*Lb, 32]) with small
    lanes first: digest row of block k's small-lane j = k*Ls + j, big-lane
    j = K*Ls + k*Lb + j.
    """
    from hdrf_tpu.ops import cdc_pallas

    k = w3d.shape[0]
    stride_words = plan.T * plan.R * 128 + pad_words
    words_l, tables_l, ols_l, olb_l = [], [], [], []
    for i in range(k):
        if w3d.dtype == jnp.uint8:
            # HBM-resident u8 block (the streamed worker deployment): LE
            # words via the MXU combine — a u8->u32 bitcast materializes
            # the 32x-padded minor-dim-4 layout (be_word_image's rationale).
            padded = jnp.pad(w3d[i], (0, plan.n_pad - w3d.shape[1]))
            w2d = cdc_pallas.le_word_image(padded).reshape(-1, 128)
        else:
            w2d = w3d[i]
        wbe, table, ols, olb = cdc_pallas.fused_block(w2d, plan,
                                                      interpret)
        words_l.append(jnp.concatenate(
            [wbe.reshape(-1), jnp.zeros(pad_words, jnp.uint32)]))
        tables_l.append(table[0])
        base = jnp.int32(i * stride_words * 4)
        ols_l.append(ols.at[0].add(base))
        olb_l.append(olb.at[0].add(base))
    words = jnp.concatenate(words_l)
    ol_s = jnp.concatenate(ols_l, axis=1)
    ol_b = jnp.concatenate(olb_l, axis=1)
    digs = jnp.concatenate([_bucket_sha_dev(words, ol_s, plan.b_small),
                            _bucket_sha_dev(words, ol_b, b_big)], axis=0)
    return jnp.stack(tables_l), digs


@dataclasses.dataclass
class BatchJob:
    """A group of K equal-length blocks reduced with one dispatch + one
    readback per stage (vs 2 awaited round trips PER BLOCK on the
    per-block path — the dominant cost through a high-latency transport)."""
    k: int                    # blocks in the group
    n: int                    # padded bytes per block (uniform)
    blocks: jax.Array | None  # (K, n) resident u8 (until cuts final)
    words: jax.Array          # (K, n/4 + pad_words) resident BE word image
    cand: jax.Array           # (K, 1 + 2*cap) packed candidates (D2H async)
    cap: int
    true_n: int               # unpadded byte length per block
    rung: int = 0             # doublings of the first-shot cap at dispatch
    cuts: list[np.ndarray] | None = None
    _sha_parts: tuple | None = None
    _ev: object = None        # ledger token: prep dispatch -> cand readback
    _ev_sha: list | None = None  # ledger tokens: sha dispatches -> digest rb
    # Fused-CDC path state (cdc_pallas): cuts selected on device, SHA
    # enqueued against fixed-capacity lane tables before any readback.
    fused: bool = False
    tables: jax.Array | None = None   # (K, 8+cap) cut tables (D2H async)
    plan: object = None               # cdc_pallas.FusedPlan
    _digs: jax.Array | None = None    # (K*Ls + K*Lb, 32) fused digests
    _host: list | None = None         # host u8 blocks for overflow fallback
    # Mixed-size groups (bucket-padded coalescing): per-block unpadded
    # lengths; None means every block is true_n bytes.
    true_ns: list[int] | None = None


def _host_sizes(datas) -> list[int]:
    return [d.size if isinstance(d, np.ndarray) else len(d) for d in datas]


@dataclasses.dataclass
class BlockJob:
    n: int
    block: jax.Array | None   # resident u8 image (until cuts are final)
    words: jax.Array          # resident BE word image
    cand: jax.Array           # packed candidate readback (D2H in flight)
    cap: int
    rung: int = 0             # doublings of the first-shot cap at dispatch
    cuts: np.ndarray | None = None
    _sha_parts: tuple | None = None  # (sels, lane_counts, digests_dev)
    _ev: object = None        # ledger token: prep dispatch -> cand readback
    _ev_sha: list | None = None  # ledger tokens: sha dispatches -> digest rb


class ResidentReducer:
    """Async block-reduction front end over the device-resident pipeline.

    Usage (overlapped):
        jobs = [r.submit(b) for b in blocks]      # H2D + scan dispatches
        for j in jobs: r.start_sha(j)             # cut select + SHA dispatches
        results = [r.finish(j) for j in jobs]     # (cuts, digests)
    """

    def __init__(self, cdc: CdcConfig | None = None,
                 fused_mode: str | None = None,
                 skip_ahead: bool | None = None):
        from hdrf_tpu.ops.cdc_pallas import cdc_pallas_mode, cdc_skip_ahead

        self.cdc = cdc or CdcConfig()
        self.mask = gear_mask(self.cdc)
        # 'mosaic' | 'interpret' | 'off' — resolved once so a reducer's jit
        # cache stays coherent; dispatch.py keys its reducer cache on this.
        self.fused = fused_mode if fused_mode is not None \
            else cdc_pallas_mode()
        # Scan-variant pin (skip-ahead + sequence select vs the PR 4 walk),
        # resolved once for the same jit-cache-coherence reason.
        self._skip_ahead = skip_ahead if skip_ahead is not None \
            else cdc_skip_ahead()
        # Gather windows must never clamp: pad the word image by the widest
        # bucket (max_chunk rounded up) + the funnel-shift lookahead word,
        # rounded to the 128-word row grid the Pallas DMA gather requires.
        max_nb = (self.cdc.max_chunk + 9 + 63) // 64
        self.pad_words = -(-(_bucket_of(max_nb) * 16 + 16) // 128) * 128
        # Two-bucket SHA dispatch plan: small bucket = exactly 2x the average
        # chunk, big bucket = exactly max_chunk.  Bucket widths are jit-cache
        # keys, not layout constraints — pow2 rounding here would double the
        # padded SHA work for the mass of the distribution.
        # Clamped to the big bucket: a degenerate config whose expected
        # chunk (2<<mask_bits) exceeds max_chunk must not widen the small
        # gather window past the word-image padding.
        self._b_small = max(1, min((2 << self.cdc.mask_bits) // 64, max_nb))
        self._b_big = max_nb
        # Batched path: four buckets (avg, 2x, 4x, max) — padded gather
        # bytes drop from ~2.45x to ~1.53x of the block at the measured
        # chunk-size distribution, and with stage batching the extra
        # dispatches are enqueued, not awaited, so they cost device time
        # only.
        self._buckets = sorted({b for b in (self._b_small // 2,
                                            self._b_small,
                                            2 * self._b_small, max_nb)
                                if 0 < b <= max_nb})
        # Candidate capacity is a static argument of _prep, so it comes
        # from a fixed ladder (``_cap``) and the highest rung a block needed
        # sticks, as TpuLz4's slice widths do: a stream of zero-dense blocks
        # (tar archives, sparse images) pays the overflow retry once, not a
        # compile per block.  Handler threads share one reducer.
        self._rung = 0
        self._rung_lock = threading.Lock()
        # (padded length, capacity) pairs the per-block ``_prep`` has been
        # dispatched at, first shots and retries: each a program this
        # process had to trace, lower and compile or load (the worker's
        # ``prep_shapes`` counter).
        self.prep_shapes: set[tuple[int, int]] = set()

    # ----------------------------------------------------- batched pipeline

    def submit_many(self, datas) -> BatchJob:
        """Start reduction of K equal-length blocks as ONE device program.

        ``datas``: list of host byte buffers (bytes / u8 ndarray) all the
        same length, or an already-HBM-resident (K, n) u8 device array
        (the streamed TPU-worker deployment).

        Host-byte groups route through the fused Pallas CDC kernel when
        enabled (cuts selected on device, SHA enqueued with no candidate
        readback); device-resident inputs and ``fused == 'off'`` take the
        XLA prep + host-select path.  Mixed-length host groups (the
        bucket-padded coalescer) always take the XLA path, padded to the
        longest member — the fused kernel's plan is per-length.
        """
        if self.fused != "off":
            if isinstance(datas, jax.Array) or len(
                    set(_host_sizes(datas))) == 1:
                return self._submit_many_fused(datas)
        return self._submit_many_xla(datas)

    def _submit_many_xla(self, datas) -> BatchJob:
        true_ns = None
        if isinstance(datas, jax.Array):
            k, n = datas.shape
            assert n > 0 and n % _PAD_GRID == 0
            true_n = n
            stacked = datas
        else:
            arrs = [np.frombuffer(d, dtype=np.uint8)
                    if not isinstance(d, np.ndarray) else d for d in datas]
            true_ns = [a.size for a in arrs]
            true_n = max(true_ns)
            assert true_n > 0
            n_pad = true_n + (-true_n) % _PAD_GRID
            if any(a.size != n_pad for a in arrs):
                arrs = [a if a.size == n_pad
                        else np.concatenate(
                            [a, np.zeros(n_pad - a.size, np.uint8)])
                        for a in arrs]
            stacked = jax.device_put(np.stack(arrs))
            k, n = stacked.shape
        # int32 flat-byte-offset headroom for the bucket gather
        assert k * (n + 4 * self.pad_words) < (1 << 31), \
            "batch too large for i32 flat offsets; split it"
        rung = self._rung
        cap = self._cap(n, rung)
        _M.gauge("prep_cap_words", cap)
        ev = _ledger.dispatch(
            "resident.prep_batch", batch=k,
            h2d_bytes=0 if isinstance(datas, jax.Array) else k * n,
            key=(k, n, cap))
        # a shorter member's zero tail gives no candidate: its true length
        # goes down with it
        ns = np.asarray(true_ns or [true_n] * k, np.uint32)
        if ns.min() == true_n:
            true_ns = None
        words, cand = _prep_batch(stacked, ns, self.mask, cap,
                                  self.pad_words)
        cand.copy_to_host_async()
        return BatchJob(k=k, n=n, blocks=stacked, words=words, cand=cand,
                        cap=cap, true_n=true_n, rung=rung, true_ns=true_ns,
                        _ev=ev)

    def _submit_many_fused(self, datas) -> BatchJob:
        """Fused-kernel group submit: ONE program selects cuts on device
        and hashes both lane buckets; the cut-table readback and the SHA
        digests start D2H together — nothing is awaited here."""
        from hdrf_tpu.ops import cdc_pallas

        if isinstance(datas, jax.Array):
            # HBM-resident group: LE words form on device (MXU combine in
            # _fused_batch); the raw array doubles as the fallback input.
            k, true_n = datas.shape
            assert true_n > 0 and true_n % _PAD_GRID == 0
            arrs, w3d, h2d = datas, datas, 0
        else:
            arrs = [np.ascontiguousarray(
                        np.frombuffer(d, dtype=np.uint8)
                        if not isinstance(d, np.ndarray) else d)
                    for d in datas]
            true_n = arrs[0].size
            assert all(a.size == true_n for a in arrs), \
                "submit_many needs equal lengths"
            assert true_n > 0
            k, w3d = len(arrs), None
        plan = cdc_pallas.plan_for(true_n, self.mask, self.cdc.mask_bits,
                                   self.cdc.min_chunk, self.cdc.max_chunk,
                                   self._b_small, self._b_big,
                                   skip_ahead=self._skip_ahead)
        stride = plan.n_pad + 4 * self.pad_words
        assert k * stride < (1 << 31), \
            "batch too large for i32 flat offsets; split it"
        if w3d is None:
            buf = np.zeros((k, plan.n_pad), dtype=np.uint8)
            for i, a in enumerate(arrs):
                buf[i, :true_n] = a
            # Host-side u32 view = free little-endian word formation; the
            # kernel byteswaps to BE in-register (no separate MXU pass).
            w3d = jax.device_put(buf.view(np.uint32).reshape(k, -1, 128))
            h2d = k * plan.n_pad
        interpret = self.fused == "interpret"
        ev = _ledger.dispatch("resident.cdc_fused", batch=k,
                              h2d_bytes=h2d,
                              key=(k, plan.n_pad, plan.cap, self.fused))
        tables, digs = _fused_batch(w3d, plan, self.pad_words, self._b_big,
                                    interpret)
        tables.copy_to_host_async()
        # SHA is enqueued already — against fixed-capacity lane tables, so
        # no cut count (hence no readback) gates it.  One ledger dispatch
        # per bucket keeps parity with the XLA path's accounting.
        evs = [_ledger.dispatch("resident.sha", batch=k,
                                key=(b, lanes, "fused"))
               for b, lanes in ((plan.b_small, k * plan.Ls),
                                (self._b_big, k * plan.Lb))]
        digs.copy_to_host_async()
        return BatchJob(k=k, n=plan.n_pad, blocks=None, words=None,
                        cand=None, cap=plan.cap, true_n=true_n,
                        fused=True, tables=tables, plan=plan, _digs=digs,
                        _host=arrs, _ev=ev, _ev_sha=evs)

    def _cap(self, size: int, rung: int) -> int:
        """Candidate capacity (bitmap words) of ``_prep`` for a block padded
        to ``size`` bytes, at ``rung`` of its ladder.  ``size`` is the
        padded length (a rung of the block-length ladder), never the true
        one: ``cap`` is a static argument.  Rung 0 is the
        first-shot size for content-like data (about 2x the expected
        candidate words + slack); each further rung doubles it, up to the
        hard ceiling ``size // 32`` (every bitmap word non-zero, where no
        block overflows): at most 8 rungs for a 128 MiB block.  ``cap`` is a
        jit-cache key, so these are all the ``_prep`` programs a block
        length can compile.  A high rung costs its readback (``1 + 2*cap``
        int32) and a little device time: a 128 MiB block's ``_prep`` ran
        54 ms at rung 0, 63 at rung 3, 96 at the ceiling (v5e, PR 27)."""
        first = max(1024, (size >> max(self.cdc.mask_bits - 1, 0)) + 1024)
        return max(1, min(size // 32, first << rung))

    def _cuts_from_cand(self, cand_row: np.ndarray, cap: int, rung: int,
                        block, true_n: int) -> np.ndarray:
        """Candidate row -> selected cut points.  The packed layout is
        [count, idx x cap, vals x cap]; a dense-candidate overflow (count >
        cap, e.g. long zero runs where every position hashes to 0) retries
        _prep once at the smallest rung of the capacity ladder (``_cap``)
        that holds ``count``, and the reducer dispatches later blocks at
        that rung.  The ONE place that understands this layout — shared by
        the per-block and batched paths."""
        from hdrf_tpu import native

        count = int(cand_row[0])
        if count > cap:
            ceiling = block.shape[0] // 32
            while cap < count:
                rung += 1
                cap = min(2 * cap, ceiling)
            with self._rung_lock:
                self._rung = max(self._rung, rung)
            _M.incr("prep_retries")
            _M.gauge("prep_cap_words", cap)
            with _profiler.phase("prep_wait"):
                self.prep_shapes.add((block.shape[0], cap))
                ev = _ledger.dispatch("resident.prep_retry",
                                      key=(block.shape, cap))
                _, cd = _prep(block, np.uint32(true_n), self.mask, cap,
                              self.pad_words)
                cand_row = np.asarray(cd)
                _ledger.readback(ev, d2h_bytes=cand_row.nbytes)
            count = int(cand_row[0])
        idx = cand_row[1:1 + count].astype(np.uint32)
        vals = cand_row[1 + cap:1 + cap + count].view(np.uint32)
        pos = gear._words_to_positions(idx, vals, true_n)
        return native.cdc_select(pos, true_n, self.cdc.min_chunk,
                                 self.cdc.max_chunk)

    def _start_sha_fused(self, bj: BatchJob) -> None:
        """Await the cut tables (the SHA work is already enqueued), derive
        each chunk's digest row from the kernel's two-bucket binning rule,
        or — on a kernel-reported capacity overflow — discard the fused
        lanes and rerun the whole group through the XLA oracle path (cut
        boundaries are never truncated)."""
        from hdrf_tpu.ops import cdc_pallas as cp

        tables = np.asarray(bj.tables)        # the one awaited readback
        _ledger.readback(bj._ev, d2h_bytes=tables.nbytes)
        bj._ev = None
        bj.tables = None
        if self._skip_ahead:
            # Sequence-select telemetry rides the header lanes of the one
            # readback that already happens — zero extra D2H.
            from hdrf_tpu.reduction import accounting

            accounting.record_scan_summary(
                int(tables[:, cp.H_SURV].sum()),
                int(tables[:, cp.H_CANDS].sum()))
        if tables[:, cp.H_OVERFLOW].any():
            for ev in bj._ev_sha or ():       # fused SHA results discarded
                _ledger.readback(ev, d2h_bytes=0)
            bj._ev_sha = None
            bj._digs = None
            nj = self._submit_many_xla(bj._host)
            bj.fused = False
            bj._host = None
            bj.n, bj.true_n, bj.cap, bj.rung = (nj.n, nj.true_n, nj.cap,
                                                nj.rung)
            bj.true_ns = nj.true_ns
            bj.blocks, bj.words, bj.cand = nj.blocks, nj.words, nj.cand
            bj._ev = nj._ev
            self.start_sha_many(bj)
            return
        plan = bj.plan
        cuts_all, place = [], []
        for i in range(bj.k):
            nc = int(tables[i, cp.H_COUNT])
            cuts = tables[i, cp.TABLE_HDR:cp.TABLE_HDR + nc].astype(
                np.uint64)
            cuts_all.append(cuts)
            starts = np.concatenate([[0], cuts[:-1]]).astype(np.int64)
            lens = cuts.astype(np.int64) - starts
            small = (lens + 9 + 63) // 64 <= plan.b_small
            rank = np.where(small, np.cumsum(small) - 1,
                            np.cumsum(~small) - 1)
            place.append(np.where(small, i * plan.Ls + rank,
                                  bj.k * plan.Ls + i * plan.Lb + rank))
        bj.cuts = cuts_all
        bj._sha_parts = ("fused", place, bj._digs)
        bj._digs = None
        bj._host = None

    def start_sha_many(self, bj: BatchJob) -> None:
        if bj.fused:
            self._start_sha_fused(bj)
            return
        cand = np.asarray(bj.cand)            # ONE readback for the group
        _ledger.readback(bj._ev, d2h_bytes=cand.nbytes)
        bj._ev = None
        cuts_all, starts_all, lens_all = [], [], []
        for k in range(bj.k):
            tn = bj.true_ns[k] if bj.true_ns is not None else bj.true_n
            cuts = self._cuts_from_cand(cand[k], bj.cap, bj.rung,
                                        bj.blocks[k], tn)
            starts = np.concatenate([[0], cuts[:-1]]).astype(np.int64)
            cuts_all.append(cuts)
            starts_all.append(starts)
            lens_all.append((cuts - starts).astype(np.int64))
        bj.cuts = cuts_all
        # Global flat lane lists, bucketed by padded SHA block count.
        stride_b = bj.words.shape[1] * 4      # bytes per block row incl. pad
        blk = np.concatenate([np.full(len(c), k, np.int64)
                              for k, c in enumerate(cuts_all)])
        chunk_i = np.concatenate([np.arange(len(c)) for c in cuts_all])
        starts = np.concatenate(starts_all)
        lens = np.concatenate(lens_all)
        nb = (lens + 9 + 63) // 64
        flat_off = blk * stride_b + starts
        parts, sels, evs = [], [], []
        lo = 0
        for B in self._buckets:
            m = (nb > lo) & (nb <= B)
            lo = B
            if not m.any():
                continue
            sel = np.nonzero(m)[0]
            L = _lane_count_geo(sel.size)
            ol = np.zeros((2, L), dtype=np.int32)
            ol[0, :sel.size] = flat_off[sel]
            ol[1, :sel.size] = lens[sel]
            evs.append(_ledger.dispatch("resident.sha", batch=sel.size,
                                        h2d_bytes=ol.nbytes, key=(B, L)))
            parts.append(_bucket_sha_best(bj.words.reshape(-1), ol, B))
            sels.append((blk[sel], chunk_i[sel]))
        if parts:
            alld = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                    else parts[0])
            alld.copy_to_host_async()          # ONE digest readback
        else:
            alld = None
        bj._sha_parts = (sels, [p.shape[0] for p in parts], alld)
        bj._ev_sha = evs
        bj.blocks = None

    def finish_many(self, bj: BatchJob) -> list[tuple[np.ndarray, np.ndarray]]:
        if bj._sha_parts is None:
            self.start_sha_many(bj)
        if bj.fused:
            _, place, digs_dev = bj._sha_parts
            digs = np.asarray(digs_dev)
            for i, ev in enumerate(bj._ev_sha or ()):
                _ledger.readback(ev, d2h_bytes=digs.nbytes if i == 0 else 0)
            bj._ev_sha = None
            bj._sha_parts = None
            return [(c, digs[rows]) for c, rows in zip(bj.cuts, place)]
        sels, lane_counts, digs_dev = bj._sha_parts
        outs = [np.empty((len(c), 32), dtype=np.uint8) for c in bj.cuts]
        if digs_dev is not None:
            digs = np.asarray(digs_dev)
            for i, ev in enumerate(bj._ev_sha or ()):
                _ledger.readback(ev, d2h_bytes=digs.nbytes if i == 0 else 0)
            bj._ev_sha = None
            at = 0
            for (blks, idxs), L in zip(sels, lane_counts):
                rows = digs[at:at + blks.size]
                at += L
                for k in np.unique(blks):
                    m = blks == k
                    outs[int(k)][idxs[m]] = rows[m]
        bj.words = None
        return list(zip(bj.cuts, outs))

    def max_group(self, n: int) -> int:
        """Largest equal-length group of n-byte blocks one submit_many can
        take: bounded by i32 flat byte offsets in the bucket gather and a
        cap on the unrolled _prep_batch program size.  The fused path pads
        to its (larger) supertile grid, so both strides bound the group."""
        n_pad = n + (-n) % _PAD_GRID
        stride = n_pad + 4 * self.pad_words
        if self.fused != "off":
            from hdrf_tpu.ops import cdc_pallas

            plan = cdc_pallas.plan_for(max(n, 1), self.mask,
                                       self.cdc.mask_bits,
                                       self.cdc.min_chunk,
                                       self.cdc.max_chunk,
                                       self._b_small, self._b_big,
                                       skip_ahead=self._skip_ahead)
            stride = max(stride, plan.n_pad + 4 * self.pad_words)
        return max(1, min(((1 << 31) - 1) // stride, 16))

    def reduce_many(self, datas: list) -> list[tuple[np.ndarray, np.ndarray]]:
        """Batched multi-block reduction: groups of equal-length blocks run
        as single device programs (split to fit the i32 offset bound); odd
        sizes fall back to the per-block path.  Results keep input order."""
        arrs = [np.frombuffer(d, dtype=np.uint8)
                if not isinstance(d, np.ndarray) else d for d in datas]
        by_len: dict[int, list[int]] = {}
        for i, a in enumerate(arrs):
            by_len.setdefault(a.size, []).append(i)
        out: list = [None] * len(arrs)
        for size, idxs in by_len.items():
            if size == 0 or len(idxs) == 1:
                for i in idxs:
                    out[i] = self.reduce(arrs[i])
                continue
            g = self.max_group(size)
            for at in range(0, len(idxs), g):
                part = idxs[at:at + g]
                if len(part) == 1:
                    out[part[0]] = self.reduce(arrs[part[0]])
                    continue
                bj = self.submit_many([arrs[i] for i in part])
                self.start_sha_many(bj)
                for i, res in zip(part, self.finish_many(bj)):
                    out[i] = res
        return out

    def submit(self, data: bytes | np.ndarray | jax.Array,
               n: int | None = None) -> BlockJob:
        """Start reduction of one block.  ``data`` may be host bytes or an
        already-HBM-resident u8 device array (the gRPC-streamed TPU-worker
        deployment lands packets in HBM before reduction starts; ``n`` gives
        the true length when the device array carries pad).  The programs
        are keyed by the block's rung (``job.block.shape``) and ``job.cap``,
        which follows it."""
        if isinstance(data, jax.Array):
            block, n = data, n if n is not None else data.shape[0]
        else:
            block = (np.frombuffer(data, dtype=np.uint8)
                     if not isinstance(data, np.ndarray) else data)
            n = block.size
        if n == 0:
            job = BlockJob(n=0, block=None, words=None, cand=None, cap=0,
                           cuts=np.empty(0, dtype=np.uint64))
            job._sha_parts = ([], [], None)
            return job
        # Up to its rung of the block-length ladder with zeros (_prep drops
        # the candidates past ``n``): a rung is its own rung, so a caller
        # that landed the block at one (the worker) pays nothing here.
        size = block_rung(block.shape[0])
        if isinstance(block, jax.Array):
            if block.shape[0] != size:
                block = jnp.pad(block, (0, size - block.shape[0]))
        else:
            if n != size:
                a = np.zeros(size, np.uint8)
                a[:n] = block
                block = a
            block = jax.device_put(block)
        rung = self._rung
        cap = self._cap(size, rung)
        self.prep_shapes.add((size, cap))
        _M.gauge("prep_cap_words", cap)
        # Stage spans of the per-block path (the reduction worker's stage
        # clock, utils/profiler.py): ``prep_wait`` from this dispatch to
        # the candidates on the host, ``select`` the host cut selection and
        # SHA bucket planning, ``sha_wait`` SHA dispatch to digests.
        with _profiler.phase("prep_wait"):
            ev = _ledger.dispatch(
                "resident.prep",
                h2d_bytes=(0 if isinstance(data, jax.Array)
                           else block.shape[0]),
                key=(block.shape, cap))
            words, cand = _prep(block, np.uint32(n), self.mask, cap,
                                self.pad_words)
            cand.copy_to_host_async()
        return BlockJob(n=n, block=block, words=words, cand=cand, cap=cap,
                        rung=rung, _ev=ev)

    def start_sha(self, job: BlockJob) -> None:
        if job.cand is None:  # empty block prepared entirely in submit()
            return
        with _profiler.phase("prep_wait"):
            cand = np.asarray(job.cand)
            _ledger.readback(job._ev, d2h_bytes=cand.nbytes)
        job._ev = None
        with _profiler.phase("select"):
            cuts = self._cuts_from_cand(cand, job.cap, job.rung, job.block,
                                        job.n)
            job.cuts = cuts
            starts = np.concatenate([[0], cuts[:-1]]).astype(np.int64)
            lens = (cuts - starts).astype(np.int64)
            nb = (lens + 9 + 63) // 64
            # TWO fixed buckets, not one per power of two (fewer dispatches
            # and jit shapes); the small bucket covers the mass of the
            # chunk-size distribution (~2x the mean), the big one the tail,
            # and padded-lane waste stays comparable to pow2 bucketing.
            order = np.arange(len(cuts))
        sels, parts, evs = [], [], []
        # each bucket is planned and dispatched in turn, as before the stage
        # clock: with both planned first, a worker's first block (the
        # kernels' lowering) read 4-7 s longer on the chip, cause not found
        # (my chip runs, PR 25; PERF.md section 7)
        # Lane counts are shapes of the SHA programs too: floored at what
        # content-like data needs at the rung's whole length (a small chunk
        # an average chunk's bytes, a big one every ``max_chunk``), so the
        # files under one rung share one program a bucket; denser data
        # climbs ``_lane_count``'s powers of two from there.
        size = job.block.shape[0]
        for sel, B, floor in (
                (order[nb <= self._b_small], self._b_small,
                 size >> self.cdc.mask_bits),
                (order[nb > self._b_small], self._b_big,
                 size // self.cdc.max_chunk)):
            if not sel.size:
                continue
            with _profiler.phase("select"):
                L = _lane_count(max(sel.size, floor))
                ol = np.zeros((2, L), dtype=np.int32)
                ol[0, :sel.size] = starts[sel]
                ol[1, :sel.size] = lens[sel]
            with _profiler.phase("sha_wait"):
                evs.append(_ledger.dispatch("resident.sha", batch=sel.size,
                                            h2d_bytes=ol.nbytes, key=(B, L)))
                parts.append(_bucket_sha_best(job.words, ol, B))
                sels.append(sel)
        # One device-side concat -> ONE digest readback (each extra D2H costs
        # a fixed round trip).
        with _profiler.phase("sha_wait"):
            if parts:
                alld = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                        else parts[0])
                alld.copy_to_host_async()
            else:  # empty block: no chunks, no digests
                alld = None
        job._sha_parts = (sels, [p.shape[0] for p in parts], alld)
        job._ev_sha = evs
        job.block = None  # cuts are final; release the u8 image

    def finish(self, job: BlockJob) -> tuple[np.ndarray, np.ndarray]:
        if job._sha_parts is None:
            self.start_sha(job)
        sels, lane_counts, digs_dev = job._sha_parts
        out = np.empty((len(job.cuts), 32), dtype=np.uint8)
        if digs_dev is not None:
            with _profiler.phase("sha_wait"):
                digs = np.asarray(digs_dev)
                for i, ev in enumerate(job._ev_sha or ()):
                    _ledger.readback(ev,
                                     d2h_bytes=digs.nbytes if i == 0 else 0)
                job._ev_sha = None
                at = 0
                for sel, L in zip(sels, lane_counts):
                    out[sel] = digs[at:at + sel.size]
                    at += L
        job.words = None  # release the HBM word image
        return job.cuts, out

    def reduce(self, data: bytes | np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Synchronous single-block convenience: (cuts, digests).  Host
        bytes ride the fused group path as a group of one; device-resident
        arrays and n == 0 keep the per-block XLA path."""
        if self.fused != "off" and not isinstance(data, jax.Array):
            a = (np.frombuffer(data, dtype=np.uint8)
                 if not isinstance(data, np.ndarray) else data)
            if a.size:
                bj = self.submit_many([a])
                self.start_sha_many(bj)
                return self.finish_many(bj)[0]
        job = self.submit(data)
        self.start_sha(job)
        return self.finish(job)
