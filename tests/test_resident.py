"""Device-resident reduction pipeline (ops/resident.py) against the native
C++ oracle — including the degenerate inputs the verify skill calls out
(zero runs make every position a Gear candidate; empty blocks are legal)."""

import hashlib
import os

import numpy as np
import pytest

from hdrf_tpu import native
from hdrf_tpu.config import CdcConfig
from hdrf_tpu.ops import resident
from hdrf_tpu.ops.dispatch import gear_mask
from hdrf_tpu.ops.resident import ResidentReducer
from hdrf_tpu.utils import metrics


@pytest.fixture(scope="module")
def reducer():
    return ResidentReducer(CdcConfig())


def _oracle(data: np.ndarray, cdc: CdcConfig):
    cuts = native.cdc_chunk(data, gear_mask(cdc), cdc.min_chunk, cdc.max_chunk)
    starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
    digs = native.sha256_batch(data, starts, (cuts - starts).astype(np.uint64))
    return cuts, digs


def test_matches_oracle(reducer):
    rng = np.random.default_rng(3)
    a = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
    cuts, digs = reducer.reduce(a)
    wc, wd = _oracle(a, reducer.cdc)
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)


def test_unaligned_length(reducer):
    rng = np.random.default_rng(4)
    a = rng.integers(0, 256, size=777_777, dtype=np.uint8)
    cuts, digs = reducer.reduce(a)
    wc, wd = _oracle(a, reducer.cdc)
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)


def test_dense_candidates_zero_run(reducer):
    """A long zero run makes every position a candidate (G[0]==0); the packed
    candidate capacity overflows and the pipeline must retry, not raise."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
    a[100_000:900_000] = 0
    cuts, digs = reducer.reduce(a)
    wc, wd = _oracle(a, reducer.cdc)
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)


def test_all_zeros(reducer):
    a = np.zeros(300_000, dtype=np.uint8)
    cuts, digs = reducer.reduce(a)
    wc, wd = _oracle(a, reducer.cdc)
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)


def test_empty_block(reducer):
    cuts, digs = reducer.reduce(b"")
    assert cuts.size == 0 and digs.shape == (0, 32)


def test_overlapped_jobs(reducer):
    rng = np.random.default_rng(6)
    blocks = [rng.integers(0, 256, size=1 << 19, dtype=np.uint8)
              for _ in range(3)]
    jobs = [reducer.submit(b) for b in blocks]
    for j in jobs:
        reducer.start_sha(j)
    for b, j in zip(blocks, jobs):
        cuts, digs = reducer.finish(j)
        wc, wd = _oracle(b, reducer.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)


def test_reduce_many_batched(reducer):
    """The batched path (one dispatch + one readback per stage for a group
    of equal-length blocks) must be bit-identical to the per-block path and
    the native oracle — including dense-candidate retries and mixed sizes
    that fall back per block."""
    rng = np.random.default_rng(7)
    blocks = [rng.integers(0, 256, size=1 << 19, dtype=np.uint8)
              for _ in range(3)]
    odd = rng.integers(0, 256, size=(1 << 19) + 999, dtype=np.uint8)
    dense = np.zeros(1 << 19, dtype=np.uint8)  # every position a candidate
    dense2 = dense.copy()
    inputs = blocks + [odd, dense, dense2, np.empty(0, np.uint8)]
    results = reducer.reduce_many(inputs)
    assert len(results) == len(inputs)
    for data, (cuts, digs) in zip(inputs, results):
        if data.size == 0:
            assert cuts.size == 0 and digs.shape == (0, 32)
            continue
        wc, wd = _oracle(data, reducer.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)


def test_fused_front_end_matches_oracle():
    """The fused Pallas front end (HDRF_CDC_PALLAS; interpret mode on the
    CPU mesh) drives the SAME reduce_many contract: mixed sizes, a dense
    zero block that fills the cut table to within two entries of the plan
    cap (every position a candidate), and an empty block — all
    oracle-identical.  The overflow fallback proper and the ledger shape
    are pinned in tests/test_cdc_pallas.py."""
    rng = np.random.default_rng(8)
    reducer = ResidentReducer(CdcConfig(), fused_mode="interpret")
    inputs = [rng.integers(0, 256, size=1 << 19, dtype=np.uint8),
              rng.integers(0, 256, size=333_333, dtype=np.uint8),
              np.zeros(1 << 19, dtype=np.uint8),
              np.empty(0, np.uint8)]
    results = reducer.reduce_many(inputs)
    assert len(results) == len(inputs)
    for data, (cuts, digs) in zip(inputs, results):
        if data.size == 0:
            assert cuts.size == 0 and digs.shape == (0, 32)
            continue
        wc, wd = _oracle(data, reducer.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)


def test_batch_lane_count_steps():
    from hdrf_tpu.ops.resident import _lane_count_geo

    assert _lane_count_geo(1) == 128
    assert _lane_count_geo(128) == 128
    assert _lane_count_geo(129) == 256
    assert _lane_count_geo(1025) == 1152  # step 2048/16=128 above 1024
    for n in (5475, 43800, 65537, 70000):
        L = _lane_count_geo(n)
        assert L >= n and L % 128 == 0 and (L - n) / n <= 0.126


def test_sha_kernel_nonmultiple_tile_lane_rows():
    """Regression: lane counts whose 128-row count is NOT a multiple of the
    SHA kernel's row tile (e.g. L=3840 -> 30 rows, tile 8) must still hash
    every lane.  The grid used to FLOOR the tile count, leaving the tail
    rows unprocessed — returning stale device memory that could even equal
    the right digests when a previous dispatch had hashed the same content
    (how the bug hid from identical-block tests while corrupting mixed
    batches)."""
    import hashlib

    import jax

    from hdrf_tpu.ops.sha256 import sha256_words

    for L in (384, 2176, 3840):
        rng = np.random.default_rng(L)
        data = rng.integers(0, 256, size=(L, 32), dtype=np.uint8)
        w = np.zeros((L, 16), dtype=np.uint32)
        be = data.reshape(L, 8, 4).astype(np.uint32)
        w[:, :8] = (be[:, :, 0] << 24) | (be[:, :, 1] << 16) \
            | (be[:, :, 2] << 8) | be[:, :, 3]
        w[:, 8] = 0x80000000
        w[:, 15] = 256
        nb = np.ones(L, np.int32)
        if jax.default_backend() == "cpu":
            out = np.asarray(sha256_words(jax.device_put(w),
                                          jax.device_put(nb)))
        else:
            from hdrf_tpu.ops.sha256_pallas import sha256_words_pallas

            out = np.asarray(sha256_words_pallas(jax.device_put(w),
                                                 jax.device_put(nb)))
        for i in (0, L // 2, L - 1, L - 128, L - 129 if L > 129 else 0):
            assert bytes(out[i]) == hashlib.sha256(
                data[i].tobytes()).digest(), (L, i)


def test_mixed_batch_distinct_blocks_match_oracle(reducer):
    """Regression companion: a batch of DISTINCT blocks (the bench shape
    that exposed the stale-row bug) must be oracle-identical per block."""
    rng = np.random.default_rng(77)
    blocks = []
    for i in range(4):
        a = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
        a[: 1 << 18] = rng.integers(97, 123, size=1 << 18, dtype=np.uint8)
        blocks.append(a)
    for data, (cuts, digs) in zip(blocks, reducer.reduce_many(blocks)):
        wc, wd = _oracle(data, reducer.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)


# ---- the candidate-capacity ladder (tar streams, BASELINE config 3) ----


@pytest.fixture(scope="module")
def versions_tar(perfbench_file):
    """``versions_tar(n, generations, seed=7)``: successive releases of one
    seeded tree as ``n``-byte tar streams — BASELINE config 3's stand-in
    corpus, made as the benchmark makes it."""
    import json

    with open(os.path.join(perfbench_file.root, "configs",
                           "versions-dedup.json")) as f:
        data = {k: v for k, v in json.load(f)["data"].items()
                if k != "generator"}
    source = perfbench_file("generators/versions.py").Source

    def make(n: int, generations: int, seed: int = 7) -> list:
        src = source(dict(data, file_bytes=n), seed, 0)
        return [src.file(g) for g in range(generations)]

    return make


TAR_BYTES = 4 << 20     # about 5 100 candidate words against 2 048 first-shot


def _reference(chunking, data: np.ndarray, cdc: CdcConfig):
    """Cuts and SHA-256 digests by ``perfbench/reference/chunking.py``
    (numpy window doubling, ``hashlib``): no line of the program."""
    ends = chunking.cuts(
        data, {"mask_bits": cdc.mask_bits, "min_chunk": cdc.min_chunk,
               "max_chunk": cdc.max_chunk})
    view, start, digs = memoryview(np.ascontiguousarray(data)), 0, []
    for end in ends:
        digs.append(hashlib.sha256(view[start:end]).digest())
        start = end
    return (np.asarray(ends, np.uint64),
            np.frombuffer(b"".join(digs), np.uint8).reshape(-1, 32))


def _prep_retries() -> int:
    return metrics.registry("resident").counter("prep_retries")


@pytest.mark.parametrize("path", ["submit", "submit_many"])
@pytest.mark.parametrize("corpus", ["tar", "zeros"])
def test_zero_dense_blocks_match_the_plain_reference(corpus, path,
                                                     versions_tar,
                                                     perfbench_file):
    """Runs of zeros (tar padding; gear(0) == 0, so every position in one
    is a candidate) overflow the first-shot capacity: both paths must give
    the plain reference's cuts and digests exactly, having retried."""
    blocks = (versions_tar(TAR_BYTES, 2) if corpus == "tar"
              else [np.zeros(1 << 20, np.uint8)] * 2)
    chunking = perfbench_file("reference/chunking.py")
    r = ResidentReducer(CdcConfig())
    before = _prep_retries()
    if path == "submit":
        jobs = [r.submit(b) for b in blocks]
        for j in jobs:
            r.start_sha(j)
        got = [r.finish(j) for j in jobs]
    else:
        bj = r.submit_many(blocks)
        r.start_sha_many(bj)
        got = r.finish_many(bj)
    assert _prep_retries() > before
    for b, (cuts, digs) in zip(blocks, got):
        wc, wd = _reference(chunking, b, r.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)


def test_tar_stream_compiles_rungs_not_blocks(versions_tar):
    """Eight releases with eight different candidate counts: the retry is
    paid once, later blocks are dispatched at the rung it found, and _prep
    is compiled for two capacities (first shot, that rung), not per block."""
    blocks = versions_tar(TAR_BYTES, 8, seed=11)
    r = ResidentReducer(CdcConfig())
    ceiling = TAR_BYTES // 32
    ladder = sorted({r._cap(TAR_BYTES, k) for k in range(32)})
    assert ladder[0] == (TAR_BYTES >> 12) + 1024 and ladder[-1] == ceiling
    assert len(ladder) <= 8
    programs, retries = resident._prep._cache_size(), _prep_retries()
    counts = set()
    for i, b in enumerate(blocks):
        job = r.submit(b)
        counts.add(int(np.asarray(job.cand)[0]))
        cuts, digs = r.finish(job)
        wc, wd = _oracle(b, r.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)
        assert _prep_retries() == retries + 1, f"block {i}"
        assert job.cap in ladder
    assert len(counts) == 8
    assert job.cap > ladder[0] and job.cap >= max(counts)
    assert resident._prep._cache_size() - programs <= 2
    assert metrics.registry("resident").snapshot()["gauges"][
        "prep_cap_words"] == job.cap


def test_random_block_stays_on_the_first_rung():
    """Content-like data never overflows: it runs the first-shot program,
    the one every earlier tree compiled (the persistent cache's key)."""
    rng = np.random.default_rng(12)
    a = rng.integers(0, 256, size=1 << 20, dtype=np.uint8)
    r = ResidentReducer(CdcConfig())
    before = _prep_retries()
    job = r.submit(a)
    cuts, digs = r.finish(job)
    wc, wd = _oracle(a, r.cdc)
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)
    assert job.rung == 0 and job.cap == (a.size >> 12) + 1024
    assert _prep_retries() == before


# ---- the block-length ladder (small files, SLive's create mix; PR 31) ----

RUNG = 3 << 19           # 1.5 MiB: a rung that is no power of two


def _ladder_bytes(n: int, tail: str, seed: int = 31) -> np.ndarray:
    a = np.random.default_rng([seed, n]).integers(0, 256, size=n,
                                                  dtype=np.uint8)
    if tail == "zeros":
        a[max(n - 3000, 0):] = 0
    return a


def test_the_ladder_is_fixed_and_every_power_of_two_a_rung():
    rung = resident.block_rung
    assert rung(RUNG) == RUNG and rung(RUNG - 1) == RUNG
    assert rung(RUNG + 1) == 2 << 20
    assert [rung(n) for n in (1, 511, 4096, 1 << 20)] == [1 << 20] * 4
    for k in range(20, 31):
        assert rung(1 << k) == 1 << k and rung((1 << k) + 1) == 3 << (k - 1)
    lengths = range(512, (128 << 20) + 1, 4096)
    rungs = sorted({rung(n) for n in lengths})
    assert len(rungs) == 15 and rungs[-1] == 128 << 20
    assert all(r % resident._PAD_GRID == 0 for r in rungs)
    assert all(n <= rung(n) < 2 * max(n, 1 << 20) for n in lengths)


@pytest.mark.parametrize("tail", ["random", "zeros"])
@pytest.mark.parametrize("n", [1, 511, 2047, 4096, RUNG, RUNG - 1, RUNG + 1,
                               1_000_003, 4_194_304])
def test_any_length_matches_the_plain_reference(n, tail, perfbench_file):
    """A block of any length lands at its rung, padded with zeros; cuts and
    digests are the plain reference's, exactly — under ``min_chunk``, at a
    rung and either side of one, and with a zero tail INSIDE the file (its
    zeros are candidates, the pad's are not)."""
    a = _ladder_bytes(n, tail)
    chunking = perfbench_file("reference/chunking.py")
    r = ResidentReducer(CdcConfig())
    before = _prep_retries()
    job = r.submit(a)
    assert job.block.shape[0] == resident.block_rung(n)
    assert job.cap == r._cap(job.block.shape[0], 0)
    cuts, digs = r.finish(job)
    if n >= 32:
        wc, wd = _reference(chunking, a, r.cdc)
    else:   # the reference's window doubling wants a whole 32-byte window
        # (PERF.md section 7): under one the file is one chunk by definition
        wc = np.asarray([n], np.uint64)
        wd = np.frombuffer(hashlib.sha256(a).digest(), np.uint8)[None]
    np.testing.assert_array_equal(cuts, wc)
    np.testing.assert_array_equal(digs, wd)
    assert _prep_retries() == before and r._rung == 0


def test_a_zero_pad_adds_no_candidate_and_a_zero_tail_does(perfbench_file):
    """100 000 bytes land at the 1 MiB rung: 948 576 pad zeros, 29 643
    bitmap words if they counted (gear(0) == 0) against a first-shot
    capacity of 1 280.  The device's count of candidate words is the
    reference's over the true bytes alone; 3 000 zeros at the end of the
    FILE add theirs."""
    chunking = perfbench_file("reference/chunking.py")
    r = ResidentReducer(CdcConfig())

    def words(a):
        job = r.submit(a)
        count = int(np.asarray(job.cand)[0])
        want = chunking.candidates(a, chunking.spread_mask(r.cdc.mask_bits))
        assert count == np.unique((want - 1) // 32).size
        r.finish(job)
        return count

    plain = words(_ladder_bytes(100_000, "random"))
    tailed = words(_ladder_bytes(100_000, "zeros"))
    assert plain < 40                      # one candidate in 8 192 positions
    assert tailed >= plain + (3000 - 32) // 32 - 2
    # the batched path masks each member at its own length too: a short
    # member beside a long one overflows nothing
    before = _prep_retries()
    short, long_ = _ladder_bytes(30_000, "random"), _ladder_bytes(
        400_000, "random")
    for data, (cuts, digs) in zip((short, long_),
                                  r.reduce_many([short, long_])):
        wc, wd = _oracle(data, r.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)
    assert _prep_retries() == before


def test_forty_files_of_the_cell_compile_rungs_not_files(slive_files,
                                                        perfbench_file):
    """Forty lengths drawn as ``small-files.create`` draws them, through one
    reducer: ``_prep`` is compiled a rung they span, not a file; no pad
    overflowed the first-shot capacity or moved the remembered rung."""
    files = slive_files(40)
    sizes = [f.size for f in files]
    assert len(set(sizes)) == 40 and min(sizes) >= 4096 \
        and max(sizes) <= 4 << 20
    rungs = {resident.block_rung(n) for n in sizes}
    chunking = perfbench_file("reference/chunking.py")
    r = ResidentReducer(CdcConfig())
    programs, retries = resident._prep._cache_size(), _prep_retries()
    sha_programs = resident._bucket_sha._cache_size()
    for a in files:
        cuts, digs = r.finish(r.submit(a))
        wc, wd = _reference(chunking, a, r.cdc)
        np.testing.assert_array_equal(cuts, wc)
        np.testing.assert_array_equal(digs, wd)
    assert resident._prep._cache_size() - programs <= len(rungs) <= 5
    # the lane counts are floored by the rung: one SHA program a bucket
    assert resident._bucket_sha._cache_size() - sha_programs <= 2 * len(rungs)
    assert _prep_retries() == retries and r._rung == 0


@pytest.mark.parametrize("small, big, lanes", [
    (8_968, 2_578, (16384, 4096)),      # a TeraGen block (teragen-1dn)
    (17_411, 1_362, (32768, 2048)),     # a tar generation (versions-dedup)
    (1, 1, (16384, 2048))])             # the floors themselves
def test_the_lane_floors_keep_a_full_blocks_programs(small, big, lanes):
    """At 128 MiB the rung's lane floors sit at or under what the accepted
    cells' data needs, so their SHA programs are the ones they had."""
    cdc = CdcConfig()
    size = 128 << 20
    got = (resident._lane_count(max(small, size >> cdc.mask_bits)),
           resident._lane_count(max(big, size // cdc.max_chunk)))
    assert got == lanes
    assert (resident._lane_count(small), resident._lane_count(big)) == \
        (lanes if small > 1 else (128, 128))
