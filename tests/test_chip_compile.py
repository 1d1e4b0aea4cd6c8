"""Compile the chip's default path for a v5e that is described, not attached.

The TPU compiler is installed in the sandbox; ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and ``.lower(...).compile()`` raises what the
chip's compiler would raise — Mosaic's refusals, VMEM/HBM exhaustion, kernels
that cannot be partitioned — at no chip time.  One case per Pallas kernel and
per jitted program the worker's TPU default path runs, at the deployment's
real widths (``CdcConfig()`` 13 / 2 KiB / 64 KiB, 128 MiB block, 32 MiB
container), plus the four-device ``reduce_sharded`` programs.  Nothing runs:
a pass here is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture, never at import,
and every case lives in this one file: only one process at a time may load
the TPU library, and under ``-n 6 --dist loadfile`` that is the worker this
file goes to.  Code that asks ``jax.default_backend()`` sees the CPU here, so
each case compiles the kernel or the jitted step itself and steers from the
test (``impl="pallas"``, ``fused="mosaic"``, a patched ``default_backend``).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from hdrf_tpu.config import CdcConfig, NameNodeConfig, ReductionConfig

BLOCK = NameNodeConfig().block_size           # 128 MiB
CONTAINER = ReductionConfig().container_size  # 32 MiB
CDC = CdcConfig()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from hdrf_tpu.parallel.sharded import make_mesh

    return make_mesh(n_data=1, n_seq=4, devices=list(topo.devices)[:4])


def _reducer():
    from hdrf_tpu.ops.resident import ResidentReducer

    return ResidentReducer(CDC, fused_mode="off")


def _compile(fn, *shapes, sharding, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = (fn.lower(*args, **static) if hasattr(fn, "lower")
               else jax.jit(fn).lower(*args))
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    return compiled


# ------------------------------------------------------------ single chip

def _sha(lanes, bucket):
    from hdrf_tpu.ops.sha256_pallas import sha256_words_pallas

    return (sha256_words_pallas,
            [((lanes, bucket * 16), jnp.uint32), ((lanes,), jnp.int32)], {})


def _gather(lanes, bucket):
    from hdrf_tpu.ops.gather_pallas import gather_pad_messages

    nw = BLOCK // 4 + _reducer().pad_words
    return (lambda w, ol: gather_pad_messages(w, ol, bucket),
            [((nw,), jnp.uint32), ((2, lanes), jnp.int32)], {})


def _sort_rows(t, e, n_val):
    from hdrf_tpu.ops import sort_pallas

    return (lambda k, *v: sort_pallas.sort_rows(k, *v, impl="pallas"),
            [((t, e), jnp.int32)] * (1 + n_val), {})


def _match_deltas(t, e):
    from hdrf_tpu.ops import sort_pallas

    return (lambda v: sort_pallas.match_deltas(v, None, 2, 16, impl="pallas"),
            [((t, e), jnp.uint32)], {})


def _prep(rung=0, block=BLOCK):
    from hdrf_tpu.ops import resident

    r = _reducer()
    assert resident.block_rung(block) == block
    cap = r._cap(block, rung)
    if rung == 0:     # the first shot keeps the capacity every tree has had
        assert cap == (block >> (CDC.mask_bits - 1)) + 1024
    # the block at its rung and its true length, a traced scalar
    return (resident._prep, [((block,), jnp.uint8), ((), jnp.uint32)],
            dict(mask=r.mask, cap=cap, pad_words=r.pad_words))


def _bucket_sha(lanes, bucket, block=BLOCK):
    from hdrf_tpu.ops import resident

    nw = block // 4 + _reducer().pad_words
    return (resident._bucket_sha_dma,
            [((nw,), jnp.uint32), ((2, lanes), jnp.int32)],
            dict(bucket=bucket))


_B_SMALL = (2 << CDC.mask_bits) // 64           # 256: twice the mean chunk
_B_BIG = (CDC.max_chunk + 9 + 63) // 64         # 1025: max_chunk

ONE_CHIP = {
    # ops/sha256_pallas.py — the two bucket widths of ResidentReducer
    "sha-small": lambda: _sha(8192, _B_SMALL),
    "sha-big": lambda: _sha(1024, _B_BIG),
    # ops/gather_pallas.py — DMA gather at the batched path's half bucket
    # and the two per-block buckets
    "gather-128": lambda: _gather(4096, _B_SMALL // 2),
    "gather-256": lambda: _gather(8192, _B_SMALL),
    "gather-1025": lambda: _gather(1024, _B_BIG),
    # ops/sort_pallas.py — the L1 and L2 record pack sorts of a 32 MiB
    # container (stride 2: 16 Mi entries = 2048 rows of 8192; 128 rows of
    # p1*t3/128 = 8192), the widest rows that take the kernel
    "sort-rows-L1": lambda: _sort_rows(CONTAINER // 2 // 8192, 8192, 2),
    "sort-rows-L2": lambda: _sort_rows(128, 8192, 2),
    # match_deltas is off the default path (its e = 65536 rows exceed
    # _MAX_E and take lax.sort); the kernel is kept for stride-4 rows and
    # guarded here at a width that compiles in seconds
    "match-deltas": lambda: _match_deltas(8, 8192),
    # the worker's jitted steps around those kernels, at a full block
    "prep-128MiB": _prep,
    # the candidate-capacity ladder at the rung a tar stream needs (270 336
    # words); the other rungs differ from it by that one size
    "prep-128MiB-rung3": lambda: _prep(3),
    "bucket-sha-small": lambda: _bucket_sha(16384, _B_SMALL),
    "bucket-sha-big": lambda: _bucket_sha(4096, _B_BIG),
    # the block-length ladder (PR 31): a small file's block at the 3 MiB
    # rung and at the 1 MiB floor, with the lane counts the rungs give
    "prep-3MiB": lambda: _prep(block=3 << 20),
    "prep-1MiB": lambda: _prep(block=1 << 20),
    "bucket-sha-small-3MiB": lambda: _bucket_sha(512, _B_SMALL, 3 << 20),
    "bucket-sha-big-1MiB": lambda: _bucket_sha(128, _B_BIG, 1 << 20),
}


@pytest.mark.parametrize("case", sorted(ONE_CHIP))
def test_one_chip_program_compiles_for_v5e(case, one_chip):
    fn, shapes, static = ONE_CHIP[case]()
    _compile(fn, *shapes, sharding=one_chip, **static)


def test_match_scan_compiles_for_v5e(one_chip, monkeypatch):
    """The container seal's whole device program at 32 MiB, with the sort
    sites choosing by shape as they do on the chip: Pallas for L1/L2,
    ``jax.lax.sort`` for the match-delta, L3 and escape sorts."""
    from hdrf_tpu.ops import lz4_tpu, sort_pallas

    monkeypatch.setattr(sort_pallas, "use_pallas", lambda: True)
    lz = lz4_tpu.TpuLz4()
    p1, p2, p3 = lz._shapes(CONTAINER)
    compiled = _compile(lz4_tpu._match_scan, ((CONTAINER,), jnp.uint8),
                        sharding=one_chip, stride=lz.stride,
                        min_len=lz.min_len, p1=p1, p2=p2, p3=p3)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e,n_val", [(65536, 1), (131072, 1), (524288, 1)])
def test_wide_sort_rows_take_lax_sort(e, n_val):
    """Rows past _MAX_E never reach Mosaic (compile minutes / VMEM): the
    escape packs, the match-delta rows and the L3 pack of the default
    geometry."""
    from hdrf_tpu.ops import sort_pallas

    shapes = [jax.ShapeDtypeStruct((1, e), jnp.int32)] * (1 + n_val)
    jaxpr = jax.make_jaxpr(
        lambda k, *v: sort_pallas.sort_rows(k, *v, impl="pallas"))(*shapes)
    assert "pallas_call" not in str(jaxpr) and "sort" in str(jaxpr)


# -------------------------------------------------------------- four chips

def test_sharded_scan_kernel_compiles_for_v5e(one_chip):
    """ops/cdc_pallas._scan_call at one shard of a 128 MiB block over four."""
    from hdrf_tpu.ops import cdc_pallas

    m, R = BLOCK // 4, 128
    T = -(-(m + 32) // (R * 512))
    fn = cdc_pallas._scan_call(T, R, m, False)
    _compile(fn, ((1, 1), jnp.int32), ((1, 1), jnp.uint32),
             ((T * R, 128), jnp.uint32), sharding=one_chip)


def test_reduce_sharded_scan_compiles_on_four_chips(mesh4):
    """Stage 1 of reduce_sharded: the seq-sharded candidate scan with its
    ppermute halo, the Pallas scan kernel inside shard_map."""
    from hdrf_tpu.parallel.sharded import candidate_words_sharded

    fn = candidate_words_sharded(mesh4, fused="mosaic")
    compiled = fn.lower(
        jax.ShapeDtypeStruct((BLOCK,), jnp.uint8,
                             sharding=NamedSharding(mesh4, P("seq"))),
        jax.ShapeDtypeStruct((), jnp.uint32,
                             sharding=NamedSharding(mesh4, P()))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text


def test_reduce_sharded_sha_compiles_on_four_chips(mesh4, monkeypatch):
    """Stage 3 of reduce_sharded at a 128 MiB block: the data-local SHA
    (one ppermute halo shard, XLA gather, Pallas SHA inside shard_map)."""
    from hdrf_tpu.ops.resident import _bucket_of
    from hdrf_tpu.parallel import sharded

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bucket = _bucket_of(_B_BIG)
    pad_words = -(-(bucket * 16 + 16) // 128) * 128
    fn = sharded._sha_chunks_halo(mesh4, bucket, pad_words, 1)
    lanes = 4096                                 # per device, one block
    compiled = fn.lower(
        jax.ShapeDtypeStruct((BLOCK,), jnp.uint8,
                             sharding=NamedSharding(mesh4, P("seq"))),
        jax.ShapeDtypeStruct((1, 4, 2, lanes), jnp.int32,
                             sharding=NamedSharding(mesh4, P("data", "seq")))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
