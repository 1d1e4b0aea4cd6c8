"""The plain reference against a second witness (the program's native
library, which the benchmark itself never consults), and the trace reduction
on a small recorded v5e trace."""

import os
import sys

import numpy as np

from common import BENCH, HERE

sys.path.insert(0, BENCH)

CDC = {"mask_bits": 13, "min_chunk": 2048, "max_chunk": 65536}


def test_reference_cuts_and_digests_match_the_native_witness():
    from generators import teragen, versions
    from hdrf_tpu import native
    from hdrf_tpu.config import CdcConfig
    from hdrf_tpu.ops.dispatch import gear_mask
    from reference import chunking as ref

    cdc = CdcConfig()
    assert (cdc.mask_bits, cdc.min_chunk, cdc.max_chunk) == (13, 2048, 65536)
    assert ref.spread_mask(13) == gear_mask(cdc)
    p = {"file_bytes": (5 << 20) + 77, "median_file_bytes": 6144,
         "sigma": 1.4, "edit_share": 0.03, "churn_share": 0.005,
         "edit_lines_max": 8}
    bufs = [teragen.Source(p, 3, 0).file(0), np.zeros(300_000, np.uint8),
            np.arange(100, dtype=np.uint8)]
    p["file_bytes"] = 4 << 20
    bufs.append(versions.Source(p, 3, 0).file(1))
    for buf in bufs:
        want = native.cdc_chunk(buf, gear_mask(cdc), cdc.min_chunk,
                                cdc.max_chunk)
        assert ref.cuts(buf, CDC) == want.tolist()
        table, n = ref.chunk_table(buf, CDC, 2 << 20)
        assert sum(table.values()) <= buf.size and n >= len(table)
    buf = bufs[0]
    cuts = native.cdc_chunk(buf, gear_mask(cdc), cdc.min_chunk, cdc.max_chunk)
    starts = np.concatenate([[0], cuts[:-1]]).astype(np.uint64)
    digs = native.sha256_batch(buf, starts, (cuts - starts).astype(np.uint64))
    table, n = ref.chunk_table(buf, CDC, buf.size)
    assert n == len(cuts) and {d.tobytes() for d in digs} == set(table)


def test_reference_lz4_decodes_what_the_native_encoder_wrote():
    from generators import teragen
    from hdrf_tpu import native
    from reference import chunking as ref

    buf = teragen.Source({"file_bytes": 1 << 20}, 9, 0).file(0)
    comp = bytes(native.lz4_compress(buf))
    assert len(comp) < buf.size // 2
    assert ref.lz4_block_decode(comp, buf.size) == buf.tobytes()


def test_trace_reduction_on_a_recorded_v5e_trace():
    """``data/one_block.xplane.pb``: one 128 MiB TeraGen block reduced by the
    program's ResidentReducer on a TPU v5 lite (my chip run, PR 24)."""
    import trace_reduce

    s = trace_reduce.reduce_file(os.path.join(HERE, "data",
                                              "one_block.xplane.pb"), 0.5)
    assert s["chips"] == 1 and s["device_events"] == 285
    assert abs(s["busy_s"] - 0.079142134) < 1e-9
    progs = s["programs"]
    assert progs["jit__prep_impl"]["count"] == 1
    assert abs(progs["jit__prep_impl"]["seconds"] - 0.060209768) < 1e-9
    assert progs["jit__bucket_sha_dma"]["count"] == 2
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["device_ops"][0][0] == "jit__prep_impl/fusion.1"
    # idle before the first and after the last operation counts: the traced
    # half second is 79 ms busy, and the gaps with it make the window
    assert s["idle_gaps"][0][0] == "host:np.asarray(jax.Array)"
    assert abs(sum(g[1] for g in s["idle_gaps"]) + s["busy_s"] - 0.5) < 1e-3
    busy_modules = sum(p["seconds"] for p in progs.values())
    assert s["busy_s"] <= busy_modules * 1.001

    # the readers on top of it: a roofline share from bytes and device time
    from readers import device_idle, hbm_roofline

    src = {"trace": dict(s, window_s=0.5, stats={"bytes_reduced": 128 << 20},
                         lz4={}),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"cluster": {"container_size": 32 << 20}}}
    share = hbm_roofline.read(src, {"bytes": "reduced",
                                    "exclude": ["match_scan"]})
    assert 0.1 < share < 0.5
    assert hbm_roofline.read(src, {"bytes": "scanned",
                                   "include": ["match_scan"]}) is None
    assert 80 < device_idle.read(src, {}) < 90
    assert device_idle.read({"trace": None}, {}) is None
