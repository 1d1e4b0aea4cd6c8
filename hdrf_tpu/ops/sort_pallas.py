"""Pallas fused bitonic KV sort: the match scan's sort engine on TPU.

``jax.lax.sort`` is a general-purpose comparator sort; the LZ4 match scan
(ops/lz4_tpu.py) only ever sorts u32/i32 keys with one or two carried u32
values over power-of-two rows, and that shape admits a far cheaper program:
a bitonic merge network over the (rows, 128)-tiled VPU layout where every
compare-exchange is two ``pltpu.roll`` s + a select, entirely in VMEM
registers.  One kernel invocation fuses what XLA runs as separate HBM
round trips:

- ``match_deltas`` — the whole delta pipeline of the match scan: in-kernel
  key construction (hash16 << pos_bits | position; the _pos2_row interleave
  for stride 2), the hash-group bitonic sort, the neighbor compare
  (collision-exact, degenerate-gram exclusion, 65535 offset cap) fused
  between the merge networks, and the un-permute bitonic sort back to
  position order — one HBM read of the 4-gram image, one HBM write of the
  position-ordered deltas.
- ``sort_rows`` — the generic per-row KV sort used by the record pack
  sorts (L1/L2/L3 and the escape packs of the packed readback).

Network shape: element i of a row lives at tile (i // 128, i % 128); a
compare-exchange at stride j is a sublane roll (j >= 128) or a lane roll
(j < 128) pair selected by bit j of the index, so no stage gathers.
Unsigned key order is preserved by biasing u32 keys into i32 once at load
(x ^ 0x80000000) and unbiasing at store.  The network is unstable where
keys tie; every call site here either has unique keys (position-salted) or
ties only among don't-care slots (invalid-record padding), which is why
results are bit-identical to ``jax.lax.sort`` on the live data
(tests/test_sort_pallas.py asserts both properties).

``jax.lax.sort`` runs instead off-TPU (the 8-virtual-device CPU test
mesh), for sub-1024-entry rows (tile underflow), for rows wider than
``_MAX_E`` (compile time and VMEM, see there), and under
``HDRF_SORT_PALLAS=0``; ``interpret=True`` runs the same kernel through the
Pallas interpreter so the CPU mesh can execute the network itself.  At the
default geometry (32 MiB container, stride 2) that puts the L1 and L2 record
pack sorts (e = 8192) on the kernel and the match-delta sorts (e = 65536),
the L3 pack (e = 524288) and the escape packs (e = 131072) on
``jax.lax.sort``.

Re-expresses the sort stage the reference reaches through its JNI hash
table (DataDeduplicator.java:770-781 codec path) in the TPU-native
"sorting is the hash table" formulation (SURVEY.md; ops/lz4_tpu.py module
docstring).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MIN_E = 1024          # below this the (R, 128) view loses whole-tile rows
# Widest row the kernels take.  The networks are fully unrolled, so Mosaic's
# compile time grows faster than e*log^2(e): compiled for v5e (PR 22),
# sort_rows with one value took 11 s at e=32768, 39 s at 65536 and 149 s at
# 131072; match_deltas took 35 s at 32768 and at 65536 ran out of VMEM after
# 134 s.  Wider rows take ``jax.lax.sort``, chosen here by shape.
_MAX_E = 32768
_BIAS = np.uint32(0x80000000)
_HASH_MUL = np.uint32(2654435761)   # golden-ratio multiplier (lz4.cpp hash4)


def use_pallas() -> bool:
    """Trace-time gate: Mosaic kernels only on a real TPU backend (the
    test mesh is 8 virtual XLA:CPU devices), overridable for A/B timing."""
    if os.environ.get("HDRF_SORT_PALLAS", "1") == "0":
        return False
    return jax.default_backend() == "tpu"


def _to_i32(x):
    """Order-preserving reinterpret to i32 (u32 keys are biased so the
    network's signed compares realize unsigned order)."""
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x ^ _BIAS, jnp.int32)
    return x


def _from_i32(x, dtype):
    if dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _BIAS
    return x


def _bit(shape, b: int):
    """(i & b) != 0 over the flat index i = sublane*128 + lane of a
    (R, 128) tile, for a single-bit b.  Bits past the row range come out
    all-false, which is exactly the all-ascending final merge."""
    if b >= _LANES:
        return (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                & (b // _LANES)) != 0
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 1) & b) != 0


def _partner(x, j: int):
    """x[i ^ j] for single-bit stride j: the two roll directions selected
    by bit j of the index (pltpu.roll(x, s, ax): out[i] = x[i - s])."""
    if j >= _LANES:
        jr = j // _LANES
        fwd = pltpu.roll(x, jr, 0)                    # x[r - jr]
        bwd = pltpu.roll(x, x.shape[0] - jr, 0)       # x[r + jr]
    else:
        fwd = pltpu.roll(x, j, 1)
        bwd = pltpu.roll(x, _LANES - j, 1)
    return jnp.where(_bit(x.shape, j), fwd, bwd)


def _network(key, vals, e: int):
    """The bitonic merge network over one (R, 128) row of e = R*128
    entries.  i32 key, i32 values; ascending.  Equal-key pairs never
    exchange (both sides keep their own KV), so ties stay in place."""
    for kk in range(1, e.bit_length()):
        k = 1 << kk
        j = k >> 1
        while j:
            pk = _partner(key, j)
            pvs = [_partner(v, j) for v in vals]
            # want_max = ascending XOR low-slot; low-slot = bit j clear.
            want_max = jnp.logical_xor(~_bit(key.shape, k),
                                       ~_bit(key.shape, j))
            # (Mosaic refuses a select over two i1 vectors — "Unsupported
            # target bitwidth for truncation" — so the choice is and/or.)
            take = (want_max & (pk > key)) | (~want_max & (pk < key))
            key = jnp.where(take, pk, key)
            vals = [jnp.where(take, pv, v) for pv, v in zip(pvs, vals)]
            j >>= 1
    return key, vals


# ---------------------------------------------------------------- sort_rows


@functools.cache
def _sort_rows_call(e: int, n_val: int, key_unsigned: bool, interpret: bool):
    r = e // _LANES
    sign = np.int32(-2**31)       # bias on raw i32 bits == u32 ^ 0x80000000

    def kernel(*refs):
        key = refs[0][0]
        if key_unsigned:
            key = key ^ sign
        vals = [refs[1 + i][0] for i in range(n_val)]
        key, vals = _network(key, vals, e)
        if key_unsigned:
            key = key ^ sign
        refs[1 + n_val][0] = key
        for i in range(n_val):
            refs[2 + n_val + i][0] = vals[i]

    spec = pl.BlockSpec((1, r, _LANES), lambda i: (i, 0, 0))

    def call(key, *vals):
        t = key.shape[0]
        outs = pl.pallas_call(
            kernel,
            grid=(t,),
            in_specs=[spec] * (1 + n_val),
            out_specs=[spec] * (1 + n_val),
            out_shape=[jax.ShapeDtypeStruct((t, r, _LANES), jnp.int32)
                       ] * (1 + n_val),
            interpret=interpret,
        )(_i32_tiles(key, r), *[_i32_tiles(v, r) for v in vals])
        sk = jax.lax.bitcast_convert_type(outs[0], key.dtype).reshape(t, e)
        svs = [jax.lax.bitcast_convert_type(o, v.dtype).reshape(t, e)
               for o, v in zip(outs[1:], vals)]
        return (sk, *svs)

    return call


def _i32_tiles(x, r: int):
    """(t, e) -> (t, R, 128) i32 (raw bitcast; key bias happens in-kernel
    so padding constants supplied by callers keep their u32 meaning)."""
    x = jax.lax.bitcast_convert_type(x, jnp.int32)
    return x.reshape(x.shape[0], r, _LANES)


def _pow2_pad(key, vals, pad_key, pad_vals):
    """Pad rows to the next power of two so the network applies; pad keys
    must sort at or past every live key (callers pass their sentinel)."""
    e = key.shape[1]
    ep = 1 << (e - 1).bit_length()
    if ep == e:
        return key, vals
    ext = ((0, 0), (0, ep - e))
    key = jnp.pad(key, ext, constant_values=pad_key)
    vals = [jnp.pad(v, ext, constant_values=pv)
            for v, pv in zip(vals, pad_vals)]
    return key, vals


def sort_rows(key, *vals, impl: str | None = None, interpret: bool = False,
              pad_key=None, pad_vals=None):
    """Per-row ascending KV sort of (t, e) arrays (e along dimension 1):
    the drop-in for ``jax.lax.sort((key, *vals), dimension=1, num_keys=1)``
    at the match scan's call sites.  i32 or u32 key; i32/u32 values ride
    the same permutation.  Non-power-of-two rows are padded with
    ``pad_key``/``pad_vals`` (required then: the pad must be the caller's
    end-of-row sentinel) and the padded tail is returned too, so output
    width is the padded width only when e was already a power of two —
    callers that slice prefixes are unaffected.
    """
    if impl is None:
        impl = "pallas" if (use_pallas() or interpret) else "xla"
    e = key.shape[1]
    ep = 1 << (e - 1).bit_length()
    if impl != "pallas" or e < _MIN_E or ep > _MAX_E:
        return jax.lax.sort((key, *vals), dimension=1, num_keys=1)
    if ep != e:
        assert pad_key is not None, "non-pow2 rows need a pad sentinel"
        key, vals = _pow2_pad(key, list(vals), pad_key, pad_vals)
        e = ep
    return _sort_rows_call(e, len(vals), key.dtype == jnp.uint32,
                           interpret)(key, *vals)


# ------------------------------------------------------------- match_deltas


def _prev1(x, fill, shape):
    """Flat shift-right-by-one over the (R, 128) view: out[i] = x[i-1],
    out[0] = fill — the sorted-order left neighbor for the match compare."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lr = pltpu.roll(x, 1, 1)                 # x[r, c-1]; wrong at c == 0
    rr = pltpu.roll(lr, 1, 0)                # x[r-1, 127] lands at c == 0
    out = jnp.where(lane == 0, rr, lr)
    return jnp.where((lane == 0) & (row == 0), fill, out)


@functools.cache
def _match_deltas_call(e: int, stride: int, pos_bits: int, interpret: bool):
    r = e // _LANES
    pmask = np.uint32((1 << pos_bits) - 1)

    def kernel(v_ref, d_ref):
        shape = (r, _LANES)
        v = jax.lax.bitcast_convert_type(v_ref[0], jnp.uint32)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        idx = row * _LANES + lane
        if stride == 2:                       # _pos2_row: [0,2,4...,1,3,5...]
            half = e // 2
            posn = jnp.where(idx < half, 2 * idx, 2 * (idx - half) + 1)
        else:
            posn = idx
        posn = posn.astype(jnp.uint32)
        h = (v * _HASH_MUL) >> jnp.uint32(32 - 16)
        key = (h << jnp.uint32(pos_bits)) | posn

        sk, (sv,) = _network(
            _to_i32(key), [jax.lax.bitcast_convert_type(v, jnp.int32)], e)
        sk = _from_i32(sk, jnp.uint32)
        sv = jax.lax.bitcast_convert_type(sv, jnp.uint32)

        # Neighbor compare, fused between the two merge networks (exact
        # collision rejection via the carried 4-gram; degenerate-gram and
        # offset-cap rules identical to the XLA reference below).
        pk = _prev1(sk, jnp.uint32(0xFFFFFFFF), shape)
        pv = _prev1(sv, jnp.uint32(0), shape)
        same = (sk >> jnp.uint32(pos_bits)) == (pk >> jnp.uint32(pos_bits))
        nondegen = sv != ((sv << jnp.uint32(8)) | (sv >> jnp.uint32(24)))
        okm = same & (sv == pv) & nondegen
        delta = jnp.where(okm,
                          ((sk & pmask) - (pk & pmask)) * jnp.uint32(stride),
                          jnp.uint32(0))
        delta = jnp.where(delta <= jnp.uint32(65535), delta, jnp.uint32(0))

        # Un-permute to position order (pos keys unique per row; they fit
        # i32 directly, but the shared bias path keeps one compare form).
        _, (d,) = _network(
            _to_i32(sk & pmask),
            [jax.lax.bitcast_convert_type(delta, jnp.int32)], e)
        d_ref[0] = d

    spec = pl.BlockSpec((1, r, _LANES), lambda i: (i, 0, 0))

    def call(vals):
        t = vals.shape[0]
        out = pl.pallas_call(
            kernel,
            grid=(t,),
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((t, r, _LANES), jnp.int32),
            interpret=interpret,
        )(_i32_tiles(vals, r))
        return jax.lax.bitcast_convert_type(out, jnp.uint32).reshape(t, e)

    return call


def match_deltas_xla(vals, posn, stride: int, pos_bits: int):
    """The XLA reference pipeline: hash-group ``lax.sort``, neighbor
    compare, un-permute ``lax.sort`` — the original ops/lz4_tpu.py:228-261
    formulation, kept verbatim as the CPU-mesh path and the kernel's
    bit-identity oracle."""
    t = vals.shape[0]
    h = (vals * _HASH_MUL) >> jnp.uint32(32 - 16)
    key = (h << jnp.uint32(pos_bits)) | posn
    sk, sv = jax.lax.sort((key, vals), dimension=1, num_keys=1)
    pk = jnp.concatenate([jnp.full((t, 1), 0xFFFFFFFF, jnp.uint32),
                          sk[:, :-1]], axis=1)
    pv = jnp.concatenate([jnp.zeros((t, 1), jnp.uint32), sv[:, :-1]], axis=1)
    same = (sk >> jnp.uint32(pos_bits)) == (pk >> jnp.uint32(pos_bits))
    nondegen = sv != ((sv << jnp.uint32(8)) | (sv >> jnp.uint32(24)))
    okm = same & (sv == pv) & nondegen
    pmask = jnp.uint32((1 << pos_bits) - 1)
    delta = jnp.where(okm, ((sk & pmask) - (pk & pmask)) * jnp.uint32(stride),
                      jnp.uint32(0))
    delta = jnp.where(delta <= jnp.uint32(65535), delta, jnp.uint32(0))
    _, d = jax.lax.sort((sk & pmask, delta), dimension=1, num_keys=1)
    return d


def match_deltas(vals, posn, stride: int, pos_bits: int,
                 impl: str | None = None, interpret: bool = False):
    """(t, e) u32 4-gram entries -> (t, e) u32 deltas in position order:
    stages 2-3 of the match scan as ONE device op.  ``posn`` is the entry
    position map (only the XLA path consumes it; the kernel rebuilds it
    from the flat index).  Both paths produce bit-identical deltas: sort
    keys are position-salted, hence unique, hence permutation-unique."""
    if impl is None:
        impl = "pallas" if (use_pallas() or interpret) else "xla"
    e = vals.shape[1]
    if impl != "pallas" or e < _MIN_E or e > _MAX_E or e & (e - 1):
        return match_deltas_xla(vals, posn, stride, pos_bits)
    return _match_deltas_call(e, stride, pos_bits, interpret)(vals)
