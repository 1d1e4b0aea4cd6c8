"""Fused Pallas SHA-256 compression kernel.

The XLA lane-parallel scan (ops/sha256.py:93 sha256_words) materializes the message
schedule per block step and round-trips carry state through HBM between scan
iterations; measured ~0.8 GB/s on v5e.  This kernel keeps the compression in
VMEM/registers: the grid walks (lane tiles) x (block chunks), the digest
state lives in the revisited output block across the chunk axis, and the
schedule + 64 rounds are fully unrolled on (8, 128) u32 tiles — the shape
the VPU natively retires.

Same contract as sha256_words: words u32[L, B*16] pre-padded big-endian
messages, nblocks i32[L], digests u8[L, 32].  Bit-identical outputs
(asserted in tests against the XLA path / hashlib).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hdrf_tpu.ops.sha256 import _H0, _K

_TILE = 8    # lane rows per grid step (sublane dim of the u32 VPU tile)
_BC = 32     # 64-byte blocks per grid step (VMEM stage = _BC*16*_TILE*128*4)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _kernel(wt_ref, nb_ref, out_ref):
    """Grid (T, B/_BC).  wt (_BC, 16, _TILE, 128) message words; nb
    (_TILE, 128) per-lane block counts; out (8, _TILE, 128) digest state,
    revisited across the chunk axis (same out block for every k)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        for i in range(8):
            out_ref[i] = jnp.full((_TILE, 128), np.uint32(_H0[i]), jnp.uint32)

    state = tuple(out_ref[i] for i in range(8))
    nb = nb_ref[...]
    base = k * _BC

    def block_step(j, state):
        w = [wt_ref[j, i] for i in range(16)]
        for i in range(16, 64):
            s0 = _rotr(w[i - 15], 7) ^ _rotr(w[i - 15], 18) \
                ^ (w[i - 15] >> np.uint32(3))
            s1 = _rotr(w[i - 2], 17) ^ _rotr(w[i - 2], 19) \
                ^ (w[i - 2] >> np.uint32(10))
            w.append(w[i - 16] + s0 + w[i - 7] + s1)
        a, b, c, d, e, f, g, h = state
        for i in range(64):
            s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + np.uint32(_K[i]) + w[i]
            s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + s0 + maj
        new = tuple(s + v for s, v in zip(state, (a, b, c, d, e, f, g, h)))
        active = (base + j) < nb
        return tuple(jnp.where(active, n, s) for n, s in zip(new, state))

    state = jax.lax.fori_loop(0, _BC, block_step, state)
    for i in range(8):
        out_ref[i] = state[i]


@jax.jit
def sha256_words_pallas(words: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Drop-in replacement for ops.sha256.sha256_words on TPU."""
    L, nwords = words.shape
    B = nwords // 16
    R = L // 128
    # Lane-rows pad UP to a whole number of tiles: flooring T here left the
    # tail rows of non-multiple-of-_TILE lane counts UNPROCESSED — the
    # output block then carried stale device memory, which even masqueraded
    # as correct digests whenever a previous dispatch had hashed the same
    # content into that buffer.
    R_p = max(-(-R // _TILE) * _TILE, _TILE)
    T = R_p // _TILE
    wt = jnp.transpose(words.reshape(L, B, 16), (1, 2, 0)).reshape(
        B, 16, R, 128)
    if B % _BC:
        wt = jnp.pad(wt, ((0, _BC - B % _BC), (0, 0), (0, 0), (0, 0)))
    if R_p != R:
        wt = jnp.pad(wt, ((0, 0), (0, 0), (0, R_p - R), (0, 0)))
        nb2 = jnp.pad(nblocks.reshape(R, 128), ((0, R_p - R), (0, 0)))
    else:
        nb2 = nblocks.reshape(R, 128)
    Bp = wt.shape[0]
    out = pl.pallas_call(
        _kernel,
        grid=(T, Bp // _BC),
        in_specs=[
            pl.BlockSpec((_BC, 16, _TILE, 128), lambda t, k: (k, 0, t, 0)),
            pl.BlockSpec((_TILE, 128), lambda t, k: (t, 0)),
        ],
        out_specs=pl.BlockSpec((8, _TILE, 128), lambda t, k: (0, t, 0)),
        out_shape=jax.ShapeDtypeStruct((8, R_p, 128), jnp.uint32),
    )(wt, nb2.astype(jnp.int32))
    st = out[:, :R].reshape(8, L).T  # (L, 8)
    o = jnp.stack([(st >> np.uint32(s)).astype(jnp.uint8)
                   for s in (24, 16, 8, 0)], axis=-1)
    return o.reshape(L, 32)


def selfcheck_odd_lane_rows() -> bool:
    """The kernel against hashlib at lane counts that are NOT a multiple of
    a tile's rows (the stale-tail-rows regression above).  Only a real chip
    runs the Mosaic kernel, so the device-owning worker runs this when
    ``chip_smoke.py`` asks for its report."""
    import hashlib

    for L in (384, 3840):
        data = np.random.default_rng(L).integers(0, 256, size=(L, 32),
                                                 dtype=np.uint8)
        w = np.zeros((L, 16), dtype=np.uint32)
        be = data.reshape(L, 8, 4).astype(np.uint32)
        w[:, :8] = (be[:, :, 0] << 24) | (be[:, :, 1] << 16) \
            | (be[:, :, 2] << 8) | be[:, :, 3]
        w[:, 8] = 0x80000000
        w[:, 15] = 256
        out = np.asarray(sha256_words_pallas(
            jax.device_put(w), jax.device_put(np.ones(L, np.int32))))
        if any(bytes(out[i]) != hashlib.sha256(data[i].tobytes()).digest()
               for i in range(L)):
            return False
    return True
