"""TeraGen-shaped files: 100-byte rows, every row unique.

The row layout is the one ``chip_smoke.teragen_rows`` writes (Hadoop 1.x
TeraGen): 10 random key bytes, 10 ASCII digits of the row id, 78 filler bytes
of per-row shifting 10-letter blocks, CR LF.  Row ids run on from file to file
as TeraGen's do from map to map, and the key bytes come from a generator
seeded by (seed, client, file), so no two rows of a run are equal and every
chunk of every file is new to the store.
"""

from __future__ import annotations

import numpy as np

ROW = 100
PARALLEL = True     # file k needs no other file: drivers may make several at once


def _five_digits() -> np.ndarray:
    """(100000, 5) ASCII digits of 00000..99999."""
    v = np.arange(100_000, dtype=np.int32)
    return np.stack([v // 10 ** (4 - d) % 10 + 48 for d in range(5)],
                    axis=1).astype(np.uint8)


def make(params: dict, seed: int, client: int, index: int) -> np.ndarray:
    """File ``index`` of client ``client``: ``params['file_bytes']`` bytes."""
    n = int(params["file_bytes"])
    rows = -(-n // ROW)
    rng = np.random.default_rng([seed, client, index + 1_000_000])
    rec = np.empty((rows, ROW), dtype=np.uint8)
    rec[:, :10] = rng.integers(0, 256, size=(rows, 10), dtype=np.uint8)
    first = (client * 4096 + (index % 4096)) * rows
    ids = np.arange(first, first + rows, dtype=np.int64) % 10 ** 10
    five = _five_digits()
    rec[:, 10:15] = five[ids // 100_000]
    rec[:, 15:20] = five[ids % 100_000]
    # the filler depends on id % 26 alone: lay one period down by broadcast
    fill = (np.arange(78) // 10).astype(np.uint8)[None, :]
    table = (65 + (np.arange(26, dtype=np.uint8)[:, None] + fill) % 26)
    table = np.roll(table.astype(np.uint8), -(first % 26), axis=0)
    whole = rows // 26 * 26
    rec[:whole].reshape(-1, 26, ROW)[:, :, 20:98] = table
    rec[whole:, 20:98] = table[:rows - whole]
    rec[:, 98], rec[:, 99] = 13, 10
    return rec.reshape(-1)[:n]


class Source:
    """What a driver holds per client: ``file(k)`` gives file ``k``."""

    def __init__(self, params: dict, seed: int, client: int):
        self.params, self.seed, self.client = params, seed, client

    def file(self, k: int) -> np.ndarray:
        return make(self.params, self.seed, self.client, k)
