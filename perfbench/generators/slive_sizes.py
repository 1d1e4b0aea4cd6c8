"""SLive's create-only mix: files of a size drawn uniformly from a range.

Apache Hadoop's HDFS stress test (``org.apache.hadoop.fs.slive.SliveTest``)
with ``-create 100,uniform`` draws each file's byte count uniformly from
``-writeSize min,max``, writes it whole and closes it.  Here file ``k`` of a
client has ``size(k)`` bytes, drawn from (seed, client, k) alone, of TeraGen
rows (``generators/teragen.make``: ten random key bytes a row, so no row of
a run equals another and every chunk of every file is new to the store; the
row ids start at a multiple of the file's own row count, as ``make`` lays
them).  SLive's own ``DataWriter`` fills a file with seeded longs; the rows
keep the stored ratio and the seal's work comparable with ``teragen-1dn``.

Files ``k < setup_files`` are the warm files: their sizes step geometrically
from ``size_min`` to ``size_max``, so that a program whose compiled shapes
follow a file's length meets most of them before the window whatever ladder
of lengths it has.  The window's files follow, drawn uniformly.

The deployment needs a program whose compiled shapes are bounded over block
lengths: ``Source`` refuses one that has no block-length ladder
(``_require_ladder``), before any file is made.
"""

from __future__ import annotations

import importlib.util

import numpy as np

from generators import teragen

PARALLEL = True     # file k needs no other file: drivers may make several at once


def _require_ladder() -> None:
    """A program without ``hdrf_tpu.ops.resident.block_rung`` compiles three
    to five programs for every file length it has not met: on the chip it
    wrote 6-8 files in a window (0.14-0.20 MB/s, 64 s of compiles; PR 31),
    which is no reading of this deployment, so the cell ends here with an
    exit code, soon.  The module's text is read and not imported: a client
    process never imports JAX."""
    spec = importlib.util.find_spec("hdrf_tpu.ops.resident")
    with open(spec.origin, encoding="utf-8") as f:
        if "\ndef block_rung(" in f.read():
            return
    raise RuntimeError(
        "small-files needs a program with a block-length ladder "
        f"(block_rung in {spec.origin}): without one every file's length "
        "compiles programs of its own")


class Source:
    """What a driver holds per client: ``file(k)`` gives file ``k``."""

    def __init__(self, params: dict, seed: int, client: int):
        _require_ladder()
        self.params, self.seed, self.client = params, seed, client
        self.lo, self.hi = int(params["size_min"]), int(params["size_max"])
        self.warm = int(params.get("setup_files", 0))

    def size(self, k: int) -> int:
        if k < self.warm:
            step = k / max(self.warm - 1, 1)
            return int(round(self.lo * (self.hi / self.lo) ** step))
        rng = np.random.default_rng([self.seed, self.client, k + 2_000_000])
        return int(rng.integers(self.lo, self.hi, endpoint=True))

    def file(self, k: int) -> np.ndarray:
        return teragen.make(dict(self.params, file_bytes=self.size(k)),
                            self.seed, self.client, k)
