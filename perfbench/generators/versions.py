"""Successive releases of one source tree, each as a tar stream.

Stand-in for BASELINE config 3's corpus (the Linux kernel tarball set):
per client one seeded tree of files of log-normal size (median
``median_file_bytes``, shape ``sigma``), their text drawn line by line from
one fixed pool of lines over one fixed vocabulary, laid out as a ustar stream
(512-byte headers, content padded to 512).  Generation g+1 edits
``edit_share`` of the files of g by inserting, deleting or replacing up to
``edit_lines_max`` lines (offsets after the edit shift, so cut points have to
resynchronise) and adds or drops ``churn_share`` of them.  The stream is
brought to exactly ``file_bytes`` by a last member ``PADDING`` of this
client's own random bytes (zeros would be chunks every client shares, and
which of two concurrent writers stores a shared chunk is a race) and the
1024 zero bytes that end an archive; files are dropped from the end if a
generation outgrows it.

``Source.file(k)`` is generation ``k`` of this client's tree; generations
are built in order and the tree is kept, so ask for them in order.
"""

from __future__ import annotations

import math
from operator import itemgetter

import numpy as np

BLOCK = 512
_POOL_LINES = 32768
_VOCAB = 4096
_POOL_SEED = 20260930


def _pool(rng) -> list[bytes]:
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz_", np.uint8)
    wl = rng.integers(2, 11, size=_VOCAB)
    words = [letters[rng.integers(0, letters.size, size=int(k))].tobytes()
             for k in wl]
    # Zipf-like: low ranks are picked far more often, as keywords are
    rank = (rng.pareto(1.1, size=_POOL_LINES * 12) * 8).astype(np.int64)
    rank %= _VOCAB
    counts = rng.integers(2, 12, size=_POOL_LINES)
    indent = rng.integers(0, 4, size=_POOL_LINES)
    lines, at = [], 0
    for n, ind in zip(counts.tolist(), indent.tolist()):
        ws = [words[i] for i in rank[at:at + n].tolist()]
        at += n
        lines.append(b"\t" * ind + b" ".join(ws) + b"\n")
    return lines


def _header(name: bytes, size: int, mtime: int) -> bytes:
    h = bytearray(BLOCK)
    h[0:len(name)] = name
    h[100:108] = b"0000644\0"
    h[108:116] = b"0001750\0"
    h[116:124] = b"0001750\0"
    h[124:136] = b"%011o\0" % size
    h[136:148] = b"%011o\0" % mtime
    h[148:156] = b"        "
    h[156:157] = b"0"
    h[257:265] = b"ustar\0" + b"00"
    h[265:269] = b"root"
    h[297:301] = b"root"
    h[148:156] = b"%06o\0 " % sum(h)
    return bytes(h)


class _File:
    __slots__ = ("name", "ids", "mtime", "blob")

    def __init__(self, name: bytes, ids: list, mtime: int):
        self.name, self.ids, self.mtime, self.blob = name, ids, mtime, None


class Source:
    def __init__(self, params: dict, seed: int, client: int):
        self.n = int(params["file_bytes"])
        self.p = params
        self.rng = np.random.default_rng([seed, client, 3_000_000])
        # one vocabulary and one pool of lines for every seed and client: the
        # seed orders the text, it does not change how well it compresses
        self.pool = _pool(np.random.default_rng(_POOL_SEED))
        self.mean_line = sum(map(len, self.pool)) / len(self.pool)
        self.mu = math.log(float(params["median_file_bytes"]))
        self.sigma = float(params["sigma"])
        self.serial = 0
        self.gen = 0
        self.files: list[_File] = []
        self.filler = self.rng.bytes(min(self.n // 8, 16 << 20))
        budget = self.n - 4 * BLOCK
        used = 0
        while True:
            f = self._new_file(0)
            cost = self._cost(f)
            if used + cost > budget:
                break
            self.files.append(f)
            used += cost

    # ------------------------------------------------------------- the tree

    def _new_file(self, mtime: int) -> _File:
        size = float(np.clip(self.rng.lognormal(self.mu, self.sigma),
                             64, self.n // 16))
        lines = max(int(size / self.mean_line), 1)
        ids = self.rng.integers(0, len(self.pool), size=lines).tolist()
        self.serial += 1
        name = b"linux/d%03d/f%06d.c" % (self.serial % 211, self.serial)
        return _File(name, ids, 1_600_000_000 + mtime)

    def _render(self, f: _File) -> bytes:
        if f.blob is None:
            got = itemgetter(*f.ids)(self.pool) if len(f.ids) > 1 \
                else (self.pool[f.ids[0]],)
            body = b"".join(got)
            f.blob = (_header(f.name, len(body), f.mtime) + body
                      + b"\0" * (-len(body) % BLOCK))
        return f.blob

    def _cost(self, f: _File) -> int:
        return len(self._render(f))

    def _evolve(self) -> None:
        self.gen += 1
        rng, p = self.rng, self.p
        n = len(self.files)
        for i in rng.choice(n, size=max(int(n * p["edit_share"]), 1),
                            replace=False).tolist():
            f = self.files[i]
            k = int(rng.integers(1, int(p["edit_lines_max"]) + 1))
            at = int(rng.integers(0, len(f.ids)))
            op = int(rng.integers(0, 3))
            fresh = rng.integers(0, len(self.pool), size=k).tolist()
            if op == 0:                         # insert
                f.ids[at:at] = fresh
            elif op == 1 and len(f.ids) > k:    # delete
                del f.ids[at:at + k]
            else:                               # replace
                f.ids[at:at + k] = fresh
            f.mtime, f.blob = 1_600_000_000 + self.gen, None
        churn = max(int(n * p["churn_share"] / 2), 1)
        for i in sorted(rng.choice(n, size=churn, replace=False).tolist(),
                        reverse=True):
            del self.files[i]
        for _ in range(churn):
            self.files.insert(int(rng.integers(0, len(self.files))),
                              self._new_file(self.gen))

    # ------------------------------------------------------------ the stream

    def file(self, k: int) -> np.ndarray:
        if k < self.gen:
            raise ValueError(f"generation {k} asked after {self.gen}")
        while self.gen < k:
            self._evolve()
        budget = self.n - 4 * BLOCK
        while sum(map(self._cost, self.files)) > budget:
            self.files.pop()
        while True:
            parts = [self._render(f) for f in self.files]
            pad = self.n - sum(map(len, parts)) - 3 * BLOCK
            if pad <= len(self.filler):
                break
            self.files.append(self._new_file(self.gen))
        parts.append(_header(b"linux/PADDING", pad, 1_600_000_000))
        parts.append(self.filler[:pad])
        parts.append(b"\0" * (2 * BLOCK))
        out = np.frombuffer(b"".join(parts), np.uint8)
        if out.size != self.n:
            raise AssertionError(
                f"tar stream is {out.size} bytes, not {self.n}")
        return out
