"""Share of the HBM roofline: the bytes the algorithm has to read, once,
over the chip's HBM bandwidth, over the device time of the programs that did
it in the traced seconds.

``bytes``: ``"reduced"`` — user bytes the worker reduced in the traced
seconds (its ``bytes_reduced`` delta); ``"scanned"`` — containers the match
scan took (``lz4.scan`` delta) times the container size.  ``include`` /
``exclude``: substrings of program names (``XLA Modules`` events) that pick
the programs.  Nothing to read (no such program ran, or no bytes) is None.
"""


def needed_bytes(kind: str, src: dict) -> int:
    tr = src["trace"]
    if kind == "reduced":
        return int(tr["stats"].get("bytes_reduced", 0))
    if kind == "scanned":
        return int(tr["lz4"].get("scan", 0)
                   * src["config"]["cluster"]["container_size"])
    raise ValueError(f"unknown bytes function {kind!r}")


def read(src: dict, params: dict):
    tr = src["trace"]
    if tr is None:
        return None
    inc, exc = params.get("include"), params.get("exclude", [])
    secs = sum(p["seconds"] for name, p in tr["programs"].items()
               if (inc is None or any(s in name for s in inc))
               and not any(s in name for s in exc))
    nbytes = needed_bytes(params["bytes"], src)
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / src["peaks"]["hbm_bytes_per_s"] / secs
