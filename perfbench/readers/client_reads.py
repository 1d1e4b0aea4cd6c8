"""The clients' completed reads over the window, from the run's operation
records (``kind == "read"``, ok only): ``"stat": "mb_s"`` is their bytes over
the window (MB = 10^6), ``"p95_ms"`` the 95th percentile of a read's wall
time — the arithmetic of ``run.py``'s ``read_mb_s`` / ``read_p95_ms``.  A
window without a completed read is nothing to read."""


def read(src: dict, params: dict):
    reads = [op for op in src.get("ops") or ()
             if op["kind"] == "read" and op["ok"]]
    if not reads or not src["window_s"]:
        return None
    if params["stat"] == "mb_s":
        return sum(op["bytes"] for op in reads) / 1e6 / src["window_s"]
    ms = sorted((op["t1"] - op["t0"]) * 1e3 for op in reads)
    return ms[min(int(round(0.95 * (len(ms) - 1))), len(ms) - 1)]
