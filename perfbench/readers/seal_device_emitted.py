"""Of the containers compressed in the window, the share whose stored stream
came from the device's match records: compress jobs less those the native
encoder emitted (record flood, bypassed scan, native win)."""


def read(src: dict, params: dict):
    jobs = src["window"]["stats"].get("compress_jobs", 0)
    if not jobs or src["trace"] is None:
        return None
    lz4 = src["window"]["lz4"]
    host = sum(lz4.get(k, 0) for k in ("native_fallbacks", "bypassed_scans",
                                       "native_wins"))
    return 100.0 * (jobs - host) / jobs
