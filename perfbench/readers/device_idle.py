"""1 - (union of the device's operation intervals / traced window), from the
profiler trace of the worker."""


def read(src: dict, params: dict):
    tr = src["trace"]
    if tr is None or not tr["window_s"] or not tr["device_events"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
