"""One stage of the worker's stage clock per op: ``stats`` delta of
``<stage>_s`` over a delta of an op counter, scaled.  Unlike ``stat_ratio``
an absent numerator is nothing to read, not 0: the worker leaves out the
key of a stage it never ran (``scan_wait`` when every scan was bypassed, the
device stages on a native backend, every stage on a program without the
clock)."""


def read(src: dict, params: dict):
    stats = src["window"]["stats"]
    den = stats.get(params["den"], 0)
    if not den or params["num"] not in stats:
        return None
    return params.get("scale", 1.0) * stats[params["num"]] / den
