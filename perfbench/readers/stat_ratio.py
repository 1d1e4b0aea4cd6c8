"""Ratio of two of the worker's ``stats`` deltas over the window, scaled."""


def read(src: dict, params: dict):
    stats = src["window"]["stats"]
    den = stats.get(params["den"], 0)
    if not den:
        return None
    return params.get("scale", 1.0) * stats.get(params["num"], 0) / den
