"""Share of the window the DataNode's phase clock gives to the named phases
(exclusive seconds: one phase owns each instant), or to one of its classes
(``"class": "idle"`` is the time no named phase covers)."""


def read(src: dict, params: dict):
    prof = src["phases"]
    if prof is None or not src["window_s"]:
        return None
    if "class" in params:
        secs = prof["classes"].get(params["class"], 0.0)
    else:
        secs = sum(prof["phases"].get(p, 0.0) for p in params["phases"])
    return 100.0 * secs / src["window_s"]
