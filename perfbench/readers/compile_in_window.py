"""Seconds the worker spent in backend compiles (or cache loads) between the
barrier and the end of the window: ``device_report()["compile_s"]`` summed
over programs, as a delta.  Should read 0."""


def read(src: dict, params: dict):
    if src["trace"] is None:        # no chip: nothing compiled for one
        return None
    return src["window"]["compile_s"]
