"""CPU seconds of the client processes (``getrusage``) over clients x
window."""


def read(src: dict, params: dict):
    runs = src["clients"]
    if not runs or not src["window_s"]:
        return None
    return 100.0 * sum(r["cpu_s"] for r in runs) / (len(runs)
                                                    * src["window_s"])
