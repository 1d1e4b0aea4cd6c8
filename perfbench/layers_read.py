"""Per-layer metrics: each is a file ``layers/<metric>.json`` naming a reader
(``readers/<reader>.py``) and its parameters.  A reader gets the run's
sources and returns a number, or ``None`` where it found nothing to read —
the metric is then left out of the line, never printed as 0.

Sources (``run.py`` fills them): ``window_s``; ``clients`` (per client
``cpu_s``, ``ops``); ``phases`` (the DataNode's phase clock over the window:
``phases`` exclusive seconds by name, ``classes``, ``inclusive`` — by span
name what the spans themselves paid — and, for N DataNodes, ``datanodes``:
``cluster.merge_phases`` says how N partitions make one); ``window`` (worker
``stats``, ``lz4`` counters and ``compile_s`` as deltas over the window);
``trace`` (the reduced profiler trace with ``stats`` and ``lz4`` deltas over
the traced seconds; ``None`` without a chip); ``peaks`` (this chip's row of
``peaks.json``); ``config``.
"""

from __future__ import annotations

import manifest


def read_all(per_layer: list, sources: dict) -> dict:
    out = {}
    for m in per_layer:
        spec = manifest.layer(m["name"])
        value = manifest.plugin("readers", spec["reader"]).read(
            sources, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
