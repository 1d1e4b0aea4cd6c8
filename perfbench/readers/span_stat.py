"""What the spans of a name themselves paid, out of the phase clock's
INCLUSIVE table (``sources["phases"]["inclusive"]``: by span name ``count``,
``wall_s`` = the spans' own lengths summed over whatever threads, clamped to
the window, ``wall_max_s``, and ``cpu_s`` where the spans carried their
thread's CPU).  ``phase_share`` reads the exclusive partition beside it: a
share of the window, one phase an instant; this reads a unit's own bill.

``spans``: the names summed; ``field``: ``wall_s`` (default), ``cpu_s`` or
``count``; ``per``: ``"window"`` (a share of the window's seconds) or a span
name (divided by that span's count: per container, per block, per tick);
``scale``.  Where the table sums N DataNodes' spans (``"datanodes"`` in the
partition, ``cluster.merge_phases``), a share of the window is of N
windows: the mean DataNode's.  ``None`` — the metric is left out of the line — where the program
has no such table, none of the named spans ended in the window, they carry
no such field, or the divisor counts nothing.
"""


def read(src: dict, params: dict):
    prof = src["phases"]
    table = prof.get("inclusive") if prof else None
    if not table:
        return None
    field = params.get("field", "wall_s")
    rows = [table[name] for name in params["spans"] if name in table]
    if not rows or not all(field in row for row in rows):
        return None
    per = params["per"]
    den = (src["window_s"] * prof.get("datanodes", 1) if per == "window"
           else table.get(per, {}).get("count", 0))
    if not den:
        return None
    return params.get("scale", 1.0) * sum(row[field] for row in rows) / den
