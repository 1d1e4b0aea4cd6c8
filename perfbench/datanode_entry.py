#!/usr/bin/env python3
"""A DataNode in a process of its own, for a layout of N DataNodes.

It is the program's ``DataNode`` unchanged, built with the values
``MiniCluster._make_dn`` gives one (data directory, heartbeat and block-report
intervals, ``container_size``, the native in-process backend, the
configuration's ``reduction`` overrides) plus the address of its own
reduction worker, registered with the harness's NameNode.  Like
``worker_entry.py`` it takes JSON lines on stdin and answers one JSON line
each on stdout: the commands of ``cluster.serve`` (``snapshot`` — the
give-way counters ride it —, ``drain_seals``, ``flush_open``, ``stored``,
``check_index``, ``decode_sealed``, ``warm_reduce``, ``plant``,
``phases_start``, ``phases``)
and ``quit``.  It never initialises JAX; ``decode_sealed`` reports the
backends it has, for ``parent_jax_backends``.

    python3 perfbench/datanode_entry.py '<spec as JSON>'

``spec``: ``dn_id``, ``data_dir``, ``nn_addrs``, ``heartbeat_s``,
``container_size``, ``reduction`` (with ``worker_addr``).
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                       # cluster, checks, faults
sys.path.insert(0, os.path.dirname(HERE))      # the program


def main(argv=None) -> int:
    spec = json.loads((argv or sys.argv[1:])[0])
    # replies go to the pipe the harness reads; anything else the process
    # prints goes to stderr
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    def reply(obj: dict) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    import cluster
    from hdrf_tpu.config import DataNodeConfig
    from hdrf_tpu.server.datanode import DataNode

    cfg = DataNodeConfig(port=0, data_dir=spec["data_dir"],
                         heartbeat_interval_s=spec["heartbeat_s"],
                         block_report_interval_s=5.0,
                         provided_mount_root="/")
    cfg.reduction.container_size = spec["container_size"]
    cfg.reduction.backend = "native"
    for k, v in spec["reduction"].items():
        setattr(cfg.reduction, k, v)
    dn = DataNode(cfg, [tuple(a) for a in spec["nn_addrs"]],
                  dn_id=spec["dn_id"]).start()
    reply({"dn_id": dn.dn_id, "addr": list(dn.addr), "pid": os.getpid()})

    state: dict = {}
    for line in sys.stdin:
        try:
            req = json.loads(line)
        except ValueError:
            continue
        if req.get("cmd") == "quit":
            break
        try:
            reply(dict(cluster.serve(dn, state, req), ok=True))
        except Exception as e:  # noqa: BLE001 — the harness decides
            import traceback

            traceback.print_exc()
            reply({"ok": False, "error": f"{type(e).__name__}: {e}"})
    dn.stop()
    reply({"ok": True})
    os._exit(0)


if __name__ == "__main__":
    raise SystemExit(main())
