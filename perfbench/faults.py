"""Faults planted under the timed path, for the tests in ``perfbench/tests``
and for the controls run on the chip (``run.py --fault <name>``).  The
benchmark's own runs never plant one: ``run.py`` prints the fault's name in
the result line and refuses to call such a run correct even if every
comparison held.

- ``digest-flip`` (an answer altered where it is produced): the worker flips
  one bit of one chunk fingerprint per block it reduces.
- ``digest-16bit`` (the control: the nearest weaker fingerprint, 16 bits in
  place of 256, the step that would tempt a PR that wants a cheaper hash):
  chunks that differ collide, so dedup drops bytes that were never stored.
- ``host-fallback`` (the control for the device guarantee): the worker
  refuses every reduce, so the DataNode reduces blocks on the host.
- ``client-half-write`` (half of the batch left out): the client library
  sends the first half of every file and acknowledges the whole.
- ``dn-truncate-sealed`` / ``dn-drop-index-entry`` (a replica altered where
  it is kept): after the window, on the last DataNode alone, one sealed
  container loses the second half of its file, or one chunk entry leaves
  the index.  Only that DataNode's comparisons may fail.
"""

from __future__ import annotations

WORKER_FAULTS = ("digest-flip", "digest-16bit", "host-fallback")
CLIENT_FAULTS = ("client-half-write",)
DATANODE_FAULTS = ("dn-truncate-sealed", "dn-drop-index-entry")
ALL = WORKER_FAULTS + CLIENT_FAULTS + DATANODE_FAULTS


def plant_in_worker(worker, fault: str) -> None:
    """Called by ``worker_entry.py`` before the worker serves."""
    if fault not in WORKER_FAULTS:
        return
    import numpy as np

    from hdrf_tpu.server import reduction_worker as rw

    real_send = rw.send_frame

    def send(sock, frame):
        if isinstance(frame, dict) and "digests" in frame:
            if fault == "host-fallback":
                frame = {"error": "Refused", "message": "planted fault"}
            else:
                d = np.frombuffer(frame["digests"], np.uint8).reshape(-1, 32)
                d = d.copy()
                if fault == "digest-flip" and len(d):
                    d[len(d) // 2, 7] ^= 1
                elif fault == "digest-16bit":
                    d[:, 2:] = 0
                frame = dict(frame, digests=d.tobytes())
        return real_send(sock, frame)

    rw.send_frame = send


def plant_in_client(fault: str) -> None:
    """Called by each load-generator process before its first operation."""
    if fault not in CLIENT_FAULTS:
        return
    from hdrf_tpu.client.filesystem import HdrfClient

    real_write = HdrfClient.write

    def write(self, path, data, **kw):
        return real_write(self, path, data[:len(data) // 2], **kw)

    HdrfClient.write = write


def plant_in_datanode(dn, fault: str) -> None:
    """Called by the harness on the last DataNode after ``flush_open()``."""
    if fault == "dn-drop-index-entry":
        with dn.index._lock:
            digest = min(dn.index._chunks)
            del dn.index._chunks[digest]
    elif fault == "dn-truncate-sealed":
        import os

        cid = min(c for c in dn.containers.container_ids()
                  if dn.containers.sealed_file_bytes(c) is not None)
        for top, _, names in os.walk(dn.config.data_dir):
            if f"{cid}.sealed" in names:
                path = os.path.join(top, f"{cid}.sealed")
                os.truncate(path, os.path.getsize(path) // 2)
                return
        raise FileNotFoundError(f"no file for sealed container {cid}")
