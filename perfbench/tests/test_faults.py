"""The comparison has been shown to fail: with the timed path broken
underneath (``faults.py``), a rehearsal ends ``correct: false`` on a
comparison of its own, not only on the missing chip."""

import pytest

from common import failing, rehearse

EXPECT = {
    # an answer altered where it is produced: the true digest is missing
    # from the index, or (where another block holds the same chunk) the
    # chunk is stored once more under the false one
    "digest-flip": {"digests_missing|unique_chunks_gap"},
    # the control: 16-bit fingerprints, so distinct chunks collide
    "digest-16bit": {"unique_chunks_gap", "unique_bytes_gap"},
    # the device guarantee broken: blocks reduced on the host
    "host-fallback": {"worker_fallbacks", "degraded_writes"},
    # half of the batch left out
    "client-half-write": {"logical_bytes_gap", "readback_bad"},
    # a replica altered where it is kept, after the window
    "dn-truncate-sealed": {"sealed_decode_failures", "stored_bytes_gap"},
    "dn-drop-index-entry": {"digests_missing"},
}


@pytest.mark.parametrize("fault", sorted(EXPECT))
@pytest.mark.parametrize("workload", ["teragen-1dn.ingest",
                                      "versions-dedup.ingest"])
def test_a_planted_fault_fails_its_comparison(workload, fault, shelved_root):
    out, rows = rehearse(workload, extra=["--fault", fault],
                         root=shelved_root)
    last = rows[-1]
    assert out.returncode != 0 and last["correct"] is False
    bad = set(failing(last))
    for names in EXPECT[fault]:
        assert bad & set(names.split("|")), (names, bad, out.stderr[-2000:])
    assert last["fault"] == fault and "fault_planted" in bad


@pytest.mark.parametrize("workload", ["teragen-1dn.pread",
                                      "teragen-1dn.pread-ingest"])
def test_a_read_cell_sees_wrong_bytes_stored(workload, shelved_root):
    out, rows = rehearse(workload, root=shelved_root,
                         extra=["--fault", "digest-16bit"])
    last = rows[-1]
    assert last["correct"] is False
    assert {"unique_chunks_gap"} <= set(failing(last))
    assert last["failed"] > 0 and "ops_failed" in failing(last)
