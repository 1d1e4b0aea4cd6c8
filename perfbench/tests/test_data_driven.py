"""A later PR adds a configuration, a traffic mix and a per-layer metric as
new files and new entries in ``BENCHMARK.json``, and edits no file that is
there: the harness finds them by name."""

import json

from common import checkout, failing, rehearse


def test_entries_alone_add_the_cells_whose_files_are_there(shelved_root):
    for trace, want in ((0, {"read_mb_s", "read_p95_ms", "setup_s"}),
                        (1, {"client.busy_pct.read", "dn.decode_pct",
                             "dn.net_send_pct"})):
        out, rows = rehearse("teragen-1dn.pread", trace=trace,
                             root=shelved_root)
        last = rows[-1]
        assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
        assert set(last["metrics"]) == want
        assert last["read_samples"] == last["attempted"] > 20
    out, rows = rehearse("versions-dedup.ingest", root=shelved_root,
                         seed=2**31 + 5)
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert set(last["metrics"]) == {"write_mb_s", "stored_pct", "setup_s"}
    # about 97 % of each generation's chunks are already stored
    note = [r for r in rows if r.get("phase") == "checks"][0]["reference"]
    assert note["unique_bytes"] < 0.5 * note["logical_bytes"]


def test_new_files_are_taken_without_an_edit(tmp_path):
    root = checkout(tmp_path, _throwaway)
    out, rows = rehearse("throwaway.two-writers", root=str(root))
    last = rows[-1]
    assert failing(last) == ["device_not_tpu"], out.stderr[-3000:]
    assert set(last["metrics"]) == {"write_mb_s", "stored_pct", "setup_s"}
    assert last["metrics"]["stored_pct"]["value"] > 99     # random bytes
    out, rows = rehearse("throwaway.two-writers", trace=1, root=str(root))
    assert rows[-1]["metrics"]["throwaway.ops"]["value"] == \
        rows[-1]["attempted"]


def _throwaway(bench, pb):
    """A configuration, a generator, a mix, a per-layer metric and its
    reader, all as new files; nothing that was there changes."""
    before = {p: p.read_bytes() for p in pb.rglob("*") if p.is_file()}
    cfg = json.load(open(pb / "configs" / "teragen-1dn.json"))
    cfg["name"] = "throwaway"
    cfg["data"] = {"generator": "constant_rows"}
    (pb / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (pb / "generators" / "constant_rows.py").write_text(
        "import numpy as np\n"
        "class Source:\n"
        "    def __init__(self, params, seed, client):\n"
        "        self.n, self.s = params['file_bytes'], seed * 131 + client\n"
        "    def file(self, k):\n"
        "        rng = np.random.default_rng([self.s, k])\n"
        "        return rng.integers(0, 256, self.n, dtype=np.uint8)\n")
    mix = json.load(open(pb / "traffic" / "ingest.json"))
    mix["name"], mix["clients"] = "two-writers", 2
    (pb / "traffic" / "two-writers.json").write_text(json.dumps(mix))
    (pb / "layers" / "throwaway.ops.json").write_text(json.dumps(
        {"metric": "throwaway.ops", "reader": "count_ops", "params": {}}))
    (pb / "readers" / "count_ops.py").write_text(
        "def read(src, params):\n    return float(len(src['ops']))\n")

    bench["configs"].append({"name": "throwaway", "source": "a test",
                             "file": "perfbench/configs/throwaway.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "throwaway.two-writers",
                               "config": "throwaway",
                               "traffic": "two-writers", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("write_mb_s", "stored_pct"):
            m["workloads"].append("throwaway.two-writers")
    bench["per_layer"].append({"name": "throwaway.ops", "unit": "ops",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "write_mb_s",
                               "workloads": ["throwaway.two-writers"]})
    assert {p: p.read_bytes() for p in before} == before
