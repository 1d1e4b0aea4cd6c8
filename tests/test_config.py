"""Config system tests (replaces reference's hardcoded DataNode.java:412-458 statics)."""

import pytest

from hdrf_tpu.config import HdrfConfig


def test_defaults():
    cfg = HdrfConfig()
    assert cfg.namenode.replication == 3
    assert cfg.datanode.reduction.cdc.avg_chunk == 8192
    assert cfg.datanode.reduction.container_size == 1 << 25


def test_set_dotted():
    cfg = HdrfConfig()
    cfg.set("namenode.replication", 2)
    cfg.set("datanode.reduction.default_scheme", "zstd")
    cfg.set("datanode.reduction.cdc.mask_bits", 16)
    assert cfg.namenode.replication == 2
    assert cfg.datanode.reduction.default_scheme == "zstd"
    assert cfg.datanode.reduction.cdc.avg_chunk == 65536


def test_env_style_underscore_ambiguity():
    cfg = HdrfConfig.load(env={
        "HDRF_DATANODE_REDUCTION_DEFAULT_SCHEME": "lz4",
        "HDRF_NAMENODE_BLOCK_SIZE": "1048576",
        "HDRF_IGNORED_UNKNOWN_KEY": "x",
    })
    assert cfg.datanode.reduction.default_scheme == "lz4"
    assert cfg.namenode.block_size == 1048576


def test_toml_layer(tmp_path):
    p = tmp_path / "hdrf.toml"
    p.write_text("[namenode]\nreplication = 1\n[datanode.reduction]\ndefault_scheme = 'direct'\n")
    cfg = HdrfConfig.load(path=str(p), env={})
    assert cfg.namenode.replication == 1
    assert cfg.datanode.reduction.default_scheme == "direct"


def test_type_coercion():
    cfg = HdrfConfig()
    cfg.set("namenode.heartbeat_interval_s", "2")
    assert cfg.namenode.heartbeat_interval_s == 2.0


@pytest.mark.parametrize("keys", [
    ["nope.nothing"],
    # the write pipeline's options, removed with it (PR 29): a deployment
    # file that still sets one is told so, not silently obeyed
    ["datanode.reduction." + k for k in (
        "pipeline_depth", "pipeline_max_inflight", "mesh_plane",
        "mesh_lanes_per_device", "mesh_bucket_slots")],
], ids=["no-such-section", "removed-write-pipeline-options"])
def test_unknown_key(keys):
    cfg = HdrfConfig()
    for key in keys:
        with pytest.raises(KeyError, match="unknown config key"):
            cfg.set(key, 1)
