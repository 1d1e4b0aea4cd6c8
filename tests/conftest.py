"""Test env: force JAX onto an 8-device virtual CPU mesh.

Sharding tests (tests/test_sharding.py) exercise real Mesh/shard_map code
paths on these virtual devices, mirroring how the driver's dryrun validates
multi-chip compilation without real chips.  Platform selection happens via
the process environment before anything imports JAX, so the two variables
are set here, at conftest import (nothing imports JAX at interpreter
start).  An operator-set device-count flag is kept.  On-chip checks live in
``chip_smoke.py`` (run through the chip tool), not in this suite.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hdrf_tpu.utils.cleanenv import clean_cpu_env  # noqa: E402

_env = clean_cpu_env(8, keep_existing_count=True)
os.environ["JAX_PLATFORMS"] = _env["JAX_PLATFORMS"]
os.environ["XLA_FLAGS"] = _env["XLA_FLAGS"]

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-size kernel runs excluded from the tier-1 "
        "sweep (the Pallas interpreter pays ~1 min per full-width network)")
    if not hasattr(config, "workerinput"):
        # The native library builds itself on first load when it is missing
        # or older than its sources.  Build it here, once, in the process
        # that starts any xdist workers and before them: workers that each
        # found it missing would each run ``make``, and one would load the
        # file while another's link was rewriting it ("file too short").
        from hdrf_tpu import native

        native._load()


@pytest.fixture(autouse=True)
def _clear_fault_injection():
    yield
    from hdrf_tpu.utils import fault_injection
    fault_injection.clear()


PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


@pytest.fixture(scope="session")
def perfbench_file():
    """``perfbench_file(rel)``: a file of ``perfbench/`` as a module, without
    putting the benchmark on ``sys.path`` (its plain reference, readers and
    corpus generators import nothing of the program);
    ``perfbench_file.root`` is the directory."""
    import importlib.util

    def load(rel: str):
        spec = importlib.util.spec_from_file_location(
            "perfbench_" + os.path.basename(rel)[:-3],
            os.path.join(PERFBENCH, rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    load.root = PERFBENCH
    return load



@pytest.fixture(scope="session")
def slive_files(perfbench_file):
    """``slive_files(count)``: the first ``count`` files of one client's
    window in ``small-files.create`` (SLive's create mix, sizes uniform in
    4 KiB-4 MiB), made as the benchmark makes them."""
    import json

    with open(os.path.join(perfbench_file.root, "configs",
                           "small-files.json")) as f:
        data = {k: v for k, v in json.load(f)["data"].items()
                if k != "generator"}
    with pytest.MonkeyPatch.context() as mp:
        # the generator imports its neighbour ``generators.teragen``
        mp.syspath_prepend(perfbench_file.root)
        source = perfbench_file("generators/slive_sizes.py").Source
    src = source(dict(data, file_bytes=128 << 20), 2**31 + 31, 0)
    first = int(data["setup_files"])
    return lambda count: [src.file(k) for k in range(first, first + count)]
