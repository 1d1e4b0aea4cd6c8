"""One source of truth for the XLA:CPU process environment.

Platform selection only takes effect through the process environment
before JAX is first imported, so code that needs an n-device virtual CPU
mesh (tests/conftest.py, __graft_entry__'s dryrun, the multihost and
multichip harness children) builds it here: ``JAX_PLATFORMS=cpu`` plus the
``--xla_force_host_platform_device_count`` flag and nothing else (the
reference's MiniDFSCluster.java:141 fixes its test environment the same way:
in one place, before any daemon starts).
"""

from __future__ import annotations

import os


def clean_cpu_env(n_devices: int, base: dict | None = None,
                  keep_existing_count: bool = False) -> dict:
    """Env dict for a child process with ``n_devices`` virtual CPU devices.

    ``keep_existing_count=True`` preserves an operator-set
    ``--xla_force_host_platform_device_count`` flag (``n_devices`` is then
    only the default); ``False`` forces exactly ``n_devices``.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _with_device_count_flag(
        env.get("XLA_FLAGS", ""), n_devices, keep_existing_count)
    return env


def _with_device_count_flag(flags_str: str, n_devices: int,
                            keep_existing: bool) -> str:
    flags = flags_str.split()
    existing = [f for f in flags
                if "xla_force_host_platform_device_count" in f]
    if existing and keep_existing:
        return flags_str
    flags = [f for f in flags if f not in existing]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    return " ".join(flags)
