"""Test configuration: force an 8-virtual-device CPU platform so sharding
tests exercise real meshes without TPU hardware."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The suite is a CPU suite whatever the machine holds: the config API
# wins over an inherited JAX_PLATFORMS.
jax.config.update("jax_platforms", "cpu")

# The persistent XLA cache stays DISABLED under pytest: round-5
# experiments re-enabled it (zlib codec, then serialize-only->=0.5s
# compiles) and the full suite crashed mid-run both times with a fatal
# interpreter dump, while isolated 120-serialization probes pass —
# the crash needs full-suite compile volume in one process and has not
# been re-examined on jax 0.9.0. JAX's own switch, set through the
# config so it holds when JAX_COMPILATION_CACHE_DIR is inherited; the
# environment variable carries it to the child processes tests spawn.
jax.config.update("jax_enable_compilation_cache", False)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.testing.oracle import SqliteOracle  # noqa: E402


@pytest.fixture(scope="session")
def tpch_tiny() -> TpchConnector:
    return TpchConnector(scale=0.01)


@pytest.fixture(scope="session")
def oracle(tpch_tiny) -> SqliteOracle:
    o = SqliteOracle()
    o.load_connector(tpch_tiny)
    return o


@pytest.fixture(scope="session")
def engine(tpch_tiny):
    from presto_tpu import Engine
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    return e
