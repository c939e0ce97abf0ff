"""presto_tpu — a TPU-native distributed SQL query engine.

A from-scratch rebuild of the capabilities of the reference MPP SQL engine
(Trino ~v360, see /root/reference) designed TPU-first:

- Columnar data lives in HBM as struct-of-arrays JAX arrays with validity
  masks (the analog of trino-spi's Page/Block, reference
  core/trino-spi/src/main/java/io/trino/spi/Page.java:33).
- Row expressions compile to jitted XLA kernels instead of JVM bytecode
  (reference core/trino-main/.../sql/gen/ExpressionCompiler.java).
- Group-by / join hash tables are static-shape scatter/gather kernels on
  device (reference operator/MultiChannelGroupByHash.java:55,
  operator/join/PagesHash.java:35).
- Distribution is a jax.sharding.Mesh + shard_map: hash repartition is an
  all_to_all over ICI, broadcast join build sides are all_gathers, and
  partial->final aggregation is the psum-tree analog of Trino's
  partial aggregation (reference sql/planner/optimizations/AddExchanges.java).

Static shapes everywhere: filters carry selection masks instead of
compacting, hash tables have planner-chosen capacities with host-side
retry on overflow, and exchanges pad to fixed per-partition capacities.
"""

import os
import sys
import time

# the process trace's ``import`` span (obs/trace.py) runs from here to
# this file's last line; the tracer does not exist yet, so the clock is
# read by hand and the interval handed over at the end
_T_IMPORT = time.monotonic()
_JAX_WAS_IMPORTED = "jax" in sys.modules

import jax  # noqa: E402

_JAX_IMPORT_S = 0.0 if _JAX_WAS_IMPORTED else time.monotonic() - _T_IMPORT

# SQL semantics need 64-bit integers (BIGINT, scaled DECIMAL) and float64.
# This must run before any array is materialised.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: SQL plans compile to large monolithic
# programs (minutes for multi-join queries on the TPU compiler); caching
# the compiled executables on disk makes repeat processes pay the compile
# once per program. Where JAX_COMPILATION_CACHE_DIR is set JAX reads it
# itself and no directory is set in code; otherwise the cache lives at a
# fixed path inside the checkout (the path is part of the cache key, so
# it must not move). Switch it off with JAX's own
# JAX_ENABLE_COMPILATION_CACHE=false / jax_enable_compilation_cache.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     os.pardir, ".xla_cache")))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
# pin the entry codec to zlib: the zstandard one-shot C compressor
# segfaulted on the multi-hundred-MB serialized executables long
# sessions produce; zlib is slower but never crashed the process.
# Private attributes, checked against jax 0.9.0: compress/decompress
# consult `zstd`, then `zstandard`, then fall back to zlib.
from jax._src import compilation_cache as _jcc  # noqa: E402
_jcc.zstd = None
_jcc.zstandard = None

from presto_tpu.types import (  # noqa: E402
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    VARCHAR,
    DecimalType,
    DataType,
)
from presto_tpu.block import Column, Table  # noqa: E402
from presto_tpu.session import Session  # noqa: E402
from presto_tpu.engine import Engine  # noqa: E402
from presto_tpu.obs import trace as _trace  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "BIGINT",
    "BOOLEAN",
    "DATE",
    "DOUBLE",
    "INTEGER",
    "VARCHAR",
    "DecimalType",
    "DataType",
    "Column",
    "Table",
    "Session",
    "Engine",
]

_trace.TRACER.add_process_span(
    "import", _trace.from_monotonic(_T_IMPORT), _trace.now(),
    jax_s=_JAX_IMPORT_S)
