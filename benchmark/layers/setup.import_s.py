"""Seconds the program's package took to import (the process trace's
``import`` span: ``presto_tpu/__init__.py`` from its first line to its
last, JAX's import inside it where the package was the first to import
JAX, as it is under the harness)."""

from pathlib import Path

import verify

setup_spans = verify.load_attr(
    Path(__file__).with_name("setup.unattributed_s.py"), "setup_spans")


def read(ctx):
    found = setup_spans("setup.import_s")
    imports = [s for s in (found[0] if found else ())
               if s["name"] == "import"]
    return imports[0]["t1"] - imports[0]["t0"] if imports else None
