"""What chip_smoke.py rests on, held on the CPU: its steps run at
SF 0.01 (the mesh on virtual devices) — but
``main`` still refuses to pass off the chip — plus the two start-up
properties a one-process-per-chip device needs: the compile cache goes
where JAX_COMPILATION_CACHE_DIR says, and importing the program
initialises no backend."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke_engine():
    return chip_smoke.build_engine(0.01, 19920101)


def test_served_leg_answers_equal_numpy(smoke_engine, capsys):
    engine, conn = smoke_engine
    # every check inside raises SmokeFailure: exact answers against
    # the NumPy reference, zero compiles for literal variants, a
    # changed answer after INSERT
    chip_smoke.served_leg(engine, conn)
    out = capsys.readouterr().out
    for label in ("q06", "q01", "q03", "q06 variant", "q01 variant",
                  "q03 variant", "select after ctas",
                  "select after insert"):
        assert f"[served] {label}: equals the NumPy reference" in out
    assert chip_smoke.pinned_lineitem_bytes(conn) > 0


def test_served_leg_fails_on_a_wrong_answer(monkeypatch):
    engine, conn = chip_smoke.build_engine(0.01, 19920101)
    monkeypatch.setattr(chip_smoke, "ref_q6",
                        lambda conn, year: [["0.0001"]])
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="q06: answer differs"):
        chip_smoke.served_leg(engine, conn)


def test_mesh_leg_on_virtual_devices(smoke_engine, capsys):
    engine, conn = smoke_engine
    chip_smoke.mesh_leg(engine, conn, jax.devices())
    out = capsys.readouterr().out
    assert "[mesh] q01: equals one-device answer" in out
    assert "[mesh] exchange step: row count" in out
    assert "all_to_all" in out
    assert "[mesh] q03 over the mesh: NOT RUN, owed" in out
    with pytest.raises(chip_smoke.SmokeFailure, match="four devices"):
        chip_smoke.mesh_leg(engine, conn, jax.devices()[:1])


def test_main_refuses_to_pass_without_a_chip(capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert "no CPU fallback" in captured.err
    assert '"ok"' not in captured.out


_STARTUP_CHILD = """
import json
import presto_tpu.exec.executor, presto_tpu.server.server
import presto_tpu.client, presto_tpu.cli
import jax
from jax._src import xla_bridge
print(json.dumps({
    "cache_dir": jax.config.jax_compilation_cache_dir,
    "backends_initialized": xla_bridge.backends_are_initialized()}))
"""


@pytest.mark.parametrize("env_dir", ["/x", None])
def test_startup_cache_dir_and_no_backend_on_import(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _STARTUP_CHILD], capture_output=True,
        text=True, timeout=120, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # set: JAX reads the variable itself and the program sets nothing
    # in code; unset: a fixed path inside the checkout
    assert out["cache_dir"] == (env_dir
                                or os.path.join(REPO, ".xla_cache"))
    # importing the executor, server, client and CLI claims
    # no device (a parent that plans and children that execute)
    assert out["backends_initialized"] is False
