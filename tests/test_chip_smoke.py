"""chip_smoke.py at tiny sizes on the CPU: every phase and check of the
one-chip and four-chip paths runs here, and the script itself refuses a
device that is not a TPU."""
import os
import subprocess
import sys

import jax

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_at_tiny_size(capsys):
    chip_smoke.one_chip(0, scale=0.05, capacity=4096)
    out = capsys.readouterr().out
    for phase in ("data", "build", "flush0", "flush3", "repeat", "ingest",
                  "after_ingest", "exact"):
        assert f"[{phase}]" in out, out


def test_four_chip_phases_on_virtual_devices():
    code = ("import chip_smoke as S; "
            "S.four_chips(0, scale=0.1, capacity=16384); print('OK four')")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert "OK four" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]
    for phase in ("build_sharded", "update_sharded", "flush_local",
                  "flush_sync", "exact_local", "exact_sync"):
        assert f"[{phase}]" in r.stdout, r.stdout
