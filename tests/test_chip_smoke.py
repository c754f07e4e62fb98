"""chip_smoke.py on the CPU: its phases agree at a tiny size, and the
script refuses to report success anywhere but on a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

import chip_smoke

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(rate=20_000, n_keys=200, seconds=0.4, n_key_buckets=256,
            batch_size=256)


@pytest.mark.parametrize("name", ["A", "B", "C"])
def test_phase_device_equals_host_at_tiny_size(name):
    reports = chip_smoke.run_phase(name, seed=3,
                                   **dict(chip_smoke.PHASES[name], **TINY))
    dev, host = reports
    assert dev["placement"] == "device" and host["placement"] == "host"
    assert dev["device_steps"] > 0 and dev["results"] > 0
    assert dev["snapshots"] > 0
    if name == "C":
        assert dev["killed"] and not host["killed"]


def test_phase_reports_a_mismatch(monkeypatch):
    """A device result that differs from the host's fails the phase."""
    real = chip_smoke.run_q5

    def skewed(placement, **kw):
        rows, stats = real(placement, **kw)
        if placement == "device":
            rows["value"][0] += 1
        return rows, stats

    monkeypatch.setattr(chip_smoke, "run_q5", skewed)
    with pytest.raises(chip_smoke.PhaseMismatch):
        chip_smoke.run_phase("A", **dict(chip_smoke.PHASES["A"], **TINY,
                                         guarantee="none"))


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_script_fails_without_tpu(args):
    r = _run_script(ROOT, *args)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs" in r.stderr and "cpu" in r.stderr


def test_script_fails_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


SPMD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, jax
    import chip_smoke
    print(json.dumps(chip_smoke.run_spmd(jax.devices(), seed=1, n_steps=6,
                                         batch=1024, n_buckets=512,
                                         rate=100_000)))
""")


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SPMD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["phase"] == "4-chip" and report["results"] > 0
