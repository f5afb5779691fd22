"""CPU rehearsal of chip_smoke.py (guide on-chip-measurement, section 2.1):
toy sizes, Pallas interpreted, every phase and every child hand-off run on
the CPU backend — and the no-fallback rule, tested: the script never says
ok and never exits 0 unless the device that did the work is a TPU."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, devices: int = 1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    return r.returncode, r.stdout, r.stdout + r.stderr


def _phase_lines(out: str) -> dict:
    """{phase: 'passed' | 'FAILED'} from the per-phase verdict lines."""
    verdicts = {}
    for line in out.splitlines():
        for word in ("passed", "FAILED"):
            if line.startswith("[") and f"] {word}" in line:
                verdicts[line[1:line.index("]")]] = word
    return verdicts


@pytest.mark.parametrize(
    "args, devices, phases",
    [
        (("--tiny",), 1, ("probe", "serve", "reference", "train", "paged", "handover")),
        (("--tiny", "--chips", "4"), 4, ("probe", "tp4-serve", "tp4-engines", "fsdp4-train")),
    ],
    ids=["one_chip", "four_chips"],
)
def test_rehearsal_runs_every_phase_and_refuses_ok_off_tpu(args, devices, phases):
    rc, out, log = _smoke(*args, devices=devices)
    verdicts = _phase_lines(out)
    assert verdicts == {p: "passed" for p in phases}, log[-4000:]
    assert rc != 0, "a CPU run must not exit 0"
    assert '"ok"' not in out, "a CPU run must not print a result"
    assert "not on a TPU" in out.splitlines()[-1]


def test_a_failing_phase_fails_the_run():
    """--sabotage gives the reference other weights: the served-vs-reference
    comparison must notice, and the failed phase must fail the run."""
    rc, out, log = _smoke("--tiny", "--sabotage", "reference")
    verdicts = _phase_lines(out)
    assert verdicts.get("serve") == "passed", log[-4000:]
    assert verdicts.get("reference") == "FAILED", log[-4000:]
    assert rc != 0 and '"ok"' not in out


def test_without_tiny_a_cpu_run_stops_at_the_probe():
    rc, out, _ = _smoke()
    assert rc != 0 and '"ok"' not in out
    assert "JAX finds no TPU" in out and "[serve]" not in out
