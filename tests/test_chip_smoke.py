"""chip_smoke.py rehearsed without the chip: at tiny sizes on the CPU
(through its test-only override) every phase must pass and end on the
result line; without the override, a CPU run must fail and print none."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, tiny=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    env.pop("CHIP_SMOKE_TINY", None)
    if tiny:
        env["CHIP_SMOKE_TINY"] = "1"
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)


def _phases(stdout: str) -> dict:
    return {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
            for line in stdout.splitlines() if line.startswith("phase ")}


@pytest.mark.parametrize("chips,phases,devices", [
    ((), {"served", "inproc"}, 1),
    (("--chips", "4"), {"mesh"}, 4),
])
def test_smoke_rehearsal_passes(chips, phases, devices):
    out = _smoke(*chips, devices=devices)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": devices}}
    got = _phases(out.stdout)
    assert set(got) == phases
    for fields in got.values():
        assert fields["texts_equal"] == fields["docs"]
        assert fields.get("host_escalations", 0) == 0


def test_smoke_on_the_cpu_fails_without_a_result():
    out = _smoke(tiny=False)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not tpu" in out.stderr
