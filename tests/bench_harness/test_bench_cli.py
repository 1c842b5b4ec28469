"""The command itself: without an accelerator it runs nothing."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest

ROOT = manifest.ROOT


def _run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run"] + args, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", sorted(manifest.Manifest().cells))
def test_no_accelerator_no_run(cell):
    p = _run(["--workload", cell, "--seed", "3", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout == ""
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("chipbench:") and "nothing was run" in last


def test_unknown_workload_exits_non_zero():
    p = _run(["--workload", "nope", "--seed", "3", "--seconds", "1"])
    assert p.returncode != 0 and p.stdout == ""
    assert "no workload 'nope'" in p.stderr


def test_command_is_the_manifests():
    cmd = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["command"]
    assert cmd == ["python3", "-m", "chipbench.run"]
