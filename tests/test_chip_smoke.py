"""chip_smoke.py's contract where there is no chip: it fails, with no
result line — and its one debugging aid, ``--cpu-debug``, drives the same
three legs at toy size."""

import json
import os
import resource
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, timeout=600, file_limit=None):
    # two virtual devices: --cpu-debug then also takes the several-chips
    # path (train fsdp=2, serve tensor=2 over llama_tiny's 2 KV heads)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}

    def limit():
        resource.setrlimit(resource.RLIMIT_FSIZE, (file_limit, file_limit))

    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout,
                          preexec_fn=limit if file_limit else None)


def _results(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_no_chip_is_a_nonzero_exit_and_no_result():
    proc = _run([SMOKE])
    assert proc.returncode != 0
    assert _results(proc.stdout) == []
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert _results(proc.stdout) == []


@pytest.mark.slow
def test_cpu_debug_drives_all_three_legs():
    # a machine may cap the size of one file (the driver's refused a
    # 1.64 GB model.safetensors): the toy weights are 183 KB, saved in
    # 64 KiB shards, and nothing else the smoke writes is large
    proc = _run([SMOKE, "--cpu-debug"], file_limit=128 << 10)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "cpu_debug": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    legs = [line.split(":")[0] for line in proc.stdout.splitlines()
            if line.startswith("[chip_smoke] ")]
    assert legs == ["[chip_smoke] train", "[chip_smoke] serve",
                    "[chip_smoke] check"]
