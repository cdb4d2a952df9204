"""``chip_smoke.py`` on the CPU: its rehearsals pass end to end, and without
a TPU, or without the rest of the checkout, it fails and prints no result.

Each case runs the script in its own process, as a user would."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, script=SCRIPT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def _result(proc):
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]) if lines else None


@pytest.mark.parametrize("args,count,phases", [
    (["--rehearse"], 1, ("[A]", "[B]", "[C]", "[D]")),
    (["--rehearse", "--chips", "4"], 4, ("[A]", "[S]")),
])
def test_rehearsal_passes(tmp_path, args, count, phases):
    proc = _run(args, tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert _result(proc) == {"ok": True, "rehearsal": True,
                             "device": {"platform": "cpu", "kind": "cpu",
                                        "count": count}}
    tags = [ln.split()[0] for ln in proc.stdout.splitlines()
            if ln.startswith("[")]
    assert set(phases) <= set(tags)
    # the workdir under TMPDIR is removed
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("chip_smoke_")]


def test_without_tpu_fails_with_no_result(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_alone_without_checkout_fails(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    proc = _run([], tmp_path, script=str(alone / "chip_smoke.py"))
    assert proc.returncode != 0
    assert "src/repro" in proc.stderr
    assert proc.stdout.strip() == ""
