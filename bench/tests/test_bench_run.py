"""``bench/run.py`` refuses to run without a TPU: non-zero exit and no
result line."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=120)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


@pytest.mark.parametrize("workload", ["rm1.backlog", "no.such-cell"])
def test_no_tpu_no_result(workload):
    p = _run("--workload", workload, "--seed", str(2 ** 31 + 3),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []
    assert "bench:" in p.stderr
