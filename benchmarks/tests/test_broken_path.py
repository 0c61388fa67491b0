"""The rest of a run with the harness's look for a chip skipped
(``--rehearse``, a small corpus, the CPU): sound, the checks pass; with the
timed path broken where answers are produced (serve.py ``--fault
shift_ids``: every coalesced dispatch delivers its ids rolled by one slot
against their distances), they fail. Neither ever reports ``correct``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rehearse(extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "sift-flat-l2.c32", "--seed", "2400000999", "--seconds", "2",
         "--trace", "0", "--rows", "4096", "--rehearse"] + extra,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,passes", [("", True), ("shift_ids", False)],
                         ids=["sound", "shifted_ids"])
def test_run_with_the_timed_path_sound_and_broken(fault, passes):
    result = rehearse(["--fault", fault] if fault else [])
    assert result["rehearsal"] is True
    assert result["correct"] is False
    assert result["checks_passed"] is passes
    assert result["attempted"] > 0


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "sift-flat-l2.c32", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
