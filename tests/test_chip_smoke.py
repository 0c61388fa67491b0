"""chip_smoke.py must keep walking its own code: the CPU rehearsal runs every
phase at a tiny size, and without the rehearsal flag the script refuses to
run off a TPU. The chip run itself is made through the builder's tool."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          capture_output=True, text=True, timeout=600,
                          env=env)


def test_refuses_to_run_off_a_tpu():
    proc = _run()
    assert proc.returncode != 0
    assert proc.stdout == ""          # no result line of any kind
    assert "no TPU" in proc.stderr


def test_cpu_rehearsal_walks_every_phase():
    proc = _run("--rehearse", "--rows", "8192")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    phases = [x.get("phase") for x in lines[:-1]]
    assert phases == ["start", "P1_flat", "P2_filtered", "P3_delete",
                      "P4_hybrid", "P5_import", "P5_compressed_filtered",
                      "totals"]
    assert all(x["ok"] for x in lines[:-1])
    # a rehearsal can never print the success line
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] is True
    assert lines[-1]["checks_passed"] is True


def test_cpu_rehearsal_of_four_chips_places_two_shards_a_device():
    """``--chips 4`` on four forced CPU devices: the eight-shard
    collection through the served path first (P7), then the mesh phase."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = _run("--rehearse", "--rows", "8192", "--chips", "4", env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    assert [x.get("phase") for x in lines[:-1]] == [
        "start", "P7_placed", "P6_sharded", "P6_single", "P6_agreement"]
    assert all(x["ok"] for x in lines[:-1])
    placed = lines[1]
    assert placed["local_devices"] == 4 and placed["stray_arrays"] == []
    assert sorted(len(v) for v in placed["shards_by_device"].values()) == [
        2, 2, 2, 2]
    assert placed["recall_at_10"] >= 0.99
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] is True
