"""``msmarco-8shard-cosine.c32`` walked on the CPU (``--rehearse``, 4,096
rows in eight shards of about 512): the class is created with its
``shardingConfig``, the import splits by shard, the 256-object read-back
spans the shards, every reply is judged against the whole corpus, and a
traced run reads the fan-out's two stages. The run takes its 150-s settle
cap (``run.py``'s ``canaries_sealed`` reads a collection of one shard
only: PERF.md section 7), so about four minutes. Never ``correct``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_eight_shard_cell_walks_and_reads_its_stages():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "msmarco-8shard-cosine.c32", "--seed", "3400000999", "--seconds",
         "3", "--trace", "1", "--rows", "4096", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    result = lines[-1]
    assert result["rehearsal"] is True and result["correct"] is False
    assert result["checks_passed"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = next(ln["compared"] for ln in lines if "compared" in ln)
    assert compared["readback_mismatches"]["value"] == 0
    assert compared["recall_at_k"]["value"] >= 0.99
    metrics = result["metrics"]
    assert metrics["fanout_wait_ms"]["value"] >= 0.0
    assert metrics["merge_ms"]["value"] > 0.0
    # one queue_wait a request, not one a shard: Searches over the
    # dispatches of all eight batchers
    assert 0.0 < metrics["batch_occupancy"]["value"] < 32.0 / 8
    # no device line on the CPU: the roofline reader reads nothing
    assert "shard_scan_roofline_pct" not in metrics
    settle = next(ln["settle"] for ln in lines if "settle" in ln)
    assert settle["canaries_sealed"] is False      # the harness's check
