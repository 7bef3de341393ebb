"""Multi-process harness: real localhost subprocesses through
``paddle_tpu.distributed.launch`` + ``init_parallel_env`` on a 2-process
CPU ring (reference methodology: tests/unittests/test_dist_base.py:642,
test_collective_base.py:34 — subprocess workers + result files).
"""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_launch_two_process_ring(tmp_path):
    script = os.path.join(os.path.dirname(__file__), "dist_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--coordinator_port", "23851",
           script, str(tmp_path)]
    r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=280)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])

    results = {}
    for rank in (0, 1):
        path = tmp_path / f"result.{rank}.json"
        assert path.exists(), (r.stdout[-2000:], r.stderr[-2000:])
        results[rank] = json.loads(path.read_text())

    for rank, res in results.items():
        assert res["rank"] == rank
        # sum over ranks of (rank+1) = 3, elementwise
        np.testing.assert_allclose(res["all_reduce"], [3.0, 3.0, 3.0])
        np.testing.assert_allclose(res["all_gather"],
                                   [[0.0, 0.0], [1.0, 1.0]])
        # broadcast from src=1 -> rank 1's value (8.0) everywhere
        np.testing.assert_allclose(res["broadcast"], [8.0, 8.0])
        # dygraph DataParallel: allreduced half-batch grads == full-batch
        assert res["grad_max_err"] < 1e-5, res
