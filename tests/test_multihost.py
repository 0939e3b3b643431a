"""Multi-host (multi-process) collective DP over the DCN analog.

parallel/multihost.py had no test. This is the
reference's subprocess-cluster pattern (test_dist_base.py:423
_run_cluster_nccl2) mapped to TPU-native collectives: 2 processes × 4
virtual CPU devices form one 8-device mesh via jax.distributed (gloo as the
DCN stand-in), ParallelExecutor compiles the same SPMD step it uses
single-process, and the losses must match a single-process 8-device run
exactly (same seeds, same global batch).
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from port_utils import free_ports

HERE = os.path.dirname(__file__)
RUNNER = os.path.join(HERE, "multihost_runner.py")

N_PROCS = 2
DEVICES_PER_PROC = 4
STEPS = 8


def _env(endpoints=None, trainer_id=None, devices=DEVICES_PER_PROC):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(HERE, ".."), env.get("PYTHONPATH", "")]
    )
    if endpoints is not None:
        env["PADDLE_TRAINER_ENDPOINTS"] = endpoints
        env["PADDLE_TRAINER_ID"] = str(trainer_id)
    return env


def _run(cmd, env, timeout):
    """Run one child; stderr goes to a temp file (a PIPE nobody drains can
    deadlock a chatty child), and a timeout kills rather than leaks it."""
    with tempfile.NamedTemporaryFile(
        mode="w+", prefix="mh_", suffix=".err", delete=False
    ) as ef:
        p = None
        try:
            p = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=ef, text=True, env=env
            )
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        finally:
            if p is not None and p.poll() is None:
                p.kill()
            ef.flush()
            ef.seek(0)
            err = ef.read()
            os.unlink(ef.name)
    return p.returncode, out, err


def _losses(out):
    lines = [l for l in out.splitlines() if l.startswith("LOSSES ")]
    assert lines, "no LOSSES line in output:\n%s" % out
    return json.loads(lines[0][len("LOSSES "):])


def test_two_process_mesh_matches_single_process():
    # multi-process cluster: rank 0's endpoint doubles as the coordinator,
    # exercising init_distributed's fluid-env defaulting
    # only endpoint[0] (the coordinator) is actually bound; the rest of the
    # list just conveys num_processes, mirroring the reference's env contract
    endpoints = ",".join("127.0.0.1:%d" % p for p in free_ports(N_PROCS))
    procs, err_files = [], []
    try:
        for pid in range(N_PROCS):
            # stderr to files: sequential communicate() below would deadlock
            # if an undrained concurrent rank filled a stderr PIPE
            ef = tempfile.NamedTemporaryFile(
                mode="w+", prefix="mh_rank%d_" % pid, suffix=".err", delete=False
            )
            err_files.append(ef)
            procs.append(
                subprocess.Popen(
                    [sys.executable, RUNNER, "--steps", str(STEPS)],
                    stdout=subprocess.PIPE,
                    stderr=ef,
                    text=True,
                    env=_env(endpoints, pid),
                )
            )
        outs = []
        for p, ef in zip(procs, err_files):
            out, _ = p.communicate(timeout=300)
            ef.flush()
            ef.seek(0)
            assert p.returncode == 0, "rank failed:\n%s" % ef.read()[-4000:]
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for ef in err_files:
            name = ef.name
            ef.close()
            if os.path.exists(name):
                os.unlink(name)

    per_rank = [_losses(o) for o in outs]
    for o in outs:
        # the mesh really spanned both processes
        assert "DEVICES %d local %d" % (
            N_PROCS * DEVICES_PER_PROC, DEVICES_PER_PROC,
        ) in o, o

    # every rank observes the SAME replicated loss sequence
    np.testing.assert_allclose(per_rank[0], per_rank[1], rtol=1e-6)
    losses = np.asarray(per_rank[0])
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses

    # single-process 8-device run: identical losses (same seeds/global batch)
    rc, out, err = _run(
        [sys.executable, RUNNER, "--steps", str(STEPS), "--single_process"],
        _env(devices=N_PROCS * DEVICES_PER_PROC),
        timeout=300,
    )
    assert rc == 0, err[-4000:]
    single = _losses(out)
    np.testing.assert_allclose(per_rank[0], single, rtol=2e-5, atol=1e-7)
