"""True multi-process distributed training on localhost (reference
unittests/test_dist_base.py:245-422 — Popen pservers with role flags, then
trainers, losses pickled over stdout and checked for convergence). The
threaded variant lives in test_transpiler.py; this one exercises real
process isolation: separate interpreters, sockets across processes, COMPLETE
teardown.

Round-4 matrix: beyond the dense MLP case, the
reference's subprocess family is covered by
- word2vec embedding cluster (dist_word2vec.py): row-sliced shared embedding
  table across pservers, LOSS PARITY vs a single-process run on the same
  deterministic batch schedule,
- dist save/load resume (dist_save_load.py): checkpoint_notify -> pserver
  shard checkpoints -> fresh cluster restores and continues the EXACT loss
  trajectory,
- gradient-merge x pserver (test_dist_mnist_batch_merge.py): k-round
  accumulate-then-apply on the pservers, parity vs the equivalent
  single-process schedule.

Parity math (sync SGD): pservers SUM the per-trainer grads (no 1/N), so a
cluster of T trainers on batches b_1..b_T equals one process stepping on
concat(b_1..b_T) with lr' = T * lr (mean-loss grad of the concat is the
trainer-sum / T). With gradient merge k and avg=True the apply uses
(sum over k rounds)/k, so the single-process equivalent steps once per k
rounds on the concat of all T*k window batches with the same lr' = T * lr.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np

from port_utils import free_ports

HERE = os.path.dirname(__file__)
RUNNER = os.path.join(HERE, "dist_runner.py")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(HERE, ".."), env.get("PYTHONPATH", "")]
    )
    return env


class Cluster:
    """Popen a pserver per endpoint + n trainers of dist_runner.py; collect
    per-trainer loss lists; assert clean teardown."""

    def __init__(self, n_pservers=2, n_trainers=2, **common):
        self.eps = ["127.0.0.1:%d" % p for p in free_ports(n_pservers)]
        self.endpoints = ",".join(self.eps)
        self.n_trainers = n_trainers
        self.common = common
        self.env = _env()
        self.procs = []
        self.stderr_files = {}

    def spawn(self, role, **kw):
        cmd = [sys.executable, RUNNER, "--role", role, "--endpoints",
               self.endpoints, "--trainers", str(self.n_trainers)]
        for k, v in dict(self.common, **kw).items():
            cmd += ["--%s" % k, str(v)]
        # stderr -> temp file: an undrained PIPE filling with jax/absl
        # warnings would deadlock the child, DEVNULL would lose the
        # traceback when it dies; a file keeps both properties
        ef = tempfile.NamedTemporaryFile(
            mode="w+", prefix="dist_%s_" % role, suffix=".err", delete=False
        )
        p = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=ef, text=True, env=self.env
        )
        self.stderr_files[p] = ef
        self.procs.append(p)
        return p

    def child_stderr(self, p):
        ef = self.stderr_files[p]
        ef.flush()
        ef.seek(0)
        return ef.read()

    def run(self, pserver_args=None, trainer_args=None):
        """Full lifecycle; returns [losses_trainer_0, losses_trainer_1, ...]."""
        pservers = [
            self.spawn("pserver", current_endpoint=ep, **(pserver_args or {}))
            for ep in self.eps
        ]
        # wait until all bind (reference start_pserver waits with timeout);
        # a reader thread per pserver keeps the readiness wait time-bounded:
        # readline() itself blocks, so the deadline is enforced from outside
        ready = {}

        def wait_ready(p):
            line = ""
            while "PSERVER_READY" not in line:
                line = p.stdout.readline()
                if not line and p.poll() is not None:
                    return
            ready[p] = True

        waiters = [
            threading.Thread(target=wait_ready, args=(p,), daemon=True)
            for p in pservers
        ]
        for w in waiters:
            w.start()
        for w in waiters:
            w.join(timeout=120)
        for p in pservers:
            assert ready.get(p), "pserver not ready: %s" % self.child_stderr(p)

        trainers = [
            self.spawn("trainer", trainer_id=i, **(trainer_args or {}))
            for i in range(self.n_trainers)
        ]
        all_losses = []
        for tr in trainers:
            out, _ = tr.communicate(timeout=240)
            assert tr.returncode == 0, "trainer failed:\n%s" % self.child_stderr(tr)
            loss_lines = [l for l in out.splitlines() if l.startswith("LOSSES ")]
            assert loss_lines, "no losses in trainer output:\n%s\n%s" % (
                out, self.child_stderr(tr),
            )
            all_losses.append(json.loads(loss_lines[0][len("LOSSES "):]))

        # pservers exit cleanly after all trainers COMPLETE
        for p in pservers:
            p.wait(timeout=60)
            assert p.returncode == 0
        return all_losses

    def cleanup(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for ef in self.stderr_files.values():
            name = ef.name
            ef.close()
            if os.path.exists(name):
                os.unlink(name)


def _make_init_dir(model, dirname, n_pservers=2):
    """Write a shared-initialization dir: full seed-21 params (trainers load
    them by name) plus their transpiler-sliced .blockN rows (pservers load
    their shards) — aligning every role with the single-process parity
    reference. Needed because get_startup_program re-draws initializers at
    SHARD shape (documented deviation from the reference, which slices the
    initialized full tensor), so cluster and single-process inits would
    otherwise diverge."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import io as fluid_io
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.transpiler import (
        DistributeTranspiler,
        DistributeTranspilerConfig,
    )

    from dist_runner import build

    from paddle_tpu.framework import Parameter

    main, startup, loss = build(model, 0.1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope(seed=21)
    with scope_guard(scope):
        exe.run(startup)
        # Parameters ONLY: non-param persistables (the learning-rate var,
        # optimizer state) must keep each role's own values — loading the
        # init-dir lr would silently override the cluster's --lr
        params = {
            v.name: np.asarray(scope.find_var(v.name))
            for v in main.list_vars()
            if isinstance(v, Parameter)
            and scope.find_var(v.name) is not None
        }
    config = DistributeTranspilerConfig()
    config.min_block_size = 1
    t = DistributeTranspiler(config)
    dummy_eps = ",".join("127.0.0.1:%d" % (1 + i) for i in range(n_pservers))
    t.transpile(trainer_id=0, program=main, pservers=dummy_eps, trainers=2,
                startup_program=startup)
    arrays = dict(params)
    for pname, pblocks in t.param_blocks.items():
        if pname not in params:
            continue
        for pb in pblocks:
            if pb.sliced:
                arrays[pb.name()] = params[pname][pb.begin:pb.begin + pb.rows]
    fluid_io.save_arrays(dirname, arrays)
    return dirname


def _single_process_losses(model, lr, n_trainers, steps, gm_k=1):
    """The parity reference: one process on the concat batch schedule (see
    module docstring for the math). Returns per-round losses on the concat
    batch == mean over trainers of the cluster's per-trainer losses."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope, scope_guard

    from dist_runner import build, make_batch

    main, startup, loss, eval_prog = build(
        model, lr * n_trainers, with_eval=True
    )
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope(seed=21)
    losses = []
    with scope_guard(scope):
        exe.run(startup)
        window = []
        for s in range(steps):
            batches = [make_batch(model, t, s) for t in range(n_trainers)]
            concat = {
                k: np.concatenate([b[k] for b in batches]) for k in batches[0]
            }
            window.append(concat)
            (lv,) = exe.run(eval_prog, feed=concat, fetch_list=[loss.name])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
            if len(window) == gm_k:
                apply_feed = {
                    k: np.concatenate([w[k] for w in window])
                    for k in window[0]
                }
                exe.run(main, feed=apply_feed, fetch_list=[loss.name])
                window = []
    return losses


def test_two_pservers_two_trainers_subprocess():
    cluster = Cluster(model="mlp", steps=12)
    try:
        all_losses = cluster.run()
    finally:
        cluster.cleanup()
    for losses in all_losses:
        assert np.isfinite(losses).all()
        assert np.mean(losses[-3:]) < np.mean(losses[:3]) * 0.8, losses


def test_dist_word2vec_embedding_cluster(tmp_path):
    """Sparse-model tier (dist_word2vec analog): the [64, 8] shared
    embedding table is row-sliced across 2 pservers (min_block_size=1);
    cluster loss trajectory must MATCH the single-process run."""
    steps = 8
    init_dir = _make_init_dir("word2vec", str(tmp_path / "init"))
    cluster = Cluster(model="word2vec", steps=steps, lr=0.2)
    try:
        all_losses = cluster.run(
            pserver_args={"load_dir": init_dir},
            trainer_args={"load_dir": init_dir},
        )
    finally:
        cluster.cleanup()
    dist_mean = np.mean(all_losses, axis=0)  # == loss on the concat batch
    single = _single_process_losses("word2vec", 0.2, 2, steps)
    # the parity IS the contract (reference test_dist_base compares dist vs
    # local losses the same way); the toy sum%64 task is not learnable in 8
    # steps, so assert the trajectory is live + finite rather than falling
    assert np.isfinite(dist_mean).all()
    assert np.ptp(dist_mean) > 0  # params are actually updating
    np.testing.assert_allclose(dist_mean, single, rtol=2e-3, atol=2e-4)


def test_dist_save_load_resume(tmp_path):
    """dist_save_load analog: 6 steps + checkpoint_notify -> pserver shard
    checkpoints; a FRESH cluster restores them and continues; its losses
    must equal steps 6..12 of an uninterrupted cluster."""
    ckpt = str(tmp_path / "ckpt")

    full = Cluster(model="mlp", steps=12, lr=0.05)
    try:
        full_losses = np.mean(full.run(), axis=0)
    finally:
        full.cleanup()

    phase1 = Cluster(model="mlp", steps=6, lr=0.05)
    try:
        p1 = np.mean(
            phase1.run(trainer_args={"save_dir": ckpt, "save_after": 6}),
            axis=0,
        )
    finally:
        phase1.cleanup()
    assert os.path.isdir(ckpt) and os.listdir(ckpt), "no checkpoint written"
    np.testing.assert_allclose(p1, full_losses[:6], rtol=1e-4)

    phase2 = Cluster(model="mlp", steps=6, lr=0.05)
    try:
        p2 = np.mean(
            phase2.run(
                pserver_args={"load_dir": ckpt},
                # trainers also resume from the checkpoint (shard slices
                # reassembled) — their local init would skew step 6's loss
                trainer_args={"start_step": 6, "load_dir": ckpt},
            ),
            axis=0,
        )
    finally:
        phase2.cleanup()
    np.testing.assert_allclose(p2, full_losses[6:], rtol=1e-3, atol=1e-5)


def test_dist_save_load_resume_gradient_merge_midwindow(tmp_path):
    """Composition: checkpoint_notify lands MID gradient-merge window (5
    rounds into gm_k=2 => one round accumulated). The window accumulator +
    phase ride in the checkpoint under __gm_* names, so the resumed cluster
    continues the exact trajectory of an uninterrupted one."""
    ckpt = str(tmp_path / "ckpt")
    args = dict(model="mlp", lr=0.02)

    full = Cluster(steps=12, **args)
    try:
        full_losses = np.mean(full.run(pserver_args={"gm_k": 2}), axis=0)
    finally:
        full.cleanup()

    phase1 = Cluster(steps=5, **args)
    try:
        phase1.run(
            pserver_args={"gm_k": 2},
            trainer_args={"save_dir": ckpt, "save_after": 5},
        )
    finally:
        phase1.cleanup()
    assert any(f.startswith("__gm_") for f in os.listdir(ckpt)), (
        "mid-window checkpoint must carry the merge accumulator"
    )

    phase2 = Cluster(steps=7, **args)
    try:
        p2 = np.mean(
            phase2.run(
                pserver_args={"gm_k": 2, "load_dir": ckpt},
                trainer_args={"start_step": 5, "load_dir": ckpt},
            ),
            axis=0,
        )
    finally:
        phase2.cleanup()
    np.testing.assert_allclose(p2, full_losses[5:], rtol=1e-3, atol=1e-6)


def test_dist_gradient_merge_pserver(tmp_path):
    """Batch-merge x pserver composition (test_dist_mnist_batch_merge
    analog): gm_k=2 accumulates two sync rounds on the pservers before each
    optimizer apply; parity vs the single-process window schedule."""
    steps, gm_k = 8, 2
    init_dir = _make_init_dir("mlp", str(tmp_path / "init"))
    # lr low enough that the trajectory is smooth: parity comparison should
    # measure the update math, not f32-noise amplification through a twitchy
    # high-lr relu net
    cluster = Cluster(model="mlp", steps=steps, lr=0.02)
    try:
        all_losses = cluster.run(
            pserver_args={"gm_k": gm_k, "load_dir": init_dir},
            trainer_args={"load_dir": init_dir},
        )
    finally:
        cluster.cleanup()
    dist_mean = np.mean(all_losses, axis=0)
    single = _single_process_losses("mlp", 0.02, 2, steps, gm_k=gm_k)
    # rtol: f32 reduction-order differences (concat-batch mean vs summed
    # per-trainer means) compound over 4 applies to ~1e-3
    np.testing.assert_allclose(dist_mean, single, rtol=5e-3, atol=1e-5)
    # params freeze within a window: rounds 0 and 1 see the same params,
    # and the trajectory still converges across windows
    assert dist_mean[-1] < dist_mean[0]
