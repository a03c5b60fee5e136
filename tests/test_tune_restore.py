"""Tuner.restore: experiment-state checkpointing + resume.

Shape parity with the reference suite (python/ray/tune/tests/test_tuner_restore.py):
a SIGKILLed driver's experiment restores from its directory, checkpointed trials
resume from their latest checkpoints (never rerun from scratch), finished trials
keep their results, searcher state (TPE observations) survives the restore.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import tune


_DRIVER = """
import os, sys, time
sys.path.insert(0, {repo!r})
import ray_tpu
from ray_tpu import tune
from ray_tpu.train.checkpoint import Checkpoint

ray_tpu.init(num_cpus=2, worker_env={{"JAX_PLATFORMS": "cpu"}})

def slow_trial(config):
    import json, tempfile
    # Count every executed iteration in a file OUTSIDE the trial dir so the
    # restore test can prove checkpointed work is not redone.
    marker_dir = {markers!r}
    start = 1
    ckpt = tune.get_checkpoint()
    if ckpt is not None:
        with open(os.path.join(ckpt.path, "state.json")) as f:
            start = json.load(f)["iter"] + 1
    for i in range(start, 6):
        with open(os.path.join(marker_dir, f"{{config['x']}}_{{i}}"), "a") as f:
            f.write("1")
        time.sleep(0.6)
        d = tempfile.mkdtemp()
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({{"iter": i}}, f)
        tune.report({{"score": float(config["x"] * 10 + i)}},
                    checkpoint=Checkpoint(d))

tune.Tuner(
    slow_trial,
    param_space={{"x": tune.grid_search([1, 2, 3, 4])}},
    tune_config=tune.TuneConfig(metric="score", mode="max",
                                max_concurrent_trials=2),
    run_config=tune.RunConfig(name="restore_exp", storage_path={storage!r}),
).fit()
print("DRIVER_DONE")
"""


def test_killed_driver_experiment_restores(ray_start_regular, tmp_path):
    """Kill the driver mid-sweep; Tuner.restore completes the grid without
    rerunning checkpointed iterations."""
    storage = str(tmp_path / "storage")
    markers = str(tmp_path / "markers")
    os.makedirs(storage)
    os.makedirs(markers)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _DRIVER.format(repo=repo, storage=storage, markers=markers)
    # Own session/process group: the kill below takes out the driver AND its
    # cluster daemons + trial actors in one shot (host-death semantics) —
    # surviving orphan actors would keep executing iterations and taint the
    # exactly-once assertion.
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        cwd=str(tmp_path),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    exp_dir = os.path.join(storage, "restore_exp")
    state_file = os.path.join(exp_dir, "experiment_state.pkl")
    # Wait until real progress exists: a snapshot AND >= 3 checkpointed
    # iterations, then SIGKILL the driver (no cleanup, no final snapshot).
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if os.path.isfile(state_file) and len(os.listdir(markers)) >= 3:
            break
        if proc.poll() is not None:
            out = proc.stdout.read().decode()
            pytest.fail(f"driver exited early:\n{out}")
        time.sleep(0.3)
    else:
        proc.kill()
        pytest.fail("driver made no restorable progress in time")
    os.killpg(proc.pid, signal.SIGKILL)
    proc.wait()
    time.sleep(1.0)  # let the object-store arena/socket teardown settle

    # What the snapshot knew at kill time: per-trial checkpointed iteration.
    import json
    import pickle

    with open(state_file, "rb") as f:
        snap = pickle.load(f)
    ckpt_iter = {}  # x value -> iteration covered by the snapshotted checkpoint
    for ts in snap["trials"]:
        path = ts.get("latest_checkpoint")
        if path and not os.path.isabs(path):
            path = os.path.join(exp_dir, path)  # stored experiment-relative
        if path and os.path.isfile(os.path.join(path, "state.json")):
            with open(os.path.join(path, "state.json")) as f:
                ckpt_iter[ts["config"]["x"]] = json.load(f)["iter"]
    assert ckpt_iter, "snapshot recorded no trial checkpoints before the kill"

    assert tune.Tuner.can_restore(exp_dir)
    tuner = tune.Tuner.restore(exp_dir)
    grid = tuner.fit()
    assert len(grid) == 4
    scores = sorted(r.metrics["score"] for r in grid)
    assert scores == [15.0, 25.0, 35.0, 45.0], scores  # every trial reached iter 5

    # Checkpoint-resume semantics (at-least-once PAST the checkpoint, never
    # from scratch): every iteration covered by a trial's snapshotted
    # checkpoint executed exactly once across both driver lives — the restore
    # resumed AFTER it, not from iteration 1.
    for marker in os.listdir(markers):
        x, it = (int(v) for v in marker.split("_"))
        count = len(open(os.path.join(markers, marker)).read())
        if it <= ckpt_iter.get(x, 0):
            assert count == 1, (
                f"trial x={x} reran checkpointed iteration {it} "
                f"(snapshot covered up to {ckpt_iter[x]})"
            )
        else:
            assert count <= 2, f"iteration {marker} executed {count} times"


def test_restore_preserves_tpe_searcher_state(ray_start_regular, tmp_path):
    """The searcher's observation history survives a snapshot/restore cycle:
    after restoring, the TPE searcher continues from its recorded trials
    instead of restarting its initialization phase."""
    import pickle

    from ray_tpu.tune.search import TPESearch

    def objective(config):
        tune.report({"score": float(config["x"])})

    space = {"x": tune.uniform(0, 1)}
    searcher = TPESearch(space, metric="score", mode="max", n_initial=2, seed=7)
    tune.Tuner(
        objective,
        param_space=space,
        tune_config=tune.TuneConfig(num_samples=3, metric="score", mode="max",
                                    search_alg=searcher),
        run_config=tune.RunConfig(name="tpe_state", storage_path=str(tmp_path)),
    ).fit()
    exp_dir = os.path.join(str(tmp_path), "tpe_state")
    with open(os.path.join(exp_dir, "experiment_state.pkl"), "rb") as f:
        state = pickle.load(f)
    restored = pickle.loads(state["searcher"])
    # The snapshotted searcher carries all completed observations.
    assert len(restored._observed) >= 3
    # And a full restore cycle keeps completed trials completed: fit() after
    # restore returns immediately with the same 3 results.
    tuner = tune.Tuner.restore(exp_dir)
    grid = tuner.fit()
    assert len(grid) == 3


def test_restore_restart_errored(ray_start_regular, tmp_path):
    """restart_errored=True reruns failed trials on restore (reference:
    Tuner.restore(restart_errored=True))."""
    flag = tmp_path / "fail_once"
    flag.write_text("fail")

    def flaky(config):
        if config["x"] == 2 and flag.read_text() == "fail":
            raise RuntimeError("boom")
        tune.report({"score": float(config["x"])})

    grid1 = tune.Tuner(
        flaky,
        param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=tune.RunConfig(name="flaky_exp", storage_path=str(tmp_path)),
    ).fit()
    errs = [r for r in grid1 if r.error is not None]
    assert len(errs) == 1
    flag.write_text("ok")
    exp_dir = os.path.join(str(tmp_path), "flaky_exp")
    grid2 = tune.Tuner.restore(exp_dir, restart_errored=True).fit()
    assert all(r.error is None for r in grid2)
    assert sorted(r.metrics["score"] for r in grid2) == [1.0, 2.0, 3.0]
