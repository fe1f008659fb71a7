"""Checkpoint/resume: a restored session continues bit-for-bit like the
uninterrupted run (params, mode state, round counter, host sampling RNG)."""

import numpy as np
import pytest

import cv_train
from commefficient_tpu.utils import checkpoint as ckpt
from commefficient_tpu.utils.config import make_parser, resolve_defaults


def _args(tmp, extra=()):
    argv = [
        "--dataset", "cifar10", "--mode", "sketch", "--num_clients", "8",
        "--num_workers", "2", "--local_batch_size", "4", "--k", "100",
        "--num_cols", "2000", "--num_rows", "3", "--lr_scale", "0.05",
        "--data_root", "/nonexistent", *extra,
    ]
    return resolve_defaults(make_parser("cv").parse_args(argv))


@pytest.fixture()
def small_session(tmp_path, monkeypatch):
    import flax.linen as nn

    import commefficient_tpu.data.cifar as cifar_mod

    orig = cifar_mod.load_cifar_fed

    def tiny(*a, **kw):
        kw.update(synthetic_train=64, synthetic_test=32)
        return orig(*a, **kw)

    monkeypatch.setattr(cv_train, "load_cifar_fed", tiny)

    # checkpoint logic is model-agnostic; a 2-layer MLP compiles in seconds
    # where ResNet-9 takes ~40-80 s per session on this 1-core box (the
    # real model's CLI path is covered by test_determinism/test_golden)
    class _TinyNet(nn.Module):
        num_classes: int = 10
        dtype: str = "float32"

        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(self.num_classes)(x)

    monkeypatch.setattr(cv_train, "ResNet9", _TinyNet)
    return tmp_path


def test_save_restore_resume_equivalence(small_session, tmp_path):
    args = _args(tmp_path)
    # run A: 6 uninterrupted rounds
    sa, _ = cv_train.build(args)
    for i in range(6):
        sa.run_round(0.05)
    # run B: 3 rounds, checkpoint, fresh session, restore, 3 more
    sb, _ = cv_train.build(_args(tmp_path))
    for i in range(3):
        sb.run_round(0.05)
    path = ckpt.save(str(tmp_path / "ck"), sb)
    sc, _ = cv_train.build(_args(tmp_path))
    ckpt.restore(path, sc)
    assert sc.round == 3
    for i in range(3):
        sc.run_round(0.05)

    import jax

    for a, b in zip(jax.tree.leaves(sa.state["params"]), jax.tree.leaves(sc.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    for a, b in zip(
        jax.tree.leaves(sa.state["mode_state"]), jax.tree.leaves(sc.state["mode_state"])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_comm_mb_total_checkpointed_under_dropout(small_session, tmp_path):
    """Cumulative communication is MEASURED (survivor-scaled under dropout),
    so a resumed run must restore the measured sum — deriving it as
    round * static-per-round-estimate overstates it (ADVICE r3)."""
    args = _args(tmp_path, extra=("--client_dropout", "0.5"))
    s, _ = cv_train.build(args)
    measured = 0.0
    dropped_any = False
    for _ in range(6):
        m = s.run_round(0.05)
        measured += m["comm_total_mb"]
        dropped_any = dropped_any or m["participants"] < s.num_workers
    assert dropped_any  # the seed produces at least one non-full round
    assert s.comm_mb_total == pytest.approx(measured)
    static = s.round * s.comm_per_round["comm_total_mb"]
    assert s.comm_mb_total < static  # the distinction is non-trivial here

    path = ckpt.save(str(tmp_path / "ck"), s)
    s2, _ = cv_train.build(_args(tmp_path, extra=("--client_dropout", "0.5")))
    ckpt.restore(path, s2)
    assert s2.comm_mb_total == pytest.approx(measured)
    # and it keeps accumulating measured figures after resume
    m = s2.run_round(0.05)
    assert s2.comm_mb_total == pytest.approx(measured + m["comm_total_mb"])


def test_cohort_size_change_across_checkpoint_warns(small_session, tmp_path, capsys):
    """Restoring into a session with a different num_workers (mesh rounding
    or a flag change) silently breaks exact client-sequence replay — the
    restore must say so loudly."""
    s, _ = cv_train.build(_args(tmp_path))
    s.run_round(0.05)
    path = ckpt.save(str(tmp_path / "ck"), s)
    # the 8-way mesh rounds every cohort to a multiple of 8; 32 clients with
    # --num_workers 16 stays 16, vs the saved session's 8
    s2, _ = cv_train.build(
        _args(tmp_path, extra=("--num_clients", "32", "--num_workers", "16"))
    )
    capsys.readouterr()
    ckpt.restore(path, s2)
    assert "will NOT replay" in capsys.readouterr().out
    # same cohort: no warning
    s3, _ = cv_train.build(_args(tmp_path))
    capsys.readouterr()
    ckpt.restore(path, s3)
    assert "will NOT replay" not in capsys.readouterr().out


def test_latest_and_prune(small_session, tmp_path):
    args = _args(tmp_path)
    s, _ = cv_train.build(args)
    paths = []
    for i in range(5):
        s.run_round(0.05)
        paths.append(ckpt.save(str(tmp_path / "ck"), s, keep=2))
    import os

    remaining = sorted(os.listdir(tmp_path / "ck"))
    assert len(remaining) == 2
    assert ckpt.latest(str(tmp_path / "ck")).endswith(remaining[-1])


def test_restore_via_relative_checkpoint_dir(small_session, tmp_path, monkeypatch):
    """`--checkpoint_dir ck` (relative, as every CLI example uses): orbax's
    tensorstore rejects relative paths at RESTORE time while save() abspaths,
    so latest() must return an absolute path — the asymmetry let a run save
    for hours and then crash the --resume (observed round 4, session 3)."""
    import os

    args = _args(tmp_path)
    s, _ = cv_train.build(args)
    for _ in range(2):
        s.run_round(0.05)
    monkeypatch.chdir(tmp_path)
    ckpt.save("ck_rel", s)
    path = ckpt.latest("ck_rel")
    assert os.path.isabs(path), path
    s2, _ = cv_train.build(_args(tmp_path))
    ckpt.restore(path, s2)  # raised ValueError before the fix
    assert s2.round == s.round
    np.testing.assert_array_equal(
        np.asarray(s2.state["round"]), np.asarray(s.state["round"])
    )


def test_save_readback_catches_silent_bitrot(small_session, tmp_path, monkeypatch):
    """Manifest verification on SAVE: media that acknowledges a write and
    stores different bytes must fail the save LOUDLY (counted, raised inside
    the retry wrapper) — not surface hours later at restore when the damaged
    checkpoint is the only copy. A transient bitrot recovers via the
    re-write; persistent bitrot exhausts retries and raises."""
    from commefficient_tpu.resilience import FaultPlan, RetryPolicy

    s, _ = cv_train.build(_args(tmp_path))
    s.run_round(0.05)

    real_manifest = ckpt._write_manifest
    lies = {"left": 1}

    def lying_media(path):
        # manifest records the TRUE hashes; then the 'media' flips a byte of
        # the largest staged file — exactly what the post-commit read-back
        # exists to catch (write-path corruption under an intact manifest)
        real_manifest(path)
        if lies["left"] > 0:
            lies["left"] -= 1
            target = FaultPlan._largest_data_file(path)
            with open(target, "r+b") as f:
                b = f.read(1)
                f.seek(0)
                f.write(bytes([b[0] ^ 0xFF]))

    monkeypatch.setattr(ckpt, "_write_manifest", lying_media)
    before = ckpt.save_verify_failures()
    path = ckpt.save(str(tmp_path / "ck"), s,
                     retry_policy=RetryPolicy(max_retries=2, base_delay_s=0.001))
    assert ckpt.save_verify_failures() == before + 1  # counted in metrics
    assert ckpt.verify(path) is True  # the retry re-wrote a clean copy

    lies["left"] = 99  # persistent bitrot: every attempt fails, loudly
    with pytest.raises(ckpt.CheckpointVerifyError):
        ckpt.save(str(tmp_path / "ck2"), s,
                  retry_policy=RetryPolicy(max_retries=1, base_delay_s=0.001))
    assert ckpt.save_verify_failures() == before + 3

    # a corrupt RE-SAVE of an already-checkpointed round must put the
    # displaced verified-good copy back, never destroy it
    with pytest.raises(ckpt.CheckpointVerifyError):
        ckpt.save(str(tmp_path / "ck"), s,
                  retry_policy=RetryPolicy(max_retries=0))
    assert ckpt.verify(path) is True  # the good round survived the attempt

    # the opt-out keeps the old (unverified) save behavior
    lies["left"] = 1
    p3 = ckpt.save(str(tmp_path / "ck3"), s,
                   retry_policy=RetryPolicy(max_retries=0),
                   verify_on_save=False)
    assert ckpt.verify(p3) is False  # damage committed silently, as opted


def test_cifar100_build_path_round(small_session, tmp_path):
    """--dataset cifar100 through the full cv_train build path (the parser
    offered the choice with nothing behind it until round 4); loader-level
    100-class assertions live in test_data.py::test_cifar100_loader."""
    args = _args(tmp_path, extra=("--dataset", "cifar100"))
    s, _ = cv_train.build(args)
    m = s.run_round(0.05)
    assert np.isfinite(m["loss_sum"]) and m["count"] > 0


# ---------------------------------------- orbax is imported on first use

_NO_ORBAX_PROBE = """
import json, sys
for name in sys.argv[1:]:
    __import__(name)
from commefficient_tpu.obs import registry
print(json.dumps({
    "orbax": sorted(m for m in sys.modules if m.startswith("orbax")),
    "cloud_logging": sorted(m for m in sys.modules
                            if m.startswith("google.cloud.logging")),
    "gauge": "checkpoint_orbax_import_s" in registry.default().snapshot(),
}))
"""


@pytest.mark.parametrize("modules", [
    ("commefficient_tpu.runner", "commefficient_tpu.utils.checkpoint",
     "commefficient_tpu.federated.api"),
    ("cv_train", "gpt2_train"),
], ids=["run_loop", "trainers"])
def test_import_loads_no_orbax(modules):
    """Importing the run loop, the checkpoint module or the trainers loads no
    orbax (nor the cloud logging stack it pulls in): a run that never
    checkpoints never pays that import, 35-37 s of a process start on a TPU
    host. Fresh process, so no earlier test's import can hide one."""
    import json
    import subprocess
    import sys

    from conftest import hermetic_subprocess_env, repo_root

    out = subprocess.run(
        [sys.executable, "-c", _NO_ORBAX_PROBE, *modules],
        capture_output=True, text=True, timeout=120,
        env=hermetic_subprocess_env(), cwd=repo_root(),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen == {"orbax": [], "cloud_logging": [], "gauge": False}, seen


def test_make_save_ckpt_loads_orbax_before_round_0(small_session, tmp_path,
                                                   monkeypatch, capsys):
    """Building the save closure imports orbax and records what that took,
    so no save (periodic, writer-thread, emergency) pays the import mid-run;
    a save and restore_latest through the loader reproduce the state."""
    import sys

    import jax

    from commefficient_tpu.obs import registry
    from commefficient_tpu.runner.loop import make_save_ckpt

    monkeypatch.setattr(ckpt, "_OCP", None)  # as in a fresh process
    s, _ = cv_train.build(_args(tmp_path))
    capsys.readouterr()
    save = make_save_ckpt(s, str(tmp_path / "ck"))
    assert "orbax.checkpoint" in sys.modules
    assert ckpt._OCP is sys.modules["orbax.checkpoint"]
    assert registry.default().gauge("checkpoint_orbax_import_s").value > 0
    assert "checkpoints: orbax loaded in" in capsys.readouterr().out

    for _ in range(2):
        s.run_round(0.05)
    path = save()
    assert ckpt.verify(path) is True
    s2, _ = cv_train.build(_args(tmp_path))
    assert ckpt.restore_latest(str(tmp_path / "ck"), s2) == path
    assert s2.round == s.round == 2
    for a, b in zip(jax.tree.leaves(s.state), jax.tree.leaves(s2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # loaded once: the restore printed no second start-up line
    assert "orbax loaded" not in capsys.readouterr().out


def test_trainer_with_checkpoint_dir_prints_orbax_line(small_session, tmp_path,
                                                       monkeypatch, capsys):
    """cv_train --checkpoint_dir loads orbax in set-up, before the first
    round's row, and says what it took on one start-up line."""
    monkeypatch.setattr(ckpt, "_OCP", None)
    ck = str(tmp_path / "ck")
    argv = ["--dataset", "cifar10", "--mode", "sketch", "--num_clients", "8",
            "--num_workers", "2", "--local_batch_size", "4", "--k", "100",
            "--num_cols", "2000", "--num_rows", "3", "--lr_scale", "0.05",
            "--data_root", "/nonexistent", "--num_rounds", "2",
            "--checkpoint_dir", ck, "--checkpoint_every", "1"]
    cv_train.main(argv)
    out = capsys.readouterr().out
    assert out.count("checkpoints: orbax loaded in") == 1, out[-2000:]
    assert out.index("orbax loaded") < out.index("train_loss")  # the header
    assert ckpt.latest(ck).endswith("round_00000002")
