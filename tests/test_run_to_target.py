"""run_to_target's cross-session accounting (the time-to-target rows are
the framework's north-star evidence — their provenance fields must not
regress). Runs the real script against a throwaway checkpoint dir and
reads the row it prints on stdout."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "run_to_target.py")


def _load_script():
    spec = importlib.util.spec_from_file_location("_run_to_target", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _FakeTrainer:
    """Scripted trainer: emits a fixed in-training eval sequence through the
    metrics callback and a fixed confirmation-eval sequence, so the
    crossing/confirmation protocol can be tested without training."""

    def __init__(self, evals, confirms):
        self.evals = list(evals)
        self.confirms = list(confirms)
        self.confirm_calls = []
        self.closed = False

    def train(self, total_env_steps=None, callback=None):
        while self.evals:
            callback(
                {
                    "fps": 1000.0,
                    "env_steps": 1000,
                    "episode_return": 5.0,
                    "eval_return": self.evals.pop(0),
                }
            )
        return []

    def evaluate(self, num_episodes=32, seed=1234, **kw):
        self.confirm_calls.append((num_episodes, seed))
        return self.confirms.pop(0)

    def close(self):
        self.closed = True


def _rows(stdout):
    rows = [json.loads(ln) for ln in stdout.splitlines() if ln.startswith("{")]
    return [r for r in rows if r.get("kind") == "time_to_target"]


def _run_protocol(monkeypatch, capsys, fake, argv_tail=()):
    monkeypatch.setenv("ASYNCRL_FORCE_CPU", "1")
    import asyncrl_tpu.api.factory as factory

    monkeypatch.setattr(factory, "make_agent", lambda cfg: fake)
    monkeypatch.setattr(
        sys,
        "argv",
        ["run_to_target.py", "cartpole_impala", "--target", "18",
         "--budget-seconds", "300", *argv_tail],
    )
    mod = _load_script()
    rc = mod.main()
    return rc, _rows(capsys.readouterr().out)


def test_unconfirmed_crossing_is_not_banked(monkeypatch, tmp_path, capsys):
    """A lucky in-training crossing whose fresh-seed confirmation eval
    disagrees must NOT produce reached=true (VERDICT r4 Next #3), and the
    rejected crossing must survive into later sessions' rows."""
    ckpt = tmp_path / "arm"
    ckpt.mkdir()
    fake = _FakeTrainer(evals=[20.0], confirms=[10.0])
    rc, rows = _run_protocol(
        monkeypatch, capsys, fake, argv_tail=(f"checkpoint_dir={ckpt}",)
    )
    assert rc == 1  # not reached
    (row,) = rows
    assert row["reached"] is False
    assert row["env_id"] == "CartPole-v1"  # the env actually trained
    assert row["unconfirmed_crossings"] == 1
    assert row["confirm_return"] == 10.0
    # The confirmation is the protocol's guarantee: >= 64 fresh-seed
    # episodes, independent of the in-training eval stream (seed 1234).
    (call,) = fake.confirm_calls
    assert call[0] >= 64
    assert call[1] != 1234
    assert fake.closed
    # The rejection is persisted (a SIGKILL'd session must not launder the
    # arm's history): a follow-up session that confirms still reports the
    # earlier rejected crossing.
    sidecar = json.loads((ckpt / "run_to_target_elapsed.json").read_text())
    assert sidecar["unconfirmed_crossings"] == 1
    (ckpt / "checkpoint_marker").write_text("x")  # make the resume real
    fake2 = _FakeTrainer(evals=[19.0], confirms=[18.5])
    rc2, rows2 = _run_protocol(
        monkeypatch, capsys, fake2, argv_tail=(f"checkpoint_dir={ckpt}",)
    )
    assert rc2 == 0
    row2 = rows2[-1]
    assert row2["reached"] is True
    assert row2["unconfirmed_crossings"] == 1  # carried from session 1


def test_crossing_banked_only_after_confirmation(monkeypatch, capsys):
    """First crossing fails confirmation and training resumes; the second
    crossing confirms and banks reached=true with both numbers."""
    fake = _FakeTrainer(evals=[20.0, 19.5], confirms=[10.0, 19.0])
    rc, rows = _run_protocol(monkeypatch, capsys, fake)
    assert rc == 0, rows
    (row,) = rows
    assert row["reached"] is True
    assert row["eval_return"] == 19.5  # the in-training crossing eval
    assert row["confirm_return"] == 19.0  # the independent confirmation
    assert row["confirm_episodes"] >= 64
    assert row["unconfirmed_crossings"] == 1
    # Retry confirmations draw fresh seeds, not a repeat of the first.
    assert fake.confirm_calls[0][1] != fake.confirm_calls[1][1]


def _run(ckpt_dir, budget="8"):
    env = dict(os.environ, ASYNCRL_FORCE_CPU="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            SCRIPT,
            "cartpole_impala",
            "--target",
            "1000000",  # unreachable: we test accounting, not learning
            "--budget-seconds",
            budget,
            f"checkpoint_dir={ckpt_dir}",
            "checkpoint_every=5",
            "num_envs=32",
            "log_every=2",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
        cwd=REPO,
    )
    return proc, _rows(proc.stdout)


@pytest.mark.slow
def test_cross_platform_resume_is_labeled(tmp_path):
    ckpt = tmp_path / "arm"
    proc, rows = _run(ckpt)
    assert proc.returncode == 1, proc.stderr  # budget exhausted, not reached
    (row,) = rows
    assert row["reached"] is False
    assert "platforms" not in row  # single-platform run: no mixed flag

    # Sidecar recorded this session's platform.
    sidecar = json.loads(
        (ckpt / "run_to_target_elapsed.json").read_text()
    )
    assert sidecar["platforms"] == ["cpu"]
    assert sidecar["seconds"] > 0

    # Simulate the arm's history having come from the chip: a resume on
    # CPU must then label the blended stats.
    sidecar["platforms"] = ["tpu"]
    (ckpt / "run_to_target_elapsed.json").write_text(json.dumps(sidecar))
    proc2, rows2 = _run(ckpt)
    assert proc2.returncode == 1, proc2.stderr
    (row2,) = rows2
    assert row2["platforms"] == ["cpu", "tpu"]
    assert row2["mean_fps_mixed_platforms"] is True
    assert row2["resumed_sessions"] == 1
    sidecar2 = json.loads(
        (ckpt / "run_to_target_elapsed.json").read_text()
    )
    assert sidecar2["platforms"] == ["cpu", "tpu"]
    assert sidecar2["sessions"] == 2
