"""``make_agent``: the reference's user-facing factory (BASELINE.json:5;
SURVEY.md §3.4) — config -> assembled agent, with a ``backend`` selection
point. ``backend="tpu"`` is the Anakin in-HBM path; ``backend="sebulba"``
drives host envs against an on-device double buffer; ``backend="cpu_async"``
is the thread-based parity path mirroring the reference's default A3C mode.
"""

from __future__ import annotations

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.utils.config import Config


def make_agent(
    config: Config | None = None, restore: str | None = None, **overrides
):
    """Build a Trainer for ``config``.

    Any Config field can be passed as a keyword override, e.g.::

        agent = make_agent(env_id="CartPole-v1", algo="impala", backend="tpu")
        agent.train()

    ``restore=path`` loads initial state from an existing checkpoint
    directory (read-only; ongoing saves go to ``config.checkpoint_dir``).
    """
    config = (config or Config()).replace(**overrides)

    # Fail fast on enum-like fields the backends only consult at trace time
    # (a bad algo would otherwise surface mid-train, after env/model build).
    if config.algo not in ("a3c", "impala", "ppo", "qlearn"):
        raise ValueError(
            f"unknown algo {config.algo!r}; expected a3c|impala|ppo|qlearn"
        )
    if config.torso not in ("mlp", "nature_cnn", "impala_cnn"):
        raise ValueError(
            f"unknown torso {config.torso!r}; expected "
            "mlp|nature_cnn|impala_cnn"
        )
    if config.core not in ("ff", "lstm"):
        raise ValueError(f"unknown core {config.core!r}; expected ff|lstm")

    # The process record's ``setup.agent``: what the program owns of a
    # process's set-up. The trainers mark its parts themselves.
    with introspect.phase(span_names.SETUP_AGENT):
        return _build(config, restore)


def _build(config: Config, restore: str | None):
    if config.backend == "tpu":
        from asyncrl_tpu.api.trainer import Trainer

        return Trainer(config, restore=restore)
    if config.backend == "sebulba":
        from asyncrl_tpu.api.sebulba_trainer import SebulbaTrainer

        return SebulbaTrainer(config, restore=restore)
    if config.backend == "cpu_async":
        try:
            from asyncrl_tpu.api.cpu_async import CpuAsyncTrainer
        except ImportError as e:
            raise NotImplementedError(
                "backend='cpu_async' is not built yet (planned: thread-based "
                "parity path mirroring the reference's A3C mode)"
            ) from e
        return CpuAsyncTrainer(config, restore=restore)
    raise ValueError(
        f"unknown backend {config.backend!r}; expected tpu|sebulba|cpu_async"
    )
