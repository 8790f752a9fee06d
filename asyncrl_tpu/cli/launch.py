"""Multi-host launch: ``python -m asyncrl_tpu.cli.launch`` (one invocation
per host).

The reference is single-host (threads + queues, SURVEY.md §5.8a); this is
the TPU-native multi-host entry. Every host runs the SAME command (plus its
own ``--process-id``), joins the ``jax.distributed`` runtime, builds the
hybrid (dcn × dp) mesh over the global device set, and drives the identical
train step — gradients all-reduce over ICI within a slice and DCN across
slices, with zero algorithm changes (parallel/distributed.py).

On Cloud TPU pods the coordinator/world-size/rank are auto-detected — just
run the same command on every host with no distributed flags. Elsewhere
(e.g. CPU multi-process testing, tests/test_multiprocess.py) pass
``--coordinator host:port --num-processes N --process-id I`` explicitly.
"""

from __future__ import annotations

import argparse
import json


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="asyncrl-tpu-launch",
        description="Join a multi-host run and train (one invocation per "
        "host; same command everywhere).",
    )
    parser.add_argument("preset", help="preset name (see asyncrl_tpu.configs)")
    parser.add_argument(
        "overrides", nargs="*", help="config overrides as key=value"
    )
    parser.add_argument(
        "--coordinator", default=None,
        help="coordinator host:port (omit on Cloud TPU: auto-detected)",
    )
    parser.add_argument(
        "--num-processes", type=int, default=None,
        help="world size (omit on Cloud TPU)",
    )
    parser.add_argument(
        "--process-id", type=int, default=None,
        help="this host's rank (omit on Cloud TPU)",
    )
    parser.add_argument(
        "--dcn-size", type=int, default=None,
        help="outer mesh axis size (default: one group per process)",
    )
    parser.add_argument(
        "--steps", type=int, default=None, help="override total_env_steps"
    )
    args = parser.parse_args(argv)

    from asyncrl_tpu.parallel import distributed

    distributed.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    import jax

    from asyncrl_tpu.api.trainer import Trainer
    from asyncrl_tpu.cli.common import resolve_config
    from asyncrl_tpu.utils import runtime

    runtime.enable_compile_cache()

    cfg = resolve_config(args.preset, args.overrides, args.steps)
    if cfg.backend != "tpu":
        raise SystemExit(
            f"multi-host launch is Anakin-only (backend='tpu'); "
            f"got {cfg.backend!r}"
        )

    mesh = distributed.make_hybrid_mesh(dcn_size=args.dcn_size)
    is_lead = jax.process_index() == 0
    if is_lead:
        print(
            json.dumps(
                {
                    "processes": jax.process_count(),
                    "global_devices": jax.device_count(),
                    "local_devices": jax.local_device_count(),
                    "mesh": {
                        ax: int(mesh.shape[ax]) for ax in mesh.axis_names
                    },
                }
            ),
            flush=True,
        )

    trainer = Trainer(cfg, mesh=mesh)
    # Every process drives the same jitted steps (multi-controller SPMD);
    # only the lead process reports.
    hist = trainer.train(callback=print if is_lead else None)
    if is_lead and hist:
        final = {
            k: float(v)
            for k, v in hist[-1].items()
            if isinstance(v, (int, float)) or getattr(v, "ndim", 1) == 0
        }
        print(json.dumps({"final": final}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
