from asyncrl_tpu.models.networks import (
    ActorCritic,
    ImpalaCNN,
    MLPTorso,
    NatureCNN,
    RecurrentActorCritic,
    build_model,
    is_recurrent,
    reset_core,
    settle_core,
)

__all__ = [
    "ActorCritic",
    "ImpalaCNN",
    "MLPTorso",
    "NatureCNN",
    "RecurrentActorCritic",
    "build_model",
    "is_recurrent",
    "reset_core",
    "settle_core",
]
