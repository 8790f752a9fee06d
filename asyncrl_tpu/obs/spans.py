"""The span taxonomy: every instrumented stage of the async host path.

One module owns the vocabulary so the instrumentation sites, the report's
stall attribution, and the flight recorder can never drift on what a span
name means. Names are ``<stage>.<what>``; the stage prefix groups spans in
the Perfetto export (``cat``) and the report tables.

Wait vs compute: a span is a WAIT span when the thread is blocked on
another pipeline stage (queue empty/full, slab reuse, device readiness) —
the report attributes thread idleness to these by name. Everything else is
compute. The classification is by exact name first, then by the
``*_wait`` suffix convention, so a new wait span is classified correctly
even before it is added to the cause table.
"""

from __future__ import annotations

# Actor threads (rollout/sebulba.py ActorThread._run).
ACTOR_INFERENCE = "actor.inference"      # batched action selection + sync
ACTOR_ENV_STEP = "actor.env_step"        # host env pool step
ACTOR_LEASE_WAIT = "actor.lease_wait"    # staging-slab row acquisition
ACTOR_QUEUE_PUT = "actor.queue_put"      # fragment hand-off (incl. backpressure)

# Staging ring internals (rollout/staging.py).
STAGING_REUSE_WAIT = "staging.reuse_wait"  # blocked on in-flight slab readiness

# Shared inference server (rollout/inference_server.py).
SERVER_COLLECT_WAIT = "server.collect_wait"  # waiting for client requests
SERVER_SERVE = "server.serve"                # coalesce + batched device call

# Serving core (asyncrl_tpu/serve/): continuous batching + zero-drain swaps.
SERVE_ADMIT_WAIT = "serve.admit_wait"    # client blocked at the admission gate
SERVE_BATCH_FILL = "serve.batch_fill"    # scheduler holding a partial batch open
SERVE_DISPATCH = "serve.dispatch"        # coalesce + batched device call
SERVE_SWAP_DRAIN = "serve.swap_drain"    # waiting for old-generation batches

# External gateway (serve/gateway.py): the wire boundary over the serve core.
GATEWAY_ADMIT_WAIT = "gateway.admit_wait"  # request held at tenant admission
GATEWAY_SERVE = "gateway.serve"            # backend call (act/evaluate)

# Elastic runtime (asyncrl_tpu/runtime/elastic.py): the save → reconfigure
# → restore barrier around a fleet-scale action. Runs on the learner
# (window-close) thread; a COMPUTE span — its cost is the price of a scale
# event, not a wait on another stage.
ELASTIC_RECONFIGURE = "elastic.reconfigure"

# Learner drain (api/sebulba_trainer.py train loop + learn/rollout_learner.py).
LEARNER_QUEUE_WAIT = "learner.queue_wait"    # fragment queue empty (starved)
LEARNER_H2D = "learner.h2d"                  # device_put dispatch
LEARNER_H2D_WAIT = "learner.h2d_wait"        # unhidden transfer barrier
LEARNER_UPDATE = "learner.update"            # jitted update dispatch
LEARNER_METRICS = "learner.metrics_drain"    # device_get of pending metrics
LEARNER_EVAL = "learner.eval"                # in-training greedy evaluation

# Set-up phases (obs/introspect.py ``phase``): always in the process record,
# and spans when the tracer is armed. COMPUTE — set-up waits on no stage.
SETUP_AGENT = "setup.agent"                # api/factory.py: the trainer's construction
SETUP_ENV = "setup.env"                    # env (Anakin) / probe pool + spec (Sebulba)
SETUP_MODEL = "setup.model"                # build_model
SETUP_MESH = "setup.mesh"                  # make_mesh
SETUP_LEARNER = "setup.learner"            # Learner / RolloutLearner construction
SETUP_INIT_STATE = "setup.init_state"      # learner.init_state (params, actor state)
SETUP_CHECKPOINT = "setup.checkpoint"      # checkpoint.setup (restore / auto-resume)
SETUP_FIRST_UPDATE = "setup.first_update"  # a learner's first update call: trace, lower, compile or load

# What JAX reports of each program it builds (obs/introspect.py's
# jax.monitoring listener): always in the process record, and already-timed
# spans (``trace.record_span``, meta ``fun``) when the tracer is armed.
COMPILE_TRACE = "compile.trace"            # function -> jaxpr
COMPILE_LOWER = "compile.lower"            # jaxpr -> MLIR module
COMPILE_BACKEND = "compile.backend"        # backend compile, or the load that stood in for it
COMPILE_CACHE_LOAD = "compile.cache_load"  # persistent-cache retrieval

# Spans where the thread is blocked on ANOTHER stage of the pipeline.
WAIT_SPANS = frozenset({
    ACTOR_LEASE_WAIT,
    ACTOR_QUEUE_PUT,
    STAGING_REUSE_WAIT,
    SERVER_COLLECT_WAIT,
    SERVE_ADMIT_WAIT,
    SERVE_BATCH_FILL,
    SERVE_SWAP_DRAIN,
    GATEWAY_ADMIT_WAIT,
    LEARNER_QUEUE_WAIT,
    LEARNER_H2D_WAIT,
})

# What a high share in each wait span MEANS — the stall-attribution table's
# causal reading, kept next to the names so instrumentation and diagnosis
# cannot drift apart.
WAIT_CAUSES = {
    LEARNER_QUEUE_WAIT: (
        "learner starved for fragments: actors (env stepping / inference) "
        "are the bottleneck"
    ),
    LEARNER_H2D_WAIT: (
        "host->device transfer time not hidden behind the previous "
        "update's compute"
    ),
    ACTOR_LEASE_WAIT: (
        "no free staging slab row: waiting on slab reuse — the learner/"
        "device side is the bottleneck or the ring is too shallow"
    ),
    STAGING_REUSE_WAIT: (
        "waiting on an in-flight slab's device readiness (slab reuse): "
        "deepen staging_slabs or speed up the consuming update"
    ),
    ACTOR_QUEUE_PUT: (
        "fragment queue full (backpressure): the learner drain is the "
        "bottleneck"
    ),
    SERVER_COLLECT_WAIT: (
        "inference server idle between requests: actors are busy stepping "
        "envs (healthy) or dead/restarting (check supervisor counters)"
    ),
    SERVE_ADMIT_WAIT: (
        "clients held at the serve admission gate (SLO backpressure or "
        "inflight cap): the server is the bottleneck — it cannot keep "
        "latency inside target at the offered load"
    ),
    SERVE_BATCH_FILL: (
        "scheduler holding partial batches open for more requests: clients "
        "are slow to submit (healthy under light load); a high share paired "
        "with mostly deadline-flush dispatches means the deadline budget is "
        "long relative to client cadence — tighten serve_deadline_ms"
    ),
    SERVE_SWAP_DRAIN: (
        "waiting for in-flight batches pinned to an old param generation "
        "to retire: dispatches are long relative to the publish cadence "
        "(teardown/barrier paths only — the swap itself never blocks)"
    ),
    GATEWAY_ADMIT_WAIT: (
        "external requests held at the gateway's tenant admission layer "
        "(token bucket / per-tenant SLO class): offered wire load exceeds "
        "the tenant's provisioned rate — shed responses carry Retry-After"
    ),
}


def is_wait(name: str) -> bool:
    """WAIT span? Exact taxonomy membership, else the suffix convention."""
    return name in WAIT_SPANS or name.endswith("_wait")


def stage_of(name: str) -> str:
    """The stage prefix (``actor``/``server``/``learner``/``staging``)."""
    return name.split(".", 1)[0]


# Thread-name -> thread-group mapping (the flight recorder's "distinct
# thread groups" and the report's per-group rollup). Threads the framework
# names map to their subsystem; anything else groups as its own name, and
# a thread can override explicitly via ``trace.tag_thread``.
_GROUP_PREFIXES = (
    ("actor-", "actor"),
    ("inference-server", "server"),
    ("serve-core", "server"),
    ("flightrec-", "flightrec"),
    ("obs-http", "obs"),
    ("gateway-", "gateway"),
    ("checkpoint", "checkpoint"),
)


def thread_group(thread_name: str) -> str:
    for prefix, group in _GROUP_PREFIXES:
        if thread_name.startswith(prefix):
            return group
    return thread_name
