"""Shared inference server: ONE device call serving every actor thread.

The podracer/Sebulba architecture dedicates an inference thread so the
accelerator sees one large action-selection batch per env step instead of
one small batch per actor (SURVEY.md §7.3 "host↔device throughput"). With
per-thread inference (the default), T actor threads cost T dispatches per
step, each a dispatch plus a D2H read of the actions, which serializes
into the hot loop T times over. The server coalesces: actor threads submit their
observation slices, a dedicated thread concatenates them, runs the SAME
jitted ``make_inference_fn`` callable once over the combined batch, and
hands each client its slice of the results.

Batching policy: serve once every live client has a request pending, or
after ``max_wait_s`` — whichever comes first. In steady state all actors
block on inference every step, so full batches are the norm; the timeout
only covers clients that are mid-fragment-emit, dead, or restarting.
Partial batches change the call's batch size and recompile once per
distinct size (jit cache keyed on shape) — rare by construction, and
since ISSUE 8 *measured* rather than assumed: the trainer wraps the
shared inference callable in ``obs.introspect.instrument``, so every
distinct batch shape lands in the ``infer_recompile`` counter (exported
next to ``infer_coalesce_batch``) and a ``kind=event`` compile
annotation with static-shape blame in ``timeseries.jsonl``.

Semantics note vs per-thread inference: the server always evaluates under
the LATEST published params, so behaviour params can refresh mid-fragment
(per-thread actors pin params for a whole fragment). The per-step
``behaviour_logp`` recorded with each action remains exact — which is all
V-trace / the ε-greedy Q recording need — and this is precisely the
published-weights semantics of the podracer inference thread.

Client façade: ``server.client(i)`` returns a callable with the exact
``make_inference_fn`` signature (params and key arguments are accepted and
ignored — the server uses the ParamStore and its own key stream), so
``ActorThread`` runs unchanged whether it holds the jitted function or a
server client.

Slab coalescing: clients submit raw HOST arrays (no per-client
``jnp.asarray`` — that was one device transfer per client per round); the
server packs them into a preallocated host batch slab and the jitted call
transfers the whole slab ONCE per round. Device-resident request leaves
(the recurrent core on an accelerator) still concatenate on device — they
never round-trip through the host. Results slice on host: actions/logp
are numpy row-slices, and on a CPU-backed server (cpu_async) the core
slices are numpy VIEWS of the device buffer too — no copy-through-device
per client (the ``_slice`` fix). ``coalesce_rounds``/``coalesce_rows``
feed the ``infer_coalesce_batch`` metric.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from asyncrl_tpu.obs import spans as span_names
from asyncrl_tpu.obs import trace
from asyncrl_tpu.utils import faults


class ServerClosed(RuntimeError):
    """Raised into clients when the server stops while they wait."""


class InvariantViolation(RuntimeError):
    """§5.2b debug-mode failure: the serve/consume handshake discipline is
    broken. FATAL — kills the server thread and surfaces to every client;
    never downgraded to a per-request error (a transport-integrity bug must
    abort the run, not feed the actor-restart loop)."""


def _on_cpu(tree) -> bool:
    """True when every device leaf of ``tree`` lives on a CPU device (the
    cpu_async host-pinned server). Numpy leaves count as CPU."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, np.ndarray):
            continue
        try:
            if any(d.platform != "cpu" for d in leaf.devices()):
                return False
        except AttributeError:
            return False
    return True


def _slice(tree, start, stop):
    """Row-slice every leaf. Numpy leaves give zero-copy views; device
    leaves give device-side slices (small, and they stay resident for the
    client's next submit)."""
    return jax.tree.map(lambda x: x[start:stop], tree)


def pack_rows(slabs: dict, key, parts, total_rows: int) -> np.ndarray:
    """Copy ``parts`` back-to-back into the slab registered under ``key``
    in ``slabs``; returns the ``[total_rows, ...]`` view. The slab grows to
    the largest (rows, tail-shape, dtype) seen and is then reused forever —
    steady state allocates nothing. Shared by the InferenceServer (keys are
    leaf positions) and the serve core (keys are (policy, position) pairs,
    so policies with different request shapes never thrash one slab)."""
    tail, dtype = parts[0].shape[1:], parts[0].dtype
    slab = slabs.get(key)
    if (
        slab is None
        or slab.shape[0] < total_rows
        or slab.shape[1:] != tail
        or slab.dtype != dtype
    ):
        slab = np.empty((total_rows, *tail), dtype)
        slabs[key] = slab
    offset = 0
    for part in parts:
        n = part.shape[0]
        np.copyto(slab[offset:offset + n], part)
        offset += n
    return slab[:total_rows]


def coalesce_args(slabs: dict, key_prefix, args_list, total_rows: int):
    """Merge per-client request pytrees into one batch pytree.

    Host (numpy) leaves pack into the caller's preallocated slabs — a host
    memcpy per client, then ONE device transfer of the slab when the jitted
    call consumes it. Device-resident leaves (the recurrent core on an
    accelerator) concatenate on device; bouncing them through the host
    would add a D2H sync per round."""
    flats = [jax.tree.flatten(args)[0] for args in args_list]
    treedef = jax.tree.structure(args_list[0])
    merged = []
    for pos in range(len(flats[0])):
        parts = [flat[pos] for flat in flats]
        if all(isinstance(p, np.ndarray) for p in parts):
            merged.append(
                pack_rows(slabs, (key_prefix, pos), parts, total_rows)
            )
        else:
            merged.append(jnp.concatenate(parts, axis=0))
    return jax.tree.unflatten(treedef, merged)


class InferenceServer(threading.Thread):
    """Coalesces actor-thread inference requests into one batched call.

    ``mode`` names the wrapped callable's signature (the four
    ``make_inference_fn`` variants):

    - ``"ff"``:      (params, obs, key)                    -> (a, logp, key)
    - ``"eps"``:     (params, obs, key, eps)               -> (a, logp, key)
    - ``"rec"``:     (params, obs, key, core, done)        -> (..., core)
    - ``"rec_eps"``: (params, obs, key, core, done, eps)   -> (..., core)
    """

    MODES = ("ff", "eps", "rec", "rec_eps")

    def __init__(
        self,
        inference_fn: Callable,
        store,
        num_clients: int,
        stop_event: threading.Event,
        mode: str = "ff",
        seed: int = 0,
        max_wait_s: float = 0.002,
        device=None,
    ):
        super().__init__(name="inference-server", daemon=True)
        if mode not in self.MODES:
            raise ValueError(f"unknown mode {mode!r}; expected {self.MODES}")
        if num_clients < 1:
            raise ValueError(f"num_clients must be >= 1, got {num_clients}")
        self._fn = inference_fn
        self._store = store
        self._n = num_clients
        self._stop_event = stop_event
        self._mode = mode
        self._max_wait = max_wait_s
        # ``jax.default_device`` is thread-local (same constraint as
        # ActorThread.device): cpu_async pins the server to host CPU so its
        # concat/dispatch cannot land on an attached accelerator.
        self._device = device
        self._key = jax.random.PRNGKey(seed ^ 0x5E21EA)
        self._cond = threading.Condition()
        self._pending: list[Any] = [None] * num_clients  # guarded-by: _cond
        # Result/error slots are event-handshake-owned, not lock-guarded:
        # the server owns slot i from collect to event.set(), the client
        # owns it from its wait() returning to the consuming swap.
        # lint: thread-shared-ok(event handshake: Event.set/wait is the ownership hand-off; §5.2b debug mode asserts the discipline)
        self._results: list[Any] = [None] * num_clients
        # lint: thread-shared-ok(event handshake, same protocol as _results)
        self._errors: list[BaseException | None] = [None] * num_clients
        self._events = [threading.Event() for _ in range(num_clients)]
        from asyncrl_tpu.utils.debug import sync_debug_enabled

        # §5.2b debug mode: a result slot must be EMPTY when served (a
        # non-empty slot means a double-serve or an unconsumed reply —
        # the handshake discipline is broken).
        self._debug = sync_debug_enabled()
        # The exception that killed the server thread, whatever its type:
        # clients re-raise the REAL cause from _submit instead of a bland
        # ServerClosed, and the trainer's supervisor reads it to decide
        # abort (InvariantViolation) vs rebuild (anything else).
        # lint: thread-shared-ok(single-writer latch: only the dying server thread writes; readers re-read after is_alive() turns false)
        self._fatal: BaseException | None = None
        # Progress stamp for the trainer's heartbeat watchdog (refreshed
        # every collect/serve loop iteration).
        # lint: thread-shared-ok(GIL-atomic float stamp; the watchdog reads staleness only)
        self.heartbeat = time.monotonic()
        self._fault_serve = faults.site("server.serve")
        # Preallocated host batch slabs, one per flattened request-leaf
        # position (grown to the largest batch seen); server-thread-only.
        self._slabs: dict[Any, np.ndarray] = {}
        # Coalescing counters for the infer_coalesce_batch metric: total
        # served rounds and total request rows (plain ints under the GIL;
        # the trainer only reads them).
        self.coalesce_rounds = 0  # lint: thread-shared-ok(GIL-atomic int; single-writer, metrics-only reader)
        self.coalesce_rows = 0  # lint: thread-shared-ok(GIL-atomic int; single-writer, metrics-only reader)

    # ------------------------------------------------------------- client

    def client(self, index: int) -> Callable:
        """A drop-in replacement for the jitted inference callable (same
        signature per ``mode``; params/key arguments are ignored)."""
        if not 0 <= index < self._n:
            raise IndexError(f"client index {index} out of range 0..{self._n - 1}")

        def call(params, obs, key, *rest):
            del params  # server reads the ParamStore
            # Host arrays pass through untouched — the server packs them
            # into its batch slab for ONE transfer per round (a client-side
            # jnp.asarray here would be a per-client device transfer).
            out = self._submit(index, (np.asarray(obs), *rest))
            if self._mode in ("rec", "rec_eps"):
                actions, logp, core = out
                return actions, logp, key, core
            actions, logp = out
            return actions, logp, key

        return call

    def _submit(self, index: int, args):  # thread-entry: infer-client@actor
        event = self._events[index]
        event.clear()
        with self._cond:
            self._pending[index] = args
            self._cond.notify_all()
        while not event.wait(timeout=0.2):
            if self._stop_event.is_set() or not self.is_alive():
                if self._fatal is not None:
                    raise self._fatal
                raise ServerClosed("inference server stopped")
        if self._fatal is not None:
            # Integrity violation: no slot content can be trusted anymore
            # (including a stale result that was about to be consumed).
            raise self._fatal
        err = self._errors[index]
        if err is not None:
            self._errors[index] = None
            raise err
        result, self._results[index] = self._results[index], None
        if result is None:
            # The event can also fire from run()'s shutdown wakeup with
            # neither a result nor an error written (stop raced our wait).
            if self._fatal is not None:
                raise self._fatal
            raise ServerClosed("inference server stopped")
        return result

    # ------------------------------------------------------------- server

    def run(self) -> None:  # thread-entry: infer-server@server
        try:
            if self._device is not None:
                with jax.default_device(self._device):
                    self._run()
            else:
                self._run()
        # lint: broad-except-ok(thread boundary: the cause is latched in _fatal and re-raised into every client; see below)
        except BaseException as e:
            # Fatal: remember why the server died so every subsequent
            # client call re-raises the REAL cause (not a bland
            # ServerClosed) — an InvariantViolation aborts the run, any
            # other death lets the trainer's supervisor rebuild the server
            # and re-wire clients. The exception is NOT re-raised out of
            # the thread: delivery to clients is the contract, and an
            # escaping thread exception would only feed Python's
            # unhandled-thread hook (and, under pytest, a warning that can
            # mask a REAL stray thread crash in the same run — VERDICT r2
            # Weak #5). Log it instead.
            self._fatal = e
            print(
                f"InferenceServer: fatal {type(e).__name__}: {e}",
                file=sys.stderr,
            )
        finally:
            # Wake anyone still waiting so they observe the closed server.
            for event in self._events:
                event.set()

    def _run(self) -> None:
        while not self._stop_event.is_set():
            self.heartbeat = time.monotonic()
            with trace.span(span_names.SERVER_COLLECT_WAIT):
                batch = self._collect()
            if batch:
                if self._fault_serve is not None:
                    # Outside _serve's per-request try: an injected crash
                    # kills the SERVER (recorded in _fatal, recovered by
                    # the trainer's rebuild), not just one batch.
                    self._fault_serve.fire(stop=self._stop_event.is_set)
                with trace.span(span_names.SERVER_SERVE):
                    self._serve(batch)

    def _collect(self):
        """Wait for requests; return [(client_index, args), ...] in index
        order, clearing the pending slots."""
        with self._cond:
            self._cond.wait_for(
                lambda: self._stop_event.is_set()
                or any(p is not None for p in self._pending),
                timeout=0.1,
            )
            if self._stop_event.is_set():
                return []
            deadline = time.monotonic() + self._max_wait
            while any(p is None for p in self._pending):
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._stop_event.is_set():
                    break
                self._cond.wait_for(
                    lambda: self._stop_event.is_set()
                    or all(p is not None for p in self._pending),
                    timeout=remaining,
                )
            batch = [
                (i, p) for i, p in enumerate(self._pending) if p is not None
            ]
            for i, _ in batch:
                self._pending[i] = None
            return batch

    def _coalesce(self, args_list, total_rows: int):
        """Merge per-client request pytrees into one batch pytree (the
        shared :func:`coalesce_args`; this server's slabs are keyed on
        leaf position alone — one client population, one shape family)."""
        return coalesce_args(self._slabs, None, args_list, total_rows)

    def _serve(self, batch) -> None:
        if self._debug:
            # Checked for the WHOLE batch before any slot is written, so a
            # violation can't poison already-served clients; raised outside
            # the per-request try so it escalates (fatal) instead of being
            # delivered as an ordinary per-client error.
            occupied = [i for i, _ in batch if self._results[i] is not None]
            if occupied:
                raise InvariantViolation(
                    f"inference-server handshake invariant broken: result "
                    f"slot(s) {occupied} served while occupied"
                )
        indices = [i for i, _ in batch]
        try:
            sizes = [int(args[0].shape[0]) for _, args in batch]
            merged = self._coalesce([args for _, args in batch], sum(sizes))
            params, _ = self._store.get()
            out = self._fn(params, merged[0], self._key, *merged[1:])
            if self._mode in ("rec", "rec_eps"):
                actions, logp, self._key, core = out
            else:
                actions, logp, self._key = out
                core = None

            offsets = np.cumsum([0] + sizes)
            # This blocks until the batched call finishes — which also
            # means the input slabs are consumed and safe to overwrite at
            # the next round's pack.
            actions = np.asarray(actions)
            logp = np.asarray(logp)
            if core is not None and _on_cpu(core):
                # cpu_async bugfix: a host-pinned server must hand back
                # numpy VIEWS (np.asarray of a CPU jax array is zero-copy),
                # not per-client device-sliced arrays — the old path paid
                # one device slice op per client per round.
                core = jax.tree.map(np.asarray, core)
            self.coalesce_rounds += 1
            self.coalesce_rows += int(offsets[-1])
            for (i, _), a, b in zip(batch, offsets[:-1], offsets[1:]):
                if core is None:
                    self._results[i] = (actions[a:b], logp[a:b])
                else:
                    self._results[i] = (
                        actions[a:b], logp[a:b], _slice(core, a, b)
                    )
                self._events[i].set()
        # lint: broad-except-ok(per-request boundary: the failure is delivered to every waiting client, then the server keeps serving)
        except BaseException as e:
            for i in indices:
                self._errors[i] = e
                self._events[i].set()
