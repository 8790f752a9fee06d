"""Device-resident replay ring: IMPACT-style sample reuse (ROADMAP 3).

The Sebulba learner consumes each staged slab roughly once, so learner
FLOPs are rate-limited by actor throughput — ``learner_stall_frac`` is
the dominant wait in every traced run. IMPACT (arXiv:1912.00167) shows
that multiple SGD passes per sample, with importance weights clipped
against a slowly-updated target network, recover the sample-efficiency
loss of reuse; "Parallel Actors and Learners" (arXiv:2110.01101) is the
decoupling argument a replay tier completes. This module is the data
half: a small circular replay of the most recent consumed slabs kept in
DEVICE memory (HBM — the hand-off back to the learner never crosses the
host link), reusing the staging-ring generation/lease discipline
(rollout/staging.py):

- Preallocated ``[R, T, B, ...]`` device buffers, one leaf per
  ``Rollout`` field, allocated once for the trainer's lifetime with the
  fragment's own sharding (leading ring axis unsharded).
- ``publish`` lands a fresh (already-transferred) slab into the cursor
  row via a jitted ``dynamic_update_index_in_dim`` — the existing
  donation/overlap path's device copy, optionally donating the old
  buffer for in-place reuse. Eviction is oldest-generation by
  construction (the cursor is the ring order).
- The learner **leases** a row to replay (:meth:`DeviceReplayRing.
  lease_sample`, least-reused-first — a fresh slab is always sampled
  before an already-replayed one) and ``consume``\\s it; eviction or a
  rollback quarantine *voids* outstanding leases, so a zombie read
  raises :class:`ReplayStaleError` instead of returning a NEWER slab's
  rows — the staging generation fence, applied to device data.
- A rollback quarantine (runtime/durability.py, the PR-10 path) empties
  the ring: replayed data produced under (or poisoned by) a diverging
  policy must never reach the learner again.

The update-side half — the clipped target network whose log-probs
anchor the importance ratio — lives in learn/rollout_learner.py; the
per-sample reuse-count/target-lag telemetry drains through
:class:`ReuseWindow` into the PR-8 staleness ledger's window keys.

Thread contract: single-thread by design, like ``introspect.
StalenessWindow`` — the trainer's learner-drain thread publishes,
leases, consumes, AND quarantines (the rollback policy runs at window
close on that same thread), so there is no lock and no cross-thread
visibility question.
"""

# protocol: replay-lease mint=DeviceReplayRing.lease_sample,DeviceReplayRing._outstanding,lease_sample ops=consume:held->consumed,void:held->voided open=held terminal=voided initial=held

from __future__ import annotations

import numpy as np

import jax

from asyncrl_tpu.rollout.buffer import Rollout
from asyncrl_tpu.rollout.staging import StaleLeaseError


class ReplayStaleError(StaleLeaseError):
    """A voided replay lease was consumed: its row was evicted by a
    newer publish (oldest-generation eviction) or the ring was
    quarantined by the rollback policy. The reader must drop the pass —
    the row's device memory now holds (or is about to hold) a NEWER
    slab's data, and returning it would silently train on the wrong
    sample."""


def validate_replay_config(config) -> None:
    """Constructor-time replay checks, shared by every builder of the
    host-fragment update step (RolloutLearner today): the degenerate
    configurations fail silently mid-train, so they must fail loudly
    here instead."""
    if config.replay_slabs <= 0:
        return
    if config.algo != "impala":
        raise ValueError(
            f"replay_slabs={config.replay_slabs} requires algo='impala': "
            "the IMPACT-mode update anchors the V-trace importance ratio "
            "against the clipped target network, which only the V-trace "
            f"loss consumes (got algo={config.algo!r})"
        )
    if config.updates_per_call != 1:
        raise ValueError(
            "replay_slabs > 0 requires updates_per_call=1: the ring "
            "stores single [T, B, ...] fragments, and a fused [K>1] "
            "stack would replay K stale fragments as one indivisible "
            "unit"
        )
    if config.core != "ff":
        raise ValueError(
            "replay_slabs > 0 requires core='ff': the target-network "
            "anchor forward has no carry channel for a recurrent core "
            "(the staging fragment's init_core belongs to the ORIGINAL "
            "behaviour rollout, not a replayed re-forward)"
        )
    if config.normalize_obs or config.normalize_returns:
        raise ValueError(
            "replay_slabs > 0 does not compose with normalize_obs/"
            "normalize_returns: the jitted step folds every consumed "
            "fragment into the running stats, and it cannot tell a "
            "fresh fragment from a replayed one — each slab would fold "
            "in replay_passes times, inflating the sample count and "
            "biasing the mean/var (and the reward-scaling denominator) "
            "toward reused slabs"
        )
    if config.replay_passes < 1:
        raise ValueError(
            f"replay_passes={config.replay_passes} must be >= 1 "
            "(1 = fresh pass only; the ring still fills for later "
            "windows)"
        )
    if config.target_update_period < 1:
        raise ValueError(
            f"target_update_period={config.target_update_period} must "
            "be >= 1"
        )
    if config.replay_rho_clip < 1.0:
        raise ValueError(
            f"replay_rho_clip={config.replay_rho_clip} must be >= 1: a "
            "cap below 1 would down-weight perfectly on-policy data"
        )


class ReplayLease:
    """One replay read permit for one ring row, generation-stamped.

    Mirrors ``staging.SlabLease`` at the device tier: ``consume`` is the
    single read+release op (the obligation window stays one statement
    wide on the drain thread), ``void`` is the eviction/quarantine
    fence. Single-thread contract (see module docstring)."""

    __slots__ = ("ring", "row", "gen", "_voided")

    def __init__(self, ring: "DeviceReplayRing", row: int, gen: int):
        self.ring = ring
        self.row = row
        self.gen = gen
        self._voided = False

    def valid(self) -> bool:
        return (
            not self._voided
            and self.ring._row_gen[self.row] == self.gen
        )

    def consume(self) -> tuple[Rollout, int, int]:
        """Read the leased row and release the lease in one step:
        ``(slab, reuse_count, behaviour_update)`` — the device pytree, the
        row's cumulative consumption count (fresh pass included), and
        the learner-update count its behaviour params were published at
        (the staleness ledger's lag base). Raises
        :class:`ReplayStaleError` if the row was evicted or quarantined
        since the lease was minted."""
        ring = self.ring
        ring._release(self)
        if not self.valid():
            raise ReplayStaleError(
                f"replay lease gen {self.gen} on row {self.row} was "
                "voided (evicted by a newer publish, or quarantined by "
                "the rollback policy); refusing to return the row"
            )
        ring._row_reuse[self.row] += 1
        reuse = ring._row_reuse[self.row]
        behaviour = ring._row_behaviour[self.row]
        # Adopted rows (publish ref=True) hand back the adopted pytree
        # itself — zero-copy on the replay read path too; installed rows
        # gather a fresh copy out of the stacked buffer (which is what
        # keeps the LEARNER's donation of replayed fragments safe there).
        ref = ring._row_ref[self.row]
        slab = (
            ref
            if ref is not None
            else ring._take(ring._buf, np.int32(self.row))
        )
        return slab, reuse, behaviour

    def void(self) -> None:
        """Fence this lease (eviction/quarantine path): any later
        ``consume`` raises. Idempotent."""
        self._voided = True
        self.ring._release(self)


class DeviceReplayRing:
    """The preallocated ``[R, T, B, ...]`` device ring + its row ledger.

    ``template`` is the one-fragment ``jax.ShapeDtypeStruct`` pytree
    (``staging.fragment_template`` — the same single source of slab
    geometry the host ring uses); ``sharding`` is the STACKED pytree of
    ``NamedSharding``\\s (``rollout_learner.rollout_sharding(mesh,
    template, stacked=True)`` — leading ring axis unsharded) or None
    for default single-device placement (unit tests). ``donate=True``
    (the default) donates the old ring buffer into each install — the
    donate-and-rebind idiom on a buffer that is PRIVATE to the ring, so
    the write is in-place and a publish never pays an R-fold buffer
    copy. This is independent of ``config.donate_buffers``, which
    governs the train step's arguments (and ``consume`` always hands out
    a fresh gather, so the LEARNER's donation of replayed fragments
    stays safe either way)."""

    def __init__(
        self,
        template: Rollout,
        sharding: Rollout | None = None,
        rows: int = 2,
        donate: bool = True,
    ):
        if rows < 1:
            raise ValueError(f"replay rows={rows} must be >= 1")
        self._rows = rows
        if sharding is None:
            self._buf = jax.tree.map(
                lambda sds: jax.device_put(
                    np.zeros((rows, *sds.shape), np.dtype(sds.dtype))
                ),
                template,
            )
        else:
            self._buf = jax.tree.map(
                lambda sds, sh: jax.device_put(
                    np.zeros((rows, *sds.shape), np.dtype(sds.dtype)), sh
                ),
                template,
                sharding,
            )
        def _install(buf, slab, row):
            return jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_index_in_dim(
                    b, s, row, 0
                ),
                buf,
                slab,
            )

        def _take(buf, row):
            return jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(
                    b, row, 0, keepdims=False
                ),
                buf,
            )

        # The row index is a TRACED scalar (np.int32 at the call sites):
        # one compile serves every row, so the ring can never be the
        # recompile storm the introspect counters watch for.
        self._install = jax.jit(
            _install, donate_argnums=(0,) if donate else ()
        )
        self._take = jax.jit(_take)
        self._gen = 0
        self._cursor = 0
        self._row_gen = [0] * rows  # 0 = empty row
        self._row_reuse = [0] * rows
        self._row_behaviour = [0] * rows
        # Zero-copy adoptions (rollout/device_queue.py): a row published
        # with ref=True stores the caller's device pytree here instead
        # of installing into the stacked buffer; None = the row lives in
        # self._buf (the install path).
        self._row_ref: list[Rollout | None] = [None] * rows
        self._out: dict[int, ReplayLease] = {}  # row -> outstanding lease

    # ------------------------------------------------------------ facade

    @property
    def rows(self) -> int:
        return self._rows

    def fill_frac(self) -> float:
        """Filled rows / ring depth — the ``replay_fill_frac`` window
        gauge (and the elastic scale-down signal's input)."""
        return sum(1 for g in self._row_gen if g > 0) / self._rows

    def _outstanding(self, row: int) -> ReplayLease | None:
        """The row's outstanding (leased, not yet consumed) lease."""
        return self._out.get(row)

    def _release(self, lease: ReplayLease) -> None:
        if self._out.get(lease.row) is lease:
            del self._out[lease.row]

    # ----------------------------------------------------------- publish

    def publish(
        self, slab: Rollout, behaviour_update: int = 0, ref: bool = False
    ) -> None:
        """Land a fresh DEVICE slab into the cursor row (oldest-
        generation eviction: the cursor is the ring order). Called with
        the just-transferred fragment BEFORE the learner update can
        donate it; the install is a device-to-device copy (or in-place
        under donation). ``behaviour_update`` is the learner-update
        count the slab's behaviour params were published at — replayed
        consumptions report staleness against it.

        ``ref=True`` ADOPTS the slab by reference — the zero-copy
        publish path for fragments already resident in HBM behind the
        device rollout queue's ledger (rollout/device_queue.py): no row
        install, no install barrier (the slab is a committed device
        array with no host alias to tear), and ``consume`` later hands
        back the SAME pytree instead of a gathered copy. jax arrays are
        immutable, so queue slot reuse can never corrupt the adoption;
        the caller's one obligation is that the consuming updates do NOT
        donate the fragment (the trainer enables ref publishing only
        with ``config.donate_buffers`` off — a donating update would
        delete the adopted buffers under the ring)."""
        row = self._cursor
        lease = self._outstanding(row)
        if lease is not None:
            # Eviction fences zombies: the displaced row's in-flight
            # lease voids, so its consume raises instead of returning
            # the NEWER slab's rows.
            lease.void()
        self._gen += 1
        self._row_gen[row] = self._gen
        # The fresh pass consumes the slab once, directly (the trainer
        # feeds it to the learner without a ring round-trip), so a
        # published row starts at reuse 1, not 0.
        self._row_reuse[row] = 1
        self._row_behaviour[row] = int(behaviour_update)
        self._cursor = (row + 1) % self._rows
        if ref:
            # Dropping a previous adoption (or shadowing a stacked-buffer
            # row) is pure ledger work: the old reference frees when the
            # last holder drops it.
            self._row_ref[row] = slab
            return
        self._row_ref[row] = None
        self._buf = self._install(self._buf, slab, np.int32(row))
        # Barrier: the install is an INDEPENDENT async reader of the
        # fresh slab, and the staging ring's retire gate only waits for
        # the learner UPDATE's output — on a backend where the device
        # fragment zero-copy aliases the host staging slab (the CPU
        # client), the slab could otherwise be reclaimed and rewritten
        # while the install still reads the alias, landing a torn slab
        # in the ring. Blocking here closes that window before the
        # caller can even dispatch the consuming update (one device-
        # local row write under donation — microseconds, and the drain
        # already barriers the H2D of these same bytes).
        jax.block_until_ready(self._buf)

    # ------------------------------------------------------------ sample

    def lease_sample(self, rng: np.random.Generator) -> ReplayLease | None:
        """Lease the least-reused filled row (fresh-first: a slab the
        learner has seen fewer times always samples before a more-reused
        one; ties break by ``rng`` draw). None when the ring holds no
        leasable row (empty, or every filled row already leased)."""
        candidates = [
            r
            for r in range(self._rows)
            if self._row_gen[r] > 0 and r not in self._out
        ]
        if not candidates:
            return None
        low = min(self._row_reuse[r] for r in candidates)
        pool = [r for r in candidates if self._row_reuse[r] == low]
        row = pool[int(rng.integers(len(pool)))] if len(pool) > 1 else pool[0]
        lease = ReplayLease(self, row, self._row_gen[row])
        self._out[row] = lease
        return lease

    # -------------------------------------------------------- quarantine

    def quarantine(self) -> int:
        """Void every outstanding lease and empty the ring (the PR-10
        rollback path extended to the replay tier, and the trainer's
        ``stop()`` hygiene): a diverging policy's replayed tail must
        never feed another update, and a new cohort starts on an empty
        ring. Returns the number of filled rows dropped. Device buffers
        keep their storage — the ledger emptying alone makes every row
        unreachable until re-published."""
        for lease in list(self._out.values()):
            lease.void()
        dropped = sum(1 for g in self._row_gen if g > 0)
        self._gen += 1
        self._cursor = 0
        self._row_gen = [0] * self._rows
        self._row_reuse = [0] * self._rows
        self._row_behaviour = [0] * self._rows
        # Adopted references drop with the ledger: quarantined HBM frees
        # as soon as the device queue's slot binding also moves on.
        self._row_ref = [None] * self._rows
        return dropped


class ReuseWindow:
    """Per-window sample-reuse aggregation, the PR-8 ``StalenessWindow``
    pattern (same single-thread contract, same absent-not-zero key
    rule): the trainer observes one ``(reuse_count, target_lag)`` pair
    per consumed sample — fresh passes at reuse 1, replayed passes at
    the row's cumulative count, target_lag in learner updates since the
    last target-network refresh — and drains ``reuse_p50`` /
    ``reuse_p95`` / ``reuse_max`` / ``target_lag_mean`` at window
    close."""

    def __init__(self) -> None:
        self._reuse: list[float] = []
        self._lag: list[float] = []

    def observe(self, reuse: float, target_lag: float) -> None:
        self._reuse.append(float(reuse))
        self._lag.append(float(target_lag))

    def drain(self) -> dict[str, float]:
        if not self._reuse:
            return {}
        reuse = np.asarray(self._reuse, np.float64)
        lag = np.asarray(self._lag, np.float64)
        self._reuse, self._lag = [], []
        return {
            "reuse_p50": float(np.percentile(reuse, 50)),
            "reuse_p95": float(np.percentile(reuse, 95)),
            "reuse_max": float(reuse.max()),
            "target_lag_mean": float(lag.mean()),
        }
