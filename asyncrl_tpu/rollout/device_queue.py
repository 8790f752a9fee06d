"""Device-resident rollout hand-off: the HBM tier of the staging path.

The sebulba drain's H2D hand-off (``learner.put_rollout`` → barrier →
update) binds each transferred fragment to a bare local — nothing bounds
how many device-resident fragments can be in flight at once, and nothing
names the moment a fragment's HBM becomes reclaimable. On the host tier
the staging ring answers both with its slab ledger (``rollout/staging.py``:
generation-stamped leases, readiness-gated reuse); this module is the
same discipline one tier down. :class:`DeviceRolloutQueue` owns a fixed
set of HBM slots; ``enqueue`` claims a slot (blocking on the OLDEST
consumed slot's readiness handle when the drain has outrun the learner),
lands the host slab on the mesh through the learner's own sharded
transfer, and mints a generation-stamped :class:`DeviceLease`. The drain
reads the device fragment through the lease (``rollout()``), dispatches
the update, and ``consume``\\s the lease with the update's OUTPUT as the
readiness handle — the slot re-leases only once that update has
executed, the device-tier twin of ``StagingRing.retire``.

What this buys over the bare hand-off:

- **Bounded HBM residency.** At most ``slots`` fragments are device-
  resident at once, enforced by the ledger rather than by drain-loop
  timing. ``slots=2`` is the double-buffer: slot B's H2D overlaps slot
  A's update, and the third enqueue waits on A's handle.
- **A zero-copy replay publish path.** The fragment the replay ring
  publishes IS the queue slot's device pytree — with the queue active
  the ring can adopt it by reference (``DeviceReplayRing.publish(...,
  ref=True)``) instead of paying the device-to-device row install.
  jax arrays are immutable, so slot REUSE (rebinding the slot to the
  next fragment) can never corrupt an adopted reference; the one real
  hazard is buffer DONATION, which is why the trainer only enables ref
  publishing when ``config.donate_buffers`` is off (a donating update
  deletes the adopted buffers under the ring).
- **A machine-checked lifecycle.** The lease protocol is declared below
  and verified by the protocol-typestate pass (PROT001-004): a drain
  path that mints a lease and drops it without ``consume``/``void``
  gates in lint, not in review.

Host staging remains the CPU fallback: on backends where device arrays
alias host memory there is no HBM tier to manage, so ``config.
device_queue="auto"`` resolves off (trainer construction) and the drain
keeps the plain ``put_rollout`` path, bit-identically.

Threading: single-thread contract, like the replay ring — every method
runs on the trainer's drain thread. The actor threads never see this
object (they hand off HOST fragments through the staging ring).
"""

# protocol: devq-lease mint=DeviceRolloutQueue.enqueue ops=consume:held->consumed,void:held->voided open=held terminal=voided initial=held reads=rollout:held

from __future__ import annotations

from collections import deque
from typing import Callable

import jax

from asyncrl_tpu.rollout.buffer import Rollout
from asyncrl_tpu.rollout.staging import StaleLeaseError, _handle_ready


class DeviceLease:
    """One device-slot write-read-release permit, generation-stamped.

    States: ``held`` (fragment resident, update not yet dispatched) →
    ``consumed`` (update dispatched; slot frees when the update's output
    handle is ready) or ``voided`` (abandoned — reset/stop hygiene; the
    slot frees after the in-flight H2D is barriered out)."""

    __slots__ = ("queue", "slot", "gen", "_consumed", "_voided")

    def __init__(self, queue: "DeviceRolloutQueue", slot: int, gen: int):
        self.queue = queue
        self.slot = slot
        self.gen = gen
        self._consumed = False
        self._voided = False

    def valid(self) -> bool:
        return (
            not self._voided
            and self.queue._slot_gen[self.slot] == self.gen
        )

    def _check(self) -> None:
        if not self.valid():
            raise StaleLeaseError(
                f"device lease gen {self.gen} on slot {self.slot} is "
                "stale (queue reset, or the slot was re-leased); the "
                "fragment it named is gone"
            )

    def rollout(self) -> Rollout:
        """The leased slot's device-resident fragment pytree. Valid in
        ``held`` only — after ``consume`` the consuming update may have
        donated the buffers."""
        self._check()
        if self._consumed:
            raise StaleLeaseError(
                f"device lease on slot {self.slot} already consumed; "
                "the update may have donated the fragment"
            )
        return self.queue._slots[self.slot]

    def consume(self, ready_handle) -> None:
        """Release the slot, gated on ``ready_handle`` (the consuming
        update's OUTPUT — e.g. ``state.update_step``): the slot re-leases
        only once the handle's device work has executed, so the next
        enqueue can never race the update still reading this fragment.
        One-shot; raises :class:`StaleLeaseError` if stale."""
        self._check()
        if self._consumed:
            raise StaleLeaseError(
                f"device lease on slot {self.slot} consumed twice"
            )
        self._consumed = True
        self.queue._consume(self, ready_handle)

    def void(self) -> None:
        """Abandon the lease (reset/stop hygiene — the update was never
        dispatched). Idempotent. The slot's in-flight H2D is barriered
        before the slot frees: the host staging slab under the transfer
        may recycle the moment the drain drops its lease, and an
        unfinished async read of it would land a torn fragment in a
        recycled slot."""
        if self._voided:
            return
        self._voided = True
        self.queue._void(self)


class DeviceRolloutQueue:
    """Fixed-depth ledger of HBM-resident fragments between H2D and the
    consuming update.

    ``transfer`` is the learner's sharded host→device put
    (``RolloutLearner.put_rollout`` — ONE home for the mesh sharding of a
    fragment); ``slots`` is the residency bound, minimum 2 (a single slot
    cannot overlap slot i+1's transfer with slot i's update — the whole
    point of the tier).

    Slots hold REBOUND pytrees, not a preallocated stacked buffer: jax
    arrays are immutable, so "reuse" is ledger-level — the bound the
    queue enforces is *at most ``slots`` fragments resident*, with the
    old slab's HBM returned the moment its last reference (the slot
    binding, plus any replay-ring adoption) drops or its buffers are
    donated by the update that consumed it."""

    def __init__(
        self,
        transfer: Callable[[Rollout], Rollout],
        slots: int = 2,
    ):
        if slots < 2:
            raise ValueError(
                f"device_queue_slots={slots} must be >= 2: one slot "
                "serializes every transfer behind the previous update "
                "(no double-buffer), which is strictly worse than the "
                "host-staging fallback"
            )
        self._transfer = transfer
        self._slots: list[Rollout | None] = [None] * slots
        self._gen = 0
        self._slot_gen = [0] * slots
        self._free: deque[int] = deque(range(slots))
        # (slot, ready_handle) in consume order — reclamation waits on
        # the OLDEST, matching the drain's dispatch order.
        self._pending: deque[tuple[int, object]] = deque()
        self._out: dict[int, DeviceLease] = {}  # slot -> open lease
        # Times enqueue found no free slot and had to block on a pending
        # update's handle — the device-tier twin of the staging ring's
        # slab_reuse_waits signal (drain outran the learner).
        self.reuse_waits = 0
        # Fragments handed to the drain through a lease, ever.
        self.enqueued = 0

    @property
    def slots(self) -> int:
        return len(self._slots)

    # ----------------------------------------------------------- enqueue

    def enqueue(self, host_rollout: Rollout) -> DeviceLease:
        """Claim a slot, land ``host_rollout`` on the mesh through the
        learner's sharded transfer (async dispatch — the caller barriers
        where the host tier demands it), and mint the slot's lease."""
        slot = self._claim()
        self._slots[slot] = self._transfer(host_rollout)
        self._gen += 1
        self._slot_gen[slot] = self._gen
        self.enqueued += 1
        lease = DeviceLease(self, slot, self._gen)
        self._out[slot] = lease
        return lease

    def _claim(self) -> int:
        self._reap()
        if not self._free:
            if not self._pending:
                # Every slot is HELD: the drain minted more leases than
                # slots without consuming — a drain-loop bug, not
                # backpressure. Blocking would deadlock (nothing pending
                # can ever free a slot).
                raise RuntimeError(
                    f"device queue exhausted: all {self.slots} slots "
                    "hold open leases; the drain must consume (or void) "
                    "a lease per enqueue"
                )
            # Backpressure: the drain outran the learner by the full
            # queue depth. Wait for the oldest consumed slot's update.
            self.reuse_waits += 1
            slot, handle = self._pending.popleft()
            jax.block_until_ready(handle)
            self._free.append(slot)
        return self._free.popleft()

    def _reap(self) -> None:
        """Free every consumed slot whose update has already executed —
        opportunistic, so steady-state enqueues never block at all."""
        while self._pending and _handle_ready(self._pending[0][1]):
            slot, _ = self._pending.popleft()
            self._free.append(slot)

    # ----------------------------------------------------------- release

    def _consume(self, lease: DeviceLease, ready_handle) -> None:
        if self._out.get(lease.slot) is lease:
            del self._out[lease.slot]
        self._pending.append((lease.slot, ready_handle))

    def _void(self, lease: DeviceLease) -> None:
        if self._out.get(lease.slot) is not lease:
            return
        del self._out[lease.slot]
        tree = self._slots[lease.slot]
        if tree is not None:
            jax.block_until_ready(tree)
        self._free.append(lease.slot)

    # ------------------------------------------------------------ facade

    def busy(self) -> bool:
        """Any open (held) lease outstanding?"""
        return bool(self._out)

    def reset(self) -> None:
        """Void every open lease and drain every pending handle (trainer
        ``stop()`` hygiene): straggler leases read as stale, and no
        async consumer of a slot outlives the queue's ledger."""
        for lease in list(self._out.values()):
            lease.void()
        while self._pending:
            _, handle = self._pending.popleft()
            jax.block_until_ready(handle)
        self._gen += 1
        self._slot_gen = [0] * self.slots
        self._free = deque(range(self.slots))
        self._slots = [None] * self.slots
