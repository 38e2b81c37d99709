"""Who decides when the cyclic collector runs a full collection, and over
what: the serving window's policy for ``gc`` (the device-step server's;
``run/device_runner.py`` is its one user).

CPython starts a full (generation 2) collection whenever what was promoted
into the oldest generation exceeds a quarter of what survived the last
one, and each traverses every tracked object of the process: jax, the
compiled programs and the driver's tables, which never die, and the
commands in flight.  A saturated server promotes every command (it lives
for a few hundred milliseconds), so the allocator's schedule walked all
of it every few thousand commands (53-78 ms each on the chip host, a sixth
of a saturated window), under the GIL, with the loop and the step's thread
both stopped.

Here the server decides instead:

- ``take_over()`` (the end of ``DeviceRuntime.start()``): one full
  collection, then ``gc.freeze()``: everything start-up built goes to the
  permanent generation and is never traversed again.  The third threshold
  is put out of reach, so the allocator starts no full collection any
  more; generations 0 and 1 stay automatic (cheap, and they keep
  short-lived cycles bounded).
- ``run_if_due()`` (the driver task, between rounds): one ``gc.collect()``
  no sooner than ``PAUSE_MULTIPLE`` times the last full collection's own
  measured duration after it, so full collections take at most
  1 / (1 + PAUSE_MULTIPLE) of wall time whatever the heap is.  Cyclic
  garbage is still reclaimed, that much later; acyclic garbage is freed by
  reference counts as ever.
- ``hand_back()`` (``DeviceRuntime.stop()``): ``gc.unfreeze()`` and the
  thresholds found, so a process that starts and stops runtimes is left
  as it was.

The collector's state is the process's, so the hold on it is counted: the
first runtime to take over freezes and keeps the thresholds it found, the
last to hand back restores them.
"""
from __future__ import annotations

import gc
from time import monotonic_ns
from typing import Callable, Dict, Optional, Tuple

# gc.set_threshold takes a C int; generation 2's count (generation 1
# collections since the last full one) never gets there
OUT_OF_REACH = 2**31 - 1

_holders = 0  # schedules holding the process's collector
_found: Tuple[int, int, int] = (0, 0, 0)  # thresholds before the first took over
_frozen = 0  # gc.get_freeze_count() right after the freeze (it walks the list)


class CollectorSchedule:
    """One runtime's hold on the collector, the schedule of its full
    collections and their tallies.  ``note`` is the ``gc.callbacks`` hook's
    body: it times every full collection, asked for or not, and that
    measurement is what the schedule reads."""

    # a full collection of d is followed by none for PAUSE_MULTIPLE x d: a
    # constant of the policy, as CPython's quarter is of the allocator's
    PAUSE_MULTIPLE = 64

    def __init__(self, clock: Callable[[], int] = monotonic_ns):
        self._clock = clock
        self._holding = False
        self._asked = False  # inside run_if_due's own gc.collect()
        self._started = 0  # clock at the start of the full collection under way
        self._not_before = 0  # clock before which no full collection is due
        self.scheduled = 0
        self.unscheduled = 0
        self.collected = 0

    def take_over(self) -> None:
        global _holders, _found, _frozen
        if self._holding:
            return
        self._holding = True
        _holders += 1
        if _holders == 1:
            _found = gc.get_threshold()
            gc.collect()
            gc.freeze()
            _frozen = gc.get_freeze_count()
            gc.set_threshold(_found[0], _found[1], OUT_OF_REACH)

    def hand_back(self) -> None:
        global _holders
        if not self._holding:
            return
        self._holding = False
        _holders -= 1
        if _holders == 0:
            gc.set_threshold(*_found)
            gc.unfreeze()

    def note(self, phase: str, info: Dict[str, int]) -> Optional[Tuple[int, int]]:
        """``gc.callbacks`` hook: ``(started, ended)`` on the clock when a
        full collection ends, else None."""
        if info["generation"] < 2:
            return None
        if phase == "start":
            self._started = self._clock()
            return None
        if not self._started:
            return None
        started, ended = self._started, self._clock()
        self._started = 0
        self._not_before = ended + self.PAUSE_MULTIPLE * (ended - started)
        self.collected += info.get("collected", 0)
        if not self._asked:
            self.unscheduled += 1
        return started, ended

    def run_if_due(self) -> bool:
        if not self._holding or self._clock() < self._not_before:
            return False
        self.scheduled += 1
        self._asked = True
        gc.collect()
        self._asked = False
        return True

    def counters(self) -> Dict[str, int]:
        return {
            "gc_frozen_objects": _frozen if self._holding else 0,
            "gc_full_scheduled": self.scheduled,
            "gc_full_unscheduled": self.unscheduled,
            "gc_collected": self.collected,
        }
