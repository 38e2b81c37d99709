"""DevicePlane: the shared base of every device-resident executor plane.

ROADMAP item 5 (the refactor items 1-4 are written on top of): the graph
plane, the votes-table plane (executor/table_plane.py) and the Caesar
predecessors plane (executor/pred_plane.py) all need the same machinery,
and before this base each hand-rolled its own copy:

* **donated resident buffers** — the plane's state lives ON DEVICE across
  batches and every dispatch donates it back in.  Buffers fed to donated
  argnums must be XLA-owned copies (``jnp.array``), never
  ``jnp.asarray``/``device_put`` of host numpy: on CPU those zero-copy
  alias the numpy memory, and donation then hands numpy-owned memory to
  XLA — nondeterministic wrong results + glibc heap corruption under the
  persistent compile cache (the PR 4 ownership rule, regression-tested by
  ``test_resident_buffers_never_alias_host_numpy``).  :meth:`_upload`
  is the ONE place resident buffers are created, so the rule cannot be
  re-broken per plane.
* **lazy host-mirror re-materialization** — pickling (the restart plane's
  ``Executor.snapshot`` seam) fetches the resident state into a host
  mirror; device state never survives a pickle, and the next dispatch
  re-materializes from the mirror with exactly ONE counted upload
  (``resident_uploads`` — the restart acceptance signal).
* **residual re-feed** — work a dispatch could not finish comes back as
  residual columns, buffered host-side and prepended to the next feed
  (the table plane's beyond-gap runs), or stays resident on device until
  a later feed unblocks it (the pred plane's missing-blocked rows); the
  base owns the column-buffer variant.
* **per-dispatch counters** — dispatches / occupancy / residual work /
  kernel wall-ms, surfaced through ``Executor.device_counters()`` into
  the metrics snapshot, the tracer, and the bench rows.
* **kernel-threshold switches** — the ``Config`` field, else the
  built-in default, for the thresholds that route host-vs-kernel work
  (:func:`resolve_threshold`).

Capacity follows a pow2 schedule (``_grow`` doubles) so XLA compiles
O(log) distinct programs as registries fill, and growth of a live
resident state is one fetch + pad + counted re-upload.

**Accelerator fault tolerance** (the PR 17 plane): the base additionally
owns a health state machine (healthy -> suspect -> failed -> rebuilding)
and a *host twin* — the same jitted kernels run statelessly over
host-authoritative numpy state.  Arming the plane
(``Config.device_dispatch_timeout_ms``, ``Config.plane_shadow_rate`` or
an attached :class:`~fantoch_tpu.sim.device_faults.DeviceFaultInjector`)
makes every dispatch log its exact padded kernel inputs; the twin folds
that log on demand by replaying the log through the SAME kernel on
fresh ``jnp.array`` uploads of host-owned state (donation-safe by the
PR 4 rule; the twin's uploads never touch ``resident_uploads``, which
stays the rebuild acceptance signal).  Because kernel, inputs, and
starting state are bit-identical, the twin's outputs are bit-for-bit
what a healthy device would have produced — so:

* a **hang/timeout** (injected, or a real dispatch overrunning the
  deadline) raises a typed ``DeviceFailedError`` *inside* the plane:
  first occurrence marks the plane suspect and retries once; a second
  failure fails over — the resident buffers are dropped and the batch
  (and every batch after it) is served from the twin, bit-for-bit;
* a **silent bit-flip** of a resident column is caught by the sampled
  shadow-check: compare the fetched resident post-state against the
  twin's folded post-state, raise ``DeviceCorruptionError`` naming the
  first diverging row *before* any host bookkeeping consumes the
  poisoned outputs;
* **rebuild** re-uploads the folded twin state through :meth:`_upload`
  (exactly ONE counted ``resident_uploads``) once the injector's fault
  window has closed (or immediately for a genuine live failure), and
  the plane cuts back to device serving.

Unarmed (all three channels off — the default), none of this costs
anything: no log, no twin, dispatch paths unchanged.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from fantoch_tpu.core.kvs import Key
from fantoch_tpu.errors import DeviceCorruptionError, DeviceFailedError
# one canonical pow2 helper (re-exported: the planes import it from here)
from fantoch_tpu.ops.table_ops import next_pow2

# plane health gauge (numeric so merge_counters can max-fold it: worst
# state wins across an executor pool, like the depth gauges)
HEALTH_HEALTHY = 0
HEALTH_REBUILDING = 1
HEALTH_SUSPECT = 2
HEALTH_FAILED = 3
HEALTH_NAMES = {
    HEALTH_HEALTHY: "healthy",
    HEALTH_REBUILDING: "rebuilding",
    HEALTH_SUSPECT: "suspect",
    HEALTH_FAILED: "failed",
}

# armed planes fold the twin log once it holds this many dispatches, so
# an armed-but-never-checked run pays bounded host memory (folding is
# the same kernels replayed on host-uploaded state)
TWIN_FOLD_LIMIT = 64


def resolve_threshold(explicit: Optional[int], default: int) -> int:
    """A plane's kernel-size threshold: the ``Config`` field where it is
    set, else the plane's built-in default."""
    return default if explicit is None else int(explicit)


class DevicePlane:
    """Resident device state + fused dispatch per batch: the base class.

    Subclasses define the state as a tuple of host numpy arrays via three
    hooks and get buffer lifecycle, durability and counters for free:

    * :meth:`_fresh_state` — zero state at the current capacity;
    * :meth:`_pad_state` — existing host state re-padded to a (larger)
      capacity (called by :meth:`_grow` and mirror re-materialization);
    * the resident state itself is ``self._resident`` (a tuple of
      XLA-owned device arrays, or None while unmaterialized) — dispatch
      methods call :meth:`_materialize` first, read/donate the tuple,
      and write the kernel's output state back.

    The optional key registry (``bucket``) maps string keys to stable
    device row ids with pow2 capacity; planes keyed by something else
    (the pred plane's dot->slot map) drive ``_grow`` directly.
    """

    __slots__ = (
        "_key_index",
        "_keys",
        "_cap",
        "_resident",
        "_host_mirror",
        "_residuals",
        "dispatches",
        "grows",
        "resident_uploads",
        "stats",
        # --- accelerator fault tolerance ---
        "health",
        "plane_failovers",
        "plane_rebuilds",
        "degraded_ms",
        "last_failure",
        "_injector",
        "_failure_listener",
        "_fault_pid",
        "_fault_seed",
        "_shadow_rate",
        "_timeout_ms",
        "_fault_armed",
        "_twin_state",
        "_twin_log",
        "_last_failure_dispatch",
    )

    # subclasses name themselves for errors/injector matching
    plane_name = "device"

    def __init__(self, capacity: int, stats: Dict[str, float]):
        self._key_index: Dict[Key, int] = {}
        self._keys: List[Key] = []
        self._cap = next_pow2(max(capacity, 2))
        # tuple of device arrays; None = lazy (created on first dispatch)
        self._resident = None
        # host copy awaiting re-materialization (restart/unpickle path);
        # None while the live state is device-resident
        self._host_mirror: Optional[Tuple[np.ndarray, ...]] = None
        # host-buffered residual columns re-fed with the next batch
        self._residuals: Tuple[np.ndarray, ...] = ()
        self.dispatches = 0
        self.grows = 0
        # host->device materializations: 1 for the lazy initial upload,
        # +1 per restore-from-snapshot re-upload and per live grow (the
        # recovery acceptance signal: restart costs ONE upload, not one
        # per batch)
        self.resident_uploads = 0
        # per-dispatch observability tallies (observability/device.py)
        self.stats: Dict[str, float] = dict(stats)
        # --- accelerator fault tolerance (unarmed by default) ---
        self.health = HEALTH_HEALTHY
        self.plane_failovers = 0
        self.plane_rebuilds = 0
        self.degraded_ms = 0.0
        self.last_failure: Optional[BaseException] = None
        self._injector = None
        self._failure_listener = None
        self._fault_pid: Optional[int] = None
        self._fault_seed = 0
        self._shadow_rate = 0.0
        self._timeout_ms: Optional[float] = None
        self._fault_armed = False
        # host-twin shadow: folded host state + the unfolded dispatch log
        self._twin_state: Optional[Tuple[np.ndarray, ...]] = None
        self._twin_log: List = []
        self._last_failure_dispatch = -(1 << 30)

    # --- state hooks (subclass responsibility) ---

    def _fresh_state(self) -> Tuple[np.ndarray, ...]:
        """Zero host state at the current capacity."""
        raise NotImplementedError

    def _pad_state(
        self, state: Tuple[np.ndarray, ...], cap: int
    ) -> Tuple[np.ndarray, ...]:
        """``state`` re-embedded into fresh arrays at capacity ``cap``
        (>= the state's own capacity)."""
        raise NotImplementedError

    # --- key registry (string keys -> stable device rows; optional) ---

    def bucket(self, key: Key) -> int:
        idx = self._key_index.get(key)
        if idx is None:
            idx = len(self._keys)
            self._key_index[key] = idx
            self._keys.append(key)
            if idx >= self._cap:
                self._grow()
        return idx

    @property
    def key_count(self) -> int:
        return len(self._keys)

    # --- buffer lifecycle ---

    def _upload(self, state: Tuple[np.ndarray, ...]) -> None:
        """THE resident-buffer creation point: copies every array into an
        XLA-owned buffer (``jnp.array`` — the donation-safety rule; see
        the module docstring) and counts the upload."""
        import jax.numpy as jnp

        self._resident = tuple(jnp.array(a) for a in state)
        self.resident_uploads += 1

    def _fetch_state(self) -> Tuple[np.ndarray, ...]:
        """One blocking transfer for the whole resident tuple."""
        import jax

        assert self._resident is not None
        return tuple(np.asarray(a) for a in jax.device_get(self._resident))

    def _materialize(self) -> None:
        """Ensure the state is device-resident: lazy initial creation, or
        the ONE re-upload from the host mirror after restore-from-snapshot
        (the restart plane's lazy re-materialization seam)."""
        if self._resident is not None:
            return
        if self._host_mirror is not None:
            state = self._pad_state(self._host_mirror, self._cap)
            self._host_mirror = None
        else:
            state = self._fresh_state()
        self._upload(state)

    def _grow(self) -> None:
        """Double the capacity; pads the resident state when live (one
        host round-trip — rare, amortized by the pow2 schedule).  Armed
        planes pad and re-upload from the folded TWIN state instead of a
        device fetch: the twin is provably clean, so growth never bakes
        an undetected resident bit-flip into the new buffers."""
        new_cap = self._cap * 2
        if self._fault_armed and self._twin_state is not None:
            self._twin_fold()
            self._twin_state = self._pad_state(self._twin_state, new_cap)
            if self._resident is not None:
                self._upload(self._twin_state)
        elif self._resident is not None:
            state = self._fetch_state()
            self._upload(self._pad_state(state, new_cap))
        self._cap = new_cap
        self.grows += 1

    # --- residual re-feed (column-buffer variant) ---

    def _take_residuals(
        self, columns: Tuple[np.ndarray, ...]
    ) -> Tuple[np.ndarray, ...]:
        """Prepend the buffered residual columns to this batch's columns
        (so gap-filling batches coalesce with the runs they unblock) and
        clear the buffer; ``_put_residuals`` re-buffers the dispatch's
        leftover."""
        if not self._residuals:
            return columns
        merged = tuple(
            np.concatenate([r, c]) for r, c in zip(self._residuals, columns)
        )
        self._residuals = ()
        return merged

    def _put_residuals(self, columns: Tuple[np.ndarray, ...]) -> None:
        self._residuals = columns

    @property
    def residual_count(self) -> int:
        return len(self._residuals[0]) if self._residuals else 0

    # --- per-dispatch counters ---

    def _count_dispatch(self, t0: float, **adds: float) -> None:
        """Tally one dispatch: wall time since ``t0`` into
        ``stats["kernel_ms"]`` plus any per-plane increments."""
        self.dispatches += 1
        self.stats["kernel_ms"] += (time.perf_counter() - t0) * 1000.0
        for name, value in adds.items():
            self.stats[name] += value

    # --- accelerator fault tolerance ---

    def configure_faults(
        self, config, seed: int = 0, process_id: Optional[int] = None
    ) -> None:
        """Arm (or leave unarmed) the fault plane from the config: the
        per-dispatch deadline and the shadow-check rate.  Executors call
        this right after constructing the plane, before any dispatch."""
        self._timeout_ms = getattr(config, "device_dispatch_timeout_ms", None)
        self._shadow_rate = getattr(config, "plane_shadow_rate", 0.0) or 0.0
        self._fault_seed = seed
        if process_id is not None:
            self._fault_pid = process_id
        self._refresh_armed()

    def attach_injector(self, injector) -> None:
        """Attach a DeviceFaultInjector (sim/device_faults.py); arming
        the plane as a side effect so failover has a twin to serve from."""
        self._injector = injector
        self._refresh_armed()

    def attach_failure_listener(self, listener) -> None:
        """``listener(plane, exc)`` fires on every failover — the sim
        runner wires it to the nemesis trace + flight-recorder dump."""
        self._failure_listener = listener

    def _refresh_armed(self) -> None:
        self._fault_armed = (
            self._injector is not None
            or self._shadow_rate > 0.0
            or self._timeout_ms is not None
        )

    @property
    def degraded(self) -> bool:
        """True while serving from the host twin (failed, not yet
        cut back)."""
        return self.health in (HEALTH_FAILED, HEALTH_REBUILDING)

    def health_name(self) -> str:
        return HEALTH_NAMES[self.health]

    # --- host twin (armed only) ---

    def _twin_replay(self, state, entry):
        """Replay ONE logged dispatch on host-owned ``state``: run the
        plane's kernel on fresh ``jnp.array`` uploads of the state plus
        the entry's logged columns, and return ``(new_state, outputs)``
        as host numpy.  Bit-for-bit with the resident dispatch by
        construction (same kernel, same inputs)."""
        raise NotImplementedError

    def _twin_note(self, entry) -> None:
        """Log one dispatch's exact padded kernel inputs for the twin
        (no-op unarmed).  Must be called BEFORE the resident dispatch so
        a failure mid-dispatch can still replay it."""
        if not self._fault_armed:
            return
        if self._twin_state is None:
            self._twin_init()
        self._twin_log.append(entry)
        if len(self._twin_log) > TWIN_FOLD_LIMIT:
            self._twin_fold()

    def _twin_init(self) -> None:
        """First armed dispatch: the twin starts from the same state the
        resident plane did — fresh zeros, the restore mirror, or (when
        armed mid-life) a fetch of the current resident state."""
        if self._host_mirror is not None:
            self._twin_state = self._pad_state(self._host_mirror, self._cap)
        elif self._resident is not None:
            self._twin_state = self._fetch_state()
        else:
            self._twin_state = self._fresh_state()

    def _twin_fold(self):
        """Replay every logged dispatch through the kernel, advancing
        the twin state; returns the LAST dispatch's outputs (None when
        the log was empty).  Truncates the log — later entries already
        contain any residual rows the plane re-fed, so discarding the
        replayed residual outputs reproduces the state sequence
        exactly."""
        outputs = None
        state = self._twin_state
        for entry in self._twin_log:
            state, outputs = self._twin_replay(state, entry)
        self._twin_state = state
        self._twin_log = []
        return outputs

    def _twin_resync(self, state: Tuple[np.ndarray, ...]) -> None:
        """Reset the twin to a host-derived state (compaction and the
        other host-mirror rebuilds produce trusted host state directly;
        the pending log described the pre-rebuild layout)."""
        if not self._fault_armed:
            return
        self._twin_state = tuple(np.array(a) for a in state)
        self._twin_log = []

    # --- detection: injected faults, deadline, shadow-check ---

    def _fault_check_pre(self):
        """Consult the injector before a fused dispatch.  hang/raise
        faults raise the typed error here (a hung dispatch never
        completes — short-circuiting it *is* its deadline, kept
        deterministic instead of sleeping the wall budget); a corrupt
        fault is returned for the caller to apply via
        :meth:`_poison_resident`."""
        inj = self._injector
        if inj is None:
            return None
        fault = inj.on_dispatch(self.plane_name, self.dispatches)
        if fault is None:
            return None
        if fault.kind == "hang":
            raise DeviceFailedError(
                self.plane_name, self._fault_pid, "hang",
                self.dispatches, self._timeout_ms,
            )
        if fault.kind == "raise":
            raise DeviceFailedError(
                self.plane_name, self._fault_pid, "raise", self.dispatches,
                cause=RuntimeError("injected XLA runtime error"),
            )
        return fault

    def _poison_resident(self, fault) -> None:
        """Apply an injected corrupt fault: flip ``fault.bit`` of flat
        element 0 of resident state array 0 on device.  Callers apply it
        AFTER the dispatch's resident update (a post-compute HBM flip),
        so the kernel cannot overwrite the flipped cell in the same
        round and a rate-1.0 shadow check catches it deterministically
        on the faulted dispatch; the host twin never sees the flip,
        which is exactly why the compare names it."""
        import jax.numpy as jnp

        self._materialize()
        a = self._resident[0]
        flat = jnp.ravel(a)
        flat = flat.at[0].set(flat[0] ^ np.asarray(1 << fault.bit, a.dtype))
        self._resident = (flat.reshape(a.shape),) + tuple(self._resident[1:])

    def _check_deadline(self, t0: float) -> None:
        """The per-dispatch deadline, measured across dispatch + its
        blocking drain (an XLA dispatch cannot be interrupted portably;
        detection at the drain is when the hang becomes observable)."""
        if self._timeout_ms is None:
            return
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        if elapsed_ms > self._timeout_ms:
            raise DeviceFailedError(
                self.plane_name, self._fault_pid, "timeout",
                self.dispatches, self._timeout_ms,
            )

    def _shadow_sampled(self) -> bool:
        """Seeded per-dispatch shadow-check decision — a pure function
        of (seed, plane, dispatch #) so same-seed runs sample the same
        dispatches."""
        rate = self._shadow_rate
        if rate <= 0.0 or self._twin_state is None and not self._twin_log:
            return False
        if rate >= 1.0:
            return True
        import random

        draw = random.Random(
            f"{self._fault_seed}:{self.plane_name}:{self.dispatches}"
        ).random()
        return draw < rate

    def _shadow_compare(
        self, device_state: Tuple[np.ndarray, ...]
    ) -> None:
        """Bit-for-bit compare the fetched resident post-state against
        the twin's folded post-state; raises DeviceCorruptionError
        naming the first diverging row (and its key, when the row is in
        the key registry) — the auditor-style attribution."""
        self._twin_fold()
        twin = self._twin_state
        assert twin is not None
        for index, (dev, host) in enumerate(zip(device_state, twin)):
            if dev.shape == host.shape and np.array_equal(dev, host):
                continue
            if dev.shape != host.shape:
                row = 0
            else:
                diverging = np.nonzero(
                    (dev != host).reshape(dev.shape[0], -1).any(axis=1)
                )[0]
                row = int(diverging[0]) if len(diverging) else 0
            key = self._keys[row] if row < len(self._keys) else None
            raise DeviceCorruptionError(
                self.plane_name, self._fault_pid, self.dispatches,
                index, row, key,
            )

    # --- failover + rebuild ---

    def _device_failure(self, exc: BaseException) -> None:
        """One device failure observed (the batch itself is already
        served from the twin by the caller — never re-dispatched: the
        hung program may have half-applied its donation chain, so a
        re-dispatch could double-apply).  A FIRST hang/timeout is
        ambiguous (scheduler hiccup vs dead device): the plane goes
        *suspect*, drops the untrusted resident buffers, and immediately
        probes — a transient blip re-uploads the twin on the spot and
        never counts a failover; a still-broken device (the injector's
        window is open) escalates to FAILED.  A raise or a corruption
        verdict is definitive and fails over directly."""
        self.last_failure = exc
        self._resident = None
        # back-to-back hangs are not a hiccup: a second hang/timeout
        # within two dispatches of a "recovered" one escalates straight
        # to failover instead of flapping suspect -> healthy forever
        repeat = self.dispatches - self._last_failure_dispatch <= 2
        self._last_failure_dispatch = self.dispatches
        if (
            isinstance(exc, DeviceFailedError)
            and exc.kind in ("hang", "timeout")
            and self.health == HEALTH_HEALTHY
            and not repeat
        ):
            self.health = HEALTH_SUSPECT
            if self._probe_recovery():
                return
        self._enter_failed(exc)

    def _probe_recovery(self) -> bool:
        """The suspect probe: when the device answers again (no injector
        window covers it), re-upload the folded twin state and return to
        healthy — a transient hiccup costs one upload, no failover."""
        inj = self._injector
        if inj is not None and not inj.rebuild_allowed(
            self.plane_name, self.dispatches
        ):
            return False
        self._twin_fold()
        if self._twin_state is None:
            return False
        self._upload(self._pad_state(self._twin_state, self._cap))
        self._host_mirror = None
        self.health = HEALTH_HEALTHY
        return True

    def _enter_failed(self, exc: BaseException) -> None:
        self.health = HEALTH_FAILED
        self.plane_failovers += 1
        self.last_failure = exc
        # the resident buffers are no longer trusted (hung program /
        # poisoned donation chain): drop them; the twin is authoritative
        self._resident = None
        listener = self._failure_listener
        if listener is not None:
            listener(self, exc)

    def _note_degraded(self, t0: float) -> None:
        self.degraded_ms += (time.perf_counter() - t0) * 1000.0

    def _maybe_rebuild(self) -> bool:
        """Cut back to device serving: ONE counted re-upload of the
        folded twin state (the restart plane's acceptance signal,
        reused), vetoed while the injector's fault window still covers
        the device."""
        if self.health != HEALTH_FAILED:
            return False
        inj = self._injector
        if inj is not None and not inj.rebuild_allowed(
            self.plane_name, self.dispatches
        ):
            return False
        self.health = HEALTH_REBUILDING
        self._twin_fold()
        assert self._twin_state is not None
        self._upload(self._pad_state(self._twin_state, self._cap))
        self._host_mirror = None
        self.plane_rebuilds += 1
        self.health = HEALTH_HEALTHY
        return True

    def _recover_health(self) -> None:
        """A suspect probe succeeded: the failure was transient."""
        if self.health == HEALTH_SUSPECT:
            self.health = HEALTH_HEALTHY

    def fault_counters(self) -> Dict[str, float]:
        """The fault-plane slice of ``device_counters()`` (prefixed by
        the owning executor): failover/rebuild tallies, degraded wall,
        and the numeric health gauge (max-folded across pools)."""
        return {
            "failovers": self.plane_failovers,
            "rebuilds": self.plane_rebuilds,
            "degraded_ms": self.degraded_ms,
            "health": self.health,
        }

    # --- durability (Executor.snapshot pickles through here) ---

    def _all_slots(self) -> List[str]:
        slots: List[str] = []
        for klass in type(self).__mro__:
            slots.extend(getattr(klass, "__slots__", ()))
        return slots

    def __getstate__(self):
        # injector + listener are runtime wiring (the runner re-attaches
        # them after restore), never part of the durable image
        state = {
            slot: getattr(self, slot)
            for slot in self._all_slots()
            if slot
            not in (
                "_resident", "_host_mirror", "_injector",
                "_failure_listener", "last_failure",
            )
        }
        mirror = self._host_mirror
        if self._resident is not None:
            mirror = self._fetch_state()
        elif self.degraded and self._twin_state is not None:
            # snapshot taken mid-failover: the twin IS the state —
            # fold it so the restored image needs no log replay
            state["_twin_log"] = []
            outputs = self._twin_fold()
            del outputs
            state["_twin_state"] = self._twin_state
            mirror = self._twin_state
        state["_host_mirror"] = mirror
        return state

    def __setstate__(self, state) -> None:
        # fault-plane defaults first: images written before the fault
        # plane existed (or with it unarmed) stay restorable
        self.health = HEALTH_HEALTHY
        self.plane_failovers = 0
        self.plane_rebuilds = 0
        self.degraded_ms = 0.0
        self.last_failure = None
        self._fault_pid = None
        self._fault_seed = 0
        self._shadow_rate = 0.0
        self._timeout_ms = None
        self._fault_armed = False
        self._twin_state = None
        self._twin_log = []
        self._last_failure_dispatch = -(1 << 30)
        for slot, value in state.items():
            setattr(self, slot, value)
        # device state never survives a pickle: the next dispatch
        # re-materializes from the host mirror (ONE counted upload)
        self._resident = None
        self._injector = None
        self._failure_listener = None
