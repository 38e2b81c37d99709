"""The device drivers of the served path: the host-side control loop of
the protocol round, one driver a protocol family.

What this module owns: the key column (``_bucket``, ``_buckets``,
``_key_column``: which clock entry a key takes), a round's batch order
under a coordinator at every site (``_sites_in_turn``), ``_DriverCore``
(the in-flight registry, the requeue, the store's pass over a round's
executed rows, the 31-bit dot-sequence window, and through
``run/pipeline.py``'s ``PipelineCore`` the dispatch/drain pipeline), the
four drivers over it (``DeviceDriver``: the dep-commit round of EPaxos and
Atlas; ``NewtDeviceDriver``: Tempo's timestamp round;
``CaesarDeviceDriver``; ``PaxosDeviceDriver``: the leader's slot round),
and ``driver_for``, the one place that knows which of them serves which
protocol label.  A driver is usable without any networking: the driver
dry-run and the simulator-style tests call ``step`` / ``serve`` directly.

What it may not import: ``run/device_session.py`` (the session plane),
``run/device_runner.py`` (the runtime) and ``asyncio``.  The arrows point
one way, runtime -> drivers -> ``run/pipeline.py`` ->
``parallel/mesh_step.py`` (tests/test_served_layers.py holds them); jax and
``mesh_step`` are imported inside the functions that use them, so that
importing this module compiles nothing.

Partial replication (``shard_count > 1``, the dep-commit and Newt rounds):
ONE mesh carries every shard, shard s owning key buckets
``b % shard_count == s`` and replica rows ``[s*n, (s+1)*n)``; the module
docstring of ``run/device_runner.py`` tells the serving story whole.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial
from itertools import repeat, zip_longest
from operator import attrgetter, itemgetter
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple
from zlib import crc32

import numpy as np

from fantoch_tpu.core.command import FLAT, Command
from fantoch_tpu.core.config import Config
from fantoch_tpu.core.ids import Dot, ProcessId, ShardId
from fantoch_tpu.core.kvs import KVStore
from fantoch_tpu.executor.base import ExecutorResult
from fantoch_tpu.run.pipeline import (
    PackedOutput,
    PipelineCore,
    StagedColumns,
    packed_round,
    packed_shape,
)
from fantoch_tpu.utils import logger


_HASH_MIX = 2654435761  # Knuth's multiplier, 2**32 / phi
_HASH_MASK = 0xFFFFFFFF
# a batch entry ``(dot, cmd)``'s halves, and what a round's identity
# columns read off them (``_DriverCore._identity_columns``)
_DOT, _CMD = itemgetter(0), itemgetter(1)
_SOURCE, _SEQUENCE = attrgetter("source"), attrgetter("sequence")
_READ_ONLY = attrgetter("_read_only")


def _top_sequence(batch) -> int:
    """The highest dot sequence of a batch that is not empty."""
    return max(map(_SEQUENCE, map(_DOT, batch)))


def _bucket(sid: ShardId, k: str, key_buckets: int, shard_count: int) -> int:
    """One key's bucket: ``h`` of its shard's ``key_buckets //
    shard_count``, and on several shards bucket ``b`` belongs to shard
    ``b % shard_count`` (the sharded-key-axis contract of
    mesh_step.protocol_step).  ``h`` is the server's own business (which
    unrelated keys share a clock entry), not the shard rule
    (``utils.key_hash``): CRC-32 of the key's bytes in C, the same in
    every process, spread by the multiplier and reduced by its HIGH bits
    (CRC is linear, and its low bits over decimal strings fill a quarter
    fewer buckets than a random function).  ``_key_column`` spells the
    same line in its loop."""
    per_shard = key_buckets // shard_count
    h = ((crc32(k.encode()) * _HASH_MIX & _HASH_MASK) * per_shard) >> 32
    return h if shard_count == 1 else sid + shard_count * h


def _buckets(
    cmd: Command, shard_id: ShardId, key_buckets: int, shard_count: int = 1
) -> List[int]:
    """Distinct key buckets for one command, ascending: the plain
    definition, any shape (colliding keys dedup, which only coarsens
    conflicts).  The session-boundary validator decides by it for the
    rare command that fails ``_admit``'s quick test, and the driver's
    ``_key_column`` is held to it row by row (tests/test_key_column.py).

    One shard: the keys the command has on ``shard_id``.  Sharded
    (shard_count > 1): buckets span EVERY shard the command touches and
    the ``shard_id`` argument is ignored — the unified mesh orders the
    whole command."""
    if shard_count == 1:
        return sorted({_bucket(shard_id, k, key_buckets, 1) for k in cmd.keys(shard_id)})
    return sorted(
        {_bucket(sid, k, key_buckets, shard_count) for sid, k in cmd.all_keys()}
    )


def _key_column(
    batch, key_rows, shard_id: ShardId, key_buckets: int, shard_count: int = 1
) -> None:
    """One round's key column: row ``i`` of ``key_rows`` (``int32[>=
    len(batch), key_width]``, handed over filled with ``KEY_PAD``) takes
    ``_buckets`` of ``batch[i]``'s command (device key-row contract: a
    row's buckets ascend and never repeat).  One pass: a command's
    buckets are read off its own ops (its wire form, ``Command._wire``:
    the tuple its frame carried) onto the round's one flat list, which
    becomes the column by one conversion.  What a command shows
    decides its branch: one key is its bucket, two are ordered by one
    comparison (the pad where both fell in one bucket), three or more
    are sorted and deduplicated.  A command with no bucket, or more than
    the key width, is the caller's fault (the session boundary admits
    neither) and asserts, once a round: a row that fits adds
    ``key_width`` entries, and a row of pads alone starts with one."""
    from fantoch_tpu.parallel.mesh_step import KEY_PAD

    width = key_rows.shape[1]
    per_shard = key_buckets // shard_count
    pads = [[KEY_PAD] * (width - n) for n in range(width + 1)]
    sharded = shard_count > 1
    flat: List[int] = []
    for _dot, cmd in batch:
        wire = cmd._wire
        if len(wire) == FLAT:
            # one key: (source, sequence, shard, key, code, value)
            h = ((crc32(wire[3].encode()) * _HASH_MIX & _HASH_MASK) * per_shard) >> 32
            if sharded:
                flat.append(wire[2] + shard_count * h)
            elif wire[2] == shard_id:
                flat.append(h)
            else:
                flat.append(KEY_PAD)  # another shard's key: no bucket here
            flat += pads[1]
            continue
        if sharded:
            row = [
                sid + shard_count
                * (((crc32(k.encode()) * _HASH_MIX & _HASH_MASK) * per_shard) >> 32)
                for sid, keys in wire[2]
                for k, _ops in keys
            ]
        else:
            row = [
                ((crc32(k.encode()) * _HASH_MIX & _HASH_MASK) * per_shard) >> 32
                for sid, keys in wire[2]
                if sid == shard_id
                for k, _ops in keys
            ]
        n = len(row)
        if n == 2:
            a, b = row
            if a > b:
                row = (b, a)
            elif a == b:
                row = (a,)
                n = 1
        elif n > 2:
            row = sorted(set(row))
            n = len(row)
        flat += row
        if n < width:
            flat += pads[n]
    column = key_rows[: len(batch)]
    assert len(flat) == column.size, (
        "a command touches more key buckets than the device state was "
        f"initialized with (key_width={width})"
    )
    column[:] = np.array(flat, dtype=np.int32).reshape(column.shape)
    assert (column[:, 0] != KEY_PAD).all(), "a command touches no key bucket"


class _RoundOutput(NamedTuple):
    """What a dispatch leaves on the device for its drain."""

    packed: Any  # int32[(S,) L]: what the drain reads, the one leaf fetched
    rest: Any  # the output tuple's un-fetched device leaves, None elsewhere
    layout: PackedOutput  # how ``packed`` reads back, the program's own


def _sites_in_turn(batch):
    """A round's batch with its sites' commands taken in turn: the first
    of each site (in the order the sites first appear), then the second
    of each, and so on; a site's own commands keep their order.  The
    round with a coordinator at every site is given its batch so: the
    commands a round collects are concurrent, and what a socket read
    brings is some hundreds of frames of one connection, one site, in a
    row, where a replica's network would deliver five coordinators'
    ``MCollect``s interleaved (left as they arrive, only a stretch's
    first command finds its fast quorum split).  ``batch``: ``(dot,
    ...)`` entries; the site is the dot's source."""
    by_site: Dict[int, list] = {}
    for entry in batch:
        by_site.setdefault(entry[0].source, []).append(entry)
    if len(by_site) < 2:
        return batch
    return [
        entry
        for turn in zip_longest(*by_site.values())
        for entry in turn
        if entry is not None
    ]


class _DriverCore(PipelineCore):
    """The host-side machinery every device driver shares: the in-flight
    command registry, the overflow requeue channel, the KVStore, the
    serving tallies (the BaseProcess metrics twin), the 31-bit
    dot-sequence window, and — via :class:`PipelineCore`
    (run/pipeline.py) — the depth-K dispatch/drain pipeline with its
    staging ingest ring.  Keeping it in one place keeps the four
    protocol drivers from silently diverging on the registry/requeue
    contract.

    Sequence windowing: dots are unbounded host ints, device columns are
    int32.  The device only ever *compares* sequences among in-flight
    rows (tie-breaking, identity mirrors), so columns carry
    ``sequence - seq_base`` and the base advances to the oldest in-flight
    sequence whenever the window would overflow — the ClockWindow design
    of fantoch_tpu/ops/table_ops.py applied to dots (reference GC keeps
    dot state bounded the same way, fantoch/src/protocol/gc.rs:72-116).
    """

    # leave headroom so a full batch plus in-round growth never wraps
    SEQ_WINDOW_MAX = 2**31 - (1 << 20)

    # what resolves the round's dependency graph (mesh_step.resolver_name);
    # None where the round executes in clock or slot order
    resolver: Optional[str] = None
    # the leader round's name and its accept quorum (f + 1); None for the
    # leaderless rounds
    round_name: Optional[str] = None
    accept_quorum: Optional[int] = None
    # the dep-commit round's quorum rule (mesh_step.DEP_COMMIT_RULES) and
    # the (fast, write) quorum sizes it gives; None elsewhere
    rule: Optional[str] = None
    fast_quorum: Optional[int] = None
    write_quorum: Optional[int] = None

    def _init_core(
        self,
        shard_id: ShardId,
        batch_size: int,
        key_buckets: int,
        monitor_execution_order: bool,
    ) -> None:
        self.shard_id = shard_id
        self.shard_count = 1  # DeviceDriver overrides in sharded mode
        self.batch_size = batch_size
        self.key_buckets = key_buckets
        # commands in flight: registered at step entry, dropped at execution
        self._cmds: Dict[int, Tuple[Dot, Command]] = {}
        self._requeue: List[Tuple[Dot, Command]] = []
        self._seq_base = 0  # device seq column = dot.sequence - seq_base
        self.seq_epochs = 0  # window advances (observability)
        self.store = KVStore(monitor_execution_order)
        self.rounds = 0
        self.fast_paths = 0
        self.slow_paths = 0
        self.executed = 0
        # working rows a drain's Python visited: the executed rows (pads
        # among them) and, when the device dropped rows, the overflow's
        # candidates — not the working set
        self.drain_rows_walked = 0
        # commands an overflow of the device's pending buffer handed back
        # for the caller to submit again (take_requeue)
        self.requeued = 0
        self.stable_watermark = 0
        # what the round tallies over the rows it executed, summed, by
        # name (mesh_step.ROUND_TALLIES); empty where it tallies nothing
        self.round_tallies: Dict[str, int] = {}
        # ... and what it says of its last round alone (gauges), by name
        self.round_gauges: Dict[str, int] = {}
        # the programs made ready, by the rounds a dispatch of theirs
        # carries: the executable, where it takes its packed columns on
        # the mesh and how its packed output reads back (``_program``)
        self._programs: Dict[int, Tuple[Any, Any, PackedOutput]] = {}
        # the sites clients are registered at (``register_site``): a
        # client that names none is at site 0; and, where a second site's
        # hello put the programs with a coordinator at every site in
        # ``_programs``' place, the programs with one
        self._sites = {0}
        self._one_site_programs: Dict[int, Tuple[Any, Any, PackedOutput]] = {}
        # the depth-K dispatch/drain pipeline + staging ingest ring +
        # per-dispatch counters (serve/step/flush_pipeline and _staging
        # come from PipelineCore; drivers implement the halves
        # _assemble / _enqueue of a dispatch and _execute of a drain)
        self._init_pipeline()

    @property
    def in_flight(self) -> int:
        """Commands registered but not yet executed (device pending)."""
        return len(self._cmds)

    @property
    def executed_in_pass(self) -> int:
        """Of ``executed``, the commands the store's one pass applied by
        its one-op spelling (``_execute_rows``): all of them, unless the
        store has a monitor or a digest, or a method the pass spells out
        has been replaced."""
        return self.store.applied_in_pass

    @property
    def executed_off_wire(self) -> int:
        """Of ``executed_in_pass``, the commands that came off a frame
        (``Command._off_wire``), whose ops the pass read off the frame's
        own tuple: all of them on the served path; none of a round
        stepped by hand with commands the constructor made."""
        return self.store.applied_off_wire

    def _pipeline_flush_needed(self, batch) -> bool:
        """True when the upcoming dispatch may trigger a rebase that
        must not happen with rounds in flight.  The dot drivers all
        share the sequence-window trigger; drivers add their own
        (gid epoch, clock window, slot log)."""
        if not batch:
            return False
        return _top_sequence(batch) - self._seq_base >= self.SEQ_WINDOW_MAX

    def _init_sharded_mesh(
        self, mesh_step, num_replicas: int, shard_count: int,
        key_buckets: int, pending_capacity: int, key_width: int, mesh,
        init_state_fn,
    ):
        """Shared sharded-mesh setup (DeviceDriver + NewtDeviceDriver):
        num_replicas is PER SHARD, the state holds shard_count *
        num_replicas replica rows, bucket b % shard_count encodes the
        owning shard."""
        self.shard_count = shard_count
        assert key_buckets % shard_count == 0, (
            "key_buckets must split evenly across shards"
        )
        total_rows = shard_count * num_replicas
        self._mesh = (
            mesh
            if mesh is not None
            else mesh_step.make_mesh(
                num_replicas=total_rows, shard_count=shard_count
            )
        )
        self._state = init_state_fn(
            self._mesh,
            total_rows,
            key_buckets=key_buckets,
            pending_capacity=pending_capacity,
            key_width=key_width,
        )

    @property
    def mesh(self):
        """The (replica x batch) device mesh the round runs on, the
        driver's own or the one it was handed (``mesh=``)."""
        return self._mesh

    # whether the round can have a coordinator at every site (the
    # dep-commit, the Newt and the Caesar drivers say where); the rounds
    # with one coordinator serve site 0 alone
    serves_sites = False

    @property
    def sites_registered(self) -> int:
        """Sites clients have registered at (gauge)."""
        return len(self._sites)

    def register_site(self, site: int) -> None:
        """A client plane's hello names the site its clients are at
        (``ClientHi.site``).  The first site but 0 makes the round with a
        coordinator at every site ready before this returns
        (``_make_site_programs``: compiled, or loaded, through the
        persistent cache under ``precompile`` spans), and every dispatch
        from the next one on runs it: same state, same columns, so nothing
        is rebuilt and a round in flight drains as it was dispatched.
        Raises ``ValueError`` for a site the driver cannot serve: every
        site but 0 where the round has one coordinator, and a site that is
        none of the replicas'."""
        if site in self._sites:
            return
        if not self.serves_sites:
            raise ValueError(
                f"clients at site {site}: this round has one coordinator, "
                "replica 0 (fpaxos's always, caesar's under --device-key-width "
                "above 1, newt's under that or --shard-count above 1; a "
                "coordinator at every site is served under epaxos and atlas, "
                "and under newt and caesar with one key a command on one shard)"
            )
        if not 0 <= site < self.num_replicas:
            raise ValueError(
                f"clients at site {site}: the sites are the replicas, "
                f"0 to {self.num_replicas - 1}"
            )
        if len(self._sites) == 1:
            self._make_site_programs()
        self._sites.add(site)

    def _state_shapes(self):
        """The state's shapes and places, not the state: what a program
        is lowered on while a round may hold the state (beside the step's
        thread)."""
        import jax

        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=leaf.sharding
            ),
            self._state,
        )

    def _column_specs(self):
        """What ``_assemble`` stages for one round, and so what the
        round function takes after the state: (name, shape, dtype, fill)
        a column.  The one place that says so: the staging ring lays a
        slot's one buffer out by it and the round's program unpacks that
        buffer by it (``pipeline.packed_columns``; the dtype is the
        round's, the buffer is ``int32`` and a ``bool`` column 0/1 in
        it).  Here the key/src/seq columns of the rounds that order by
        key (dep-commit, Newt, Caesar); the leader round has its own."""
        from fantoch_tpu.parallel.mesh_step import KEY_PAD

        b = self.batch_size
        return (
            ("key", (b, self.key_width), np.int32, KEY_PAD),
            ("src", (b,), np.int32, 0),
            ("seq", (b,), np.int32, 0),
        )

    def _assemble(self, batch: List[Tuple[Dot, Command]]):
        """The dot-keyed drivers' assembly (Newt/Caesar): fill the
        fixed-size key/src/seq columns and register commands under
        packed (source, window sequence)."""
        assert len(batch) <= self.batch_size
        staged = self._staging(*self._column_specs())
        self._assemble_round(batch, *staged)
        return staged

    # whether a chain of S rounds is one dispatch of a program of its own
    # (Newt's ``lax.scan`` of S rounds) or S dispatches of the round's
    fuses_chains = False

    def _jit_rounds(self, S: int):
        """The jitted program of ``S`` rounds a dispatch: here the
        round's, the only one."""
        assert S == 1
        return self._step

    def _program(self, S: int = 1):
        """The program of ``S`` rounds a dispatch, where it takes its
        packed columns and how its packed output reads back: compiled,
        or loaded, the first time that length is asked
        for (``_precompile``) and kept.  A server asks for every length
        it may dispatch before its first client (``precompile_chains``),
        so its dispatches compile nothing; a driver stepped without that
        start-up reaches the same executable at its first dispatch."""
        ready = self._programs.get(S)
        if ready is None:
            ready = self._programs[S] = self._precompile(self._jit_rounds(S), S)
        return ready

    def _precompile(self, jitted, S: int = 1, state=None):
        """The packed program of the jitted round function ``jitted``
        (``_lowered``), compiled, or loaded, through the persistent
        compile cache (the jit's own cache is not touched), under one
        ``precompile`` span.  ``state``: what stands for the state where
        a round may hold the real one (a serving driver)."""
        with self.stages.span("precompile", S):
            lowered, layout = self._lowered(jitted, S, state)
            return self._compiled(lowered, layout)

    @staticmethod
    def _compiled(lowered, layout):
        """A ``_programs`` entry of a lowered packed program."""
        program = lowered.compile()
        return program, program.input_shardings[0][1], layout

    # the fields of the round's output tuple no drain reads: they stay
    # device leaves of the token (``_RoundOutput.rest``)
    _unfetched_outputs: Tuple[str, ...] = ()

    def _lowered(self, jitted, S: int = 1, state=None):
        """The round function under ``jitted`` (a ``mesh_step.jit_*``
        form: ``(state, *columns) -> (state, out)``) as the program a
        dispatch runs, ``(state, packed) -> (state, packed_out, rest)``
        (``pipeline.packed_round``, in one ``jax.jit`` that donates the
        state), traced and lowered on the state's shapes and the packed
        columns' (``_column_specs`` as one ``int32`` array, under a
        leading ``S`` for a program of several rounds, split along the
        batch axis as the columns are), not compiled yet; and how its
        packed output reads back."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from fantoch_tpu.parallel.mesh_step import BATCH_AXIS

        specs = self._column_specs()
        lead = () if S == 1 else (S,)
        program, layout = packed_round(
            jitted.__wrapped__, specs, self._unfetched_outputs,
            out_sharding=NamedSharding(self._mesh, PartitionSpec()),
        )
        packed = jax.ShapeDtypeStruct(
            lead + packed_shape(specs), np.int32,
            sharding=NamedSharding(
                self._mesh, PartitionSpec(*(None,) * (len(lead) + 1), BATCH_AXIS)
            ),
        )
        lowered = jax.jit(program, donate_argnums=(0,)).lower(
            self._state if state is None else state, packed
        )
        return lowered, layout

    def precompile_chains(self, lengths: Sequence[int]) -> List[int]:
        """Make the program behind every chain length in ``lengths``
        ready before serving: its own where the driver fuses a chain into
        one dispatch, the round's where a chain is S plain rounds (every
        length is then ready once the round is).  One ``precompile`` span
        a program.  Returns the lengths now ready, in order; it stops at
        the first that cannot be made ready (the longer ones need more of
        whatever it lacked), and the caller keeps its tuner off the rest.
        A round that cannot be compiled raises here, at start-up: nothing
        could be served without it."""
        ready: List[int] = []
        for length in lengths:
            S = length if self.fuses_chains else 1
            try:
                self._program(S)
            except Exception as exc:  # the compiler's own errors are many
                if S == 1:
                    raise
                logger.warning(
                    "chain length %d cannot be made ready (%r): serving "
                    "with chains of at most %d", S, exc, max(ready, default=1),
                )
                break
            ready.append(length)
        return ready

    @property
    def precompiled_programs(self) -> int:
        """Programs made ready (gauge)."""
        return len(self._programs) + len(self._one_site_programs)

    def _columns_to_device(self, staged: StagedColumns, sharding):
        """The assembled columns of a dispatch, handed to jax as the one
        buffer they are views of: one array, straight to where its
        program takes it."""
        import jax

        self.transfers += 1
        return jax.device_put(staged.packed, sharding)

    def _enqueue(self, staged: StagedColumns, S: int = 1) -> _RoundOutput:
        """Submit one dispatch of ``S`` rounds over the assembled
        columns; returns its outputs, un-fetched."""
        program, sharding, layout = self._program(S)
        self._state, packed_out, rest = program(
            self._state, self._columns_to_device(staged, sharding)
        )
        # the copy back follows the program on the device without the
        # host: a round left in flight has its bytes on the host by the
        # time its deferred fetch asks for them, and an immediate fetch
        # finds the copy it would have started itself
        packed_out.copy_to_host_async()
        self.rounds += S
        return _RoundOutput(packed_out, rest, layout)

    def _fetch(self, out: _RoundOutput):
        """One leaf comes down, the packed array, and the round's own
        output tuple is rebuilt from it as numpy views (None where a
        field stayed on the device)."""
        return out.layout.unpack(super()._fetch(out.packed))

    def _assemble_round(self, batch, key_rows, src_row, seq_row) -> None:
        """Fill one round's fixed-size key/src/seq columns in place and
        register its commands under their packed (source, window
        sequence); with a coordinator at every site the batch's sites
        take turns (``_sites_in_turn``)."""
        if len(self._sites) > 1:
            batch = _sites_in_turn(batch)
        _key_column(batch, key_rows, self.shard_id, self.key_buckets, self.shard_count)
        self._identity_columns(batch, src_row, seq_row)

    def _identity_columns(
        self, batch, src_row, seq_row, read_row=None, valid_row=None,
        first_gid: Optional[int] = None,
    ) -> None:
        """One round's identity columns and its registry entries, a
        column at a time, for every driver.  ``src_row`` and ``seq_row``
        take the dots' sources and window sequences (``sequence -
        _seq_base``; the window is advanced first where the batch's top
        sequence asks for it, ``_ensure_seq_window``); where the driver
        stages them, ``read_row`` takes which commands only read and
        ``valid_row`` which rows hold a command.  Each is one conversion,
        and the rows past the batch keep their fill.  The registry takes
        the batch's own entries in one update: under the gids from
        ``first_gid`` on where the driver keys by gid, under the packed
        (source, window sequence) where it keys by dot."""
        n = len(batch)
        if not n:
            return
        dots = list(map(_DOT, batch))
        sequences = np.fromiter(map(_SEQUENCE, dots), np.int64, n)
        self._ensure_seq_window(batch, int(sequences.max()))
        sequences -= self._seq_base
        assert sequences.min() >= 0, (
            f"dot sequence {int(sequences.min()) + self._seq_base} outside "
            f"the device window (base {self._seq_base})"
        )
        src_row[:n] = np.fromiter(map(_SOURCE, dots), np.int32, n)
        seq_row[:n] = sequences
        if read_row is not None:
            read_row[:n] = np.fromiter(map(_READ_ONLY, map(_CMD, batch)), np.bool_, n)
        if valid_row is not None:
            valid_row[:n] = True
        if first_gid is None:
            keys = self._packed_column(src_row, seq_row, slice(n))
        else:
            keys = range(first_gid, first_gid + n)
        self._cmds.update(zip(keys, batch))

    @staticmethod
    def _packed_column(work_src, work_seq, rows) -> List[int]:
        """``_packed`` of the working rows ``rows``, made once as a
        column (the identity columns are non-negative ``int32``; a pad
        row's key is registered by no one either way)."""
        return (
            (work_src[rows].astype(np.int64) << 32) | work_seq[rows]
        ).tolist()

    def _execute_ordered(
        self, order, executed, work_src, work_seq
    ) -> List[ExecutorResult]:
        """Pop and execute the round's executed rows in device order
        (shared by every dot-keyed drain; pad rows are registered by no
        one and skip).  Only the executed rows are visited: the mask
        picks them out of the working set, device order kept."""
        live = order[executed[order]]
        return self._execute_rows(self._packed_column(work_src, work_seq, live))

    def _execute_rows(self, keys: List[int], fast=None) -> List[ExecutorResult]:
        """Pop a round's executed rows from the registry as a column
        (``keys``: their registry keys, device order kept; a row
        registered by no one is padding and drops out) and apply their
        commands to the KVStore in that order: in the store's one pass
        (``KVStore.execute_commands``) while what the pass spells out is
        what ``_execute_entry`` would do, a command at a time through
        ``_execute_entry`` otherwise.  The tallies move once a round;
        ``fast`` (the dependency rounds') marks the rows that took the
        fast path, counted among those the registry held."""
        entries = list(map(self._cmds.pop, keys, repeat(None)))
        cmds = [entry[1] for entry in entries if entry is not None]
        self.drain_rows_walked += len(keys)
        self.executed += len(cmds)
        if fast is not None:
            if len(cmds) == len(keys):
                self.fast_paths += int(np.count_nonzero(fast))
            else:
                self.fast_paths += sum(
                    1
                    for entry, is_fast in zip(entries, fast.tolist())
                    if is_fast and entry is not None
                )
        store = self.store
        if (
            getattr(self._execute_entry, "__func__", None) is _EXECUTE_ENTRY
            and store.plain
        ):
            return store.execute_commands(
                cmds, self.shard_id if self.shard_count == 1 else None
            )
        results: List[ExecutorResult] = []
        for cmd in cmds:
            results.extend(self._execute_entry(cmd))
        return results

    def _registered_rows(self, rows, work_src, work_seq) -> List[int]:
        """Of the working rows ``rows`` (an overflow's candidates, in
        working order), those the registry still holds."""
        self.drain_rows_walked += len(rows)
        return [
            w
            for w, packed in zip(
                rows.tolist(), self._packed_column(work_src, work_seq, rows)
            )
            if packed in self._cmds
        ]

    def _requeue_rows(self, rows, work_src, work_seq, label: str) -> None:
        """Re-queue overflow-dropped working rows under their original
        dots (shared drain tail)."""
        requeued = 0
        for w in rows:
            entry = self._cmds.pop(
                self._packed(work_src[w], work_seq[w]), None
            )
            if entry is not None:
                requeued += 1
                self._requeue.append(entry)
        self.requeued += requeued
        if requeued:
            logger.warning(
                "%s device pending overflow: re-queueing %d commands",
                label, requeued,
            )

    def _execute_entry(self, cmd: Command) -> List[ExecutorResult]:
        """Execute one ordered command against the KVStore.  Sharded mode:
        the unified mesh owns every shard's keyspace, so each touched
        shard's portion executes at the command's single execution point
        (the partials the per-shard executors would emit)."""
        if self.shard_count == 1:
            return cmd.execute(self.shard_id, self.store)
        results: List[ExecutorResult] = []
        for sid in cmd.shards():
            results.extend(cmd.execute(sid, self.store))
        return results

    def take_requeue(self) -> List[Tuple[Dot, Command]]:
        """Commands dropped by a device pending-buffer overflow, to be fed
        into the next batch by the caller."""
        out, self._requeue = self._requeue, []
        return out

    def give_back(self, pending: List[Tuple[Dot, Command]]) -> None:
        """What the caller took (``take_requeue``) and had no round for,
        and the rounds its truncated chain handed back, in their order:
        they lead the requeue again, ahead of whatever an overflow put
        there meanwhile."""
        self._requeue[:0] = pending

    @property
    def has_requeue(self) -> bool:
        """Overflow-requeued commands are waiting (the serving loop's
        ingest gate never holds these — they were admitted a round ago)."""
        return bool(self._requeue)

    @staticmethod
    def _packed(src, seq) -> int:
        """Registry key for dot-identified commands (device-window seq)."""
        return (int(src) << 32) | int(seq)

    # --- the 31-bit dot-sequence window ---

    def _ensure_seq_window(
        self, batch: List[Tuple[Dot, Command]], top_sequence: int
    ) -> None:
        """Advance the sequence window if this batch, whose highest dot
        sequence is ``top_sequence``, would overflow it.

        The new base is the oldest sequence still relevant to the device:
        min over in-flight registry dots, requeued dots, and the incoming
        batch.  Live device comparisons all involve rows at or above it,
        so the uniform shift is order-preserving; the driver-specific
        ``_shift_seq_state`` rebases device-resident and mirrored
        sequence columns."""
        top = top_sequence - self._seq_base
        if top < self.SEQ_WINDOW_MAX:
            return
        # the rebase rewrites device-resident sequence columns an
        # in-flight round still references; _pipeline_flush_needed
        # shares the trigger, so pipelined paths flushed already
        assert self._undrained == 0, (
            "dot-sequence window advance with a pipelined round in flight"
        )
        live = [dot.sequence for dot, _ in batch]
        live += [dot.sequence for dot, _ in self._cmds.values()]
        live += [dot.sequence for dot, _ in self._requeue]
        floor = min(live)
        shift = floor - self._seq_base
        new_top = top - shift
        if shift <= 0 or new_top >= 2**31 - 1:
            # a long-pinned in-flight dot keeps the window span >= 2^31:
            # no rebase can fit it — fail loudly (asserts vanish under -O)
            raise RuntimeError(
                "dot-sequence window cannot advance: oldest in-flight "
                f"sequence {floor} leaves a span of {new_top} >= 2^31"
            )
        self._seq_base = floor
        self.seq_epochs += 1
        self._on_seq_window_advanced(shift)
        logger.info(
            "advanced dot-sequence window to base %d (epoch %d)",
            floor, self.seq_epochs,
        )

    def _on_seq_window_advanced(self, shift: int) -> None:
        """Rebase driver-held sequence state after a window advance: the
        dot-keyed registry and the device-resident pend_seq column — the
        dot-keyed drivers' shape.  (Dead device slots are masked by
        their key/slot columns and match no registry key, so the blind
        shift is safe.)  DeviceDriver overrides: its registry keys on
        gids and its device pend is masked by pend_gid."""
        import jax
        import jax.numpy as jnp

        self._rekey_registry_for_window()
        st = self._state
        pend_seq = np.asarray(st.pend_seq, dtype=np.int64) - shift
        # rebuilt state fields use jnp.array (an XLA-owned COPY), never
        # jnp.asarray: asarray zero-copy aliases the numpy buffer on the
        # CPU backend, and the step functions donate this state — donating
        # an alias hands numpy-owned memory to XLA (use-after-free).
        # Same rule at every _replace() rebase below.
        self._state = st._replace(
            pend_seq=jax.device_put(
                jnp.array(pend_seq.astype(np.int32)), st.pend_seq.sharding
            )
        )

    def _drain_and_carry(
        self, out, label: str, committed_noun: str
    ) -> List[ExecutorResult]:
        """The dot-keyed drivers' shared tail (Newt/Caesar): execute the
        round's executed rows in device order against the KVStore, using
        the step's own ``work_src``/``work_seq`` identity columns — the
        device pending buffer carries its identity, so no host mirror
        exists to drift (and a dispatched round can be drained later:
        dispatch/drain pipelining).  Committed overflow cannot be
        re-proposed (its timestamp already entered the replicas' tables)
        and fails loudly; uncommitted overflow re-queues under the
        original dot."""
        order = np.asarray(out.order)
        executed = np.asarray(out.executed)
        committed = np.asarray(out.committed)
        work_src = np.asarray(out.work_src)
        work_seq = np.asarray(out.work_seq)
        results = self._execute_ordered(order, executed, work_src, work_seq)

        # the device counts its valid unexecuted rows and says how many
        # fell beyond its pending capacity; the registered unexecuted
        # rows are among those it counted, so with none dropped there is
        # nothing to look for
        if int(out.pend_dropped) > 0:
            # a carried row is one the round did not execute and the
            # registry still holds; committed first in working order
            # (both device carries sort committed rows ahead — carry_rank
            # in the mesh steps); rows beyond the device pending capacity
            # were dropped there
            carried = self._registered_rows(
                np.flatnonzero(~executed), work_src, work_seq
            )
            carried.sort(key=lambda w: (not committed[w], w))
            dropped = carried[self._pend_cap:]
            if any(committed[w] for w in dropped):
                raise RuntimeError(
                    f"{label} device pending buffer overflowed with "
                    f"committed-but-{committed_noun} commands: raise "
                    "pending_capacity (a committed timestamp cannot be "
                    "re-proposed)"
                )
            self._requeue_rows(dropped, work_src, work_seq, label)
        return results

    def _rekey_registry_for_window(self) -> None:
        """Shared helper for dot-keyed registries (Newt/Paxos): recompute
        packed keys under the new seq_base."""
        self._cmds = {
            self._packed(dot.source, dot.sequence - self._seq_base): entry
            for entry in self._cmds.values()
            for dot in (entry[0],)
        }


# the method ``_execute_rows`` takes the store's pass in place of, by name
_EXECUTE_ENTRY = _DriverCore._execute_entry


class DeviceDriver(_DriverCore):
    """Host control loop around the donated-state device protocol step.

    One ``step()`` call = one full commit+execute round for every replica
    at once.  The driver owns:

      * the device-resident ``ReplicaState`` (donated each step — the
        arrays never round-trip to the host),
      * the gid -> Command registry for commands in flight (committed rows
        execute in device order; quorum-degraded rows carry in the device
        pending buffer and stay registered),
      * the host KVStore + execution of ordered commands (the state
        machine is control-plane: string keys, tiny values — it stays on
        the host by design, fantoch/src/kvs.rs).

    Key hashing: string keys map to ``key_buckets`` conflict buckets
    (``_bucket``: a C hash of the key's bytes, the server's own and not
    the shard rule's; a round's key column is made from its commands'
    ops in one pass, ``_key_column``, and nothing is remembered between
    rounds).  Bucket collisions create *false* dependencies — extra
    ordering, never missed ordering — so correctness is preserved and
    only parallelism is lost (same argument as the reference's
    worker-partitioned KeyDeps, which also orders by hash partition).
    """

    def __init__(
        self,
        num_replicas: int,
        *,
        batch_size: int = 256,
        key_buckets: int = 4096,
        key_width: int = 1,
        pending_capacity: int = 256,
        live_replicas: Optional[int] = None,
        shard_id: ShardId = 0,
        shard_count: int = 1,
        monitor_execution_order: bool = False,
        mesh=None,
        f: int = 1,
        rule: str = "epaxos",
        site_base: ProcessId = 1,
    ):
        from fantoch_tpu.parallel import mesh_step

        self._init_core(shard_id, batch_size, key_buckets, monitor_execution_order)
        self.key_width = key_width
        self._init_sharded_mesh(
            mesh_step, num_replicas, shard_count, key_buckets,
            pending_capacity, key_width, mesh, mesh_step.init_state,
        )
        # the quorums and the fast-path test of the round: EPaxos's, or
        # Atlas's with its f (mesh_step.quorum_sizes)
        self.rule, self.f = rule, f
        self.fast_quorum, self.write_quorum = mesh_step.quorum_sizes(
            num_replicas, f, rule
        )
        self._step = mesh_step.jit_protocol_step(
            self._mesh, live_replicas=live_replicas, shard_count=shard_count,
            f=f, rule=rule,
        )
        self.resolver = mesh_step.resolver_name(key_width)
        # the round's tallies over the rows it executed, summed
        # (mesh_step.StepOutput): dependency slots committed, key slots
        # with a command before them on the bucket and those of them
        # where both are reads, reads, commands on more than one shard;
        # then what the round with a coordinator at every site adds
        # (SITE_ROUND_TALLIES; 0 while the round with one serves)
        self.round_tallies = dict.fromkeys(mesh_step.SITE_ROUND_TALLIES, 0)
        # a coordinator at every site: the sites clients registered at
        # (``register_site``; a command's coordinator is its dot's
        # source, ``site_base + site``), the round's second program once a
        # second site made it ready (dispatches run it from then on); the
        # largest component of the last such round (gauge)
        self.num_replicas = num_replicas
        self.site_base = site_base
        self._live_replicas = live_replicas
        self._site_program: Optional[Tuple[Any, Any, PackedOutput]] = None
        self.round_gauges = dict.fromkeys(mesh_step.SITE_ROUND_GAUGES, 0)
        self._next_gid = 0  # host mirror of state.next_gid
        self._frontier_base = 0  # executed-count carried across gid epochs
        self.gid_epochs = 0

    # --- a coordinator at every site ---

    # the round can have a coordinator at every site under either rule, at
    # any ``f`` the quorum formula admits, any key width and shard count
    serves_sites = True

    def _make_site_programs(self) -> None:
        """The round's second program (``protocol_step(sites=n)``)."""
        from fantoch_tpu.parallel import mesh_step

        self._site_program = self._precompile(
            mesh_step.jit_protocol_step(
                self._mesh, live_replicas=self._live_replicas,
                shard_count=self.shard_count, f=self.f, rule=self.rule,
                sites=self.num_replicas, site_base=self.site_base,
            ),
            state=self._state_shapes(),
        )
        self.resolver = mesh_step.resolver_name(
            self.key_width, sites=self.num_replicas
        )

    def _program(self, S: int = 1):
        return self._site_program or super()._program(S)

    @property
    def precompiled_programs(self) -> int:
        return len(self._programs) + (self._site_program is not None)

    # --- the serving round ---

    def _column_specs(self):
        """The dep-commit round's columns: the key/src/seq columns and
        which commands only read (staged as 0/1)."""
        return super()._column_specs() + (("read", (self.batch_size,), np.bool_, False),)

    # gid space is int32 and the key clock holds raw gids; when the space
    # nears exhaustion the epoch resets — rebase clock/frontier/pending
    # against the oldest in-flight gid instead of dying by assert
    # (the ClockWindow design of ops/table_ops.py applied to gids; the
    # reference's GC keeps dot state bounded forever the same way,
    # fantoch/src/protocol/gc.rs:72-116)
    GID_RESET_THRESHOLD = 2**31 - (1 << 20)

    def _gid_epoch_reset(self) -> None:
        import jax
        import jax.numpy as jnp

        st = self._state
        # after a step, registry keys == the gids still carried on-device
        delta = min(self._cmds.keys(), default=self._next_gid)
        if delta <= 0:
            raise RuntimeError(
                "gid epoch reset ineffective: a command from gid 0 is "
                "still in flight"
            )

        def rebased(clock):
            # entries older than the oldest live gid clamp to -1 ("no live
            # predecessor") — exactly their meaning to dep pruning, which
            # treats out-of-working-set deps as already executed
            gids = np.asarray(clock, dtype=np.int64)
            gids = np.where(gids >= delta, gids - delta, -1)
            return jax.device_put(jnp.array(gids.astype(np.int32)), clock.sharding)

        pend_gid = np.asarray(st.pend_gid, dtype=np.int64)
        pend_gid = np.where(pend_gid >= 0, pend_gid - delta, -1)
        frontier = np.asarray(st.frontier, dtype=np.int64)
        fmin = int(frontier.min())
        self._frontier_base += fmin
        self._state = st._replace(
            key_clock=rebased(st.key_clock),
            read_clock=rebased(st.read_clock),
            frontier=jax.device_put(
                jnp.array((frontier - fmin).astype(np.int32)),
                st.frontier.sharding,
            ),
            next_gid=jax.device_put(
                jnp.int32(self._next_gid - delta), st.next_gid.sharding
            ),
            pend_gid=jax.device_put(
                jnp.array(pend_gid.astype(np.int32)), st.pend_gid.sharding
            ),
        )
        self._next_gid -= delta
        self._cmds = {g - delta: v for g, v in self._cmds.items()}
        self.gid_epochs += 1
        logger.info(
            "gid epoch reset: rebased by %d (epoch %d, next_gid %d)",
            delta, self.gid_epochs, self._next_gid,
        )

    def _on_seq_window_advanced(self, shift: int) -> None:
        import jax
        import jax.numpy as jnp

        # registry keys are gids — only the device pend_seq column carries
        # window sequences (dead slots are masked by pend_gid on-device)
        st = self._state
        pend_seq = np.asarray(st.pend_seq, dtype=np.int64) - shift
        pend_gid = np.asarray(st.pend_gid)
        pend_seq = np.where(pend_gid >= 0, pend_seq, -1)
        self._state = st._replace(
            pend_seq=jax.device_put(
                jnp.array(pend_seq.astype(np.int32)), st.pend_seq.sharding
            )
        )

    # serve/step/flush_pipeline come from _DriverCore; one device round
    # covers up to ``batch_size`` new commands (the rest of the fixed
    # batch is padding; excess raises) and returns the per-key results of
    # every command *executed* that round — including commands carried
    # from previous degraded rounds.  Under overlap, the device round
    # overlaps the host's result-emit loop (what the overlap buys on the
    # chip: not measured).

    def _pipeline_flush_needed(self, batch) -> bool:
        # a gid epoch reset rebases the registry and frontier base,
        # which drain reads — retire the in-flight round first (rare:
        # once per 2^31 gids)
        return (
            self._next_gid + self.batch_size >= self.GID_RESET_THRESHOLD
            or super()._pipeline_flush_needed(batch)
        )

    def _assemble(self, batch: List[Tuple[Dot, Command]]):
        """One round's key/src/seq/read columns, each command registered
        under the gid its row will get, and the batch length: the round
        gives every row of the fixed batch a gid and resolves the padding
        too, so the drain needs it to leave the padding out."""
        assert len(batch) <= self.batch_size, (
            f"batch {len(batch)} exceeds the compiled batch size "
            f"{self.batch_size}; chunk at the caller"
        )
        b = self.batch_size
        staged = self._staging(*self._column_specs())
        key, src, seq, read = staged
        if self._next_gid + b >= self.GID_RESET_THRESHOLD:
            assert self._undrained == 0, (
                "gid epoch reset with a pipelined round in flight; "
                "flush_pipeline first"
            )
            self._gid_epoch_reset()
            if self._next_gid + b >= 2**31 - 1:
                raise RuntimeError(
                    "gid space exhausted: a long-stuck in-flight command "
                    "pins the epoch (oldest live gid too old to rebase)"
                )
        if self._site_program is not None:
            batch = _sites_in_turn(batch)
        _key_column(batch, key, self.shard_id, self.key_buckets, self.shard_count)
        self._identity_columns(
            batch, src, seq, read_row=read, first_gid=self._next_gid
        )
        return staged, len(batch)

    def _enqueue(self, staged):
        columns, n_batch = staged
        out = super()._enqueue(columns)
        self._next_gid += self.batch_size
        return out, n_batch

    # no drain reads the carry's count, and the committed dependencies
    # only ``_finish_order``, in the rounds that have ``finish`` rows
    _unfetched_outputs = ("deps_gid", "pending")

    def _token_outputs(self, tok):
        return tok[0]

    def _execute(self, tok, out) -> List[ExecutorResult]:
        """Execute one fetched round's resolved commands in device
        order against the KVStore."""
        order = np.asarray(out.order)
        resolved = np.asarray(out.resolved)
        gids = np.asarray(out.gids)
        fast = np.asarray(out.fast_path)
        self.stable_watermark = self._frontier_base + int(out.stable)

        # only the rows the round resolved are visited, device order
        # kept; the working set is the pending buffer, then the batch,
        # whose rows past the batch's length are padding
        pend_cap = len(gids) - self.batch_size
        ours = (gids >= 0) & resolved
        ours[pend_cap + tok[1]:] = False
        # valid new rows that missed the fast path took the Synod round
        self.slow_paths += int(out.slow_paths)
        tallies = self.round_tallies
        counts = out.tallies.tolist()
        for name, count in zip(tallies, counts):
            tallies[name] += count
        # the round with a coordinator at every site: its gauge stands
        # last (kept from the last round that had a component of several
        # rows: a round of padding between two full ones has none), and
        # the rows of a key's run its resolver did not cut execute where
        # the host's Tarjan puts them (keys apart: after the rest is as
        # good as between)
        finish = getattr(out, "finish", None)
        if finish is not None:
            if counts[-1]:
                self.round_gauges["scc_rows_max"] = counts[-1]
            finish = ours & np.asarray(finish)
            ours &= ~finish
        live = order[ours[order]]
        if finish is not None and finish.any():
            live = np.concatenate(
                [live, self._finish_order(tok[0].rest, gids, finish, counts[-1])]
            )
        results = self._execute_rows(gids[live].tolist(), fast[live])

        # device pending overflow: rows beyond the pending capacity were
        # dropped by the device (loudly — out.pend_dropped).  Re-register
        # them for the next round under fresh gids: they never executed
        # and never entered any key clock, so resubmission is safe.
        if int(out.pend_dropped) > 0:
            # working order == device carry order
            carried = gids[(gids >= 0) & ~resolved].tolist()
            self.drain_rows_walked += len(carried)
            dropped = carried[pend_cap:]
            logger.warning(
                "device pending buffer overflowed: re-queueing %d commands",
                len(dropped),
            )
            for gid in dropped:
                entry = self._cmds.pop(gid, None)
                if entry is not None:
                    self.requeued += 1
                    self._requeue.append(entry)
        return results

    def _finish_order(self, out, gids, finish, largest: int) -> np.ndarray:
        """The working rows ``finish`` marks, in the order the host's
        Tarjan gives them (``executor/graph/deps_graph.tarjan_order``:
        components in dependency order, each in dot order), under a
        ``finish`` span; their committed dependencies (``out``: the
        round's un-fetched leaves) are fetched here, one transfer more,
        in these rounds alone.  What it finds of components joins the
        round's tallies (the device counted the rows it handed over),
        ``largest`` being the device's own largest of this round."""
        from fantoch_tpu.executor.graph.deps_graph import tarjan_order

        with self.stages.span("finish", self._span_round):
            rows = np.flatnonzero(finish)
            row_of = {gid: at for at, gid in enumerate(gids[rows].tolist())}
            # the oracle's processes are 1 .. n: a dot by its site
            dots = []
            for gid in row_of:
                dot = self._cmds[gid][0]
                site = (dot.source - self.site_base) % self.num_replicas
                dots.append(Dot(1 + site, dot.sequence))
            # (a dependency outside the rows executed before them)
            self.transfers += 1
            deps = [
                sorted({row_of[dep] for dep in row if dep in row_of})
                for row in np.asarray(out.deps_gid)[rows].tolist()
            ]
            order, sizes = tarjan_order(dots, deps, self.num_replicas)
            several = [size for size in sizes if size > 1]
            self.round_tallies["scc_rows"] += sum(several)
            self.round_tallies["scc_count"] += len(several)
            if several:  # beside the device's own of this round
                self.round_gauges["scc_rows_max"] = max([largest] + several)
        return rows[order]


class NewtDeviceDriver(_DriverCore):
    """Host control loop around the device-resident Newt timestamp round
    (parallel/mesh_step.newt_protocol_step): proposals, pmax commit
    clocks, count-of-max fast path and order-statistic stability all run
    as one device program; the host executes stable commands in
    (clock, dot) order against the KVStore.

    Commands carry up to ``key_width`` key buckets (a command executes
    once its clock is stable on every key it touches).  Commands are
    identified by their dot (timestamp ordering needs no gid), so the
    registry keys on packed (source, sequence).
    """

    def __init__(
        self,
        num_replicas: int,
        *,
        f: int = 1,
        tiny_quorums: bool = False,
        batch_size: int = 256,
        key_buckets: int = 4096,
        key_width: int = 1,
        pending_capacity: int = 256,
        live_replicas: Optional[int] = None,
        shard_id: ShardId = 0,
        shard_count: int = 1,
        monitor_execution_order: bool = False,
        mesh=None,
        site_base: ProcessId = 1,
    ):
        from fantoch_tpu.parallel import mesh_step

        self._init_core(shard_id, batch_size, key_buckets, monitor_execution_order)
        self.key_width = key_width
        self._init_sharded_mesh(
            mesh_step, num_replicas, shard_count, key_buckets,
            pending_capacity, key_width, mesh, mesh_step.init_newt_state,
        )
        # a coordinator at every site: the sites clients registered at
        # (``register_site``; a command's coordinator is its dot's source,
        # ``site_base + site``), the chain lengths a server's tuner may
        # dispatch (``precompile_chains``), the programs with one
        # coordinator once a second site put theirs in their place, and
        # what the round with a coordinator at every site tallies
        # (mesh_step.NEWT_SITE_ROUND_TALLIES; 0 while one coordinator serves)
        self.num_replicas = num_replicas
        self.site_base = site_base
        self._chain_lengths: List[int] = [1]
        self.round_tallies = dict.fromkeys(mesh_step.NEWT_SITE_ROUND_TALLIES, 0)
        self._step = mesh_step.jit_newt_step(
            self._mesh, f=f, tiny_quorums=tiny_quorums,
            live_replicas=live_replicas, shard_count=shard_count,
        )
        # what the program of a chain is built with (``_jit_rounds``)
        self._step_kwargs = dict(
            f=f, tiny_quorums=tiny_quorums,
            live_replicas=live_replicas, shard_count=shard_count,
        )
        # no host identity mirror: the step outputs carry the working
        # rows' (src, seq) columns (NewtStepOutput.work_src/work_seq)
        self._pend_cap = pending_capacity
        self._clock_floor = 0  # timestamps GC'd below this (host int)
        self._max_clock = 0  # highest committed device clock seen
        self.clock_epochs = 0

    # timestamp clocks are int32 and grow ~1 per conflicting command per
    # bucket; when the stable watermark nears the cap, advance the clock
    # window (ops/table_ops.ClockWindow semantics: every live comparison
    # happens above the GC'd stable floor, so the uniform shift is
    # order-preserving; below-floor entries clamp to the bottom)
    CLOCK_RESET_THRESHOLD = 2**31 - (1 << 22)

    def _advance_clock_window(self, floor: int) -> None:
        import jax
        import jax.numpy as jnp

        from fantoch_tpu.ops.table_ops import shift_table

        st = self._state
        pend_clock = np.asarray(st.pend_clock, dtype=np.int64)
        live = pend_clock >= 0
        # committed-but-unstable clocks sit strictly above the stable
        # floor (stable would have executed them), so none clamp
        assert (pend_clock[live] > floor).all(), (
            "carried committed clock at/below the stable floor"
        )
        pend_clock = np.where(live, pend_clock - floor, -1)
        # (the shifted tables go back where the old ones lived: a
        # precompiled program takes its state at that sharding only)
        self._state = st._replace(
            key_clock=jax.device_put(
                shift_table(st.key_clock, floor), st.key_clock.sharding
            ),
            vote_frontier=jax.device_put(
                shift_table(st.vote_frontier, floor), st.vote_frontier.sharding
            ),
            pend_clock=jax.device_put(
                jnp.array(pend_clock.astype(np.int32)),
                st.pend_clock.sharding,
            ),
        )
        self._clock_floor += floor
        self.clock_epochs += 1
        logger.info(
            "advanced newt clock window by %d (epoch %d)",
            floor, self.clock_epochs,
        )

    def _pipeline_flush_needed(self, batch) -> bool:
        # drain may advance the clock window only with nothing in
        # flight (an in-flight round's clocks are in pre-shift units);
        # per-bucket clocks grow by at most the working-set size per
        # round, so a margin of one working set per in-flight round
        # (chains count their S rounds) plus the upcoming one guarantees
        # every drain stays under the threshold while rounds are
        # outstanding
        work = self._pend_cap + self.batch_size
        margin = (self._undrained_rounds + 1) * work
        return (
            self._max_clock + margin >= self.CLOCK_RESET_THRESHOLD
            or super()._pipeline_flush_needed(batch)
        )

    # a chain is one dispatch: length 1 is the round itself
    # (``jit_newt_step``), a longer one the ``lax.scan`` of that many
    # rounds (``jit_newt_multi_step``), a program a length
    fuses_chains = True

    def _jit_rounds(self, S: int):
        from fantoch_tpu.parallel import mesh_step

        if S == 1:
            return self._step
        return mesh_step.jit_newt_multi_step(self._mesh, **self._step_kwargs)

    # --- a coordinator at every site ---

    @property
    def serves_sites(self) -> bool:
        """Whether the round can have a coordinator at every site: with one
        key a command on one shard (``newt_protocol_step(sites=n)``; per-shard
        rings and a row's several runs are not written)."""
        return self.shard_count == 1 and self.key_width == 1

    def precompile_chains(self, lengths: Sequence[int]) -> List[int]:
        """``_DriverCore.precompile_chains``, and the lengths now ready are
        kept: what a second site's hello makes ready again."""
        self._chain_lengths = super().precompile_chains(lengths) or [1]
        return self._chain_lengths

    def _make_site_programs(self) -> None:
        """The round with a coordinator at every site, once for every
        chain length a dispatch may run (those ``precompile_chains`` kept;
        the round alone on a driver stepped by hand): each lowered here and
        compiled, or loaded, beside the others (the compiler leaves the
        interpreter lock, tracing does not: 14.8 s for four on an empty
        cache on a v5e where one after another they took 24.5 s, against a
        client's 30 s wait for its ack; PR 53's chip run), a ``precompile``
        span for its lowering and one for the wait.  A length asked for
        later is built as these were (``_jit_rounds``)."""
        from fantoch_tpu.parallel import mesh_step

        self._step_kwargs = dict(
            self._step_kwargs, sites=self.num_replicas, site_base=self.site_base
        )
        self._step = mesh_step.jit_newt_step(self._mesh, **self._step_kwargs)
        state = self._state_shapes()
        ready = {}
        with ThreadPoolExecutor(len(self._chain_lengths)) as pool:
            compiling = []
            for S in self._chain_lengths:
                with self.stages.span("precompile", S):
                    lowered = self._lowered(self._jit_rounds(S), S, state)
                compiling.append((S, pool.submit(self._compiled, *lowered)))
            for S, compiled in compiling:
                with self.stages.span("precompile", S):
                    ready[S] = compiled.result()
        self._one_site_programs, self._programs = self._programs, ready

    def _chain_windows_blocked(
        self, batches: Sequence[List[Tuple[Dot, Command]]]
    ) -> bool:
        """True when a window rebase (clock or dot-sequence) could land
        mid-chain — inside one dispatch no rebase can happen, so such a
        chain is dispatched round by round (each rebases in its assembly
        or its drain as usual).  The clock margin counts every round
        still in flight plus this chain's S."""
        S = len(batches)
        work = self._pend_cap + self.batch_size
        tops = map(_top_sequence, filter(None, batches))
        top = max(tops, default=0) - self._seq_base
        return (
            self._max_clock + (self._undrained_rounds + S) * work
            >= self.CLOCK_RESET_THRESHOLD
            or top >= self.SEQ_WINDOW_MAX
        )

    def _dispatches(self, batches):
        """S rounds in ONE device dispatch: the host assembles all S
        rounds' key/src/seq columns up front, the replica state threads
        round-to-round on device via ``lax.scan``, and the chain pays a
        single dispatch round-trip — where the fixed per-dispatch cost
        dominates a round, per-round cost drops toward kernel time (the
        serving twin of the votes-table plane's ``fused_table_rounds``).
        Under overlap up to ``pipeline_depth`` such chains stay in
        flight.  A chain that a window rebase could land in goes round by
        round instead, and nothing is in flight across the rebase
        (``_pipeline_flush_needed``, asked for each of its rounds)."""
        if len(batches) > 1 and not self._chain_windows_blocked(batches):
            return [batches]
        return super()._dispatches(batches)

    def _dispatch_chain(self, chain):
        """A chain of one is the round itself, from the staging ring; a
        longer one is assembled whole and runs the program of its length
        (``_dispatches`` saw that no rebase can land in it)."""
        if len(chain) == 1:
            return super()._dispatch_chain(chain)
        return self._dispatch_halves(
            self._assemble_chain, partial(self._enqueue, S=len(chain)), chain
        )

    def _assemble_chain(self, batches: Sequence[List[Tuple[Dot, Command]]]):
        # chains allocate fresh staging (shape varies with S and chains
        # already amortize the dispatch; the ring serves the per-round
        # hot path): one buffer, the columns under a leading S
        staged = StagedColumns(self._column_specs(), lead=(len(batches),))
        keys, srcs, seqs = staged
        epochs = self.seq_epochs
        for r, batch in enumerate(batches):
            assert len(batch) <= self.batch_size
            self._assemble_round(batch, keys[r], srcs[r], seqs[r])
        assert self.seq_epochs == epochs, (
            "dot-sequence window advance inside a chain "
            "(_chain_windows_blocked must prevent this)"
        )
        return staged

    def _enqueue(self, columns, S: int = 1):
        """The token says how many rounds it carries."""
        return super()._enqueue(columns, S), S

    def _token_rounds(self, tok) -> int:
        return tok[1]

    def _token_outputs(self, tok):
        return tok[0]

    def _execute(self, tok, outs) -> List[ExecutorResult]:
        """Execute one fetched token's stable commands in (clock, dot)
        order: a single round, or a whole chain's rounds (ONE
        device->host transfer either way)."""
        S = tok[1]
        if S == 1:
            return self._drain_round(outs)
        results: List[ExecutorResult] = []
        for r in range(S):
            results.extend(
                self._drain_round(type(outs)(*(np.asarray(a)[r] for a in outs)))
            )
        return results

    def _drain_round(self, out) -> List[ExecutorResult]:
        """One (already fetched) round's drain: advance watermark /
        clock-window bookkeeping and execute its stable commands."""
        device_wm = int(out.stable_watermark)
        # overflow trigger = the MAX committed clock (a hot key's clock
        # races ahead while cold keys pin the min watermark); the rebase
        # floor is still the stable watermark — the only provably-safe
        # shift
        clocks = np.asarray(out.clock)
        if clocks.size:
            self._max_clock = max(self._max_clock, int(clocks.max()))
        # int_max = "no keys seen this round" sentinel: skip both the
        # report and the window check
        if device_wm < 2**31 - 1:
            self.stable_watermark = self._clock_floor + device_wm
            if self._max_clock >= self.CLOCK_RESET_THRESHOLD:
                assert self._undrained == 0, (
                    "clock-window advance with a pipelined round in "
                    "flight (_pipeline_flush_needed must prevent this)"
                )
                if device_wm > 0:
                    self._advance_clock_window(device_wm)
                    self._max_clock -= device_wm
                if self._max_clock >= self.CLOCK_RESET_THRESHOLD:
                    # wm pinned at 0 (stalled voters) or lagging by the
                    # whole window: no safe rebase exists — fail loudly
                    # before int32 wraps
                    raise RuntimeError(
                        "newt clock window pinned: the stable floor lags "
                        "the hot key's clock by >= the whole window "
                        "(raise pending_capacity or investigate stalled "
                        "voters)"
                    )
        self.slow_paths += int(out.slow_paths)
        # fast/slow tallies are commit-time facts: a fast-committed command
        # may only *stabilize* (execute) rounds later, when the flag is no
        # longer set — counting at execution would undercount
        self.fast_paths += int(np.asarray(out.fast_path).sum())
        # what the round with a coordinator at every site adds to the
        # round's output (mesh_step.NewtSiteStepOutput)
        counts = getattr(out, "tallies", None)
        if counts is not None:
            tallies = self.round_tallies
            for name, count in zip(tallies, counts.tolist()):
                tallies[name] += count

        return self._drain_and_carry(out, "newt", "unstable")


class CaesarDeviceDriver(_DriverCore):
    """Host control loop around the device-resident Caesar round
    (parallel/mesh_step.caesar_protocol_step): timestamp proposals over
    the clock index, 3n/4+1 fast-quorum agreement, the MRetry
    counter-proposal folded into the same step, and wait-condition-gated
    execution in (clock, dot) order against the KVStore — the fourth
    consensus shape on the device plane
    (fantoch_ps/src/protocol/caesar.rs:216-451; execution =
    fantoch_ps/src/executor/pred/mod.rs:132-186).

    Carry contract is the Newt driver's: commands key on packed
    (source, window sequence); working-row identity comes from the step
    outputs (no host mirror); committed overflow cannot be re-proposed
    (a committed timestamp is final) and fails loudly, uncommitted
    overflow re-queues under the original dot.
    """

    # int32 timestamp headroom guard: Caesar has no per-key vote
    # frontier to derive a provably-safe rebase floor from (the Newt
    # driver's stable watermark), so exhaustion fails loudly instead of
    # windowing — at one clock tick per conflicting command per bucket,
    # that is > 2^31 conflicts on one bucket
    CLOCK_GUARD = 2**31 - (1 << 22)

    def __init__(
        self,
        num_replicas: int,
        *,
        batch_size: int = 256,
        key_buckets: int = 4096,
        key_width: int = 1,
        pending_capacity: int = 256,
        live_replicas: Optional[int] = None,
        shard_id: ShardId = 0,
        monitor_execution_order: bool = False,
        mesh=None,
        site_base: ProcessId = 1,
    ):
        from fantoch_tpu.parallel import mesh_step

        self._init_core(shard_id, batch_size, key_buckets, monitor_execution_order)
        self.key_width = key_width
        # a coordinator at every site: a command's coordinator is its dot's
        # source, ``site_base + site``; the program with one coordinator
        # once a second site put the other in its place; what the round
        # with a coordinator at every site tallies and its gauge
        # (mesh_step.CAESAR_SITE_ROUND_TALLIES / _GAUGES; 0 while one
        # coordinator serves)
        self.num_replicas = num_replicas
        self.site_base = site_base
        self._live_replicas = live_replicas
        self.round_tallies = dict.fromkeys(mesh_step.CAESAR_SITE_ROUND_TALLIES, 0)
        self.round_gauges = dict.fromkeys(mesh_step.CAESAR_SITE_ROUND_GAUGES, 0)
        self._mesh = (
            mesh
            if mesh is not None
            else mesh_step.make_mesh(num_replicas=num_replicas)
        )
        self._state = mesh_step.init_caesar_state(
            self._mesh,
            num_replicas,
            key_buckets=key_buckets,
            pending_capacity=pending_capacity,
            key_width=key_width,
        )
        self._step = mesh_step.jit_caesar_step(
            self._mesh, num_replicas=num_replicas, live_replicas=live_replicas
        )
        self._pend_cap = pending_capacity

    # --- a coordinator at every site ---

    @property
    def serves_sites(self) -> bool:
        """Whether the round can have a coordinator at every site: with one
        key a command (``caesar_protocol_step(sites=n)``; a row's several
        runs are not written, and the round has one shard)."""
        return self.key_width == 1

    def _make_site_programs(self) -> None:
        """The round with a coordinator at every site: lowered on the
        state's shapes and compiled, or loaded, beside the step's thread
        under a ``precompile`` span (about 8.0 s on an empty cache on a v5e
        at the cell's shape, 7.4 s of it the compiler's, against a client's
        30 s wait for its ack; PR 59's chip runs).  The program with one
        coordinator is kept."""
        from fantoch_tpu.parallel import mesh_step

        self._step = mesh_step.jit_caesar_step(
            self._mesh, num_replicas=self.num_replicas,
            live_replicas=self._live_replicas,
            sites=self.num_replicas, site_base=self.site_base,
        )
        ready = {1: self._precompile(self._step, state=self._state_shapes())}
        self._one_site_programs, self._programs = self._programs, ready

    def _execute(self, _tok, out) -> List[ExecutorResult]:
        """Execute one fetched round's wait-cleared commands in
        (clock, dot) order."""
        wm = int(out.watermark)
        if wm >= self.CLOCK_GUARD:
            raise RuntimeError(
                "caesar timestamp space nearing int32 exhaustion"
            )
        self.stable_watermark = max(self.stable_watermark, wm)
        self.slow_paths += int(out.slow_paths)
        self.fast_paths += int(np.asarray(out.fast_path).sum())
        # what the round with a coordinator at every site adds to the
        # round's output (mesh_step.CaesarSiteStepOutput): its tallies,
        # then the recursion's depth, of which the highest is kept
        counts = getattr(out, "tallies", None)
        if counts is not None:
            *sums, passes = counts.tolist()
            tallies = self.round_tallies
            for name, count in zip(tallies, sums):
                tallies[name] += count
            gauges = self.round_gauges
            gauges["wait_passes"] = max(gauges["wait_passes"], passes)

        return self._drain_and_carry(out, "caesar", "blocked")


class PaxosDeviceDriver(_DriverCore):
    """Host control loop around the device-resident leader-based slot
    round (parallel/mesh_step.paxos_protocol_step): replica 0 assigns
    consecutive slots, acceptor acks are one psum, and execution is
    strictly contiguous in slot order — the FPaxos/MultiSynod class
    (fantoch_ps/src/bin/fpaxos.rs served through fantoch/src/run/mod.rs:105)
    as a mesh program.

    Commands need no key rows (the slot log totally orders them), so
    ``key_width`` is None: the session validator accepts any width.  The
    registry keys on packed (source, sequence); working-row identity and
    the round's exec frontier come from the step outputs (no host
    mirror), so the driver serves through the shared dispatch/drain
    pipelining scaffold like the other three.
    """

    key_width = None  # slot order needs no key rows: any command width
    round_name = "paxos_slot"

    def __init__(
        self,
        num_replicas: int,
        *,
        f: int = 1,
        batch_size: int = 256,
        key_buckets: int = 4096,
        pending_capacity: int = 256,
        live_replicas: Optional[int] = None,
        shard_id: ShardId = 0,
        monitor_execution_order: bool = False,
        mesh=None,
    ):
        from fantoch_tpu.parallel import mesh_step

        self._init_core(shard_id, batch_size, key_buckets, monitor_execution_order)
        self._mesh = (
            mesh
            if mesh is not None
            else mesh_step.make_mesh(num_replicas=num_replicas)
        )
        self._state = mesh_step.init_paxos_state(
            self._mesh, pending_capacity=pending_capacity
        )
        self._step = mesh_step.jit_paxos_step(
            self._mesh,
            f=f,
            num_replicas=num_replicas,
            live_replicas=live_replicas,
        )
        # no host identity mirror (PaxosStepOutput.work_src/work_seq);
        # fast_paths stays 0 — leader-based: every commit is the one path
        self._pend_cap = pending_capacity
        self.accept_quorum = f + 1
        self._slot_base = 0  # slots below base + exec_frontier executed
        self._next_slot = 0  # host mirror of state.next_slot
        self.slot_epochs = 0  # slot-space rebases (device_slot_epochs)

    def _column_specs(self):
        """The leader round takes no key rows: which rows of the batch
        hold a command (staged as 0/1), and the dots."""
        b = self.batch_size
        return (
            ("valid", (b,), bool, False),
            ("src", (b,), np.int32, 0),
            ("seq", (b,), np.int32, 0),
        )

    # the slot log is an int32 counter growing one per command; rebase
    # against the contiguous exec frontier (every live slot is at or
    # above it) before it can wrap
    SLOT_RESET_THRESHOLD = 2**31 - (1 << 20)

    def _slot_epoch_reset(self) -> None:
        import jax
        import jax.numpy as jnp

        st = self._state
        delta = int(st.exec_frontier)
        if delta <= 0:
            raise RuntimeError(
                "slot log exhausted: nothing executed, the frontier "
                "cannot rebase the slot space"
            )
        pend_slot = np.asarray(st.pend_slot, dtype=np.int64)
        live = pend_slot >= 0
        assert (pend_slot[live] >= delta).all(), (
            "carried slot below the contiguous exec frontier"
        )
        pend_slot = np.where(live, pend_slot - delta, -1)
        self._state = st._replace(
            next_slot=jax.device_put(
                jnp.int32(self._next_slot - delta), st.next_slot.sharding
            ),
            exec_frontier=jax.device_put(
                jnp.int32(0), st.exec_frontier.sharding
            ),
            pend_slot=jax.device_put(
                jnp.array(pend_slot.astype(np.int32)), st.pend_slot.sharding
            ),
        )
        self._next_slot -= delta
        self._slot_base += delta
        self.slot_epochs += 1
        logger.info(
            "paxos slot epoch reset: rebased by %d (epoch %d)",
            delta, self.slot_epochs,
        )

    def _pipeline_flush_needed(self, batch) -> bool:
        # a slot-epoch reset replaces next_slot/frontier/pending state
        # that an in-flight round's outputs reference pre-rebase; the
        # host slot mirror only advances at drain, so while rounds are
        # in flight the device counter leads it by up to one batch each
        return (
            self._next_slot + (self._undrained + 1) * self.batch_size
            >= self.SLOT_RESET_THRESHOLD
            or super()._pipeline_flush_needed(batch)
        )

    def _assemble(self, batch: List[Tuple[Dot, Command]]):
        """One slot round's valid/src/seq columns, and the batch length
        for drain's slot-counter accounting."""
        assert len(batch) <= self.batch_size
        if self._next_slot + self.batch_size >= self.SLOT_RESET_THRESHOLD:
            assert self._undrained == 0, (
                "slot epoch reset with a round in flight "
                "(_pipeline_flush_needed must prevent this)"
            )
            self._slot_epoch_reset()
            if self._next_slot + self.batch_size >= 2**31 - 1:
                raise RuntimeError(
                    "slot log exhausted: the contiguous exec frontier is "
                    "pinned too far behind to rebase"
                )
        staged = self._staging(*self._column_specs())
        valid, src, seq = staged
        self._identity_columns(batch, src, seq, valid_row=valid)
        return staged, len(batch)

    def _enqueue(self, staged):
        columns, n_batch = staged
        return super()._enqueue(columns), n_batch

    def _token_outputs(self, tok):
        return tok[0]

    def _execute(self, tok, out) -> List[ExecutorResult]:
        """Execute one fetched round's contiguous slot prefix against
        the KVStore.  The round's own exec_frontier rides in the output,
        so a later dispatched round cannot leak its frontier into this
        one."""
        n_batch = tok[1]
        order = np.asarray(out.order)
        executed = np.asarray(out.executed)
        slot = np.asarray(out.slot)
        work_src = np.asarray(out.work_src)
        work_seq = np.asarray(out.work_seq)
        # device slot counter: + new valid rows, - rolled-back overflow
        self._next_slot += n_batch - int(out.pend_dropped)
        self.stable_watermark = self._slot_base + int(out.exec_frontier)
        # every commit in the leader class takes the same (slow) path: one
        # accept round — mirror the tally convention of the object runner.
        # The benchmark reads it as slow_path_share.sat 100: the tally's
        # name for the leader's one path, not a retry
        self.slow_paths += int(executed.sum())

        results = self._execute_ordered(order, executed, work_src, work_seq)

        # the device keeps the LOWEST pend_cap unexecuted slots (the log
        # stays dense); overflow rows are the highest slots and the
        # device rolled its slot counter back over them, so re-queueing
        # them under the same dot is safe: no acceptor holds durable
        # state for a rolled-back slot.
        if int(out.pend_dropped) > 0:
            carried = self._registered_rows(
                np.flatnonzero((slot >= 0) & ~executed), work_src, work_seq
            )
            carried.sort(key=lambda w: int(slot[w]))
            self._requeue_rows(
                carried[self._pend_cap:], work_src, work_seq, "paxos"
            )
        return results


def driver_for(
    protocol: str,
    config: Config,
    *,
    process_id: ProcessId,
    batch_size: int,
    key_buckets: int,
    key_width: int,
    pending_capacity: int,
    live_replicas: Optional[int],
    monitor_execution_order: bool,
    mesh,
) -> _DriverCore:
    """The driver that serves a protocol label, made: the one place that
    knows which family a label belongs to.  What every driver takes is
    built once; a family adds what it alone takes."""
    if protocol in ("fpaxos", "caesar") and config.shard_count != 1:
        # the leader-based slot round and the Caesar round serve full
        # replication only (their host/object runners cover partial
        # replication); the dep-commit and Newt timestamp rounds both
        # serve a sharded key axis
        raise ValueError(
            f"device-step sharding serves the dep-commit and newt "
            f"rounds; {protocol} serving is single-shard"
        )
    shared = dict(
        batch_size=batch_size,
        key_buckets=key_buckets,
        pending_capacity=pending_capacity,
        live_replicas=live_replicas,
        monitor_execution_order=monitor_execution_order,
        mesh=mesh,
    )
    if protocol == "fpaxos":
        # slot-ordered: the round has no key rows
        return PaxosDeviceDriver(config.n, f=config.f, **shared)
    # the rounds that order by key serve a coordinator at every site
    # (site ``s``'s is process ``process_id + s``)
    keyed = dict(shared, key_width=key_width, site_base=process_id)
    if protocol == "caesar":
        return CaesarDeviceDriver(config.n, **keyed)
    # the two rounds that serve a sharded key axis
    sited = dict(keyed, f=config.f, shard_count=config.shard_count)
    if protocol == "newt":
        return NewtDeviceDriver(
            config.n, tiny_quorums=config.newt_tiny_quorums, **sited
        )
    # the dep-commit round serves every other label: under Atlas's
    # quorums and fast-path rule for 'atlas', under EPaxos's for the rest
    return DeviceDriver(
        config.n, rule="atlas" if protocol == "atlas" else "epaxos", **sited
    )
