"""fantoch_tpu: a TPU-native framework for specifying, simulating and running
planet-scale consensus/SMR protocols.

Capabilities mirror the reference Rust framework (fantoch): leaderless and
leader-based protocols (EPaxos, Atlas, Newt/Tempo, Caesar, FPaxos, Basic) as
pure state machines over a shared ``Protocol`` interface, pluggable
``Executor`` ordering engines, a deterministic discrete-event simulator, and
an asyncio TCP runner — with the hot execution data plane (dependency-graph
SCC/topological resolution, key-clock proposals, vote-range stability)
re-designed as batched JAX computations instead of serial pointer
walks, and multi-chip scaling expressed as jax.sharding over a device Mesh.
"""

__version__ = "0.1.0"
