"""risingwave_tpu: a TPU-native distributed SQL streaming framework.

A from-scratch re-design of the capabilities of RisingWave (reference:
/root/reference, racevedoo/risingwave) for TPU hardware:

- columnar ``DataChunk``/``StreamChunk`` batches living as JAX device arrays
- stateful stream operators (hash join, hash agg) as jit/XLA kernels
  over device-resident hash tables
- consistent-hash (256-vnode) data parallelism mapped onto a
  ``jax.sharding.Mesh``; hash dispatch rides ICI collectives
- Chandy-Lamport aligned-barrier checkpoints; an LSM state store
  ("hummock-lite") over object storage
- a PostgreSQL-flavoured SQL frontend compiling CREATE MATERIALIZED VIEW
  into actor dataflow graphs

Layering (mirrors SURVEY.md section 1):

    common/      foundation: types, arrays, chunks, hashing, epochs, config
    ops/         jit device kernels (hash tables, grouped agg, join match)
    state/       state-store interface + relational StateTable (epoch MVCC)
    stream/      executors, actors, barriers, local + remote exchange
    parallel/    device-mesh SPMD: all_to_all dispatch, sharded agg/join,
                 elastic resharding
    storage/     hummock-lite LSM over object storage (SSTs, compaction)
    batch/       snapshot scans + batch executor tree (SELECT serving)
    frontend/    SQL parser -> binder -> planner; session; pgwire server
    meta/        barrier/checkpoint loop (epoch issue, collect, commit)
    connectors/  sources: nexmark, datagen (replayable, vectorized)
    models/      pre-built flagship pipelines (nexmark q1/q7/q8)
    native/      C++ runtime kernels (SST block codec, bloom) + loader
    utils/       metrics, tracing, JAX runtime knobs
"""

import jax

# A streaming SQL engine needs real 64-bit ints (timestamps in ms, row ids).
# JAX defaults to 32-bit; opt into x64 before any array is created. Hot-path
# kernels still request bf16/f32/int32 explicitly where it matters for MXU/VPU.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
