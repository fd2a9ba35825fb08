"""risingwave_tpu — a TPU-native streaming-dataflow SQL framework.

A ground-up reimplementation of the *capabilities* of RisingWave (an
event-streaming SQL database that incrementally maintains materialized
views over retractable changelog streams) designed TPU-first:

- Per-chunk columnar compute (expression eval, hash-agg, hash-join,
  over-window inner loops) runs as jit-compiled XLA programs on a TPU
  mesh, with fixed shapes and visibility masks instead of dynamic
  filtering.
- Data parallelism is vnode (virtual-node) sharding mapped onto a
  ``jax.sharding.Mesh`` axis; hash exchanges are ``all_to_all``
  collectives over ICI inside the jitted step, not RPC.
- Barrier alignment, checkpointing and state persistence stay on the
  host control plane (Chandy-Lamport epoch barriers), mirroring the
  reference's meta/barrier design.

Layer map (mirrors reference layers, see SURVEY.md §1):

- ``common``   — chunks/arrays/types/vnode hashing (ref: src/common)
- ``expr``     — vectorized expression + aggregate engine (ref: src/expr)
- ``state``    — device-resident state tables + stores (ref: src/storage, state_table)
- ``stream``   — streaming executors + fragment runtime (ref: src/stream)
- ``batch``    — snapshot/serving reads (ref: src/batch)
- ``parallel`` — mesh/sharding/collective exchange (ref: dispatch/exchange)
- ``sql``      — parser/binder/planner/fragmenter (ref: src/sqlparser, src/frontend)
- ``connector``— sources (nexmark, datagen) and sinks (ref: src/connector)
- ``meta``     — catalog, barrier scheduler, checkpoint manager (ref: src/meta)
"""

import os as _os
import sys as _sys

# The serving tier (serve/, ``server.py --role serving``) is ENGINE-FREE:
# it reads MV rows straight from shared SSTs and must never pay the jax
# import (nor accidentally trace anything).  Skip the eager jax import
# when the process declares itself jax-free — every compute-facing
# module still imports jax itself, so a misrouted import in a serving
# process shows up as ``"jax" in sys.modules`` (asserted by tests).
_no_jax = bool(_os.environ.get("RWT_NO_JAX")) or (
    "--role" in _sys.argv and "serving" in _sys.argv
)

if not _no_jax:
    import jax as _jax

    # int64/timestamp/decimal columns are first-class in a SQL engine;
    # enable 64-bit types before any tracing happens.  Device kernels
    # prefer int64 / float32 paths (float64 is emulated on TPU and
    # avoided in hot loops).
    _jax.config.update("jax_enable_x64", True)

    # Persistent compile cache: a cold start compiles minutes of step,
    # barrier and snapshot programs.  JAX itself honours
    # JAX_COMPILATION_CACHE_DIR; unset, the cache lives at ONE fixed
    # path inside the checkout (the path is part of the cache key, so a
    # directory that moves never hits).
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(
                _os.path.dirname(_os.path.dirname(
                    _os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )

__version__ = "0.1.0"

