"""The chip's compiler, asked without the chip.

The main-path programs — built through ``Engine`` at the sizes
``chip_smoke.py`` runs — are lowered for ONE described TPU v5e chip and
compiled by the installed TPU compiler: what it refuses here (a program
that does not fit, an op it cannot lower, a scoped-memory fault) costs no
chip time.  ``accel_tuned()`` is forced true, so the branches compiled
are the ones the chip takes (``lax.top_k`` compaction, sort-based
pre-aggregation), which no CPU test otherwise reaches.

A compile that passes is not a run: results and times come from
``chip_smoke.py`` on the chip.

Everything that touches the topology lives in module-scoped fixtures of
this one file (one process at a time may load the TPU's library; under
xdist only the worker that is given this file does).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from risingwave_tpu.common import compact
from risingwave_tpu.common.config import RwConfig
from risingwave_tpu.sql import Engine
from risingwave_tpu.stream import hash_agg

CFG = chip_smoke.FULL
HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def chip_branches():
    """The chip's trace-time branches, and no persistent cache: an entry
    written by a deviceless compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        compact.accel_tuned.cache_clear()
        mp.setattr(compact, "accel_tuned", lambda: True)
        mp.setattr(hash_agg, "accel_tuned", lambda: True)
        yield
    compact.accel_tuned.cache_clear()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _job(view: str):
    eng = Engine(RwConfig.from_dict({
        "streaming": {"chunk_size": CFG["chunk"]}, "state": CFG["state"],
    }))
    eng.execute(chip_smoke.SOURCES.format(rate=CFG["rate"]))
    eng.execute(chip_smoke.VIEWS[view])
    return eng.jobs[0]


@pytest.fixture(scope="module")
def jobs():
    """Built once per view, on the CPU, through the SQL front end."""
    cache: dict = {}

    def get(view: str):
        if view not in cache:
            cache[view] = _job(view)
        return cache[view]

    return get


def _compile(prog, one_chip, *args, text=False):
    """Lower for the described chip from shapes alone; return the
    compiled program's memory analysis (and its text, if asked)."""
    def sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip),
            tree)

    compiled = prog.lower(*(sds(a) for a in args)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"args={mem.argument_size_in_bytes >> 20}MiB "
          f"temp={mem.temp_size_in_bytes >> 20}MiB total={total >> 20}MiB")
    assert total < HBM_BYTES
    return (mem, compiled.as_text()) if text else mem


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*)$")


def _apply_scatter_widths(hlo: str, phase: str = "apply",
                          executor: str = "HashAgg") -> dict[str, int]:
    """Indices handed to every ``scatter`` of the compiled program whose
    ``op_name`` lies under a ``<executor>.<i>/<phase>`` scope, by
    instruction.  A scatter's operands are N arrays, the indices, N
    updates."""
    shape_of, scatters = {}, []
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        shape_of[name] = shape
        if op == "scatter" and re.search(
                rf'op_name="[^"]*/{executor}\.\d+/{phase}/', rest):
            scatters.append((name, rest))
    out = {}
    for name, rest in scatters:
        operands = re.findall(r"%[\w.\-]+", rest.split(")", 1)[0])
        idx = shape_of[operands[(len(operands) - 1) // 2]]
        dims = re.match(r"\w+\[([\d,]*)\]", idx).group(1)
        out[name] = int(dims.split(",")[0]) if dims else 1
    return out


def _assert_narrow_apply(hlo: str, chunk_rows: int):
    """The guard that the chunk-wide scatter does not come back: under
    ``HashAgg.<i>/apply`` every scatter takes one tile of
    representatives, never a chunk of rows (PERF.md §6, PR 27)."""
    widths = _apply_scatter_widths(hlo)
    assert len(widths) >= 5, widths  # the scope is there, and read
    assert max(widths.values()) <= hash_agg.REP_TILE < chunk_rows, widths


K0 = np.int64(0)
EPOCH = np.int64(1)


def test_q7_step(jobs, one_chip):
    job = jobs("q7")
    _, hlo = _compile(job._fused, one_chip, job.states, K0, text=True)
    _assert_narrow_apply(hlo, CFG["chunk"])


def test_q7_barrier(jobs, one_chip):
    job = jobs("q7")
    _compile(job.fragment._barrier, one_chip, job.states, EPOCH)


def test_q5_fused_window(jobs, one_chip):
    """The multi-chunk window program at the smoke's chunks_per_barrier."""
    job = jobs("q5")
    _, hlo = _compile(job._multi_prog(CFG["chunks_per_barrier"]), one_chip,
                      job.states, K0, text=True)
    _assert_narrow_apply(hlo, CFG["chunk"])


def test_q5_barrier(jobs, one_chip):
    """The pane chain's second agg runs inside the barrier's drain loop
    on 40,960-row chunks: the 64-bit reduce-window the compiler refused
    there is what ``compact._cumsum_int64`` replaced."""
    job = jobs("q5")
    _, hlo = _compile(job.fragment._barrier, one_chip, job.states, EPOCH,
                      text=True)
    _assert_narrow_apply(hlo, 40960)


def test_q5_maintain(jobs, one_chip):
    """The reclaim of both aggregates at 2^20 slots: under
    ``HashAgg.<i>/reclaim`` no scatter is handed the table, only one
    tile of movers (PERF.md §6, PR 30)."""
    from risingwave_tpu.state import hash_table

    job = jobs("q5")
    _, hlo = _compile(job.fragment._maintain, one_chip, job.states,
                      text=True)
    widths = _apply_scatter_widths(hlo, "reclaim")
    assert len(widths) >= 5, widths
    assert max(widths.values()) <= hash_table.RECLAIM_TILE, widths


def test_q8_step_person(jobs, one_chip):
    job = jobs("q8")
    prog, fused = job._make_step("p")
    assert fused
    _compile(prog, one_chip, job.states, K0)


def test_q8_barrier(jobs, one_chip):
    job = jobs("q8")
    _compile(job._make_barrier_prog(), one_chip, job.states, EPOCH)


def test_q8_maintain(jobs, one_chip):
    """The reclaim of both pool sides' tag tables at 2^22 slots: under
    ``HashJoin.<i>/reclaim`` no scatter is handed the table, only one
    tile of movers; the rows sit in a ring and are not touched (PERF.md
    §6, PR 35)."""
    from risingwave_tpu.state import hash_table

    job = jobs("q8")
    _, hlo = _compile(job._make_maintain_prog(), one_chip, job.states,
                      text=True)
    widths = _apply_scatter_widths(hlo, "reclaim", "HashJoin")
    assert len(widths) >= 4, widths
    assert max(widths.values()) <= hash_table.TAG_RECLAIM_TILE, widths


def test_int64_cumsum_in_loop(one_chip):
    """libtpu 0.0.34 runs out of scoped vmem on ``jnp.cumsum`` of int64
    inside a loop body at lengths 2^14..2^16; the limb-wise scan must
    not."""
    def loop(x):
        return jax.lax.fori_loop(
            0, 3, lambda _, v: compact._cumsum_int64(v) + 1, x)

    _compile(jax.jit(loop), one_chip,
             jax.ShapeDtypeStruct((40960,), jnp.int64))


def test_string_ring_digest_fits(one_chip):
    """q8's 2^23-row MV ring has a 24-byte name column; its block digest
    once asked for 12 GiB of padding (a ``[n/8, 8]`` view in the TPU's
    tiled layout)."""
    from risingwave_tpu.storage.digest import (
        DEFAULT_BLOCK_ELEMS, leaf_block_count, leaf_digest,
    )
    shape = (CFG["state"]["mv_ring_size"], 24)
    nb = leaf_block_count(shape, DEFAULT_BLOCK_ELEMS)
    prog = jax.jit(lambda x: leaf_digest(
        x.reshape(-1), nb, DEFAULT_BLOCK_ELEMS))
    mem = _compile(prog, one_chip, jax.ShapeDtypeStruct(shape, jnp.uint8))
    assert mem.temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("view", ["q7", "q5"])
def test_view_read_gather(jobs, one_chip, view):
    """The read's program (``materialize._live_blocks``) over the
    view's table at the smoke's size: ``lax.top_k`` over the blocks and
    one windowed gather a leaf."""
    from risingwave_tpu.stream import materialize

    job = jobs(view)
    (at, mv), = [(i, ex) for i, ex in enumerate(job.fragment.executors)
                 if isinstance(ex, materialize.MaterializeExecutor)]
    state = job.states[at]
    mem = _compile(materialize._live_blocks_fn(False, True), one_chip,
                   state.table.occupied, jax.tree.leaves(state.values))
    blocks = mv.table_size // materialize.READ_BLOCK
    table = sum(x.nbytes for x in jax.tree.leaves(
        (state.table.occupied, state.values)))
    assert mem.output_size_in_bytes <= table * max(1, blocks // 64) \
        // blocks + 4096


def test_view_read_gather_stacked_strings(one_chip):
    """A mesh view's stacked state with a string column: the same
    program under ``vmap``, the string's bytes folded into its window."""
    from risingwave_tpu.stream import materialize

    size = 1 << 20
    _compile(
        materialize._live_blocks_fn(True, True), one_chip,
        jax.ShapeDtypeStruct((4, size), jnp.bool_),
        [jax.ShapeDtypeStruct((4, size), jnp.int64),
         jax.ShapeDtypeStruct((4, size, 24), jnp.uint8),
         jax.ShapeDtypeStruct((4, size), jnp.int32)])


# -- the q5 join cell at its own sizes (benchmark/configs/nexmark_q5.json)

@pytest.fixture(scope="module")
def q5_join_job():
    import json

    cfg = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "nexmark_q5.json")))
    eng = Engine(RwConfig.from_dict(cfg["server"]["config_json"]))
    for stmt in cfg["sources_sql"]:
        eng.execute(stmt)
    eng.execute(cfg["view"]["sql"])
    return eng.jobs[0], cfg["system_params"]["chunks_per_barrier"]


def test_q5_join_window(q5_join_job, one_chip):
    """The inner aggregate runs on panes under the DAG runtime too: the
    window program's scatters take a tile of representatives."""
    job, chunks = q5_join_job
    _, hlo = _compile(job._multi_prog(chunks), one_chip, job.states,
                      {"bid": K0}, text=True)
    _assert_narrow_apply(hlo, 8192)


def test_q5_join_barrier(q5_join_job, one_chip):
    """The flush chain: the counts' changes through the max's
    materialised input, the keyed join side and the view, in one
    program; no scatter under the join's scopes is wider than a chunk
    of changes (the keyed side's stores are written a chunk at a time,
    the masked passes are elementwise)."""
    job, _ = q5_join_job
    _, hlo = _compile(job._make_barrier_prog(), one_chip, job.states,
                      EPOCH, text=True)
    for phase in ("insert", "delete", "probe", "emit"):
        widths = _apply_scatter_widths(hlo, phase, "HashJoin")
        assert max(widths.values(), default=0) <= 4 * 8192, (phase, widths)
    assert "extreme" in hlo and "HashJoin.5/delete" in hlo


def test_q5_join_maintain(q5_join_job, one_chip):
    """Every store of the plan is reclaimed by tiles of movers: the
    count tables, the max's materialised input, the keyed side."""
    from risingwave_tpu.state import hash_table

    job, _ = q5_join_job
    _, hlo = _compile(job._make_maintain_prog(), one_chip, job.states,
                      text=True)
    for ex in ("HashAgg", "HashJoin"):
        widths = _apply_scatter_widths(hlo, "reclaim", ex)
        assert len(widths) >= 4, widths
        assert max(widths.values()) <= hash_table.RECLAIM_TILE, (ex, widths)
