"""Storage layer tests: native codec, SST format, durable checkpoints."""

import os
import zlib

import numpy as np
import pytest

from risingwave_tpu.storage import codec
from risingwave_tpu.storage.sst import (
    TOMBSTONE,
    SstReader,
    merge_scan,
    write_sst,
)
from risingwave_tpu.storage.checkpoint_store import (
    RUN_GAP_BYTES,
    CheckpointStore,
)


def test_native_codec_builds():
    # the C++ library should build in this image (g++ present)
    assert codec.native_available()


def test_memcomparable_i64_order_and_roundtrip():
    vals = np.asarray(
        [-(2**63), -55, -1, 0, 1, 7, 2**62, 2**63 - 1], np.int64
    )
    enc = codec.mc_encode_i64(vals)
    assert [bytes(e) for e in enc] == sorted(bytes(e) for e in enc)
    np.testing.assert_array_equal(codec.mc_decode_i64(enc), vals)


def test_memcomparable_f64_order_and_roundtrip():
    vals = np.asarray(
        [-np.inf, -1e300, -1.5, -0.0, 0.0, 1e-300, 2.5, np.inf], np.float64
    )
    enc = codec.mc_encode_f64(vals)
    b = [bytes(e) for e in enc]
    assert b == sorted(b)
    dec = codec.mc_decode_f64(enc)
    # -0.0 encodes as +0.0 ordering-wise; compare with ==
    np.testing.assert_array_equal(dec, vals)


def test_block_roundtrip():
    keys = [f"key{i:04d}".encode() for i in range(100)]
    vals = [f"value-{i}".encode() * (i % 5 + 1) for i in range(100)]
    ko = np.cumsum([0] + [len(k) for k in keys]).astype(np.int64)
    vo = np.cumsum([0] + [len(v) for v in vals]).astype(np.int64)
    blk = codec.block_encode(
        np.frombuffer(b"".join(keys), np.uint8), ko,
        np.frombuffer(b"".join(vals), np.uint8), vo,
    )
    k2, ko2, v2, vo2 = codec.block_decode(blk)
    kb, vb = k2.tobytes(), v2.tobytes()
    got = [
        (kb[ko2[i]:ko2[i + 1]], vb[vo2[i]:vo2[i + 1]])
        for i in range(len(ko2) - 1)
    ]
    assert got == list(zip(keys, vals))


def test_sst_write_read_scan(tmp_path):
    n = 5000
    keys = [f"{i:08d}".encode() for i in range(n)]
    vals = [f"v{i}".encode() for i in range(n)]
    path = str(tmp_path / "t.sst")
    meta = write_sst(path, keys, vals, block_bytes=1024)
    assert meta.n_records == n
    r = SstReader(path)
    assert r.n_records == n
    assert r.get(b"00000042") == b"v42"
    assert r.get(b"99999999") is None
    got = list(r.scan(b"00001000", b"00001010"))
    assert [k for k, _ in got] == keys[1000:1010]


@pytest.mark.parametrize("writer,reader", [
    ("bytewise", None), (None, "bytewise"), ("slice8", "bytewise"),
])
def test_sst_checksums_do_not_depend_on_the_crc32c_loop(
        tmp_path, monkeypatch, writer, reader):
    """An SST written under one crc32c loop (the parent's was a table
    lookup a byte) verifies under another, block trailers, index and
    bloom hashes: None is the loop this CPU gets."""
    def use(impl):
        if impl is None:
            monkeypatch.undo()
        else:
            monkeypatch.setattr(
                codec, "crc32c", lambda b: codec.crc32c_with(impl, b))

    keys = [f"{i:06d}".encode() for i in range(3000)]
    vals = [f"v{i}".encode() * 3 for i in range(3000)]
    path = str(tmp_path / "t.sst")
    use(writer)
    write_sst(path, keys, vals, block_bytes=512)
    use(reader)
    r = SstReader(path)
    assert r.get(b"002999") == vals[2999]
    assert r.get(b"zzz") is None
    assert list(r.scan(b"000000", b"999999")) == list(zip(keys, vals))


def test_sst_merge_scan_newest_wins(tmp_path):
    old = str(tmp_path / "old.sst")
    new = str(tmp_path / "new.sst")
    write_sst(old, [b"a", b"b", b"c"], [b"1", b"2", b"3"])
    write_sst(new, [b"b", b"c", b"d"], [b"20", TOMBSTONE, b"40"])
    got = list(merge_scan([SstReader(new), SstReader(old)]))
    assert got == [(b"a", b"1"), (b"b", b"20"), (b"d", b"40")]


def test_checkpoint_store_survives_restart(tmp_path):
    """Job persists checkpoints; a FRESH job object recovers from disk."""
    from risingwave_tpu.common.chunk import Chunk
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.expr.node import col
    from risingwave_tpu.stream.fragment import Fragment
    from risingwave_tpu.stream.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.materialize import MaterializeExecutor
    from risingwave_tpu.stream.runtime import StreamingJob

    schema = Schema.of(("g", DataType.INT64), ("v", DataType.INT64))

    class Src:
        def __init__(self):
            self.offset = 0

        def next_chunk(self):
            ar = [np.arange(4, dtype=np.int64) % 2,
                  np.full(4, self.offset, np.int64)]
            self.offset += 1
            return Chunk.from_numpy(schema, ar)

        def state(self):
            return {"offset": self.offset}

    def build():
        agg = HashAggExecutor(
            schema, [("g", col("g"))], [count_star("n")],
            table_size=64, emit_capacity=16,
        )
        mv = MaterializeExecutor(agg.out_schema, [0], table_size=64)
        return Fragment([agg, mv]), mv

    store = CheckpointStore(str(tmp_path / "ckpt"))
    frag, mv = build()
    job = StreamingJob(Src(), frag, "j1", checkpoint_store=store)
    job.run(barriers=3, chunks_per_barrier=1)
    want = sorted(mv.to_host(job.states[1]))
    committed = job.committed_epoch
    assert store.committed_epoch("j1") == committed

    # "process restart": fresh objects, recover from disk
    frag2, mv2 = build()
    job2 = StreamingJob(Src(), frag2, "j1", checkpoint_store=store)
    job2.recover()
    assert job2.committed_epoch == committed
    assert job2.source.offset == 3
    assert sorted(mv2.to_host(job2.states[1])) == want
    # and it keeps running correctly
    job2.run(barriers=1, chunks_per_barrier=1)
    assert sorted(mv2.to_host(job2.states[1])) == [(0, 8), (1, 8)]


def test_checkpoint_store_gc(tmp_path):
    # full_interval=1: every epoch is a full snapshot, so GC can drop
    # old epochs immediately
    store = CheckpointStore(str(tmp_path), keep_epochs=2, full_interval=1)
    states = {"x": np.arange(5)}
    for e in (10, 20, 30):
        store.save("j", e, states, {})
    files = os.listdir(str(tmp_path / "j"))
    assert "epoch_10.npz" not in files
    assert "epoch_30.npz" in files
    assert store.committed_epoch("j") == 30


def test_incremental_checkpoint_bytes_scale_with_activity(tmp_path):
    """Delta checkpoints persist only dirty blocks (ref uploader
    per-epoch deltas); restore replays full + chain."""
    store = CheckpointStore(str(tmp_path), keep_epochs=8,
                            full_interval=16, block_elems=1 << 10)
    big = np.zeros(1 << 16, np.int64)  # 64 blocks
    states = {"big": big, "ctr": np.zeros((), np.int64)}
    store.save("j", 1, states, {"off": 1})
    assert store.checkpoint_kind("j", 1) == "full"
    full_bytes = store.checkpoint_bytes("j", 1)

    # touch one block + the scalar -> tiny delta
    big2 = big.copy()
    big2[5] = 99
    store.save("j", 2, {"big": big2, "ctr": np.int64(1)}, {"off": 2})
    assert store.checkpoint_kind("j", 2) == "delta"
    delta_bytes = store.checkpoint_bytes("j", 2)
    assert delta_bytes < full_bytes // 8

    # untouched epoch -> near-empty delta
    store.save("j", 3, {"big": big2, "ctr": np.int64(1)}, {"off": 3})
    assert store.checkpoint_bytes("j", 3) < delta_bytes

    # restore target epoch reconstructs through the chain
    epoch, loaded, src = store.load("j", 3)
    assert epoch == 3 and src == {"off": 3}
    assert loaded["big"][5] == 99 and int(loaded["ctr"]) == 1
    assert (loaded["big"] == big2).all()
    # time travel to the mid-chain epoch
    _, loaded2, src2 = store.load("j", 2)
    assert src2 == {"off": 2} and loaded2["big"][5] == 99


def test_incremental_checkpoint_gc_keeps_chain_base(tmp_path):
    store = CheckpointStore(str(tmp_path), keep_epochs=2,
                            full_interval=4, block_elems=1 << 10)
    arr = np.zeros(1 << 12, np.int64)
    for e in range(1, 7):
        arr = arr.copy()
        arr[e] = e
        store.save("j", e, {"a": arr}, {})
    # latest epochs stay loadable even though their base full is older
    # than keep_epochs
    epoch, loaded, _ = store.load("j")
    assert epoch == 6 and loaded["a"][6] == 6 and loaded["a"][3] == 3


def _plain_delta(old_leaves, new_leaves, lanes, block, whole=()):
    """The delta payload cut by plain numpy from host copies of two
    states: every block whose elements differ, adjacent ones joined
    into runs that never cross a shard row; in a leaf that crossed
    whole (``whole``: its indices), runs no more than
    ``RUN_GAP_BYTES`` of clean blocks apart joined too."""
    out = {}
    for i, (a, b, ln) in enumerate(zip(old_leaves, new_leaves, lanes)):
        fa, fb = np.asarray(a).reshape(-1), np.asarray(b).reshape(-1)
        rows, m = ln if ln else (1, fb.size)
        gap = RUN_GAP_BYTES // (block * fb.itemsize) if i in whole else 0
        for r in range(rows):
            ra, rb = fa[r * m:(r + 1) * m], fb[r * m:(r + 1) * m]
            runs = []          # [first block, end block)
            for k in range(max(1, -(-m // block))):
                blk = slice(k * block, min((k + 1) * block, m))
                if np.array_equal(ra[blk], rb[blk]):
                    continue
                if runs and k - runs[-1][1] <= gap:
                    runs[-1][1] = k + 1
                else:
                    runs.append([k, k + 1])
            for b0, b1 in runs:
                out[f"r_{i}_{r * m + b0 * block}"] = \
                    rb[b0 * block:min(b1 * block, m)].copy()
    return out


def _assert_payload_is(prep, want):
    got = prep["payload"]
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].ndim == 1, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def _moved(reg, job, path):
    """Bytes ``prepare`` counted as crossed from the device by ``path``
    (a path never taken has no series)."""
    try:
        return reg.get("checkpoint_fetch_bytes_total", job=job, path=path)
    except KeyError:
        return 0


def _values(n, dtype, salt):
    v = (np.arange(n, dtype=np.int64) * 2654435761 + salt) % 1000003
    return (v % 2 == 0) if dtype == "bool" else v.astype(dtype)


#: (blocks touched, all within the leaf's capacity of 2?) for four
#: deltas in a row: one block; two adjacent; the first and the last
#: (the ragged tail, where there is one); three, which is over capacity
_DIRTY_SETS = [([5], True), ([17, 18], True), ([0, -1], True),
               ([3, 40, 41], False)]


@pytest.mark.parametrize("tail", [0, 37], ids=["even", "ragged"])
@pytest.mark.parametrize("dtype", ["int64", "int32", "bool", "float32"])
def test_delta_fetch_gathers_what_plain_numpy_cuts(tmp_path, dtype, tail):
    """A delta of DEVICE leaves brings back only the dirty blocks, and
    its payload is, key for key and element for element, what plain
    numpy cuts from host copies; the chain restores the live state."""
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.common.metrics import MetricsRegistry

    block, nb = 64, 128
    n = block * nb + tail
    reg = MetricsRegistry()
    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=block,
                            metrics=reg)
    host = {"s": np.zeros((), np.int64), "x": _values(n, dtype, 1)}
    nb_all = -(-n // block)

    def save(epoch, host):
        leaves, treedef = jax.tree.flatten(
            {k: jnp.asarray(v) for k, v in host.items()})
        prep = store.prepare("j", epoch, leaves,
                             [np.shape(x) for x in leaves], treedef, {})
        store.commit(prep)
        return prep

    assert save(1, host)["kind"] == "full"
    for epoch, (touched, fits) in enumerate(_DIRTY_SETS, start=2):
        new = {"s": np.int64(epoch), "x": host["x"].copy()}
        for b in touched:
            at = (b % nb_all) * block + 3
            new["x"][at] = ~new["x"][at] if dtype == "bool" \
                else new["x"][at] + 1
        before = _moved(reg, "j", "gathered")
        prep = save(epoch, new)
        assert prep["kind"] == "delta"
        _assert_payload_is(prep, _plain_delta(
            [host["s"], host["x"]], [new["s"], new["x"]], [None, None],
            block, whole=() if fits else (1,)))
        # within its capacity the leaf crossed as two blocks' worth,
        # over it whole; the program that gathers was compiled once
        crossed = _moved(reg, "j", "gathered") - before
        assert crossed == (2 * block * new["x"].itemsize if fits else 0)
        assert store._gather_fns["j"][0]._cache_size() == 1
        host = new
        _, loaded, _ = store.load("j", epoch)
        for k in host:
            assert loaded[k].dtype == host[k].dtype
            assert loaded[k].tobytes() == host[k].tobytes()


@pytest.mark.parametrize("placed", ["one_device", "over_the_mesh"])
@pytest.mark.parametrize("m", [64 * 40 + 36, 100],
                         ids=["ragged_lanes", "lanes_under_a_block"])
def test_delta_fetch_of_a_lane_leaf(tmp_path, m, placed):
    """A mesh-stacked leaf digested in per-shard lanes: block starts
    are ``lane * m + b * block``, a lane's ragged tail is its own run,
    no run crosses a shard row, and the last lane's tail is read by a
    window that starts early, also where the leaf lies across devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from risingwave_tpu.stream.shadow import ShadowSnapshot

    S, block = 8, 64
    nb_row = -(-m // block)
    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=block)
    sharding = NamedSharding(Mesh(np.array(jax.devices()[:S]), ("s",)),
                             PartitionSpec("s"))

    def place(host):
        tree = {k: jnp.asarray(v) for k, v in host.items()}
        if placed == "over_the_mesh":
            tree = jax.device_put(tree, sharding)
        return tree

    host = {"n": np.zeros((S,), np.int64),
            "x": _values(S * m, "int64", 3).reshape(S, m)}
    sh = ShadowSnapshot(place(host), block_elems=block, shard_rows=S)

    def save(epoch, digests):
        prep = store.prepare("j", epoch, sh.leaves, sh.shapes, sh.treedef,
                             {}, digests=np.asarray(digests), lanes=sh.lanes)
        store.commit(prep)
        return prep

    assert save(1, sh.digests)["kind"] == "full"
    cap = store._gather_fn("j", sh.leaves, sh.nblocks)[1][1]
    # (lane, block) sets: a middle lane's tail; a lane's tail and the
    # next lane's first block (adjacent in memory, two runs); the
    # leaf's first block and the last lane's tail; more than capacity
    sets = [[(1, -1)], [(2, -1), (3, 0)], [(0, 0), (S - 1, -1)],
            [(r, 0) for r in range(cap + 1)]]
    for epoch, cells in enumerate(sets, start=2):
        new = {"n": host["n"] + 1, "x": host["x"].copy()}
        for r, b in cells:
            new["x"][r, min((b % nb_row) * block + 3, m - 1)] -= 1
        prep = save(epoch, sh.update(place(new)))
        assert prep["kind"] == "delta"
        _assert_payload_is(prep, _plain_delta(
            [host["n"], host["x"]], [new["n"], new["x"]],
            [(S, 1), (S, m)], block))
        host = new
        _, loaded, _ = store.load("j", epoch)
        for k in host:
            assert loaded[k].tobytes() == host[k].tobytes()
    assert store._gather_fns["j"][0]._cache_size() == 1


def test_strewn_dirty_blocks_are_written_as_few_runs(tmp_path):
    """A string column's bytes under new rows strewn over its slots: one
    dirty 512-byte block in three, each a run of its own, would be a ZIP
    member apiece.  Past its gather capacity the leaf crosses whole and
    runs closer than ``RUN_GAP_BYTES`` are written as one; runs farther
    apart stay two.  Every chain loads back byte for byte."""
    import jax
    import jax.numpy as jnp

    block = 512
    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=block)
    rows = 65536
    names = (np.arange(rows * 24, dtype=np.int64) % 251).astype(
        np.uint8).reshape(rows, 24)

    def save(epoch, host):
        leaves, treedef = jax.tree.flatten([jnp.asarray(host)])
        prep = store.prepare("j", epoch, leaves, [host.shape], treedef, {})
        store.commit(prep)
        return prep

    save(1, names)
    strewn = names.copy()
    strewn[::64] ^= 1             # every third block of the first 1,000
    strewn = strewn.copy()
    strewn[rows // 4:] = names[rows // 4:]
    prep = save(2, strewn)
    assert prep["kind"] == "delta"
    assert list(prep["payload"]) == ["r_0_0"]
    assert prep["payload"]["r_0_0"].size < rows * 24 // 4 + block
    apart = strewn.copy()
    apart[:1024] += 1             # 48 blocks from the start, and one far
    apart[-1] += 1
    prep = save(3, apart)
    assert prep["kind"] == "delta"
    last = (rows * 24 // block - 1) * block
    assert sorted(prep["payload"]) == ["r_0_0", f"r_0_{last}"]
    for epoch, host in ((2, strewn), (3, apart)):
        _, loaded, _ = store.load("j", epoch)
        assert loaded[0].tobytes() == host.tobytes()


def test_delta_of_host_arrays_dispatches_no_device_program(
        tmp_path, monkeypatch):
    """``save`` of a numpy tree (a spill tier's ``host_state``): the
    runs are cut where the arrays are; nothing is gathered on, or
    fetched from, the device."""
    import jax

    from risingwave_tpu.common.metrics import MetricsRegistry

    reg = MetricsRegistry()
    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=64,
                            metrics=reg)
    a = {"x": _values(64 * 128 + 5, "int64", 2), "s": np.int64(0)}
    store.save("t", 1, a, {})
    b = {"x": a["x"].copy(), "s": np.int64(1)}
    b["x"][[70, 8196]] = -1
    fetched = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda t: fetched.append(t) or real(t))
    store.save("t", 2, b, {})
    assert store.checkpoint_kind("t", 2) == "delta"
    assert fetched == [[]]
    assert store._gather_fns["t"][0] is None
    assert _moved(reg, "t", "gathered") == _moved(reg, "t", "whole") == 0
    _, loaded, _ = store.load("t", 2)
    assert loaded["x"].tobytes() == b["x"].tobytes()
    assert int(loaded["s"]) == 1


def test_no_compile_after_the_first_delta(tmp_path):
    """The dirty blocks' starts are an argument of one program: deltas
    with other dirty counts, leaves and paths add nothing to its cache,
    and a job of another shape gets a program of its own."""
    import jax.numpy as jnp

    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=64)
    tree = {"a": jnp.zeros(64 * 256, jnp.int64),
            "b": jnp.zeros(64 * 128, jnp.bool_),
            "c": jnp.zeros((3, 700), jnp.int32),
            "s": jnp.zeros((), jnp.int64)}
    store.save("j", 1, tree, {})
    touch = [{"a": [1]}, {"a": [1, 2, 3, 4], "b": [9]},
             {"a": range(0, 64 * 40, 64), "c": [5]}, {}]
    for epoch, cells in enumerate(touch, start=2):
        for k, at in cells.items():
            flat = tree[k].reshape(-1).at[jnp.asarray(list(at))].set(
                epoch % 2 == 0 if k == "b" else epoch)
            tree = dict(tree, **{k: flat.reshape(tree[k].shape)})
        store.save("j", epoch, tree, {})
        assert store.checkpoint_kind("j", epoch) == "delta"
        assert store._gather_fns["j"][0]._cache_size() == 1
    _, loaded, _ = store.load("j", epoch)
    for k in tree:
        np.testing.assert_array_equal(loaded[k], np.asarray(tree[k]))
    first = store._gather_fns["j"][0]
    store.save("j", 9, {"a": jnp.zeros(64 * 64, jnp.int64)}, {})
    assert store._gather_fns.get("j", (None,))[0] is not first


def test_fetch_bytes_counter_follows_the_write_set(tmp_path):
    """``checkpoint_fetch_bytes_total``: a full moves the state's
    bytes, a delta with one dirty block a leaf under 1/32 of them."""
    import jax.numpy as jnp

    from risingwave_tpu.common.metrics import MetricsRegistry

    reg = MetricsRegistry()
    store = CheckpointStore(str(tmp_path), keep_epochs=8, block_elems=64,
                            metrics=reg)
    tree = {"k": jnp.arange(64 * 128, dtype=jnp.int64),
            "v": jnp.zeros(64 * 128, jnp.int64),
            "live": jnp.zeros(64 * 128, jnp.bool_),
            "n": jnp.zeros((), jnp.int64)}
    state_bytes = sum(np.asarray(x).nbytes for x in tree.values())

    def moved():
        return _moved(reg, "j", "gathered") + _moved(reg, "j", "whole")

    store.save("j", 1, tree, {})
    assert moved() == state_bytes
    assert _moved(reg, "j", "gathered") == 0
    tree = {"k": tree["k"].at[100].set(-1), "v": tree["v"].at[100].set(7),
            "live": tree["live"].at[100].set(True), "n": jnp.int64(1)}
    store.save("j", 2, tree, {})
    assert store.checkpoint_kind("j", 2) == "delta"
    assert 0 < moved() - state_bytes < state_bytes / 32


def test_export_mv_sst(tmp_path):
    from risingwave_tpu.common.chunk import Chunk
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.stream.fragment import Fragment
    from risingwave_tpu.stream.materialize import MaterializeExecutor

    schema = Schema.of(("k", DataType.INT64), ("v", DataType.INT64))
    mv = MaterializeExecutor(schema, [0], table_size=64)
    frag = Fragment([mv])
    st = frag.init_states()
    st, _ = frag.step(st, Chunk.from_pretty("""
        I I
        + 3 30
        + 1 10
        + 2 20
    """, names=["k", "v"]))
    store = CheckpointStore(str(tmp_path))
    path = store.export_mv_sst("j", 1, mv, st[0])
    r = SstReader(path)
    import pickle
    rows = [pickle.loads(v) for _, v in r.scan()]
    assert rows == [(1, 10), (2, 20), (3, 30)]  # pk-ordered


def test_engine_free_mv_read_from_sst(tmp_path):
    """Serving an MV from its exported SST without the engine/device
    state — the batch-scan-from-storage pattern (SURVEY §3.4)."""
    import pickle

    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig
    from risingwave_tpu.storage.sst import SstReader

    eng = Engine(PlannerConfig(
        chunk_capacity=64, agg_table_size=256, agg_emit_capacity=64,
        mv_table_size=256, mv_ring_size=1024,
    ), data_dir=str(tmp_path))
    eng.execute("""
        CREATE SOURCE t (k BIGINT, v BIGINT) WITH (connector='datagen');
        CREATE MATERIALIZED VIEW m AS
        SELECT k % 4 AS g, count(*) AS n FROM t GROUP BY k % 4;
    """)
    eng.tick(barriers=2, chunks_per_barrier=1)
    entry = eng.catalog.get("m")
    live = sorted(eng.execute("SELECT g, n FROM m"))

    job = entry.job
    path = eng.checkpoint_store.export_mv_sst(
        "m", job.committed_epoch, entry.mv_executor,
        job.states[entry.mv_state_index[0]],
    )
    # a "different process": plain SST scan, no engine objects
    rows = sorted(
        (int(r[0]), int(r[1]))
        for _, v in SstReader(path).scan()
        for r in [pickle.loads(v)]
    )
    assert rows == [(int(a), int(b)) for a, b in live]


def test_engine_soak_checkpoint_bytes_stay_incremental(tmp_path):
    """A running windowed job's steady-state checkpoints are deltas
    whose bytes track epoch activity, not state size (verdict r3 ask:
    snapshot cadence can stay at 1 without full-state uploads)."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    eng = Engine(PlannerConfig(
        chunk_capacity=256, agg_table_size=1 << 12,
        agg_emit_capacity=256, mv_table_size=1 << 13,
        mv_ring_size=1 << 14,
    ), data_dir=str(tmp_path))
    eng.execute(
        "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
        " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
        " WATERMARK FOR date_time AS date_time)"
        " WITH (connector='nexmark', nexmark.table='bid',"
        " nexmark.event.rate='1000');"
        "CREATE MATERIALIZED VIEW w AS SELECT window_start,"
        " count(*) AS n FROM TUMBLE(bid, date_time,"
        " INTERVAL '1' SECOND) GROUP BY window_start;"
    )
    store = eng.checkpoint_store
    eng.tick(barriers=12, chunks_per_barrier=1)
    job = eng.jobs[0].name
    epochs = store.epochs(job)
    assert len(epochs) >= 2
    kinds = [store.checkpoint_kind(job, e) for e in epochs]
    sizes = {k: store.checkpoint_bytes(job, e)
             for e, k in zip(epochs, kinds)}
    assert "delta" in kinds, kinds
    # the steady-state deltas are a small fraction of a full snapshot
    full_size = max(store.checkpoint_bytes(job, e)
                    for e, k in zip(epochs, kinds) if k == "full") \
        if "full" in kinds else None
    delta_sizes = [store.checkpoint_bytes(job, e)
                   for e, k in zip(epochs, kinds) if k == "delta"]
    if full_size is not None and delta_sizes:
        assert min(delta_sizes) < full_size // 4, (sizes, kinds)
    # and recovery from the chain still works (cold-start bootstrap
    # replays the DDL log and restores the delta chain)
    eng2 = Engine(PlannerConfig(
        chunk_capacity=256, agg_table_size=1 << 12,
        agg_emit_capacity=256, mv_table_size=1 << 13,
        mv_ring_size=1 << 14,
    ), data_dir=str(tmp_path))
    a = sorted(map(tuple, eng.execute("SELECT * FROM w")))
    b = sorted(map(tuple, eng2.execute("SELECT * FROM w")))
    assert a == b
