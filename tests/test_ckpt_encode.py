"""The epoch object's encoder and the checksum under it (PR 36).

``CheckpointStore.commit`` lays an epoch object out once, in a buffer
the committing thread keeps (``encode_npz`` into an ``_Arena``), and
checksums it with the CPU's crc32c instruction where there is one.
What must not move: the object is the ZIP of stored ``.npy`` members
``np.savez`` wrote (``np.load`` reads it, ``testzip()`` passes), crc32c
keeps its value in every loop, and a library that is not of this
source, or answers wrong, is never used.
"""

import io
import json
import os
import pickle
import shutil
import subprocess
import sys
import threading
import zipfile

import jax
import numpy as np
import pytest

from risingwave_tpu.storage import checkpoint_store as cs
from risingwave_tpu.storage import codec
from risingwave_tpu.storage.checkpoint_store import (
    CheckpointStore,
    _Arena,
    encode_npz,
)
from risingwave_tpu.storage.hummock.object_store import InMemObjectStore

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "rwtpu_codec.cpp")


# -- crc32c ---------------------------------------------------------------
@pytest.mark.parametrize("data,want", codec.CRC32C_KNOWN,
                         ids=["digits", "zeros32", "ones32"])
def test_crc32c_known_answers(data, want):
    assert codec.crc32c(data) == want
    assert codec.crc32c_py(data) == want
    assert codec.crc32c(memoryview(data)) == want
    assert codec.crc32c(np.frombuffer(data, np.uint8)) == want


def test_crc32c_picks_a_native_loop():
    assert codec.native_available()
    assert codec.crc32c_impl() in ("hw", "slice8")
    assert codec.crc32c_with("bytewise", b"123456789") == 0xE3069283
    assert codec.crc32c_with("no-such-loop", b"123456789") is None


@pytest.mark.parametrize("impl", ["hw", "slice8", "python"])
def test_crc32c_loops_agree_with_the_bytewise_table(impl):
    """Every loop present against the old one (a table lookup a byte),
    on every length from 0 to 4,099 at offsets that are not aligned,
    and on lengths round the hardware loop's three blocks of 4,096."""
    if impl == "python":
        def loop(b):
            return codec.crc32c_py(b)
    else:
        if codec.crc32c_with(impl, b"") is None:
            pytest.skip(f"this CPU has no {impl!r} loop")

        def loop(b):
            return codec.crc32c_with(impl, b)
    rng = np.random.default_rng(36)
    pool = rng.integers(0, 256, 3 * 12288 + 64, dtype=np.uint8)
    lengths = list(range(4100)) if impl != "python" \
        else list(range(0, 4100, 41))
    lengths += [12287, 12288, 12289, 2 * 12288 + 5, 3 * 12288 + 9]
    for n in lengths:
        off = 1 + n % 7
        piece = pool[off:off + n]
        assert loop(piece) == codec.crc32c_with("bytewise", piece), \
            (impl, n, off)


def test_crc32c_python_refuses_what_it_cannot_finish():
    big = np.zeros(codec.PY_CRC32C_MAX + 1, np.uint8)
    with pytest.raises(codec.NativeCodecRequired):
        codec.crc32c_py(big)


# -- the loader -----------------------------------------------------------
def _old_source(tmp_path, crc_body: str | None) -> str:
    """A source of another time: ``rw_crc32c`` wrong (``crc_body``) or
    not there at all (None), the other functions as they are."""
    with open(_SRC) as f:
        text = f.read()
    cut = text.index("uint32_t rw_crc32c(const uint8_t* data, int64_t n)")
    end = text.index("}", cut) + 1
    new = "" if crc_body is None else (
        "uint32_t rw_crc32c(const uint8_t* data, int64_t n) "
        "{ " + crc_body + " }")
    path = str(tmp_path / "old.cpp")
    with open(path, "w") as f:
        f.write(text[:cut] + new + text[end:])
    return path


@pytest.mark.parametrize("crc_body", ["return 7u;", None],
                         ids=["wrong_answer", "no_symbol"])
def test_stale_library_is_replaced(tmp_path, crc_body):
    """A library built from an older source sits where the loader looks
    (under this source's name, newer than the source): it is opened,
    fails the check, is rebuilt, and the good one is what callers get —
    nobody reaches the Python loop."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    lib_dir = str(tmp_path / "native")
    os.makedirs(lib_dir)
    src = os.path.join(lib_dir, "rwtpu_codec.cpp")
    shutil.copy(_SRC, src)
    so = codec._so_path(src, lib_dir)
    subprocess.run(["g++", "-O1", "-shared", "-fPIC",
                    _old_source(tmp_path, crc_body), "-o", so], check=True)
    legacy = os.path.join(lib_dir, "librwtpu_codec.so")
    shutil.copy(so, legacy)
    os.utime(so, (2e9, 2e9))
    os.utime(legacy, (2e9, 2e9))
    stale = os.stat(so).st_ino

    lib = codec.open_library(src, lib_dir)
    arr = np.frombuffer(b"123456789", np.uint8)
    assert lib.rw_crc32c(codec._u8(arr), 9) == 0xE3069283
    assert lib.rw_crc32c_impl().decode() in ("hw", "slice8")
    assert os.stat(so).st_ino != stale
    # the library of the time before the hash was in the name is
    # never opened: it stays as it was
    assert os.stat(legacy).st_mtime == 2e9


def test_library_name_follows_the_source_content(tmp_path):
    a, b = str(tmp_path / "a.cpp"), str(tmp_path / "b.cpp")
    for path, text in ((a, "int x;"), (b, "int y;")):
        with open(path, "w") as f:
            f.write(text)
    name = codec._so_path(a, "d")
    assert name != codec._so_path(b, "d")
    os.utime(a, (1, 1))  # the mtime is not in it
    assert codec._so_path(a, "d") == name


def test_build_line_has_no_isa_flag(tmp_path, monkeypatch):
    """A library built on one x86-64 host must run on any other."""
    seen = []
    real = subprocess.run

    def run(cmd, **kw):
        seen.append(cmd)
        return real(cmd, **kw)

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    monkeypatch.setattr(codec.subprocess, "run", run)
    src = str(tmp_path / "rwtpu_codec.cpp")
    shutil.copy(_SRC, src)
    codec.open_library(src, str(tmp_path))
    assert len(seen) == 1
    assert not [a for a in seen[0] if a.startswith(("-march", "-m"))]


def test_broken_build_falls_back_once_and_loudly(tmp_path):
    """A source that does not compile: the process says so once and
    runs on the fallbacks; a large checksum is refused, not crawled."""
    code = (
        "import numpy as np\n"
        "from risingwave_tpu.storage import codec\n"
        f"codec._SRC = {str(tmp_path / 'bad.cpp')!r}\n"
        "assert not codec.native_available()\n"
        "assert codec.crc32c_impl() == 'python'\n"
        "assert codec.crc32c(b'123456789') == 0xE3069283\n"
        "try:\n"
        "    codec.crc32c(np.zeros(codec.PY_CRC32C_MAX + 1, np.uint8))\n"
        "except codec.NativeCodecRequired:\n"
        "    print('refused')\n"
    )
    with open(tmp_path / "bad.cpp", "w") as f:
        f.write("this is not C++\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "refused"
    assert r.stderr.count("native codec unavailable") == 1


# -- the object -----------------------------------------------------------
def _leaves():
    rng = np.random.default_rng(7)
    return {
        "bool": rng.integers(0, 2, 700).astype(bool),
        "int32": rng.integers(-9, 9, (33, 5), dtype=np.int32),
        "uint64": rng.integers(0, 1 << 63, 1029, dtype=np.uint64),
        "float": rng.standard_normal((4, 4, 3)).astype(np.float32),
        "empty": np.zeros((0,), np.int64),
        "empty2d": np.zeros((0, 8), np.float64),
        "scalar": np.array(2.5),
        "strided": np.arange(60, dtype=np.int64).reshape(6, 10)[:, ::3],
        "fortran": np.asfortranarray(
            np.arange(12, dtype=np.int16).reshape(3, 4)),
    }


@pytest.mark.parametrize("kind", ["full", "delta"])
def test_object_reads_back_and_passes_testzip(kind):
    leaves = _leaves()
    names = [f"leaf_{i}" for i in range(len(leaves))] if kind == "full" \
        else [f"r_{i}_{512 * i}" for i in range(len(leaves))]
    payload = dict(zip(names, leaves.values()))
    data = bytes(encode_npz(_Arena(), payload))
    with zipfile.ZipFile(io.BytesIO(data)) as z:
        assert z.testzip() is None
        assert z.namelist() == [n + ".npy" for n in names]
        assert {i.compress_type for i in z.infolist()} \
            == {zipfile.ZIP_STORED}
    with np.load(io.BytesIO(data)) as z:
        assert z.files == names
        for n, a in payload.items():
            assert z[n].dtype == a.dtype and z[n].shape == a.shape, n
            np.testing.assert_array_equal(z[n], a)
    # and it is the object np.savez writes, member for member (but
    # for a Fortran-ordered leaf, which np.savez leaves in that order)
    buf = io.BytesIO()
    np.savez(buf, **payload)
    with zipfile.ZipFile(buf) as old, \
            zipfile.ZipFile(io.BytesIO(data)) as new:
        for o, n, a in zip(old.infolist(), new.infolist(),
                           payload.values()):
            assert (o.filename, o.file_size, o.header_offset) \
                == (n.filename, n.file_size, n.header_offset)
            assert o.CRC == n.CRC or not a.flags.c_contiguous


def test_small_object_after_large_keeps_no_tail():
    arena = _Arena()
    big = {"leaf_0": np.arange(1 << 16, dtype=np.int64)}
    small = {"r_0_0": np.arange(3, dtype=np.int64)}
    n_big = len(encode_npz(arena, big))
    got = bytes(encode_npz(arena, small))
    want = bytes(encode_npz(_Arena(), small))
    assert len(got) == len(want) < n_big
    # (the ZIP's timestamps may differ by their 2 s grain: compare what
    # is read, and the bytes behind the end of the directory)
    with zipfile.ZipFile(io.BytesIO(got)) as z:
        assert z.testzip() is None and z.namelist() == ["r_0_0.npy"]
    assert got[-22:-18] == b"PK\x05\x06"
    with np.load(io.BytesIO(got)) as z:
        np.testing.assert_array_equal(z["r_0_0"], small["r_0_0"])


def test_arena_grows_past_a_wrong_guess():
    arena = _Arena()
    arena.start(8)
    arena.write(b"abcdefgh")
    arena.write(np.arange(5000, dtype=np.uint8) % 251)
    arena.seek(2)
    arena.write(b"XY")
    assert arena.tell() == 4
    arena.seek(5008)
    out = bytes(arena.view())
    assert out[:8] == b"abXYefgh" and len(out) == 5008
    assert out[8:] == (np.arange(5000, dtype=np.uint8) % 251).tobytes()


def test_commit_writes_full_then_delta_and_loads(tmp_path):
    """Through the store: a full and a delta written by the new
    encoder, read by ``load`` and by the benchmark's own check."""
    store = CheckpointStore(str(tmp_path), keep_epochs=4, block_elems=64)
    a = np.arange(4096, dtype=np.int64)
    flag = np.zeros(300, bool)
    store.save("j", 1, {"a": a, "f": flag, "s": np.float32(1.5)}, {"o": 1})
    a2 = a.copy()
    a2[130] = -1
    store.save("j", 2, {"a": a2, "f": flag, "s": np.float32(1.5)}, {"o": 2})
    assert [store.checkpoint_kind("j", e) for e in (1, 2)] \
        == ["full", "delta"]
    for e in (1, 2):
        with zipfile.ZipFile(str(tmp_path / "j" / f"epoch_{e}.npz")) as z:
            assert z.testzip() is None
    assert store.checkpoint_bytes("j", 2) < store.checkpoint_bytes("j", 1)
    epoch, states, src = store.load("j")
    assert (epoch, src) == (2, {"o": 2})
    np.testing.assert_array_equal(states["a"], a2)
    assert states["s"].shape == () and float(states["s"]) == 1.5
    assert store.verify_job("j")["corrupt"] == []


def _parent_encode(arena, payload):
    """The parent's statement, word for word."""
    buf = io.BytesIO()
    np.savez(buf, **payload)
    return buf.getvalue()


def test_parent_written_objects_load_and_verify():
    """An ``np.savez`` object and a manifest as the parent's code left
    them (crc32c by the table loop): the change reads and verifies."""
    obj = InMemObjectStore()
    leaves = [np.arange(700, dtype=np.int64), np.ones(9, np.float32)]
    treedef = jax.tree.structure({"a": 0, "b": 0})
    npz = _parent_encode(None, {f"leaf_{i}": x
                                for i, x in enumerate(leaves)})
    meta = pickle.dumps({"treedef": treedef, "source_state": {"o": 5},
                         "epoch": 5, "kind": "full"})
    obj.put("j/epoch_5.npz", npz)
    obj.put("j/epoch_5.meta", meta)
    obj.put("MANIFEST.json", json.dumps({"jobs": {"j": {
        "epochs": [5], "kind": {"5": "full"}, "committed": 5,
        "crc": {"5": {"npz": codec.crc32c_with("bytewise", npz),
                      "meta": codec.crc32c_with("bytewise", meta)}},
    }}}).encode())
    store = CheckpointStore("unused", object_store=obj)
    assert store.verify_job("j") == {"verified": 2, "corrupt": []}
    epoch, states, src = store.load("j")
    assert (epoch, src) == (5, {"o": 5})
    np.testing.assert_array_equal(states["a"], leaves[0])
    np.testing.assert_array_equal(states["b"], leaves[1])


def test_engine_recovers_a_data_dir_the_parent_wrote(tmp_path, monkeypatch):
    """A ``data_dir`` whose every epoch object came from the parent's
    encoder and checksum loop recovers under the change: a fresh
    ``Engine`` reads the chain (a full and deltas) and goes on."""
    from risingwave_tpu.sql import Engine
    from risingwave_tpu.sql.planner import PlannerConfig

    cfg = PlannerConfig(chunk_capacity=128, agg_table_size=512,
                        agg_emit_capacity=256, mv_table_size=1 << 10,
                        mv_ring_size=1 << 11)
    data = str(tmp_path / "data")
    with monkeypatch.context() as mp:
        mp.setattr(cs, "encode_npz", _parent_encode)
        mp.setattr(cs, "crc32c",
                   lambda b: codec.crc32c_with("bytewise", b))
        eng = Engine(cfg, data_dir=data)
        eng.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
        eng.execute(
            "CREATE MATERIALIZED VIEW mv AS "
            "SELECT k, count(*) AS n, sum(v) AS s FROM t GROUP BY k")
        for r in range(3):
            vals = ",".join(f"({k},{10 * k + r})" for k in range(40))
            eng.execute(f"INSERT INTO t VALUES {vals}")
            eng.execute("FLUSH")
        want = sorted(map(tuple, eng.execute("SELECT * FROM mv")))
        del eng
    assert len(want) == 40
    eng2 = Engine(cfg, data_dir=data)
    assert sorted(map(tuple, eng2.execute("SELECT * FROM mv"))) == want
    eng2.execute("INSERT INTO t VALUES (1, 1000)")
    eng2.execute("FLUSH")
    got = dict((int(k), int(s))
               for k, _, s in eng2.execute("SELECT * FROM mv"))
    assert got[1] == dict((int(k), int(s)) for k, _, s in want)[1] + 1000


def test_two_jobs_commit_from_two_threads_through_one_store(tmp_path):
    """Several jobs' uploaders share one store and one manifest: each
    thread lays its objects out in a buffer of its own, so neither
    writes into what the other is putting.  More threads than the
    interpreter switches between in peace."""
    store = CheckpointStore(str(tmp_path), keep_epochs=2, block_elems=64,
                            full_interval=4)
    rounds, errors = 12, []

    def state(job: int, e: int) -> dict:
        a = np.full(20_000, job, dtype=np.int64)
        a[: 64 * e] = e
        return {"a": a, "tail": np.full(job + 1, e, np.int32)}

    def run(job: int) -> None:
        try:
            for e in range(1, rounds + 1):
                store.save(f"j{job}", e, state(job, e), {"o": e})
        except Exception as exc:  # the assertion below reports it
            errors.append((job, repr(exc)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(j,))
                   for j in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not [t for t in threads if t.is_alive()]
    assert errors == []
    for job in range(4):
        name = f"j{job}"
        assert store.verify_job(name)["corrupt"] == []
        epoch, states, src = store.load(name)
        assert (epoch, src) == (rounds, {"o": rounds})
        np.testing.assert_array_equal(states["a"], state(job, rounds)["a"])
        np.testing.assert_array_equal(states["tail"],
                                      state(job, rounds)["tail"])
        for e in store.epochs(name):
            with zipfile.ZipFile(
                    str(tmp_path / name / f"epoch_{e}.npz")) as z:
                assert z.testzip() is None


def test_encode_span_says_bytes_and_loop(tmp_path):
    from risingwave_tpu.common.trace import GLOBAL_TRACE

    store = CheckpointStore(str(tmp_path))
    with GLOBAL_TRACE.root("tick", "tick"):
        store.save("j", 1, {"a": np.arange(100)}, {})
    span = [s for s in GLOBAL_TRACE.dump()
            if s["name"] == "ckpt_commit.encode"][-1]
    sizes = sum(os.path.getsize(str(tmp_path / "j" / f"epoch_1.{x}"))
                for x in ("npz", "meta"))
    assert span["attrs"]["bytes"] == sizes
    assert span["attrs"]["impl"] == codec.crc32c_impl()
