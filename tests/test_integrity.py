"""Integrity-lite: end-to-end corruption detection, quarantine, and
self-healing repair (typed IntegrityError taxonomy, checksum coverage
of SSTs / checkpoint objects / the manifest chain, the scrubber, and
the meta's repair pipeline)."""

import json
import os

import numpy as np
import pytest

from risingwave_tpu.storage import codec
from risingwave_tpu.storage.hummock import (
    HummockStorage,
    InMemObjectStore,
    LocalFsObjectStore,
    StoreFaults,
    VersionManager,
)
from risingwave_tpu.storage.integrity import (
    BlockCorruption,
    CheckpointCorruption,
    FooterCorruption,
    IntegrityError,
    ManifestCorruption,
    quarantine_list,
    verify_sst_object,
)
from risingwave_tpu.storage.sst import SstReader, build_sst_bytes


def _pairs(n=300):
    return ([f"k{i:05d}".encode() for i in range(n)],
            [f"v{i}".encode() * 3 for i in range(n)])


def _flip(data: bytes, pos: int) -> bytes:
    out = bytearray(data)
    out[pos] ^= 0x40
    return bytes(out)


# -- SST coverage: footer crc + typed block errors ----------------------
def test_sst_block_and_footer_corruption_typed():
    keys, vals = _pairs()
    data, _meta = build_sst_bytes(keys, vals, block_bytes=1024)
    store = InMemObjectStore()
    store._d["sst/ok"] = data
    assert verify_sst_object(store, "sst/ok") > 1  # multi-block

    # a flipped bit in a DATA block: open succeeds, the read raises
    store._d["sst/blk"] = _flip(data, 100)
    r = SstReader(store=store, key="sst/blk")
    with pytest.raises(BlockCorruption) as ei:
        list(r.scan())
    assert ei.value.key == "sst/blk"
    r.close()

    # a flipped bit in the INDEX region: the footer crc catches it at
    # open — the index/bloom bytes are covered end-to-end now
    store._d["sst/idx"] = _flip(data, len(data) - 40)
    with pytest.raises(FooterCorruption):
        SstReader(store=store, key="sst/idx")

    # a truncated object: typed, never a struct/json crash
    store._d["sst/trunc"] = data[:len(data) // 2]
    with pytest.raises(FooterCorruption):
        SstReader(store=store, key="sst/trunc")
    store._d["sst/tiny"] = b"xy"
    with pytest.raises(FooterCorruption):
        SstReader(store=store, key="sst/tiny")


# -- manifest hash chain ------------------------------------------------
def test_version_log_chain_detects_tamper():
    store = InMemObjectStore()
    vm = VersionManager(store, base_interval=100)
    from risingwave_tpu.storage.hummock.version import SstInfo

    for e in range(1, 5):
        vm.commit(e, adds={0: [SstInfo(
            key=f"sst/{e}", first_key=b"a", last_key=b"z",
            n_records=1, size=8)]}, removes={})
    # untampered log replays clean
    assert VersionManager(store).current.vid == 4

    key = "version/delta_000000000003.json"
    raw = store._d[key]
    # tamper INSIDE the delta body (change an SST key)
    store._d[key] = raw.replace(b"sst/3", b"sst/X")
    with pytest.raises(ManifestCorruption):
        VersionManager(store)

    # the serving-tier follower verifies the same chain
    from risingwave_tpu.serve.reader import ManifestFollower

    with pytest.raises(ManifestCorruption):
        ManifestFollower(store).refresh(None)
    store._d[key] = raw  # heal
    assert ManifestFollower(store).refresh(None).vid == 4


def test_version_log_chain_links_predecessors():
    """Each delta commits the hash of its predecessor: REPLACING one
    delta with a self-consistent but different entry still breaks the
    chain at the successor."""
    store = InMemObjectStore()
    vm = VersionManager(store, base_interval=100)
    from risingwave_tpu.storage.hummock.version import (
        SstInfo,
        VersionDelta,
        wrap_chain_doc,
    )

    for e in range(1, 4):
        vm.commit(e, adds={0: [SstInfo(
            key=f"sst/{e}", first_key=b"a", last_key=b"z",
            n_records=1, size=8)]}, removes={})
    # forge delta 2 wholesale (valid self-crc, wrong chain position)
    forged = VersionDelta(vid=2, epoch=2, adds={}, removes={})
    raw, _ = wrap_chain_doc("delta", forged.to_json(), 0xDEAD)
    store._d["version/delta_000000000002.json"] = raw
    with pytest.raises(ManifestCorruption):
        VersionManager(store)


# -- checkpoint objects: crc trailers + lineage self-heal ---------------
def _save_epochs(store, job, n):
    for e in range(1, n + 1):
        states = {"a": np.arange(64, dtype=np.int64) + e,
                  "b": np.full(16, e, dtype=np.int64)}
        store.save(job, e, states, {"offset": e * 10})


def test_checkpoint_crc_recorded_and_verified(tmp_path):
    from risingwave_tpu.common.metrics import MetricsRegistry
    from risingwave_tpu.storage.checkpoint_store import CheckpointStore

    m = MetricsRegistry()
    store = CheckpointStore(str(tmp_path), keep_epochs=8,
                            metrics=m)
    _save_epochs(store, "j", 3)
    manifest = json.loads(store.store.get("MANIFEST.json"))
    crcs = manifest["jobs"]["j"]["crc"]
    assert set(crcs) == {"1", "2", "3"}
    for e in ("1", "2", "3"):
        data = store.store.get(f"j/epoch_{e}.npz")
        assert codec.crc32c(data) == crcs[e]["npz"]
    assert store.verify_job("j")["corrupt"] == []

    # flip one stored bit in the NEWEST epoch object
    path = os.path.join(str(tmp_path), "j", "epoch_3.npz")
    with open(path, "r+b") as f:
        f.seek(30)
        b = f.read(1)
        f.seek(30)
        f.write(bytes([b[0] ^ 1]))
    assert [e for e, _ in store.verify_job("j")["corrupt"]] == [3]

    # explicit-epoch load (time travel / handover slice) must be exact
    with pytest.raises(CheckpointCorruption):
        store.load("j", 3)

    # latest-epoch load SELF-HEALS: quarantine + rewind to epoch 2
    epoch, states, src = store.load("j")
    assert epoch == 2
    assert src == {"offset": 20}
    assert int(np.asarray(states["a"])[0]) == 2 + 0
    notes = quarantine_list(store.store)
    assert any("epoch_3" in n["key"] for n in notes)
    assert m.get("integrity_errors_total", kind="checkpoint") >= 1
    assert m.get("integrity_repairs_total",
                 kind="checkpoint_rewind") >= 1
    # the corrupt epoch left the manifest; a later save moves forward
    assert store.epochs("j") == [1, 2]
    _save_epochs(store, "j", 4)  # re-saves 1..4 (4 is new)
    assert store.load("j")[0] == 4


def test_checkpoint_repair_lineage_truncates(tmp_path):
    from risingwave_tpu.storage.checkpoint_store import CheckpointStore

    store = CheckpointStore(str(tmp_path), keep_epochs=8)
    _save_epochs(store, "j", 3)
    path = os.path.join(str(tmp_path), "j", "epoch_2.meta")
    with open(path, "r+b") as f:
        f.write(b"\x00\x01\x02")
    rep = store.repair_lineage("j")
    assert rep["corrupt"] == ["j/epoch_2.meta"]
    # epoch 2 dropped; 3 is a FULL here (default interval) so it stays
    assert 2 not in store.epochs("j")
    assert store.load("j")[0] == 3


@pytest.mark.parametrize("mode", ["bit_flip", "truncate"])
@pytest.mark.parametrize("local", [True, False],
                         ids=["local_fs", "in_mem"])
def test_checkpoint_put_damaged_in_flight_is_caught(tmp_path, mode, local):
    """``commit`` hands ``put`` a view of the buffer it keeps, and the
    manifest the crc32c of the bytes BEFORE the put: a put that lands
    damaged bytes (the fault hook copies the view, only when armed)
    mismatches on read, and the buffer's next object is intact."""
    from risingwave_tpu.storage.checkpoint_store import CheckpointStore
    from risingwave_tpu.storage.hummock.object_store import (
        LocalFsObjectStore,
    )

    faults = StoreFaults(seed=3)
    faults.fail("put", substr="epoch_2.npz", mode=mode, times=1)
    obj = LocalFsObjectStore(str(tmp_path), faults=faults) if local \
        else InMemObjectStore(faults=faults)
    store = CheckpointStore(str(tmp_path), keep_epochs=8, full_interval=1,
                            object_store=obj)
    _save_epochs(store, "j", 3)
    assert faults.injected_corruptions == 1
    assert [e for e, _ in store.verify_job("j")["corrupt"]] == [2]
    with pytest.raises(CheckpointCorruption):
        store.load("j", 2)
    for e in (1, 3):
        assert int(np.asarray(store.load("j", e)[1]["b"])[0]) == e


# -- deterministic corruption faults ------------------------------------
def test_store_faults_bit_flip_and_truncate_deterministic():
    def run():
        faults = StoreFaults(seed=11)
        faults.fail("put", substr="sst/", mode="bit_flip", times=1)
        faults.fail("get", substr="blob", mode="truncate", times=1)
        store = InMemObjectStore(faults=faults)
        store.put("sst/a", b"A" * 64)
        store.put("other", b"B" * 64)  # no match: intact
        store.put("blob1", b"C" * 64)
        return (store._d["sst/a"], store._d["other"],
                store.get("blob1"), faults.injected_corruptions)

    a1, o1, g1, n1 = run()
    a2, o2, g2, n2 = run()
    assert a1 == a2 and g1 == g2 and n1 == n2 == 2
    assert a1 != b"A" * 64 and len(a1) == 64      # one bit flipped
    assert o1 == b"B" * 64                         # rule retired
    assert g1 == b"C" * 32                         # truncated read


def test_fabric_corruption_records_keys():
    from risingwave_tpu.common import faults as F

    fab = F.FaultFabric(seed=5)
    fab.fail_store("put", substr="sst/", mode="bit_flip", times=2)
    F.install(fab)
    try:
        store = InMemObjectStore()
        store.put("sst/x", b"x" * 32)
        store.put("sst/y", b"y" * 32)
        store.put("sst/z", b"z" * 32)  # rule exhausted
    finally:
        F.install(None)
    assert fab.corrupted_keys == ["sst/x", "sst/y"]
    assert store._d["sst/x"] != b"x" * 32
    assert store._d["sst/z"] == b"z" * 32
    assert fab.stats()["corrupted_keys"] == ["sst/x", "sst/y"]


# -- scrubber -----------------------------------------------------------
def test_scrubber_walks_and_reports(tmp_path):
    from risingwave_tpu.common.metrics import MetricsRegistry
    from risingwave_tpu.storage.hummock.scrubber import ScrubberService

    m = MetricsRegistry()
    storage = HummockStorage(
        LocalFsObjectStore(str(tmp_path / "hummock")), metrics=m)
    keys, vals = _pairs(200)
    storage.write_batch(list(zip(keys, vals)), epoch=1)
    storage.write_batch([(b"zz" + k, v)
                         for k, v in zip(keys, vals)], epoch=2)

    hits = []
    scrub = ScrubberService(storage, metrics=m, pace_s=0.0,
                            on_corruption=lambda k, key, ctx:
                            hits.append((k, key)))
    rep = scrub.run_once()
    assert rep["ssts_verified"] == 2 and not rep["corrupt"]
    assert m.get("scrub_objects_verified_total") == 2
    assert m.get("scrub_cursor_age_s") >= 0.0
    # durable cursor written
    assert storage.store.exists("scrub/CURSOR.json")

    # plant a bit flip in one SST: next cycle detects + reports
    sst_key = sorted(storage.versions.current.all_keys())[0]
    path = os.path.join(str(tmp_path / "hummock"), sst_key)
    with open(path, "r+b") as f:
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 8]))
    rep = scrub.run_once()
    assert ("sst", sst_key) in rep["corrupt"]
    assert hits == [("sst", sst_key)]
    assert m.get("scrub_corruptions_total", kind="sst") == 1


# -- compaction as a detection point ------------------------------------
def test_compaction_detects_quarantines_and_continues(tmp_path):
    storage = HummockStorage(
        LocalFsObjectStore(str(tmp_path)), l0_trigger=2)
    keys, vals = _pairs(100)
    storage.write_batch(list(zip(keys, vals)), epoch=1)
    storage.write_batch(list(zip(keys, vals)), epoch=2)
    bad = storage.versions.current.levels[0][0].key
    path = os.path.join(str(tmp_path), bad)
    with open(path, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff")
    seen = []
    storage.on_corruption = lambda k, key, ctx: seen.append(key)
    # the merge reads the corrupt input: abort + quarantine, no crash
    assert storage.compact_once() is False
    assert seen == [bad]
    assert any(bad in n["key"] for n in quarantine_list(storage.store))
    # the poisoned task released its level locks (no wedge)
    assert storage._busy_levels == set()


# -- in-process meta repair: corrupt export SST re-exported -------------
def test_meta_repairs_corrupt_export_sst(tmp_path):
    from risingwave_tpu.cluster import ComputeWorker, MetaService
    from risingwave_tpu.common.config import RwConfig

    cfg = RwConfig.from_dict({
        "streaming": {"chunk_size": 64},
        "state": {"agg_table_size": 256, "agg_emit_capacity": 64,
                  "mv_table_size": 256, "mv_ring_size": 512},
    })
    meta = MetaService(str(tmp_path), heartbeat_timeout_s=30.0)
    meta.start(port=0, monitor=False, compactor=False,
               scrubber=False)
    w = ComputeWorker(f"127.0.0.1:{meta.rpc_port}", str(tmp_path),
                      config=cfg).start()
    try:
        meta.execute_ddl(
            "CREATE SOURCE t (k BIGINT) WITH (connector='datagen');"
            "CREATE MATERIALIZED VIEW iv AS "
            "SELECT k % 4 AS g, count(*) AS n FROM t GROUP BY k % 4"
        )
        for _ in range(2):
            assert meta.tick(1)["committed"]
        _, before = meta.serve("SELECT g, n FROM iv")

        # corrupt the newest committed export SST on disk
        v = meta.hummock.versions.current
        bad = v.levels[0][0].key
        path = os.path.join(str(tmp_path), "hummock", bad)
        with open(path, "r+b") as f:
            f.seek(16)
            f.write(b"\x55\xaa")
        with pytest.raises(IntegrityError):
            verify_sst_object(meta.hummock.store, bad)

        # the full pipeline: quarantine + re-export + atomic replace
        res = meta.report_corruption(bad, kind="sst_block",
                                     reason="test plant", sync=True)
        assert res["repair"] == "done"
        assert bad not in meta.hummock.versions.current.all_keys()
        assert any(bad in n["key"]
                   for n in quarantine_list(meta.hummock.store))
        assert meta.repairs["sst"] == 1

        # every remaining object verifies; rows byte-identical
        rep = meta.cluster_scrub()
        assert rep["corrupt"] == []
        _, after = meta.serve("SELECT g, n FROM iv")
        assert sorted(after) == sorted(before)

        # rounds keep committing after the repair
        assert meta.tick(1)["committed"]
    finally:
        w.stop()
        meta.stop()
