"""End-to-end KV-block integrity (docs/architecture/integrity.md): the
envelope is stamped ONCE at the G1→G2 store and verified at every later
trust-boundary crossing; failures quarantine the block and degrade the
request to recompute, never to an error or to wrong bytes.

Covered here: checksum primitives, host-onboard verify + quarantine +
re-admission, quantized packed rows, G3 promotion verify, the background
scrubber (detection + injectable pacing), crash-consistent sidecar
recovery, a kill -9 mid-offload restart drill (subprocess), the
mixed-fleet refusals (G4 blockset + disagg layout handshake), and
metric-surface parity for the integrity gauges (DT011 posture).
"""

import asyncio
import dataclasses
import logging
import os
import sys
import time
from types import SimpleNamespace

import msgpack
import numpy as np
import pytest

from dynamo_tpu.block_manager import (
    BlockPool,
    DiskStorage,
    HostStorage,
    KvbmConfig,
    KvBlockManager,
    KvLayoutConfig,
)
from dynamo_tpu.block_manager.integrity import (
    CHECKSUM_ALGO,
    INTEGRITY,
    block_checksum,
    verify_block,
)
from dynamo_tpu.block_manager.offload import OffloadManager

pytestmark = pytest.mark.anyio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TORN_WORKER = os.path.join(REPO, "tests", "procs", "torn_offload_worker.py")

LAYOUT = KvLayoutConfig(
    num_layers=2, page_size=16, num_kv_heads=2, head_dim=16, dtype="float32"
)
QLAYOUT = KvLayoutConfig(
    num_layers=2, page_size=16, num_kv_heads=2, head_dim=16,
    dtype="float32", quant="int8",
)
# Mirror of tests/procs/torn_offload_worker.py LAYOUT — the drill reopens
# the child's disk file under this geometry.
TORN_LAYOUT = KvLayoutConfig(
    num_layers=1, page_size=4, num_kv_heads=1, head_dim=4, dtype="float32"
)


def _data(seed: float) -> np.ndarray:
    return np.full((LAYOUT.block_elems,), seed, np.float32)


@pytest.fixture(autouse=True)
def _reset_integrity():
    """The integrity ledger is process-global; counter assertions here
    must not see residue from other tests (or leave any behind)."""
    INTEGRITY.reset()
    yield
    INTEGRITY.reset()


def test_checksum_primitives():
    arr = np.arange(64, dtype=np.float32)
    crc = block_checksum(arr)
    # Array and raw-bytes forms agree: senders checksum tobytes() wire
    # payloads, receivers verify ndarray views — same envelope.
    assert crc == block_checksum(arr.tobytes())
    assert verify_block(arr, crc)
    assert verify_block(arr.tobytes(), crc)
    # None = legacy/unstamped: trusted, old behavior preserved.
    assert verify_block(arr, None)
    rotten = arr.copy()
    rotten.view(np.uint8)[17] ^= 0x01
    assert not verify_block(rotten, crc)
    assert CHECKSUM_ALGO == "crc32-v1"


async def test_store_stamps_once_and_match_host_quarantines():
    kvbm = await KvBlockManager(
        KvbmConfig(layout=LAYOUT, host_blocks=8)
    ).start()
    try:
        d = _data(3.0)
        kvbm.offer(42, None, tuple(range(16)), d)
        await kvbm.drain_offers(10.0)
        blk = kvbm.host_pool.get_by_hash(42)
        assert blk is not None
        # The envelope was stamped at the store, over the stored bytes.
        assert blk.checksum == block_checksum(d)
        got = kvbm.match_host([42])
        assert len(got) == 1 and np.array_equal(got[0][3], d)
        assert INTEGRITY.snapshot()["integrity_failures_total"] == 0

        # Bit-rot the host arena behind the envelope's back: the G2→G1
        # crossing must refuse the block, not serve it.
        row = kvbm.host_pool.storage.read_block(blk.idx)
        row.view(np.uint8)[7] ^= 0x01
        assert kvbm.match_host([42]) == []
        snap = INTEGRITY.snapshot()
        assert snap["integrity_failures_host"] == 1
        assert snap["integrity_failures_total"] == 1
        # Quarantined: evicted, and barred from every export surface.
        assert kvbm.host_pool.get_by_hash(42) is None
        assert 42 not in kvbm.registered_hashes()
        assert all(h != 42 for h, _, _ in kvbm.host_entries())

        # A fresh store re-stamps the envelope and lifts the bar.
        kvbm.offer(42, None, tuple(range(16)), d)
        await kvbm.drain_offers(10.0)
        assert 42 in kvbm.registered_hashes()
        got = kvbm.match_host([42])
        assert len(got) == 1 and np.array_equal(got[0][3], d)
    finally:
        await kvbm.stop()


async def test_quantized_packed_row_envelope():
    """quant="int8" tiers stamp the CRC over the PACKED row (int8 data ‖
    float32 scales); rot anywhere in it — scales included — is caught."""
    kvbm = await KvBlockManager(
        KvbmConfig(layout=QLAYOUT, host_blocks=4)
    ).start()
    try:
        d = np.linspace(-2.0, 2.0, QLAYOUT.block_elems, dtype=np.float32)
        kvbm.offer(7, None, tuple(range(16)), d)
        await kvbm.drain_offers(10.0)
        blk = kvbm.host_pool.get_by_hash(7)
        stored = np.asarray(kvbm.host_pool.storage.read_block(blk.idx))
        assert stored.dtype == np.uint8
        assert stored.nbytes == QLAYOUT.block_bytes
        assert blk.checksum == block_checksum(stored)
        got = kvbm.match_host([7])
        assert len(got) == 1 and np.array_equal(got[0][3], stored)

        # Flip a byte in the scale sidecar (the packed row's tail): the
        # envelope covers it, so the onboard must still refuse.
        kvbm.host_pool.storage.read_block(blk.idx)[-1] ^= 0x01
        assert kvbm.match_host([7]) == []
        assert INTEGRITY.snapshot()["integrity_failures_host"] == 1
    finally:
        await kvbm.stop()


async def test_disk_promotion_verifies_envelope(tmp_path):
    host = BlockPool(HostStorage(4, LAYOUT))
    disk = BlockPool(DiskStorage(4, LAYOUT, tmp_path / "kv.bin"))
    mgr = OffloadManager(host, disk)
    for i, h in enumerate((10, 11)):
        b = host.allocate_blocks(1)[0]
        host.storage.write_block(b.idx, _data(float(i + 1)))
        b = host.register_block(
            b, h, 10 if i else None, tuple(range(16)),
            checksum=block_checksum(_data(float(i + 1))),
        )
        mgr.offload(b)
        host.release(b)
    await mgr.drain()

    # The envelope rode down-tier unchanged (carried, never re-stamped).
    assert disk.get_by_hash(10).checksum == block_checksum(_data(1.0))

    # Silent SSD rot under block 11: flip one byte in the mmap.
    stor = disk.storage
    off = disk.get_by_hash(11).idx * LAYOUT.block_bytes + 13
    stor._map[off] = stor._map[off] ^ 0x01
    up = await mgr.onboard([10, 11])
    try:
        # Promotion stops AT the corrupt block: the clean prefix lands,
        # the rotten tail is quarantined for the engine to recompute.
        assert [b.sequence_hash for b in up] == [10]
        assert np.array_equal(
            np.asarray(host.storage.read_block(up[0].idx)), _data(1.0)
        )
    finally:
        for b in up:
            host.release(b)
    snap = INTEGRITY.snapshot()
    assert snap["integrity_failures_disk"] == 1
    assert disk.get_by_hash(11) is None
    assert disk.get_by_hash(10) is not None


async def test_scrub_loop_detects_and_paces(tmp_path):
    cfg = KvbmConfig(
        layout=LAYOUT,
        host_blocks=8,
        disk_blocks=8,
        disk_path=str(tmp_path / "kv.bin"),
        scrub_blocks_per_tick=4,
        scrub_interval_s=0.075,
    )
    kvbm = KvBlockManager(cfg)
    sleeps: list[float] = []

    async def pace(interval: float) -> None:
        # Injectable pacing clock: record what the loop asked for, tick
        # fast so the test doesn't wait out real intervals.
        sleeps.append(interval)
        await asyncio.sleep(0.005)

    kvbm._scrub_sleep = pace
    await kvbm.start()
    try:
        parent = None
        for i in range(3):
            kvbm.offer(100 + i, parent, tuple(range(16)), _data(float(i + 1)))
            parent = 100 + i
        await kvbm.drain_offers(10.0)
        await kvbm._g2_to_g3.drain()

        blk = kvbm.disk_pool.get_by_hash(101)
        stor = kvbm.disk_pool.storage
        off = blk.idx * LAYOUT.block_bytes + 11
        stor._map[off] = stor._map[off] ^ 0x01

        deadline = time.monotonic() + 10.0
        while INTEGRITY.snapshot()["scrub_detected_total"] < 1:
            assert time.monotonic() < deadline, \
                "scrubber never caught the planted rot"
            await asyncio.sleep(0.01)
        snap = INTEGRITY.snapshot()
        assert snap["integrity_failures_disk"] == 1
        assert snap["scrub_scanned_total"] >= 1
        # Quarantined out of the tier before any request could meet it;
        # the clean neighbors survive the sweep.
        assert kvbm.disk_pool.get_by_hash(101) is None
        assert kvbm.disk_pool.get_by_hash(100) is not None
        assert kvbm.disk_pool.get_by_hash(102) is not None
        # Every tick slept exactly the configured interval.
        assert sleeps and set(sleeps) == {cfg.scrub_interval_s}
    finally:
        await kvbm.stop()


def test_sidecar_recovery_drops_torn_tail(tmp_path):
    path = tmp_path / "g3.kv"
    stor = DiskStorage(4, LAYOUT, path, persist=True)
    for i in range(3):
        d = _data(float(i + 1))
        stor.write_block(i, d)
        stor.record_block(
            i, 100 + i, (99 + i) if i else None, tuple(range(16)),
            block_checksum(d),
        )
    stor.close()

    # Rot block 2's bytes behind the sidecar's back (the crash window
    # where the data region lost a write the index already named).
    with open(path, "r+b") as fh:
        fh.seek(2 * LAYOUT.block_bytes + 5)
        byte = fh.read(1)[0]
        fh.seek(-1, 1)
        fh.write(bytes([byte ^ 0x01]))

    INTEGRITY.reset()
    stor2 = DiskStorage(4, LAYOUT, path, persist=True)
    try:
        entries = stor2.recovered_entries()
        assert {h for _, h, *_ in entries} == {100, 101}
        for idx, h, _parent, _tokens, crc in entries:
            assert block_checksum(stor2.read_block(idx)) == crc
        snap = INTEGRITY.snapshot()
        assert snap["integrity_failures_disk"] == 1
        assert snap["scrub_detected_total"] == 1
    finally:
        stor2.close()


def test_torn_write_fault_truncates_block_recovery_drops_it(tmp_path):
    """Armed ``kvbm.torn_write`` at the G3 write seam: only half the
    row's bytes land, but the sidecar names the block with its full
    upstream checksum — restart recovery must drop exactly that block
    and keep the intact neighbors."""
    from dynamo_tpu.utils.faults import FAULTS

    path = tmp_path / "g3.kv"
    stor = DiskStorage(4, LAYOUT, path, persist=True)
    try:
        for i in range(2):
            d = _data(float(i + 1))
            stor.write_block(i, d)
            stor.record_block(i, 100 + i, None, tuple(range(16)),
                              block_checksum(d))
        torn = _data(9.0)
        before = FAULTS.injected.get("kvbm.torn_write", 0)
        FAULTS.arm("kvbm.torn_write", "truncate", times=1)
        stor.write_block(2, torn)  # torn: only the first half lands
        stor.record_block(2, 102, None, tuple(range(16)),
                          block_checksum(torn))
        assert FAULTS.injected["kvbm.torn_write"] == before + 1
        stor.close()

        INTEGRITY.reset()
        stor2 = DiskStorage(4, LAYOUT, path, persist=True)
        try:
            assert {h for _, h, *_ in stor2.recovered_entries()} == {100, 101}
            assert INTEGRITY.snapshot()["integrity_failures_disk"] == 1
        finally:
            stor2.close()
    finally:
        FAULTS.clear()


def test_torn_write_fault_tears_sidecar_recovery_starts_fresh(tmp_path):
    """Armed ``kvbm.torn_write`` at the sidecar flush: the index JSON is
    cut mid-document (a crash on a non-atomic fs). Recovery must degrade
    to an empty tier — never adopt half-parsed junk."""
    from dynamo_tpu.utils.faults import FAULTS

    path = tmp_path / "g3.kv"
    stor = DiskStorage(4, LAYOUT, path, persist=True)
    try:
        d = _data(1.0)
        stor.write_block(0, d)
        stor.record_block(0, 100, None, tuple(range(16)), block_checksum(d))
        d2 = _data(2.0)
        stor.write_block(1, d2)
        # The flush for THIS record gets torn. write_block spends no
        # budget first because corrupt() only fires at mutate sites and
        # the truncate is armed after the bytes landed.
        before = FAULTS.injected.get("kvbm.torn_write", 0)
        FAULTS.arm("kvbm.torn_write", "truncate", times=1)
        stor.record_block(1, 101, None, tuple(range(16)),
                          block_checksum(d2))
        assert FAULTS.injected["kvbm.torn_write"] == before + 1
        stor.close()

        stor2 = DiskStorage(4, LAYOUT, path, persist=True)
        try:
            assert stor2.recovered_entries() == []
        finally:
            stor2.close()
    finally:
        FAULTS.clear()


async def test_torn_write_crash_drill(tmp_path):
    """kill -9 mid-offload, then restart: the sidecar's ordering contract
    (bytes msync'd before the index names them) means the reopened tier
    serves a contiguous, byte-identical prefix of the chain — at least
    everything the child acknowledged before dying, never a torn block."""
    path = str(tmp_path / "g3.kv")
    proc = await asyncio.create_subprocess_exec(
        sys.executable, TORN_WORKER, "--path", path, "--blocks", "8",
        stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.STDOUT,
        cwd=REPO,
    )
    stored = -1
    try:
        while stored < 2:
            line = await asyncio.wait_for(proc.stdout.readline(), 60)
            assert line, "offload child died before storing 3 blocks"
            text = line.decode().strip()
            if text.startswith("STORED "):
                stored = int(text.split()[1])
        proc.kill()  # SIGKILL: no atexit, no flush, mid-offload
    finally:
        if proc.returncode is None:
            proc.kill()
        await proc.wait()

    kvbm = await KvBlockManager(
        KvbmConfig(
            layout=TORN_LAYOUT,
            host_blocks=12,
            disk_blocks=12,
            disk_path=path,
            disk_persist=True,
        )
    ).start()
    try:
        adopted = sorted(kvbm.disk_pool.registered_hashes())
        k = len(adopted)
        # Everything acknowledged before the kill survived...
        assert k >= stored + 1
        # ...and what survived is a contiguous prefix — no holes, no
        # torn tail block resurrected as valid.
        assert adopted == [1000 + j for j in range(k)]

        chain = [1000 + j for j in range(8)]
        assert await kvbm.onboard_from_disk(chain) == k
        got = kvbm.match_host(chain)
        assert len(got) == k
        for j, (h, _parent, _tokens, data) in enumerate(got):
            assert h == 1000 + j
            want = np.full(
                (TORN_LAYOUT.block_elems,), float(j + 1), np.float32
            )
            assert np.array_equal(np.asarray(data), want)
        assert INTEGRITY.snapshot()["integrity_failures_total"] == 0
    finally:
        await kvbm.stop()


async def test_offload_drain_yields_to_a_pending_done_callback(monkeypatch):
    """What hung the crash drill's child under load (one run in ~20 with
    every core busy): a copy-down task already DONE whose discard callback
    has not run yet. asyncio.gather over tasks that are all done completes
    without yielding (Python 3.12), so ``drain`` must give the loop a turn
    or it spins forever and the child never prints its next STORED line."""
    from dynamo_tpu.block_manager import offload

    mgr = offload.OffloadManager(None, None)
    task = asyncio.ensure_future(asyncio.sleep(0))
    await task
    mgr._tasks.add(task)
    task.add_done_callback(mgr._tasks.discard)  # scheduled, not yet run

    calls = 0
    real_gather = asyncio.gather

    def counted(*a, **k):
        nonlocal calls
        calls += 1
        assert calls < 50, "drain spins without yielding to the loop"
        return real_gather(*a, **k)

    monkeypatch.setattr(offload.asyncio, "gather", counted)
    await asyncio.wait_for(mgr.drain(), 5)
    assert not mgr._tasks and calls == 1


def test_legacy_peer_blockset_refused(caplog):
    """Satellite regression: a checksumming worker REFUSES a legacy
    peer's blockset loudly — its rows are unverifiable here."""
    from dynamo_tpu.block_manager.peer import layout_fingerprint
    from dynamo_tpu.block_manager.remote import RemoteBlockClient

    ours = layout_fingerprint(LAYOUT)
    assert ours["checksum"] == CHECKSUM_ALGO
    comp = SimpleNamespace(name="tpu", namespace=SimpleNamespace(name="kv"))
    client = RemoteBlockClient(None, comp, layout=ours)

    legacy = dict(ours)
    del legacy["checksum"]  # a pre-envelope build's fingerprint
    with caplog.at_level(
        logging.WARNING, logger="dynamo_tpu.block_manager.remote"
    ):
        client._apply(
            client._prefix + "beef",
            msgpack.packb({"hashes": [1, 2, 3], "layout": legacy}),
        )
    assert "beef" not in client._blocksets
    assert "REFUSED: checksum algorithm" in caplog.text

    # Same-algorithm peer: accepted.
    client._apply(
        client._prefix + "cafe",
        msgpack.packb({"hashes": [1, 2], "layout": dict(ours)}),
    )
    assert client._blocksets["cafe"] == {1, 2}


def test_disagg_layout_checksum_handshake(caplog):
    from dynamo_tpu.disagg.worker import PrefillWorker

    pw = PrefillWorker.__new__(PrefillWorker)
    pw.engine = SimpleNamespace(
        cfg=SimpleNamespace(
            model=SimpleNamespace(num_layers=2, num_cache_heads=2),
            block_size=16,
            dtype="float32",
            kv_quant=None,
        ),
        runner=None,
    )
    base = {
        "num_layers": 2,
        "num_kv_heads": 2,
        "block_size": 16,
        "dtype": "float32",
        "kv_quant": None,
    }
    # Legacy peer (no checksum field): accepted, frames ride unchecksummed.
    assert pw._check_layout({"layout": dict(base)})
    assert pw._check_layout({"layout": {**base, "checksum": CHECKSUM_ALGO}})
    # Algorithm split: rejected loudly — the decode side would quarantine
    # every frame this worker ships.
    with caplog.at_level(logging.ERROR, logger="dynamo_tpu.disagg.worker"):
        ok = pw._check_layout(
            {"request_id": "r1", "layout": {**base, "checksum": "crc32-v0"}}
        )
    assert not ok
    assert "mixed integrity fleet" in caplog.text


def test_integrity_metric_surface_parity():
    """DT011 posture: every integrity ledger key is surfaced — as a
    ForwardPassMetrics field AND a standalone-exporter gauge — under the
    kvbm_ prefix; drift in any direction fails here."""
    from dynamo_tpu.llm import metrics_exporter
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics

    snap_keys = set(INTEGRITY.snapshot())
    assert snap_keys == {
        "integrity_failures_total",
        "integrity_failures_host",
        "integrity_failures_disk",
        "integrity_failures_peer",
        "integrity_failures_frame",
        "scrub_scanned_total",
        "scrub_detected_total",
    }
    gauge_names = {name for name, _ in metrics_exporter._GAUGES}
    fpm_fields = {f.name for f in dataclasses.fields(ForwardPassMetrics)}
    for key in snap_keys:
        assert f"kvbm_{key}" in gauge_names
        assert f"kvbm_{key}" in fpm_fields
